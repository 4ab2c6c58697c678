"""The trace reduction: by hand on a synthetic trace, and on a trace
recorded on a TPU v5e and committed beside it: two 20-step calls of the
mushroom body's 12-candidate gScale sweep (a cell since taken out of the
benchmark; ``record_trace.py`` recorded it then, and now records two calls
of izh100k.run)."""

import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chipbench import trace as tr

DATA = Path(__file__).resolve().parent / "data"
RECORDED = DATA / "mbody_sweep12_20steps.xplane.pb"
P = "chipbench."


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def fake(ops, host):
    return NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python", events=host)]),
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=[ev("jit_run", 0, 10_000)]),
            NS(name="XLA Ops", events=ops)]),
    ])


def test_busy_idle_and_gaps_by_hand():
    # window 1000..11000 ns.  A while loop (2000-6000) holds a scatter and
    # a dot; one custom fusion straddles the window's end, one op lies
    # before the window
    ops = [ev("%fusion.1 = f32[8] fusion(f32[8] %a), kind=kLoop", 500, 400),
           ev("%while.1 = (s32[]) while((s32[]) %t)", 2000, 4000),
           ev("%scatter.2 = f32[8] scatter(f32[8] %z, s32[64] %i)", 2000,
              1500),
           ev("%convolution_add_fusion.3 = f32[8] fusion(f32[2] %s), "
              "kind=kOutput", 3500, 1500),
           ev("%fusion.4 = f32[8] fusion(f32[8] %v), kind=kLoop", 8000,
              1000),
           ev("%fusion.5 = f32[8] fusion(s32[64] %i), kind=kCustom", 10500,
              1000)]
    host = [ev(P + "window", 1000, 10_000),
            ev(P + "dispatch", 1000, 900),
            ev(P + "fetch", 5200, 5000)]
    r = tr.reduce_profile(fake(ops, host), P)
    assert r["window_s"] == pytest.approx(10_000e-9)
    # busy: 2000-6000, 8000-9000, 10500-11000
    assert r["busy_s"] == pytest.approx(5_500e-9)
    # propagation: scatter 1500 + dot 1500 + the custom fusion's 500 inside
    # the window; the loop's own 1000 is not propagation
    assert r["prop_s"] == pytest.approx(3_500e-9)
    # 1000-2000 (dispatch covers 900 of it), 6000-8000 and 9000-10500
    # (fetch), longest first
    assert r["idle_gaps"] == [["fetch", pytest.approx(2e-6)],
                              ["fetch", pytest.approx(1.5e-6)],
                              ["dispatch", pytest.approx(1e-6)]]
    ops_s = dict(r["device_ops"])
    assert ops_s == {"scatter.2": pytest.approx(1.5e-6),
                     "convolution_add_fusion.3 (kOutput)":
                         pytest.approx(1.5e-6),
                     "while.1": pytest.approx(1e-6),
                     "fusion.4 (kLoop)": pytest.approx(1e-6),
                     "fusion.5 (kCustom)": pytest.approx(5e-7)}


def test_missing_window_raises():
    with pytest.raises(ValueError):
        tr.reduce_profile(fake([ev("f", 0, 1)], []), P)


def test_recorded_trace():
    if not RECORDED.exists():
        pytest.fail(f"{RECORDED.name} is missing")
    from jax.profiler import ProfileData
    r = tr.reduce_profile(ProfileData.from_file(str(RECORDED)), P)
    expected = json.loads((DATA / "mbody_sweep12_20steps.json").read_text())
    assert 0 < r["busy_s"] <= r["window_s"]
    assert 0 < r["prop_s"] <= r["busy_s"]
    assert len(r["device_ops"]) <= tr.TOP and len(r["idle_gaps"]) <= tr.TOP
    secs = [s for _, s in r["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    # the sweep's mode named its per-call state set-up ``candidates``
    assert {n for n, _ in r["idle_gaps"]} <= {"dispatch", "fetch",
                                              "candidates", "other"}
    for k in ("window_s", "busy_s", "prop_s"):
        assert r[k] == pytest.approx(expected[k], rel=1e-9)
