"""Device time by named scope: by hand on a synthetic trace and HLO map,
and on a trace recorded on a TPU v5e with the HLO text of the program it
ran (``record_trace.py``: two 10-step calls of izh100k.run)."""

import gzip
import importlib
import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chipbench import scopes as sc
from chipbench import trace as tr

DATA = Path(__file__).resolve().parent / "data"
RECORDED = "izh100k_run_2calls"
P = "chipbench."


@pytest.mark.parametrize("op_name, scope", [
    ("jit(_run)/while/body/closed_call/prop/exc_exc/cond/branch_1_fun/"
     "event/scatter-add", "prop/exc_exc/event"),
    ("jit(_run)/while/body/closed_call/prop/inh_exc/compact/"
     "jit(floor_divide)/rem", "prop/inh_exc/compact"),
    ("prop/exc_inh/cond/branch_0_fun/fallback/add", "prop/exc_inh/fallback"),
    ("jit(_run)/while/body/closed_call/prop/exc_exc/dense/dot_general",
     "prop/exc_exc/dense"),
    ("jit(_run)/while/body/closed_call/prop/exc_exc/convert_element_type",
     "prop/exc_exc"),
    ("jit(_run)/while/body/closed_call/neuron/inh/mul", "neuron/inh"),
    # a population may bear a layer's name
    ("jit(_run)/while/body/closed_call/neuron/probe/mul", "neuron/probe"),
    ("jit(_run)/while/body/closed_call/rng/jit(_threefry_split)/"
     "Simulator.step/while/body/closed_call/xor", "rng"),
    ("jit(_run)/probe/min", "probe"),
    ("jit(_run)/while/body/closed_call/custom/norm/div", "custom/norm"),
    ("jit(_run)/while/body/health/add", "health"),
    # the innermost layer names an op
    ("jit(_run)/shard_map/while/body/prop/exc_exc/exchange/all-gather",
     "exchange"),
    ("jit(_run)/while/body/dynamic_slice", None),
    ("jit(_run)/while", None),
])
def test_scope_of(op_name, scope):
    assert sc.scope_of(op_name) == scope


HLO = """HloModule jit__run, is_scheduled=true

%fused_computation.2 (p: f32[8]) -> f32[8] {
  ROOT %scatter.9 = f32[8] scatter(f32[8] %p), metadata={op_name="jit(_run)/while/body/prop/exc_exc/cond/branch_1_fun/event/scatter-add" source_file="ops.py" source_line=1}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %while.1 = (s32[]) while((s32[]) %t), condition=%c, body=%b, metadata={op_name="jit(_run)/while"}
  %conditional.3 = f32[8] conditional(pred[] %q), branch_computations={%x, %y}, metadata={op_name="jit(_run)/while/body/prop/exc_exc/cond"}
  %fusion.2 = f32[8] fusion(f32[8] %a), kind=kCustom, calls=%fused_computation.2, backend_config={"k":{"v":1}}, metadata={op_name="jit(_run)/while/body/prop/exc_exc/cond/branch_1_fun/event/scatter-add" source_file="ops.py" source_line=1}
  %fusion.4 = f32[8] fusion(f32[8] %v), kind=kLoop, calls=%f4, metadata={op_name="jit(_run)/while/body/neuron/exc/add"}
  %copy.5 = f32[8] copy(f32[8] %v)
  ROOT %fusion.6 = f32[8] fusion(f32[8] %v), kind=kLoop, calls=%f6, metadata={op_name="jit(_run)/while/body/prop/inh_exc/cond/branch_0_fun/fallback/scatter-add"}
}

HloModule jit_other

ENTRY %main.2 (a: f32[8]) -> f32[8] {
  ROOT %fusion.4 = f32[8] fusion(f32[8] %v), kind=kLoop, calls=%f4, metadata={op_name="jit(other)/neuron/inh/add"}
}
"""


def test_hlo_scopes_by_module_and_instruction():
    m = sc.hlo_scopes(HLO)
    assert m[("jit__run", "fusion.2")] == "prop/exc_exc/event"
    assert m[("jit__run", "scatter.9")] == "prop/exc_exc/event"
    assert m[("jit__run", "conditional.3")] == "prop/exc_exc"
    assert m[("jit__run", "fusion.4")] == "neuron/exc"
    assert m[("jit_other", "fusion.4")] == "neuron/inh"
    assert m[("jit__run", "while.1")] is None
    assert ("jit__run", "copy.5") not in m          # no metadata


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=[])


def op(instr, start, dur):
    return ev(f"%{instr} = f32[8] {instr.split('.')[0]}(f32[8] %a)",
              start, dur)


def fake(ops, host, modules):
    return NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python", events=host)]),
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=modules),
            NS(name="XLA Ops", events=ops)]),
    ])


def synthetic():
    # window 1000..11000 ns.  jit__run runs 1500..9500: its while loop
    # (2000-7000) holds a conditional (2000-5000) that holds the event
    # scatter (2500-4500); then the neuron update, an XLA copy, the
    # fallback.  A jit_other op lies at 10000..10500.
    modules = [ev("jit__run(123)", 1500, 8000),
               ev("jit_other(77)", 9800, 1000)]
    ops = [op("while.1", 2000, 5000),
           op("conditional.3", 2000, 3000),
           op("fusion.2", 2500, 2000),
           op("fusion.4", 5000, 1000),
           op("copy.5", 6000, 500),
           op("fusion.6", 7500, 1500),
           op("fusion.4", 10000, 500)]
    host = [ev(P + "window", 1000, 10_000),
            ev(P + "dispatch", 1000, 1000),
            ev("repro.run", 1100, 800),
            ev("repro.build", 1000, 6500),
            ev("repro.device_init", 1000, 6200),
            ev("repro.serve_chunk", 9000, 1200)]
    return fake(ops, host, modules)


def test_scope_attribution_by_hand():
    r = sc.reduce_profile(synthetic(), sc.hlo_scopes(HLO), P)
    assert r["window_s"] == pytest.approx(10_000e-9)
    # busy: 2000-7000, 7500-9000, 10000-10500
    assert r["busy_s"] == pytest.approx(7_000e-9)
    assert r["scope_s"] == {
        "prop/exc_exc/event": pytest.approx(2_000e-9),
        "prop/exc_exc": pytest.approx(1_000e-9),    # the cond's own time
        "neuron/exc": pytest.approx(1_000e-9),
        "prop/inh_exc/fallback": pytest.approx(1_500e-9),
        "neuron/inh": pytest.approx(500e-9),        # jit_other's fusion.4
    }
    # the loop's own 500 ns (5000 less the conditional, the neuron update
    # and the copy) and the copy's 500
    assert r["unscoped_s"] == pytest.approx(1_000e-9)
    assert r["scopes"][0] == ["prop/exc_exc/event", pytest.approx(2e-6)]
    # 1000-2000: run overlaps 800, build and device_init 1000 each, and
    # the shorter (device_init) is the innermost; 9000-10000: serve_chunk;
    # 7000-7500: build 500, device_init 200; 10500-11000: no span
    assert r["idle_gaps_program"] == [
        ["device_init", pytest.approx(1e-6)],
        ["serve_chunk", pytest.approx(1e-6)],
        ["build", pytest.approx(5e-7)],
        ["none", pytest.approx(5e-7)]]
    total = sum(r["scope_s"].values()) + r["unscoped_s"]
    assert total == pytest.approx(r["busy_s"])

    s = sc.shares(r)
    assert s["prop_scope_share"] == pytest.approx(100 * 4.5 / 7)
    assert s["prop_fallback_share"] == pytest.approx(100 * 1.5 / 4.5)
    assert s["neuron_update_share"] == pytest.approx(100 * 1.5 / 7)
    assert s["unscoped_share"] == pytest.approx(100 * 1 / 7)


def test_a_program_without_scopes_reads_nothing():
    r = sc.reduce_profile(synthetic(), {}, P)
    assert r["scope_s"] == {} and r["scopes"] == []
    assert r["unscoped_s"] == pytest.approx(r["busy_s"])
    assert sc.shares(r) is None


def test_missing_window_raises():
    with pytest.raises(ValueError):
        sc.reduce_profile(fake([], [], []), {}, P)


def _recorded():
    pb = DATA / f"{RECORDED}.xplane.pb"
    hlo = DATA / f"{RECORDED}.hlo.txt.gz"
    for f in (pb, hlo):
        if not f.exists():
            pytest.fail(f"{f.name} is missing")
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(pb))
    scopes = sc.hlo_scopes(gzip.decompress(hlo.read_bytes()).decode())
    return pd, scopes


def test_recorded_trace_by_scope():
    pd, scopes = _recorded()
    r = sc.reduce_profile(pd, scopes, P)
    base = tr.reduce_profile(pd, P)
    expected = json.loads((DATA / f"{RECORDED}.scopes.json").read_text())
    assert r["busy_s"] == pytest.approx(base["busy_s"], rel=1e-9)
    # every busy second is under a scope or under none
    total = sum(r["scope_s"].values()) + r["unscoped_s"]
    assert total == pytest.approx(r["busy_s"], rel=1e-3)
    s = sc.shares(r)
    prop_kernel_share = 100.0 * base["prop_s"] / base["busy_s"]
    assert abs(s["prop_scope_share"] - prop_kernel_share) <= 1.0
    # each group's scatter, with the branch it took
    named = {k for k, _ in r["scopes"]}
    for g in ("exc_exc", "exc_inh", "inh_exc", "inh_inh"):
        assert {f"prop/{g}/event", f"prop/{g}/fallback"} & named, g
    for k, v in expected["scope_s"].items():
        assert r["scope_s"][k] == pytest.approx(v, rel=1e-9), k
    assert {k for k, _ in r["idle_gaps_program"]} <= {
        "run", "none"}


def test_recorded_trace_existing_reduction_unchanged():
    """The opcode-based reduction reads the new fixture as the recorder
    wrote it."""
    pd, _ = _recorded()
    r = tr.reduce_profile(pd, P)
    expected = json.loads((DATA / f"{RECORDED}.json").read_text())
    for k in ("window_s", "busy_s", "prop_s"):
        assert r[k] == pytest.approx(expected[k], rel=1e-9)


SCOPE_METRICS = ("prop_scope_share", "prop_fallback_share",
                 "neuron_update_share", "unscoped_share")


@pytest.mark.parametrize("name", SCOPE_METRICS)
def test_scope_metric_reads_the_recorded_trace(name):
    pd, scopes = _recorded()
    reader = importlib.import_module(f"chipbench.metrics.{name}")
    expected = sc.shares(
        json.loads((DATA / f"{RECORDED}.scopes.json").read_text()))
    ctx = {"scopes": sc.reduce_profile(pd, scopes, P)}
    assert reader.read(ctx) == pytest.approx(expected[name], rel=1e-9)
    assert reader.read({"scopes": None}) is None
    # a program without named scopes reads nothing
    assert reader.read({"scopes": sc.reduce_profile(pd, {}, P)}) is None
