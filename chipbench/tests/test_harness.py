"""The harness's own parts on the CPU: the compile clock, the initial
draws, the HLO text a traced run maps to scopes, and a network the harness
has never seen, brought as files alone."""

import json
import sys
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench import scopes as sc
from chipbench import work
from chipbench.modes import run
from chipbench.reference import izh_cortex
from chipbench.tests.conftest import WORKLOADS, small_cell

C = "/jax/core/compile/"
SEED = 2 ** 31 + 101


@pytest.fixture
def clock():
    c = harness.CompileClock()
    yield c
    jax.monitoring.unregister_event_time_span_listener(c.record)


def test_compile_clock_counts_nested_spans_once(clock):
    # an outer trace (0-10) reported after the two traces nested in it,
    # then its lowering and compile; an event of another kind; two
    # compiles that overlap (two threads)
    for event, s, e in [(C + "jaxpr_trace_duration", 1.0, 3.0),
                        (C + "jaxpr_trace_duration", 4.0, 6.0),
                        (C + "jaxpr_trace_duration", 0.0, 10.0),
                        (C + "jaxpr_to_mlir_module_duration", 10.0, 12.0),
                        (C + "backend_compile_duration", 12.0, 20.0),
                        ("/jax/compilation_cache/cache_hits", 20.0, 30.0),
                        (C + "backend_compile_duration", 25.0, 27.0),
                        (C + "backend_compile_duration", 26.0, 29.0)]:
        clock.record(event, s, e)
    assert clock.events == 3
    assert clock.seconds() == pytest.approx(20.0 + 4.0)
    assert clock.seconds(5.0, 26.0) == pytest.approx(15.0 + 1.0)
    # a build over 0-12: the spans' sum (16 s) would exceed its wall time
    assert clock.seconds(0.0, 12.0) == pytest.approx(12.0)
    assert clock.seconds(30.0, 40.0) == 0.0


def test_compile_clock_of_nested_jits_within_wall_time(clock):
    inner = jax.jit(lambda x: jnp.sin(x) * 2)
    outer = jax.jit(lambda x: inner(x) + inner(x * 2.0) + 0.5)
    t0 = time.time()
    outer(jnp.ones(7)).block_until_ready()
    t1 = time.time()
    spans = [(s, e) for s, e in clock.spans if t0 <= s]
    assert clock.events >= 1
    # the inner jits' traces lie inside the outer's
    assert any(a < s and e < b for s, e in spans for a, b in spans)
    assert sum(e - s for s, e in spans) > clock.seconds(t0, t1)
    assert 0 < clock.seconds(t0, t1) <= t1 - t0


def _neurons():
    return {"exc": {"V": jnp.zeros(4), "U": jnp.zeros(4)},
            "inh": {"V": jnp.zeros(3), "U": jnp.zeros(3)}}


def test_uniform_draws_of_izh100k_run_are_pinned():
    rule = harness.load_cell("izh100k.run").traffic["initial"]
    out = run.initial_neurons(_neurons(), rule, harness.model_seed(SEED))
    # the values these draws had before normal draws were added
    expected = {
        "exc": {"V": [-60.092376708984375, -64.28217315673828,
                      -67.5557861328125, -66.68566131591797],
                "U": [-12.726401329040527, -8.824087142944336,
                      -12.164859771728516, -12.370609283447266]},
        "inh": {"V": [-68.10067749023438, -65.83763122558594,
                      -67.25640106201172],
                "U": [-12.633866310119629, -10.663084030151367,
                      -10.476516723632812]}}
    for pop, vs in expected.items():
        for var, values in vs.items():
            np.testing.assert_array_equal(
                np.asarray(out[pop][var]), np.float32(values))


def test_normal_draws():
    big = {"a": {"V": jnp.zeros(20_000)}, "b": {"V": jnp.zeros(20_000)}}
    rule = {"normal": {"V": [-58.0, 10.0]}}
    out = run.initial_neurons(big, rule, harness.model_seed(SEED))
    again = run.initial_neurons(big, rule, harness.model_seed(SEED))
    uniform = run.initial_neurons(big, {"uniform": {"V": [-1.0, 1.0]}},
                                  harness.model_seed(SEED))
    for pop in big:
        v = np.asarray(out[pop]["V"])
        assert v.dtype == np.float32
        assert abs(v.mean() + 58.0) < 0.3 and abs(v.std() - 10.0) < 0.3
        np.testing.assert_array_equal(v, np.asarray(again[pop]["V"]))
        # not the uniform draw's key, rescaled
        u = np.asarray(uniform[pop]["V"])
        assert abs(np.corrcoef(u, v)[0, 1]) < 0.05
    assert not np.array_equal(out["a"]["V"], out["b"]["V"])
    with pytest.raises(ValueError, match="drawn twice"):
        run.initial_neurons(big, {"normal": {"V": [0, 1]},
                                  "uniform": {"V": [0, 1]}}, 1)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_program_hlo_names_the_scopes_of_the_calls(workload):
    cell = small_cell(workload)
    mseed = harness.model_seed(SEED)
    mode = run.Mode(harness.build_model(cell.config, mseed), cell.config,
                    cell.traffic, mseed)
    mode.warm_up()
    scopes = set(sc.hlo_scopes(mode.program_hlo()).values())
    groups = [g.name for g in mode.model.network.synapses]
    mode.release()
    for g in groups:
        assert any(s and s.startswith(f"prop/{g}") for s in scopes), g
    for pop in cell.config["record_spikes"]:
        assert f"neuron/{pop}" in scopes, pop


def _unseen_reference():
    """izh_cortex, with a layout that names a neuron model the harness has
    never seen: 3 state variables, 41 operations a neuron-step, synaptic
    events of 20 bytes."""
    ref = types.ModuleType("chipbench.reference.unseen_model")
    for name in ("build", "init_state", "simulate", "neurons", "sync"):
        setattr(ref, name, getattr(izh_cortex, name))

    def layout(cfg):
        base = izh_cortex.layout(cfg)
        return {
            "populations": {p: {"n": v["n"], "model": "unseen",
                                "state_vars": 3, "flops": 41}
                            for p, v in base["populations"].items()},
            "synapses": [dict(s, event_bytes=20) for s in base["synapses"]]}
    ref.layout = layout
    return ref


def test_a_new_model_runs_as_files(monkeypatch):
    monkeypatch.setitem(sys.modules, "chipbench.reference.unseen_model",
                        _unseen_reference())
    base = small_cell("izh100k.run")
    config = json.loads(json.dumps(base.config))
    config["reference"] = "unseen_model"
    cell = harness.Cell(
        name="unseen.run", chips=1, config=config, traffic=base.traffic,
        limits=base.limits,
        end_to_end=[{"name": "net_steps_per_s", "unit": "steps/s"},
                    {"name": "setup_s", "unit": "s"}],
        per_layer=[])
    seen = []
    necessary = work.necessary

    def spy(layout, spikes, net_steps):
        out = necessary(layout, spikes, net_steps)
        seen.append((dict(spikes), net_steps, out))
        return out
    monkeypatch.setattr(work, "necessary", spy)

    res = harness.run_cell(cell, SEED, 0.5, False, time.perf_counter(),
                           require_chip=False)
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == {"net_steps_per_s", "setup_s"}
    assert list(res)[-1] == "checks"

    (spikes, steps, w), = seen
    n, k = (config["model_config"][key] for key in ("n_total", "n_conn"))
    events = (spikes["exc"] + spikes["inh"]) * k
    assert events > 0
    assert w == {"events": events,
                 "prop_bytes": events * 20,
                 "state_bytes": steps * n * 3 * 2 * 4,
                 "flops": steps * n * 41 + events}
