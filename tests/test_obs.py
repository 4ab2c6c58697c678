"""Observability subsystem: tracing, telemetry, health monitors (PR 7).

Covers the acceptance criteria of the telemetry issue:

- span nesting / thread-safety / bounded-cap drop accounting, and Chrome
  trace_event schema validity of the exported JSON;
- the gateway's /metrics text staying byte-compatible with the PR 6
  renderer after its migration onto repro.obs.telemetry;
- the on-device health monitor against a pure-numpy oracle (exact spike
  counts, EMA fold, silent/saturated band flags, NaN guard tripping on an
  induced conductance blow-up);
- monitor-off builds producing the *same jaxpr* as unmonitored builds
  (strictly zero-cost when disabled);
- host vs sharded (up to 8 forced host devices in CI) HealthReport
  bitwise agreement for both ``run`` and ``serve_chunk``, with the
  under-scaled PN->KC configuration flagged silent;
- the ``--trace`` CLI flag (success and unwritable-path exit codes) and
  the HTTP ``/v1/trace`` debug endpoint.
"""

import asyncio
import contextlib
import json
import re
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.models.izhikevich_net import (IzhikevichNetConfig,
                                              compile_model as compile_izh,
                                              spec as izh_spec)
from repro.core.models.mushroom_body import (MushroomBodyConfig,
                                             compile_model as compile_mb)
from repro.core.snn.spec import SpecError
from repro.launch.mesh import make_snn_mesh
from repro.obs import trace as obs_trace
from repro.obs.health import HealthConfig
from repro.obs.telemetry import (Counter, LatencyWindow, MetricsRegistry,
                                 PromText, format_labels)
from repro.obs.trace import TraceCollector, validate_chrome_trace


def _n_dev() -> int:
    """Devices for in-process sharded tests, capped at 8 (same rationale
    as tests/test_engine_sharded.py)."""
    return min(jax.device_count(), 8)


# ---------------------------------------------------------------------------
# trace: spans, thread-safety, Chrome export
# ---------------------------------------------------------------------------

def test_span_nesting_and_chrome_export(tmp_path):
    c = TraceCollector()
    with c.span("outer", model="m"):
        with c.span("inner", k=1):
            pass
        c.instant("tick", j=2)
    evs = c.events()
    assert [e["name"] for e in evs] == ["inner", "tick", "outer"]
    inner, tick, outer = evs
    # nesting is ts/dur containment per tid (how the viewer reconstructs)
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert outer["ts"] <= tick["ts"] <= outer["ts"] + outer["dur"]
    assert inner["tid"] == outer["tid"]
    # every span counts the backend compiles it made
    assert outer["args"] == {"model": "m", "compiles": 0}

    path = tmp_path / "trace.json"
    assert c.export(str(path)) == 3
    doc = json.loads(path.read_text())
    assert validate_chrome_trace(doc) is None
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"]["dropped_events"] == 0


def test_jax_profiler_trace_raises_when_profiler_cannot_start(monkeypatch,
                                                             tmp_path):
    """A run that asked for a device trace must not finish without one."""
    import jax.profiler

    from repro.obs.profile import ProfilerUnavailable, jax_profiler_trace

    def refuse(logdir, *a, **k):
        raise RuntimeError("profiler already running")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    ran = []
    with pytest.raises(ProfilerUnavailable, match="already running"):
        with jax_profiler_trace(str(tmp_path)):
            ran.append(True)
    assert ran == []


def test_span_records_even_when_body_raises():
    c = TraceCollector()
    with pytest.raises(RuntimeError):
        with c.span("failing"):
            raise RuntimeError("boom")
    assert [e["name"] for e in c.events()] == ["failing"]


def test_collector_thread_safety_and_bounded_cap():
    cap, threads, per_thread = 512, 8, 200
    c = TraceCollector(cap=cap)

    def work(i):
        for j in range(per_thread):
            with c.span(f"t{i}", j=j):
                pass

    ts = [threading.Thread(target=work, args=(i,)) for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    evs = c.events()
    assert len(evs) == cap
    assert c.dropped == threads * per_thread - cap
    assert validate_chrome_trace(c.chrome_trace()) is None
    # every retained event is fully formed (no torn writes)
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in evs)


def test_collector_disabled_records_nothing():
    c = TraceCollector(enabled=False)
    with c.span("x"):
        c.instant("y")
    assert c.events() == []


def test_validate_chrome_trace_rejects_malformed():
    assert validate_chrome_trace([]) == "document is not an object"
    assert "traceEvents" in validate_chrome_trace({})
    assert "missing 'ts'" in validate_chrome_trace(
        {"traceEvents": [{"name": "a", "ph": "X", "pid": 1, "tid": 1}]})
    assert "unknown phase" in validate_chrome_trace(
        {"traceEvents": [{"name": "a", "ph": "Z", "ts": 0,
                          "pid": 1, "tid": 1}]})
    assert "non-negative dur" in validate_chrome_trace(
        {"traceEvents": [{"name": "a", "ph": "X", "ts": 0,
                          "pid": 1, "tid": 1, "dur": -1}]})


# ---------------------------------------------------------------------------
# telemetry: windows, registry, renderer
# ---------------------------------------------------------------------------

def test_latency_window_percentiles_and_lifetime_count():
    w = LatencyWindow(cap=10)
    for v in range(100):
        w.add(float(v))
    assert w.count == 100                    # lifetime
    assert w.samples() == [float(v) for v in range(90, 100)]  # windowed
    assert w.percentile(0.0) == 90.0
    assert w.percentile(1.0) == 99.0
    s = w.summary()
    assert s["count"] == 100 and s["max"] == 99.0
    assert s["p50"] == pytest.approx(94.0, abs=1.0)


def test_gateway_reexports_telemetry_latency_window():
    from repro.launch.gateway import LatencyWindow as GatewayLW
    assert GatewayLW is LatencyWindow


def test_metrics_registry_render_and_type_conflicts():
    reg = MetricsRegistry()
    c = reg.counter("requests_total")
    c.inc(model="a")
    c.inc(2, model="a")
    reg.gauge("slots").set(8, model="a")
    h = reg.histogram("lat_s", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    with pytest.raises(ValueError):
        reg.gauge("requests_total")          # registered as a Counter
    assert reg.counter("requests_total") is c  # same-type re-registration
    txt = reg.render()
    assert txt.endswith("\n")
    assert 'requests_total{model="a"} 3' in txt
    assert 'slots{model="a"} 8' in txt
    assert 'lat_s_bucket{le="0.1"} 1' in txt
    assert 'lat_s_bucket{le="+Inf"} 2' in txt
    assert "lat_s_count 2" in txt
    assert format_labels({}) == ""


def test_prom_text_quantiles_formatting():
    out = PromText()
    out.quantiles("g_seconds", {"model": "m"},
                  {"p50": 1.5, "p99": 2.0, "count": 7}, unit=1e-3)
    assert out.render() == (
        'g_seconds{model="m",quantile="50"} 0.001500\n'
        'g_seconds{model="m",quantile="99"} 0.002000\n'
        'g_seconds_count{model="m"} 7\n')


# ---------------------------------------------------------------------------
# shared models (module-scoped: builds are the expensive part)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def izh_mon():
    """Small monitored izhikevich host build + its config."""
    cfg = IzhikevichNetConfig(n_total=60, n_conn=10, seed=2)
    return compile_izh(cfg, monitor=HealthConfig()), cfg


@pytest.fixture(scope="module")
def mb_silent_pair():
    """Host + sharded monitored mushroom-body builds with PN->KC
    deliberately under-scaled (the paper's 'insufficient spiking' failure
    mode: KCs never fire).  The default collector is cleared first so the
    trace-content test can assert exactly what these builds emitted."""
    obs_trace.clear()
    cfg = MushroomBodyConfig(n_pn=16, n_lhi=4, n_kc=64, n_dn=12,
                             g_pn_kc=1e-6, seed=5)
    mon = HealthConfig(ema_tau_ms=5.0)
    host = compile_mb(cfg, monitor=mon)
    eng = compile_mb(cfg, mesh=make_snn_mesh(_n_dev()), monitor=mon)
    return host, eng, cfg


def _report_leaves_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# health monitor: numpy oracle, NaN guard, zero-cost-off, host/sharded parity
# ---------------------------------------------------------------------------

def test_health_report_matches_numpy_oracle(izh_mon):
    model, cfg = izh_mon
    mon = model.monitor
    T = 40
    with pytest.deprecated_call():           # legacy raster IS the oracle
        res = model.run(T, record_raster=True)
    rep = res.health
    assert rep is not None

    alpha = np.float32(mon.alpha(cfg.dt))
    for pop in ("exc", "inh"):
        n = model.network.populations[pop].n
        raster = np.asarray(res.raster[pop])         # [T, n] bool
        per_step = raster.sum(axis=1).astype(np.int64)
        assert int(np.asarray(rep.spike_total[pop])) == int(per_step.sum())

        inv = np.float32(1.0 / (n * cfg.dt * 1e-3))
        ema = np.float32(0.0)
        for c in per_step:
            rate = np.float32(c) * inv
            ema = ema + alpha * (rate - ema)
        np.testing.assert_allclose(np.asarray(rep.rate_ema_hz[pop]),
                                   ema, rtol=1e-5, atol=1e-6)
        mean = per_step.sum() * float(inv) / T
        np.testing.assert_allclose(np.asarray(rep.mean_rate_hz[pop]),
                                   mean, rtol=1e-5, atol=1e-6)
        lo, hi = mon.band(pop)
        assert bool(np.asarray(rep.silent[pop])) == (float(ema) < lo)
        assert bool(np.asarray(rep.saturated[pop])) == (float(ema) > hi)
    assert int(np.asarray(rep.steps)) == T
    assert not bool(np.asarray(rep.nonfinite))
    assert int(np.asarray(rep.first_bad_step)) == -1


def test_unmonitored_run_has_no_health(izh_mon):
    _, cfg = izh_mon
    plain = compile_izh(cfg)
    assert plain.monitor is None
    assert plain.run(5).health is None


def test_nan_guard_trips_on_conductance_blowup():
    # over-scaling PN->KC past the explicit-coupling stability bound is the
    # paper's float-overflow phenomenon (mushroom_body module docstring)
    cfg = MushroomBodyConfig(n_pn=16, n_lhi=4, n_kc=64, n_dn=12, seed=5)
    model = compile_mb(cfg, monitor=HealthConfig())
    T = 300
    res = model.run(T, gscales={"PN_KC": jnp.float32(500.0)})
    rep = res.health
    assert bool(np.asarray(rep.nonfinite))
    assert not bool(np.asarray(res.finite))
    assert 0 <= int(np.asarray(rep.first_bad_step)) < T


def test_monitor_off_build_has_identical_jaxpr(izh_mon):
    _, cfg = izh_mon
    off = compile_izh(cfg, monitor=HealthConfig(enabled=False))
    plain = compile_izh(cfg)
    assert off.monitor is None

    def jaxpr_of(model):
        st = model.init_state(jax.random.PRNGKey(0))
        return str(jax.make_jaxpr(
            lambda s: model.simulator.run(s, 7))(st))

    assert jaxpr_of(off) == jaxpr_of(plain)


def test_monitor_validation_errors(izh_mon):
    _, cfg = izh_mon
    with pytest.raises(SpecError, match="monitor"):
        compile_izh(cfg, monitor=HealthConfig(
            bands_hz={"nope": (1.0, 2.0)}))
    with pytest.raises(ValueError, match="ema_tau_ms"):
        HealthConfig(ema_tau_ms=0.0).validate(["exc"])
    with pytest.raises(ValueError, match="lo > hi"):
        HealthConfig(bands_hz={"exc": (5.0, 1.0)}).validate(["exc"])


def test_host_vs_sharded_health_bitwise_run(mb_silent_pair):
    host, eng, _ = mb_silent_pair
    T = 60
    rh, re = host.run(T), eng.run(T)
    assert rh.health is not None and re.health is not None
    assert _report_leaves_equal(rh.health, re.health)
    # the under-scaled PN->KC configuration is flagged: KCs silent, PNs not
    assert bool(np.asarray(rh.health.silent["KC"]))
    assert not bool(np.asarray(rh.health.silent["PN"]))
    assert not bool(np.asarray(rh.health.nonfinite))


def test_host_vs_sharded_health_bitwise_serve(mb_silent_pair):
    host, eng, _ = mb_silent_pair
    S, C = 2, 12
    steps_left = np.array([12, 5], np.int32)
    n_pn = host.network.populations["PN"].n
    rng = np.random.default_rng(0)
    stim = {"PN": rng.normal(size=(S, C, n_pn)).astype(np.float32)}
    outs = []
    for model in (host, eng):
        keys = jnp.stack([jax.random.PRNGKey(i) for i in range(S)])
        st = model.init_stream_state(keys)
        out = model.serve_chunk(st, stim, steps_left, C)
        assert len(out) == 5                 # monitored -> health appended
        outs.append(out[4])
    assert _report_leaves_equal(*outs)
    for slot in range(S):
        s = outs[0].summary(slot)
        assert s["steps"] == int(steps_left[slot])
        assert s["populations"]["KC"]["silent"]


def test_trace_contains_build_autotune_and_serve_spans(mb_silent_pair,
                                                       tmp_path):
    host, eng, _ = mb_silent_pair
    # the fixture cleared the collector before building; the sharded run
    # and serve tests above dispatched through the traced entry points
    host.run(3)
    eng.run(3)
    eng.run(3)                               # cache hit -> compile=False
    # the mushroom body's groups are all dense; an Izhikevich net's sparse
    # groups ask the autotuner for their propagation mode
    compile_izh(IzhikevichNetConfig(n_total=60, n_conn=10, seed=2))
    names = {e["name"] for e in obs_trace.events()}
    assert {"build", "validate", "codegen", "shard", "run"} <= names
    assert "choose_propagation" in names     # autotune decision audit
    run_spans = [e for e in obs_trace.events() if e["name"] == "run"]
    assert any(e["args"].get("sharded") for e in run_spans)
    assert any(e["args"].get("compile") is False for e in run_spans)

    path = tmp_path / "acceptance_trace.json"
    obs_trace.export(str(path))
    doc = json.loads(path.read_text())
    assert validate_chrome_trace(doc) is None
    tuned = [e for e in doc["traceEvents"]
             if e["name"] == "choose_propagation"]
    assert tuned and all({"tag", "mode", "capacity", "event_occupancy"}
                         <= set(e["args"]) for e in tuned)


# ---------------------------------------------------------------------------
# named scopes in the run program; the program's spans on the profiler
# ---------------------------------------------------------------------------

# 2,000 neurons x 100 targets: every group propagates in event mode
EVENT_CFG = IzhikevichNetConfig(n_total=2000, n_conn=100, seed=3)


def _build_event_net(sharded: bool):
    """The event-mode net with a spike probe, as the chip benchmark runs
    it."""
    spec = izh_spec(EVENT_CFG)
    spec.probe("exc_spikes", "exc", "spikes")
    mesh = make_snn_mesh(_n_dev()) if sharded else None
    return spec.build(dt=EVENT_CFG.dt, seed=EVENT_CFG.seed, mesh=mesh)


@pytest.fixture(scope="module", params=[False, True],
                ids=["host", "sharded"])
def event_net(request):
    """(model, optimized HLO text of its 5-step run program)."""
    model = _build_event_net(request.param)
    return model, model.run_hlo(5)


def _scoped(text: str, *parts: str) -> bool:
    """Some instruction's op_name holds the scope segments ``parts`` in
    order (JAX's own segments may lie between them)."""
    pat = "(^|/)" + "/(.+/)?".join(re.escape(p) for p in parts) + "/"
    return any(re.search(pat, n)
               for n in re.findall(r'op_name="([^"]*)"', text))


def _instructions(text: str) -> list:
    """The HLO's computations and instructions, metadata stripped (the
    source-location tables go too: they are debug information)."""
    return [re.sub(r", metadata=\{[^}]*\}", "", ln)
            for ln in text.splitlines()
            if " = " in ln or ln.startswith(("HloModule", "ENTRY", "%"))]


def test_run_hlo_names_every_group_population_and_branch(event_net):
    model, text = event_net
    assert {g.propagation_mode for g in model.network.synapses} == {"event"}
    for g in model.network.synapses:
        for part in ("compact", "event", "fallback"):
            assert _scoped(text, f"prop/{g.name}", part), (g.name, part)
    for pop in model.network.populations:
        assert _scoped(text, f"neuron/{pop}")
    for scope in ("rng", "probe"):
        assert _scoped(text, scope)
    assert _scoped(text, "exchange") == (model.engine is not None)


def test_named_scopes_leave_the_program_unchanged(event_net, monkeypatch):
    model, text = event_net
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _build_event_net(model.engine is not None).run_hlo(5)
    assert "prop/" not in plain
    assert _instructions(plain) == _instructions(text)


def test_run_is_a_span_on_the_jax_profiler(tmp_path):
    from jax.profiler import ProfileData
    model = compile_izh(IzhikevichNetConfig(n_total=60, n_conn=10, seed=4))
    jax.block_until_ready(model.run(2))
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(model.run(2))
    (path,) = tmp_path.glob("**/*.xplane.pb")
    names = {ev.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events}
    assert "repro.run" in names


def test_run_span_counts_its_compiles():
    model = compile_izh(IzhikevichNetConfig(n_total=60, n_conn=10, seed=5))
    seen = len(obs_trace.events())
    # a persistent-cache hit would load instead of compile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        for n in (3, 3, 4):
            jax.block_until_ready(model.run(n))
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    runs = [e["args"] for e in obs_trace.events()[seen:]
            if e["name"] == "run"]
    assert [a["n_steps"] for a in runs] == [3, 3, 4]
    first, repeat, new_length = (a["compiles"] for a in runs)
    assert first >= 1 and repeat == 0 and new_length >= 1


def test_run_hlo_is_the_program_run_executed():
    model = compile_izh(IzhikevichNetConfig(n_total=60, n_conn=10, seed=6))
    jax.block_until_ready(model.run(3, gscales={"exc": 1.5}))
    programs = len(model._run_cache)
    assert "prop/" in model.run_hlo(3, gscales={"exc": 1.5})
    assert len(model._run_cache) == programs       # nothing built anew
    model.run_hlo(3)                               # another program
    assert len(model._run_cache) == programs + 1


@contextlib.contextmanager
def _persistent_cache(path):
    """JAX's persistent compile cache in ``path`` for the block, every
    program cached however quick its compile; the process's cache settings
    come back after it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_compilation_cache_include_metadata_in_key")
    was = {n: getattr(jax.config, n) for n in names}
    cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        yield
    finally:
        for n, v in was.items():
            jax.config.update(n, v)
        cc.reset_cache()


def test_run_hlo_names_the_source_that_ran_with_the_cache_on(tmp_path,
                                                             monkeypatch):
    """Under the entry points' persistent cache a program that differs from
    a cached one only in its scope names compiles anew, so run_hlo names
    the scopes of the source that ran (a key without metadata would load
    the cached program, names and all)."""
    from repro.launch import compile_cache as CC
    cfg = IzhikevichNetConfig(n_total=60, n_conn=10, seed=7)
    monkeypatch.setenv(CC.CACHE_ENV, str(tmp_path))
    with _persistent_cache(tmp_path):
        CC.enable_compile_cache()
        with monkeypatch.context() as m:
            scope = jax.named_scope
            m.setattr(jax, "named_scope",
                      lambda name: scope(name.replace("prop/", "old/")))
            jax.block_until_ready(compile_izh(cfg).run(3))
        assert any(tmp_path.iterdir())               # the old names cached
        model = compile_izh(cfg)
        jax.block_until_ready(model.run(3))
        text = model.run_hlo(3)
    assert "prop/" in text and "old/" not in text


def test_run_hlo_refuses_a_cache_keyed_without_metadata(tmp_path):
    model = compile_izh(IzhikevichNetConfig(n_total=60, n_conn=10, seed=8))
    with _persistent_cache(tmp_path):
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          False)
        with pytest.raises(RuntimeError, match="metadata"):
            model.run_hlo(3)


# ---------------------------------------------------------------------------
# serving integration: SNNServer health, gateway /metrics + /v1/trace, CLI
# ---------------------------------------------------------------------------

def test_snn_server_streams_health(izh_mon):
    from repro.launch.snn_serve import SNNServer, StreamRequest
    model, _ = izh_mon
    n = model.network.populations["exc"].n
    srv = SNNServer(model, max_streams=2, chunk=8, stim_pops=("exc",))
    rng = np.random.default_rng(1)
    for i, T in enumerate((20, 11)):
        stim = {"exc": (3.0 * rng.normal(size=(T, n))).astype(np.float32)}
        srv.submit(StreamRequest(rid=i, n_steps=T, stim=stim, seed=i))
    finished = srv.run()
    assert len(finished) == 2
    for r in finished:
        assert all(c.health is not None for c in r.chunks)
        h = r.health
        assert h["steps"] == r.n_steps
        assert not h["nonfinite"] and h["first_bad_step"] == -1
        # chunk summaries aggregate: spike totals sum to the stream total
        assert h["populations"]["exc"]["spikes"] == int(
            np.sum(r.spike_counts["exc"]))


def test_gateway_metrics_text_bit_compatible_with_pr6(izh_mon):
    from repro.launch.gateway import Gateway
    model, _ = izh_mon
    gw = Gateway(chunk=6, buckets=(2,), warm=False, clock=lambda: 42.0)
    gw.register("izh", model, stim_pops=("exc",))
    n = model.network.populations["exc"].n
    rng = np.random.default_rng(3)
    for i in range(3):
        stim = {"exc": (3.0 * rng.normal(size=(10, n))).astype(np.float32)}
        gw.submit("izh", stim, 10, seed=i, priority=i % 2)
    gw.run_until_drained()

    # the PR 6 renderer, verbatim — the dashboard contract this PR must
    # not break while migrating onto obs.telemetry's PromText
    m = gw.metrics()
    lines = [f"gateway_uptime_seconds {m['uptime_s']:.3f}"]
    for name, wm in sorted(m["models"].items()):
        lab = f'{{model="{name}"}}'
        for c, v in sorted(wm["counters"].items()):
            lines.append(f"gateway_{c}_total{lab} {v}")
        lines.append(f"gateway_slots{lab} {wm['bucket']}")
        lines.append(f"gateway_active_streams{lab} {wm['active']}")
        lines.append(f"gateway_queued_streams{lab} {wm['queued']}")
        lines.append(f"gateway_slot_occupancy{lab} {wm['occupancy']:.4f}")
        lines.append(f"gateway_chunks_total{lab} {wm['chunks']}")
        for metric, unit in (("queue_wait_s", 1.0),
                             ("total_latency_s", 1.0),
                             ("step_latency_us", 1e-6)):
            s = wm[metric]
            base = metric.rsplit("_", 1)[0]
            for q in ("p50", "p99"):
                lines.append(
                    f'gateway_{base}_seconds{{model="{name}",'
                    f'quantile="{q[1:]}"}} {s[q] * unit:.6f}')
            lines.append(f'gateway_{base}_seconds_count{lab} {s["count"]}')
    expected = "\n".join(lines) + "\n"

    assert gw.render_metrics() == expected   # byte-identical (frozen clock)
    assert 'gateway_completed_total{model="izh"} 3' in expected


def test_http_trace_endpoint(izh_mon):
    from repro.launch.gateway import Gateway
    from repro.launch.gateway_http import GatewayHTTP
    model, _ = izh_mon

    async def scenario():
        gw = Gateway(chunk=6, buckets=(2,), warm=False)
        gw.register("izh", model, stim_pops=("exc",))
        srv = GatewayHTTP(gw, "127.0.0.1", 0, idle_sleep_s=0.001)
        host, port = await srv.start()
        try:
            reader, writer = await asyncio.open_connection(host, port)
            writer.write(b"GET /v1/trace HTTP/1.1\r\nHost: t\r\n\r\n")
            await writer.drain()
            raw = await reader.read()
            writer.close()
            head, _, body = raw.partition(b"\r\n\r\n")
            assert int(head.split()[1]) == 200
            assert b"application/json" in head
            doc = json.loads(body)
            assert validate_chrome_trace(doc) is None
            assert {e["name"] for e in doc["traceEvents"]} >= {"build"}
        finally:
            await srv.stop()

    asyncio.run(scenario())


def test_snn_serve_cli_trace_flag(tmp_path, capsys):
    from repro.launch.snn_serve import main
    path = tmp_path / "cli_trace.json"
    argv = ["--model", "izhikevich", "--streams", "2", "--requests", "1",
            "--steps", "8", "--chunk", "8", "--health"]
    assert main(argv + ["--trace", str(path)]) == 0
    doc = json.loads(path.read_text())
    assert validate_chrome_trace(doc) is None
    assert {e["name"] for e in doc["traceEvents"]} >= {"build",
                                                       "serve_chunk"}
    out = capsys.readouterr().out
    assert "health stream0" in out

    bad = tmp_path / "no_such_dir" / "t.json"
    assert main(argv + ["--trace", str(bad)]) == 1
    assert "cannot write trace file" in capsys.readouterr().err
