"""Serving-gateway tests: deadlines, backpressure, elastic capacity, SLOs.

The load-bearing contract (ISSUE 6 acceptance): streams that are *not*
evicted stay bit-exact against an offline ``model.run`` with the same seed
and stimulus, no matter how many neighbours were evicted mid-flight or how
often the elastic slot table resized around them — for host and sharded
builds.  Deadline logic runs on an injected fake clock so queued *and*
mid-flight eviction paths are deterministic.

Run standalone (the CI `gateway` job does, on 8 fake CPU devices):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m pytest -q tests/test_gateway.py
"""

import asyncio
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.models.izhikevich_net import (IzhikevichNetConfig,
                                              compile_model)
from repro.launch.gateway import (Gateway, GatewayOverloaded, GatewayWorker,
                                  LatencyWindow)
from repro.launch.gateway_http import GatewayHTTP
from repro.launch.mesh import make_snn_mesh


def _n_dev() -> int:
    """Cap at 8 (importing launch.dryrun elsewhere in the suite can force
    512 fake devices; a 512-way shard_map over tiny nets is all rendezvous)."""
    return min(jax.device_count(), 8)


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


@pytest.fixture(scope="module")
def host_model():
    return compile_model(IzhikevichNetConfig(n_total=40, n_conn=6))


def _stim(model, T: int, seed: int, scale: float = 3.0):
    n = model.network.populations["exc"].n
    rng = np.random.default_rng(seed)
    return {"exc": (scale * rng.normal(size=(T, n))).astype(np.float32)}


def _offline_counts(model, req):
    res = model.run(req.n_steps, stim=req.stim,
                    state=model.init_state(jax.random.PRNGKey(req.seed)))
    return res.spike_counts


def _assert_bit_exact(model, reqs):
    for r in reqs:
        off = _offline_counts(model, r)
        for k, v in off.items():
            assert np.array_equal(np.asarray(v), r.spike_counts[k]), (
                f"stream {r.rid} population {k!r} diverged from offline run")


# ---------------------------------------------------------------------------
# select_streams: the gather primitive under eviction + elastic resize
# ---------------------------------------------------------------------------

def test_select_streams_reorders_and_fresh_inits(host_model):
    keys4 = jnp.stack([jax.random.PRNGKey(i) for i in range(4)])
    st = host_model.init_stream_state(keys4)
    # shrink 4 -> 2 keeping slots [3, 1]
    keys2 = jnp.stack([jax.random.PRNGKey(9)] * 2)
    small = host_model.select_streams(st, np.array([3, 1]), keys2)
    for a, b in zip(jax.tree.leaves(small), jax.tree.leaves(st)):
        assert a.shape[0] == 2
        assert np.array_equal(np.asarray(a[0]), np.asarray(b[3]))
        assert np.array_equal(np.asarray(a[1]), np.asarray(b[1]))
    # grow 2 -> 3: slot 2 fresh-inits from its key, others carried over
    keys3 = jnp.stack([jax.random.PRNGKey(i) for i in (0, 0, 42)])
    big = host_model.select_streams(small, np.array([0, 1, -1]), keys3)
    fresh = host_model.init_state(jax.random.PRNGKey(42))
    for g, s, f in zip(jax.tree.leaves(big), jax.tree.leaves(small),
                       jax.tree.leaves(fresh)):
        assert g.shape[0] == 3
        assert np.array_equal(np.asarray(g[0]), np.asarray(s[0]))
        assert np.array_equal(np.asarray(g[1]), np.asarray(s[1]))
        assert np.array_equal(np.asarray(g[2]), np.asarray(f))


@pytest.mark.skipif(jax.device_count() < 2, reason="needs >= 2 devices")
def test_select_streams_sharded_matches_host_semantics():
    model = compile_model(IzhikevichNetConfig(n_total=64, n_conn=8),
                          mesh=make_snn_mesh(_n_dev()))
    keys = jnp.stack([jax.random.PRNGKey(i) for i in range(3)])
    st = model.init_stream_state(keys)
    keys4 = jnp.stack([jax.random.PRNGKey(i) for i in (0, 7, 0, 0)])
    out = model.select_streams(st, np.array([2, -1, 0, 1]), keys4)
    fresh = model.init_state(jax.random.PRNGKey(7))
    for o, s, f in zip(jax.tree.leaves(out), jax.tree.leaves(st),
                       jax.tree.leaves(fresh)):
        assert o.shape[0] == 4
        assert np.array_equal(np.asarray(o[0]), np.asarray(s[2]))
        assert np.array_equal(np.asarray(o[1]), np.asarray(f))
        assert np.array_equal(np.asarray(o[2]), np.asarray(s[0]))
        assert np.array_equal(np.asarray(o[3]), np.asarray(s[1]))


# ---------------------------------------------------------------------------
# lifecycle: completion, deadlines (queued + mid-flight), backpressure
# ---------------------------------------------------------------------------

def test_gateway_completes_streams_bit_exact(host_model):
    gw = Gateway(chunk=8, buckets=(2, 4), warm=False)
    gw.register("izh", host_model, stim_pops=("exc",))
    reqs = [gw.submit("izh", _stim(host_model, 20, i), 20, seed=100 + i)
            for i in range(6)]
    gw.run_until_drained()
    done = gw.collect_finished()
    assert len(done) == 6 and all(r.status == "done" for r in done)
    assert all(r.wait(0) for r in reqs)         # completion event fired
    assert all(r.steps_served == 20 for r in done)
    _assert_bit_exact(host_model, done)
    # accounting pruned on collect (bounded-memory contract)
    w = gw.workers["izh"]
    assert w.requests == {} and w.sched.timings == {}


def test_deadline_evicts_queued_request(host_model):
    """One slot, two requests: the queued one's deadline lapses before a
    slot frees, so it is evicted without ever running."""
    clk = FakeClock()
    gw = Gateway(chunk=4, buckets=(1,), clock=clk, warm=False)
    gw.register("izh", host_model, stim_pops=("exc",))
    a = gw.submit("izh", _stim(host_model, 16, 0), 16, seed=1)
    b = gw.submit("izh", _stim(host_model, 16, 1), 16, seed=2,
                  deadline_ms=50.0)
    gw.tick()                       # admits a; b queued (deadline t=0.05)
    clk.advance(1.0)
    gw.tick()                       # sweep evicts b before admission
    gw.run_until_drained()
    assert a.status == "done" and b.status == "evicted"
    assert b.steps_served == 0      # never admitted
    w = gw.workers["izh"]
    assert w.counters["evicted_queued"] == 1
    assert w.counters["evicted_active"] == 0
    _assert_bit_exact(host_model, [a])


def test_deadline_evicts_mid_flight_and_survivors_stay_exact(host_model):
    """The tentpole invariant: a mid-flight eviction reclaims the slot at
    the chunk boundary, keeps the chunks already streamed, and the
    surviving neighbour stream is still bit-exact vs its offline run."""
    clk = FakeClock()
    gw = Gateway(chunk=5, buckets=(2,), clock=clk, warm=False)
    gw.register("izh", host_model, stim_pops=("exc",))
    doomed = gw.submit("izh", _stim(host_model, 20, 0), 20, seed=11,
                       deadline_ms=100.0)
    survivor = gw.submit("izh", _stim(host_model, 20, 1), 20, seed=12)
    gw.tick()                       # both admitted, one chunk served
    assert doomed.status == "active" and doomed.steps_served == 5
    clk.advance(1.0)                # past doomed's 0.1s deadline
    gw.tick()                       # boundary sweep: mid-flight eviction
    assert doomed.status == "evicted"
    assert doomed.steps_served == 5          # partial results kept
    w = gw.workers["izh"]
    assert w.counters["evicted_active"] == 1
    third = gw.submit("izh", _stim(host_model, 10, 2), 10, seed=13)
    gw.run_until_drained()
    assert survivor.status == "done" and third.status == "done"
    _assert_bit_exact(host_model, [survivor, third])
    # evicted partial chunks match the offline prefix too: eviction only
    # masks the lane, it never rewrites what was already streamed
    off = _offline_counts(host_model, doomed)
    got = doomed.spike_counts
    res = host_model.run(5, stim={"exc": doomed.stim["exc"][:5]},
                         state=host_model.init_state(
                             jax.random.PRNGKey(doomed.seed)))
    for k, v in res.spike_counts.items():
        assert np.array_equal(np.asarray(v), got[k])
    assert off is not None          # offline full run computed fine


def test_backpressure_rejects_with_retry_after(host_model):
    gw = Gateway(chunk=4, buckets=(1,), max_queue=2, warm=False)
    gw.register("izh", host_model, stim_pops=("exc",))
    for i in range(2):              # fill the admission queue (never tick)
        gw.submit("izh", _stim(host_model, 8, i), 8, seed=i)
    with pytest.raises(GatewayOverloaded) as ei:
        gw.submit("izh", _stim(host_model, 8, 9), 8, seed=9)
    assert ei.value.model == "izh" and ei.value.queued == 2
    assert ei.value.retry_after_s > 0.0
    w = gw.workers["izh"]
    assert w.counters["rejected"] == 1
    gw.run_until_drained()          # backlog still drains fine
    assert w.counters["completed"] == 2
    with pytest.raises(KeyError, match="unknown model"):
        gw.submit("nope", {}, 4)


def test_priority_classes_order_admission(host_model):
    gw = Gateway(chunk=4, buckets=(1,), warm=False)
    gw.register("izh", host_model, stim_pops=("exc",))
    rids = [gw.submit("izh", _stim(host_model, 4, i), 4, seed=i,
                      priority=p).rid
            for i, p in enumerate([1, 0, 1, 0])]
    w = gw.workers["izh"]
    assert [r.rid for r in w.sched.queue] == [rids[1], rids[3],
                                              rids[0], rids[2]]
    gw.run_until_drained()
    t = {r: w.sched.timings[r].admitted_at for r in rids}
    assert t[rids[1]] <= t[rids[3]] <= t[rids[0]] <= t[rids[2]]


# ---------------------------------------------------------------------------
# elastic capacity
# ---------------------------------------------------------------------------

def test_elastic_grow_and_shrink_keep_streams_exact(host_model):
    """Burst demand grows the slot table to a bigger pre-compiled bucket;
    when the backlog drains the table shrinks back (after the hysteresis
    patience) — and streams alive across both transitions stay exact."""
    gw = Gateway(chunk=5, buckets=(2, 4), shrink_patience=1, warm=False)
    gw.register("izh", host_model, stim_pops=("exc",))
    w = gw.workers["izh"]
    assert w.max_streams == 2
    short = [gw.submit("izh", _stim(host_model, 5, i), 5, seed=40 + i)
             for i in range(3)]
    long = gw.submit("izh", _stim(host_model, 40, 9), 40, seed=49)
    gw.tick()
    assert w.max_streams == 4 and w.counters["grows"] == 1
    gw.run_until_drained()          # shorts finish fast; long stream
    assert w.counters["shrinks"] >= 1       # table shrank under it
    assert w.max_streams == 2
    assert all(r.status == "done" for r in short + [long])
    _assert_bit_exact(host_model, short + [long])


@pytest.mark.skipif(jax.device_count() < 2, reason="needs >= 2 devices")
def test_sharded_gateway_evictions_and_resize_stay_exact():
    """Acceptance: eviction + elastic resize on the sharded build — every
    non-evicted stream bit-exact vs the offline sharded run."""
    model = compile_model(IzhikevichNetConfig(n_total=64, n_conn=8),
                          mesh=make_snn_mesh(_n_dev()))
    clk = FakeClock()
    gw = Gateway(chunk=5, buckets=(2, 4), shrink_patience=1, clock=clk,
                 warm=False)
    gw.register("izh", model, stim_pops=("exc",))
    reqs = []
    for i in range(6):
        dl = 1.0 if i % 3 == 2 else None        # every 3rd: ~instant expiry
        reqs.append(gw.submit("izh", _stim(model, 15, i), 15,
                              seed=300 + i, deadline_ms=dl))
    gw.tick()
    clk.advance(1.0)                # expire the doomed ones mid-run
    gw.run_until_drained()
    done = gw.collect_finished()
    evicted = [r for r in done if r.evicted]
    completed = [r for r in done if r.status == "done"]
    assert len(evicted) == 2 and len(completed) == 4
    w = gw.workers["izh"]
    assert w.counters["grows"] >= 1
    _assert_bit_exact(model, completed)


# ---------------------------------------------------------------------------
# multi-model + observability
# ---------------------------------------------------------------------------

def test_multi_model_roundrobin_and_metrics(host_model):
    other = compile_model(IzhikevichNetConfig(n_total=24, n_conn=4, seed=5))
    gw = Gateway(chunk=6, buckets=(2,), warm=False)
    gw.register("big", host_model, stim_pops=("exc",))
    gw.register("small", other, stim_pops=("exc",))
    with pytest.raises(ValueError, match="already registered"):
        gw.register("big", host_model, stim_pops=("exc",))
    for i in range(3):
        gw.submit("big", _stim(host_model, 12, i), 12, seed=i)
        gw.submit("small", _stim(other, 12, 50 + i), 12, seed=50 + i)
    gw.run_until_drained()
    done = gw.collect_finished()
    assert sorted(r.model for r in done) == ["big"] * 3 + ["small"] * 3
    for name, model in (("big", host_model), ("small", other)):
        _assert_bit_exact(model, [r for r in done if r.model == name])

    m = gw.metrics()
    assert set(m["models"]) == {"big", "small"}
    for wm in m["models"].values():
        assert wm["counters"]["completed"] == 3
        assert wm["counters"]["submitted"] == 3
        assert 0.0 < wm["occupancy"] <= 1.0
        assert wm["step_latency_us"]["p99"] >= wm["step_latency_us"]["p50"]
        assert wm["queue_wait_s"]["count"] == 3
    assert m["counters"]["completed"] == 6      # gateway-wide rollup

    text = gw.render_metrics()
    assert 'gateway_completed_total{model="big"} 3' in text
    assert 'gateway_slot_occupancy{model="small"}' in text
    assert 'quantile="99"' in text and "gateway_uptime_seconds" in text


def test_latency_window_is_bounded_and_percentiled():
    w = LatencyWindow(cap=100)
    assert w.summary() == {"count": 0, "p50": 0.0, "p99": 0.0,
                           "mean": 0.0, "max": 0.0}
    for i in range(1000):
        w.add(float(i))
    assert w.count == 1000                  # lifetime count survives
    assert len(w.samples()) == 100          # window stays bounded
    assert w.percentile(0.0) == 900.0       # oldest retained sample
    assert w.percentile(1.0) == 999.0
    assert w.summary()["max"] == 999.0


def test_worker_rejects_bad_config(host_model):
    with pytest.raises(ValueError, match="buckets"):
        GatewayWorker("x", host_model, buckets=(), stim_pops=("exc",),
                      warm=False)
    with pytest.raises(ValueError, match="max_queue"):
        GatewayWorker("x", host_model, buckets=(2,), max_queue=0,
                      stim_pops=("exc",), warm=False)


# ---------------------------------------------------------------------------
# HTTP front door (stdlib asyncio)
# ---------------------------------------------------------------------------

def test_http_front_door_end_to_end(host_model):
    n = host_model.network.populations["exc"].n

    async def scenario():
        gw = Gateway(chunk=6, buckets=(2,), warm=False)
        gw.register("izh", host_model, stim_pops=("exc",))
        srv = GatewayHTTP(gw, "127.0.0.1", 0, idle_sleep_s=0.001)
        host, port = await srv.start()

        async def http(method, path, body=None):
            reader, writer = await asyncio.open_connection(host, port)
            payload = b"" if body is None else json.dumps(body).encode()
            writer.write((f"{method} {path} HTTP/1.1\r\nHost: t\r\n"
                          f"Content-Length: {len(payload)}\r\n\r\n")
                         .encode() + payload)
            await writer.drain()
            raw = await reader.read()
            writer.close()
            head, _, body_ = raw.partition(b"\r\n\r\n")
            return int(head.split()[1]), head, body_

        try:
            status, _, body = await http("GET", "/healthz")
            assert status == 200 and body.strip() == b"ok"

            stim = (0.5 * np.ones((12, n))).tolist()
            status, _, body = await http(
                "POST", "/v1/simulate",
                {"model": "izh", "n_steps": 12, "seed": 3,
                 "stim": {"exc": stim}})
            assert status == 200
            out = json.loads(body)
            assert out["status"] == "done" and out["steps_served"] == 12
            res = host_model.run(
                12, stim={"exc": np.asarray(stim, np.float32)},
                state=host_model.init_state(jax.random.PRNGKey(3)))
            for k, v in res.spike_counts.items():
                assert np.asarray(v).tolist() == out["spike_counts"][k]
            assert out["total_s"] is not None

            status, _, body = await http(
                "POST", "/v1/simulate", {"model": "nope", "n_steps": 4})
            assert status == 400 and b"unknown model" in body
            status, _, _ = await http("GET", "/v1/simulate")
            assert status == 405
            status, _, _ = await http("GET", "/nope")
            assert status == 404
            status, _, body = await http("GET", "/metrics")
            assert status == 200
            assert b'gateway_completed_total{model="izh"} 1' in body
        finally:
            await srv.stop()

    asyncio.run(scenario())


def test_http_pump_failure_answers_500(host_model):
    """A tick that raises (say, a compile error on the chip) must not kill
    the pump thread silently: the error is recorded, /healthz and every
    pending or later request answer 500 with it, and nothing hangs."""
    n = host_model.network.populations["exc"].n

    class BrokenGateway(Gateway):
        def tick(self):
            if self.has_work():
                raise RuntimeError("tick exploded")
            return False

    async def scenario():
        gw = BrokenGateway(chunk=6, buckets=(2,), warm=False)
        gw.register("izh", host_model, stim_pops=("exc",))
        srv = GatewayHTTP(gw, "127.0.0.1", 0, idle_sleep_s=0.001)
        host, port = await srv.start()

        async def http(method, path, body=None):
            reader, writer = await asyncio.open_connection(host, port)
            payload = b"" if body is None else json.dumps(body).encode()
            writer.write((f"{method} {path} HTTP/1.1\r\nHost: t\r\n"
                          f"Content-Length: {len(payload)}\r\n\r\n")
                         .encode() + payload)
            await writer.drain()
            raw = await reader.read()
            writer.close()
            head, _, body_ = raw.partition(b"\r\n\r\n")
            status = int(head.split()[1])
            return status, json.loads(body_) if status == 500 else body_

        body = {"model": "izh", "n_steps": 6, "seed": 1,
                "stim": {"exc": np.zeros((6, n)).tolist()}}
        try:
            status, _ = await http("GET", "/healthz")
            assert status == 200
            # the pending request fails with the pump's error ...
            status, out = await asyncio.wait_for(
                http("POST", "/v1/simulate", body), timeout=30)
            assert status == 500 and "tick exploded" in out["error"]
            assert gw.failure is not None
            # ... and so do health checks and later submits
            status, out = await http("GET", "/healthz")
            assert status == 500 and "tick exploded" in out["error"]
            status, out = await asyncio.wait_for(
                http("POST", "/v1/simulate", body), timeout=30)
            assert status == 500 and "tick exploded" in out["error"]
        finally:
            await srv.stop()
        assert not srv._pump.is_alive()

    asyncio.run(scenario())


# ---------------------------------------------------------------------------
# soak smoke (the CI job runs the full driver; this keeps it importable
# and its assertions honest at pytest scale)
# ---------------------------------------------------------------------------

def test_soak_smoke_modest_scale():
    from benchmarks.gateway_soak import run_soak

    row = run_soak(streams=36, n_total=24, n_conn=6, n_steps=12, chunk=6,
                   buckets=(4, 8), max_queue=8, burst=12, evict_every=6,
                   verify=True, warm=False)
    assert row["completed"] + row["evicted"] == 36
    assert row["evicted"] >= 36 // 6
    assert row["verified_streams"] == row["completed"]
    assert row["occupancy"] > 0.0
    assert row["p99_step_us"] > 0.0


# ---------------------------------------------------------------------------
# select_streams with heterogeneous dendritic delays (PR 9): the ring
# cursor and per-slot delay state must survive shrink/grow re-packing
# ---------------------------------------------------------------------------

def _delay_model(mesh=None):
    from repro.core.snn.spec import ModelSpec
    from repro.core.snn.synapses import ExpDecay
    from repro.sparse.formats import (FixedFanout, OneToOne, UniformIntDelay,
                                      UniformWeight)
    s = ModelSpec("gw_delay")
    s.add_neuron_population(
        "a", 48, "izhikevich",
        input_fn=lambda k, t, n: 6.0 * jax.random.normal(k, (n,)))
    s.add_neuron_population("b", 24, "izhikevich")
    s.add_synapse_population("ab", "a", "b", connect=FixedFanout(6),
                             weight=UniformWeight(0, 0.8),
                             psm=ExpDecay(4.0), delay=UniformIntDelay(0, 3))
    s.add_synapse_population("bb", "b", "b", connect=OneToOne(),
                             weight=0.2, delay_steps=2)
    return s.build(dt=1.0, seed=5, mesh=mesh)


def _serve1(model, st, n_streams, chunk=6):
    left = jnp.full((n_streams,), 100, jnp.int32)
    return model.serve_chunk(st, {}, left, chunk)[0]


def _slot_eq(tree_a, slot_a, tree_b, slot_b, what=""):
    for a, b in zip(jax.tree.leaves(tree_a), jax.tree.leaves(tree_b)):
        assert np.array_equal(np.asarray(a[slot_a]),
                              np.asarray(b[slot_b])), what


@pytest.mark.parametrize("sharded", [False, True])
def test_ring_cursor_and_delay_state_survive_shrink_grow(sharded):
    """A stream with in-flight spikes parked in its dendritic delay ring
    is shrunk out of a 4-slot table, served, and grown back alongside a
    fresh slot: every state leaf — the ring contents and its cursor
    included — must track an untouched 4-stream control bit for bit."""
    mesh = make_snn_mesh(_n_dev()) if sharded else None
    model = _delay_model(mesh)
    keys4 = jnp.stack([jax.random.PRNGKey(i) for i in range(4)])

    ctrl = _serve1(model, model.init_stream_state(keys4), 4)
    st = _serve1(model, model.init_stream_state(keys4), 4)
    # mid-flight state is non-trivial: something is parked in the ring
    assert np.any(np.asarray(ctrl.syn["ab"].dendritic))

    # shrink 4 -> 2 keeping [3, 1]; the delay state must ride along
    st = model.select_streams(st, np.array([3, 1]),
                              jnp.stack([jax.random.PRNGKey(9)] * 2))
    for gname in ("ab", "bb"):
        for keep, src in ((0, 3), (1, 1)):
            assert np.array_equal(
                np.asarray(st.syn[gname].dendritic[keep]),
                np.asarray(ctrl.syn[gname].dendritic[src])), gname
            assert np.array_equal(np.asarray(st.syn[gname].cursor[keep]),
                                  np.asarray(ctrl.syn[gname].cursor[src]))

    # serve both paths a second chunk; then grow 2 -> 3 with a fresh slot
    ctrl = _serve1(model, ctrl, 4)
    st = _serve1(model, st, 2)
    st = model.select_streams(
        st, np.array([0, 1, -1]),
        jnp.stack([jax.random.PRNGKey(i) for i in (0, 0, 42)]))
    _slot_eq(st, 0, ctrl, 3, "slot 3 after shrink+serve")
    _slot_eq(st, 1, ctrl, 1, "slot 1 after shrink+serve")

    # a third chunk served as the grown 3-batch: the fresh neighbour must
    # not perturb the carried streams' delay state either
    ctrl = _serve1(model, ctrl, 4)
    st = _serve1(model, st, 3)
    _slot_eq(st, 0, ctrl, 3, "slot 3 after grow+serve")
    _slot_eq(st, 1, ctrl, 1, "slot 1 after grow+serve")
    fresh = _serve1(model, model.init_stream_state(
        jnp.stack([jax.random.PRNGKey(42)])), 1)
    _slot_eq(st, 2, fresh, 0, "fresh slot vs solo serve")
