"""Event-driven propagation: bit-exactness, packing, and the propagation= API.

The event path's whole contract is that it is an *optimization, not an
approximation*: compacting the spiking pre rows (with a dense fallback on
capacity overflow), fusing the delay scatter into one kernel, and packing
spikes into uint32 bitmasks for exchange/storage must all reproduce the
dense path bit for bit.  These tests pin that down against a numpy
event-queue oracle (integer weights -> exact float arithmetic), across the
overflow boundary, through full simulations with delays + STDP, across
1-vs-8-device runs, and through the packed spikes-probe ring.
"""

import json
import subprocess
import sys
import textwrap
import warnings
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hypothesis_compat import given, settings, st  # hypothesis or fallback
from repro import flags
from repro.core.snn import bitmask as BM
from repro.core.snn.errors import SpecError
from repro.core.snn.spec import ModelSpec
from repro.core.snn.synapses import (STDP, ExpDecay, LocalConnectivity,
                                     SynapseGroup)
from repro.kernels import ops as kops
from repro.sparse import formats as F

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _int_ell(rng, n_pre, k, n_post, n_slots=1, with_delay=False):
    """Random ELL with small integer weights: float adds are exact, so any
    reordering/fallback bug shows as hard inequality, not tolerance noise."""
    post_ind = rng.integers(0, n_post, (n_pre, k)).astype(np.int32)
    g = rng.integers(1, 8, (n_pre, k)).astype(np.float32)
    valid = rng.random((n_pre, k)) < 0.8
    delay = (rng.integers(0, n_slots, (n_pre, k)).astype(np.int32)
             if with_delay else None)
    if delay is not None:
        delay = np.where(valid, delay, 0).astype(np.int32)
    return F.triple_to_ell(np.where(valid, post_ind, 0).astype(np.int32),
                           np.where(valid, g, 0).astype(np.float32),
                           valid, n_post, delay=delay)


# ---------------------------------------------------------------------------
# numpy event-queue oracle: the fused delay kernel vs literal per-spike
# queue insertion
# ---------------------------------------------------------------------------

def test_fused_delay_matches_numpy_event_queue_oracle():
    rng = np.random.default_rng(0)
    n_pre, k, n_post, n_slots, T = 40, 8, 32, 6, 30
    ell = _int_ell(rng, n_pre, k, n_post, n_slots=n_slots, with_delay=True)
    raster = rng.random((T, n_pre)) < 0.15

    # oracle: per spiking pre neuron, push g onto the (t+delay) queue row
    queue = np.zeros((T + n_slots, n_post), np.float32)
    pi = np.asarray(ell.post_ind)
    gv = np.asarray(ell.g)
    vv = np.asarray(ell.valid)
    dv = np.asarray(ell.delay)
    for t in range(T):
        for i in np.nonzero(raster[t])[0]:
            for kk in range(k):
                if vv[i, kk]:
                    queue[t + dv[i, kk], pi[i, kk]] += gv[i, kk]

    # fused kernel: one [n_slots, n_post] scatter per step
    arrived = np.zeros_like(queue)
    for t in range(T):
        contrib = np.asarray(kops.ell_spmv_delay(
            ell, jnp.asarray(raster[t], jnp.float32), n_slots))
        arrived[t:t + n_slots] += contrib
    assert np.array_equal(arrived, queue)

    # event-driven fused kernel: identical again (integer weights -> exact)
    cap = int(np.max(raster.sum(axis=1))) + 2
    arrived_ev = np.zeros_like(queue)
    for t in range(T):
        contrib = np.asarray(kops.ell_spmv_event_delay(
            ell, jnp.asarray(raster[t], jnp.float32), n_slots, cap))
        arrived_ev[t:t + n_slots] += contrib
    assert np.array_equal(arrived_ev, queue)


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000), rate=st.floats(0.0, 0.6))
def test_event_spmv_bitexact_vs_dense(seed, rate):
    """Compaction never changes a bit, at any activity level, including the
    all-silent and near-dense extremes (random float weights this time —
    the per-post accumulation order must be preserved, not just the sums)."""
    rng = np.random.default_rng(seed)
    n_pre, k, n_post = 60, 7, 48
    post_ind = rng.integers(0, n_post, (n_pre, k)).astype(np.int32)
    g = rng.standard_normal((n_pre, k)).astype(np.float32)
    valid = rng.random((n_pre, k)) < 0.9
    ell = F.triple_to_ell(post_ind, g, valid, n_post)
    spk = jnp.asarray(rng.random(n_pre) < rate, jnp.float32)
    dense = kops.ell_spmv(ell, spk)
    for cap in (8, n_pre // 2, n_pre):
        ev = kops.ell_spmv_event(ell, spk, cap)
        if kops.backend() == "ref":
            # ref scatter-adds in ascending pre order on both paths: exact
            assert np.array_equal(np.asarray(ev), np.asarray(dense)), cap
        else:
            # compaction changes the tile shapes the MXU dot reduces over,
            # so cross-shape sums round differently by ~1 ulp
            np.testing.assert_allclose(np.asarray(ev), np.asarray(dense),
                                       rtol=1e-5, atol=1e-5)


def test_event_overflow_boundary():
    """count == capacity stays on the event path; count == capacity + 1
    falls back to the dense pass — both bit-exact vs dense."""
    rng = np.random.default_rng(3)
    n_pre, k, n_post = 32, 5, 24
    ell = _int_ell(rng, n_pre, k, n_post)
    for n_spk in (10, 11):
        spikes = np.zeros(n_pre, np.float32)
        spikes[rng.choice(n_pre, n_spk, replace=False)] = 1.0
        spk = jnp.asarray(spikes)
        dense = kops.ell_spmv(ell, spk)
        at_cap = kops.ell_spmv_event(ell, spk, 10)
        assert np.array_equal(np.asarray(at_cap), np.asarray(dense)), n_spk


def test_event_spmv_under_vmap_matches_each_stream():
    """Served streams (vmap) propagate as their offline runs do: a stream
    past the capacity takes the dense fallback while its neighbours stay on
    the event path, and each equals its unbatched call bit for bit."""
    rng = np.random.default_rng(5)
    n_pre, k, n_post, n_slots = 32, 5, 24, 4
    post_ind = rng.integers(0, n_post, (n_pre, k)).astype(np.int32)
    g = rng.standard_normal((n_pre, k)).astype(np.float32)
    valid = rng.random((n_pre, k)) < 0.9
    delay = np.where(valid, rng.integers(0, n_slots, (n_pre, k)), 0)
    ell = F.triple_to_ell(post_ind, g, valid, n_post,
                          delay=delay.astype(np.int32))
    spikes = np.zeros((3, n_pre), np.float32)
    for s, n_spk in enumerate((4, 11, 0)):       # capacity 10
        spikes[s, rng.choice(n_pre, n_spk, replace=False)] = 1.0
    spikes = jnp.asarray(spikes)
    for fn in (lambda s: kops.ell_spmv_event(ell, s, 10),
               lambda s: kops.ell_spmv_event_delay(ell, s, n_slots, 10),
               lambda s: kops.ell_spmv(ell, s)):
        batched = np.asarray(jax.jit(jax.vmap(fn))(spikes))
        for s in range(spikes.shape[0]):
            assert np.array_equal(batched[s], np.asarray(fn(spikes[s]))), s


def test_fused_delay_matches_masked_pass_loop():
    """The fused kernel replaces S+1 masked single-delay passes; per slot
    it must reproduce each masked pass bit for bit (random float weights)."""
    rng = np.random.default_rng(5)
    n_pre, k, n_post, n_slots = 48, 6, 40, 5
    post_ind = rng.integers(0, n_post, (n_pre, k)).astype(np.int32)
    g = rng.standard_normal((n_pre, k)).astype(np.float32)
    valid = rng.random((n_pre, k)) < 0.85
    delay = np.where(valid, rng.integers(0, n_slots, (n_pre, k)), 0)
    ell = F.triple_to_ell(post_ind, g, valid, n_post,
                          delay=delay.astype(np.int32))
    spk = jnp.asarray(rng.random(n_pre) < 0.3, jnp.float32)
    fused = np.asarray(kops.ell_spmv_delay(ell, spk, n_slots))
    for d in range(n_slots):
        mask = np.asarray(ell.valid) & (delay == d)
        ell_d = F.triple_to_ell(post_ind, np.where(mask, g, 0), mask, n_post)
        passed = np.asarray(kops.ell_spmv(ell_d, spk))
        if kops.backend() == "ref":
            assert np.array_equal(fused[d], passed), d
        else:       # different kernels, different tile shapes: ~1 ulp
            np.testing.assert_allclose(fused[d], passed,
                                       rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# full-simulation bit-exactness: dense vs event through delays + STDP
# ---------------------------------------------------------------------------

def _event_net(propagation):
    s = ModelSpec("ev")
    s.add_neuron_population(
        "a", 80, "izhikevich",
        input_fn=lambda key, t, n: 6.0 * jax.random.normal(key, (n,)))
    s.add_neuron_population("b", 40, "izhikevich")
    s.add_synapse_population("ab", "a", "b", connect=F.FixedFanout(10),
                             weight=F.UniformWeight(0, 0.8),
                             psm=ExpDecay(4.0),
                             delay=F.UniformIntDelay(0, 4),
                             propagation=propagation)
    s.add_synapse_population("aa", "a", "a",
                             connect=F.FixedProbability(0.15),
                             weight=F.UniformWeight(0, 0.4),
                             wum=STDP(0.01), propagation=propagation)
    s.probe("raster_b", "b", "spikes")
    # engine g lives in partitioned blocks (padded) — a max-reduction probe
    # is the bit-exact cross-backend view of the plastic weights
    s.probe("gmax", "aa", "g", reduce="max", every=5)
    return s


_REF_ONLY = pytest.mark.skipif(
    kops.backend() != "ref",
    reason="the bitwise dense-vs-event contract is defined per backend; on "
           "Pallas backends compaction changes MXU tile shapes (~1 ulp), "
           "which the kernel-level tolerance tests cover instead")


@_REF_ONLY
def test_simulator_event_bitexact_vs_dense_delays_stdp():
    rd = _event_net("dense").build(dt=1.0, seed=9).run(50)
    re_ = _event_net("event").build(dt=1.0, seed=9).run(50)
    for kname in rd.spike_counts:
        assert np.array_equal(np.asarray(rd.spike_counts[kname]),
                              np.asarray(re_.spike_counts[kname])), kname
    # plastic conductances advanced through the event path bit-exactly
    assert np.array_equal(np.asarray(rd.state.syn["aa"].g),
                          np.asarray(re_.state.syn["aa"].g))
    for pname, pop in (("a", 80), ("b", 40)):
        assert np.array_equal(np.asarray(rd.state.neurons[pname]["V"]),
                              np.asarray(re_.state.neurons[pname]["V"]))
    assert np.array_equal(np.asarray(rd.recordings["raster_b"]),
                          np.asarray(re_.recordings["raster_b"]))


def test_engine_event_bitexact_vs_host():
    from repro.launch.mesh import make_snn_mesh
    n_dev = min(jax.device_count(), 8)
    r1 = _event_net("event").build(dt=1.0, seed=4).run(40)
    r2 = _event_net("event").build(dt=1.0, seed=4,
                                   mesh=make_snn_mesh(n_dev)).run(40)
    for kname in r1.spike_counts:
        assert np.array_equal(np.asarray(r1.spike_counts[kname]),
                              np.asarray(r2.spike_counts[kname])), kname
    assert np.array_equal(np.asarray(r1.recordings["raster_b"]),
                          np.asarray(r2.recordings["raster_b"]))
    assert np.array_equal(np.asarray(r1.recordings["gmax"]),
                          np.asarray(r2.recordings["gmax"]))


_SUBPROCESS = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    sys.path.insert(0, {src!r})
    sys.path.insert(0, {testdir!r})
    import numpy as np
    import jax
    from test_snn_event import _event_net
    from repro.launch.mesh import make_snn_mesh
    assert jax.device_count() == 8
    r1 = _event_net("event").build(dt=1.0, seed=2).run(40)
    r8 = _event_net("event").build(dt=1.0, seed=2,
                                   mesh=make_snn_mesh(8)).run(40)
    exact = all(
        np.array_equal(np.asarray(r1.spike_counts[k]),
                       np.asarray(r8.spike_counts[k]))
        for k in r1.spike_counts)
    probes = np.array_equal(np.asarray(r1.recordings["raster_b"]),
                            np.asarray(r8.recordings["raster_b"]))
    g = np.array_equal(np.asarray(r1.recordings["gmax"]),
                       np.asarray(r8.recordings["gmax"]))
    print(json.dumps({{"exact": exact, "probes": probes, "g": g,
                       "finite": bool(r8.finite)}}))
""")


@pytest.mark.slow
def test_event_8_device_subprocess():
    code = _SUBPROCESS.format(src=SRC,
                              testdir=str(Path(__file__).resolve().parent))
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["exact"], "8-device event run diverged from host run"
    assert res["probes"], "packed spikes-probe ring diverged across shards"
    assert res["g"], "STDP conductances diverged across shards"
    assert res["finite"]


# ---------------------------------------------------------------------------
# uint32 bitmask packing
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(n=st.integers(1, 300), seed=st.integers(0, 10_000))
def test_bitmask_round_trip(n, seed):
    rng = np.random.default_rng(seed)
    bits = rng.random(n) < 0.3
    words = BM.pack_spikes(jnp.asarray(bits))
    assert words.dtype == jnp.uint32
    assert words.shape == (BM.words_for(n),)
    assert np.array_equal(np.asarray(BM.unpack_spikes(words, n)), bits)


def test_bitmask_rows_and_segments():
    rng = np.random.default_rng(1)
    # probe-ring row format: [cap, n] packs/unpacks row-independently
    rows = rng.random((7, 70)) < 0.4
    packed = BM.pack_rows(jnp.asarray(rows))
    assert packed.shape == (7, BM.words_for(70))
    assert np.array_equal(np.asarray(BM.unpack_rows(packed, 70)), rows)
    # exchange format: per-device segments concatenate like an all-gather
    segs = rng.random((4, 33)) < 0.5
    words = BM.pack_spikes(jnp.asarray(segs))
    flat = BM.unpack_segments(words, 33)
    assert np.array_equal(np.asarray(flat), segs.reshape(-1))


def test_spikes_probe_packed_storage_matches_raster():
    """The spikes-probe ring now stores uint32 rows; the user-facing
    Recordings must still be the bool raster, bit for bit."""
    s = ModelSpec("pk")
    s.add_neuron_population(
        "a", 70, "izhikevich",
        input_fn=lambda key, t, n: 6.0 * jax.random.normal(key, (n,)))
    s.add_synapse_population("aa", "a", "a", connect=F.FixedFanout(8),
                             weight=F.UniformWeight(0, 0.5))
    s.probe("spk", "a", "spikes")
    m = s.build(dt=1.0, seed=6)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        res = m.run(25, record_raster=True)
    rec = np.asarray(res.recordings["spk"])
    assert rec.dtype == np.bool_
    assert np.array_equal(rec, np.asarray(res.raster["a"]))


# ---------------------------------------------------------------------------
# the propagation= API surface
# ---------------------------------------------------------------------------

def test_propagation_validation_and_memory_report():
    s = ModelSpec("v")
    s.add_neuron_population("a", 16, "izhikevich")
    with pytest.raises(SpecError, match="propagation"):
        s.add_synapse_population("bad", "a", "a", connect=F.OneToOne(),
                                 propagation="evnt")
    with pytest.raises(SpecError, match="incompatible"):
        s.add_synapse_population("bad2", "a", "a", connect=F.OneToOne(),
                                 representation="dense",
                                 propagation="event")
    s.add_synapse_population("aa", "a", "a", connect=F.FixedFanout(4),
                             weight=0.1, propagation="event")
    m = s.build(dt=1.0, seed=0)
    rep = [r for r in m.memory_report()
           if r.get("kind") == "synapse_group"][0]
    assert rep["propagation"] == "event"
    assert rep["propagation_mode"] == "event"
    assert rep["event_capacity"] >= 8
    # a tiny group under "auto" resolves to dense (below the crossover)
    s2 = ModelSpec("v2")
    s2.add_neuron_population("a", 16, "izhikevich")
    s2.add_synapse_population("aa", "a", "a", connect=F.FixedFanout(4),
                              weight=0.1)
    rep2 = [r for r in s2.build(dt=1.0, seed=0).memory_report()
            if r.get("kind") == "synapse_group"][0]
    assert rep2["propagation"] == "auto"
    assert rep2["propagation_mode"] == "dense"
    assert rep2["event_capacity"] is None


def test_choose_propagation_crossover():
    from repro.kernels.autotune import choose_propagation
    small = choose_propagation(200, 32, 200)
    assert small["mode"] == "dense"          # 6400 slots: below crossover
    big = choose_propagation(2048, 32, 2048)
    assert big["mode"] == "event"            # 65536 slots at 10% activity
    assert big["capacity"] < 2048
    assert 2 * big["event_slots"] <= big["dense_slots"]


def test_deprecated_step_kwargs_warn_and_match():
    rng = np.random.default_rng(8)
    ell = _int_ell(rng, 24, 4, 24)
    grp = SynapseGroup(name="g", pre="p", post="p", ell=ell)
    st0 = grp.init_state()
    spk = jnp.asarray(rng.random(24) < 0.4)
    gs = jnp.float32(1.0)
    _, cur_new = grp.step(st0, spk, gs, 1.0,
                          conn=LocalConnectivity(ell=ell, dense=None))
    with pytest.warns(DeprecationWarning, match="conn=LocalConnectivity"):
        _, cur_old = grp.step(st0, spk, gs, 1.0, ell=ell)
    assert np.array_equal(np.asarray(cur_new), np.asarray(cur_old))
    # conflicting conn= AND deprecated ell= is a named SpecError
    with pytest.raises(SpecError, match="conflict"):
        grp.step(st0, spk, gs, 1.0,
                 conn=LocalConnectivity(ell=ell, dense=None), ell=ell)


def test_pallas_mode_parsing():
    PM = flags.PallasMode
    assert flags.pallas_mode("") is PM.OFF
    assert flags.pallas_mode("0") is PM.OFF
    assert flags.pallas_mode("off") is PM.OFF
    assert flags.pallas_mode("1") is PM.ON
    assert flags.pallas_mode("TPU") is PM.ON
    assert flags.pallas_mode("interpret") is PM.INTERPRET
    with pytest.raises(ValueError, match="REPRO_USE_PALLAS"):
        flags.pallas_mode("interperet")
    with pytest.raises(ValueError, match="REPRO_USE_PALLAS"):
        flags.pallas_mode("yes please")
