"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as R
from repro.kernels.ell_spmv import ell_spmv_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.flash_xla import flash_attention_xla
from repro.kernels.hh_step import hh_step_pallas
from repro.kernels.izhikevich_step import izhikevich_step_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.models.ssm import ssd_chunked

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_pre,k,n_post,b", [
    (16, 4, 32, 1), (64, 16, 100, 4), (200, 50, 333, 2), (128, 128, 512, 8),
])
def test_ell_spmv_matches_ref(n_pre, k, n_post, b):
    g = RNG.standard_normal((n_pre, k)).astype(np.float32)
    idx = RNG.integers(0, n_post, (n_pre, k)).astype(np.int32)
    valid = RNG.random((n_pre, k)) < 0.8
    spk = (RNG.random((b, n_pre)) < 0.2).astype(np.float32)
    ref = R.ell_spmv_ref(jnp.asarray(g), jnp.asarray(idx),
                         jnp.asarray(valid), jnp.asarray(spk), n_post)
    out = ell_spmv_pallas(jnp.asarray(g), jnp.asarray(idx),
                          jnp.asarray(valid), jnp.asarray(spk),
                          n_post=n_post, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,dt", [(100, 1.0), (1000, 0.5), (4096, 1.0)])
def test_izhikevich_step_matches_ref(n, dt):
    v = RNG.uniform(-80, 25, n).astype(np.float32)
    u = RNG.uniform(-20, 5, n).astype(np.float32)
    isyn = (RNG.standard_normal(n) * 5).astype(np.float32)
    a = np.full(n, 0.02, np.float32)
    b = np.full(n, 0.2, np.float32)
    c = np.full(n, -65.0, np.float32)
    d = np.full(n, 8.0, np.float32)
    args = tuple(map(jnp.asarray, (v, u, isyn, a, b, c, d)))
    rv, ru, rs = R.izhikevich_step_ref(*args, dt)
    pv, pu, ps = izhikevich_step_pallas(*args, dt=dt, interpret=True)
    np.testing.assert_allclose(np.asarray(pv), np.asarray(rv),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(pu), np.asarray(ru),
                               rtol=2e-4, atol=2e-4)
    # spike decisions may only differ within float noise of the threshold
    diff = np.asarray(ps) != np.asarray(rs)
    assert diff.mean() < 0.002


@pytest.mark.parametrize("n,substeps", [(128, 1), (1000, 5)])
def test_hh_step_matches_ref(n, substeps):
    v = RNG.uniform(-80, 30, n).astype(np.float32)
    m = RNG.random(n).astype(np.float32)
    h = RNG.random(n).astype(np.float32)
    nn = RNG.random(n).astype(np.float32)
    isyn = (RNG.standard_normal(n) * 2).astype(np.float32)
    args = tuple(map(jnp.asarray, (v, m, h, nn, isyn)))
    ref = R.hh_step_ref(*args, 0.1, substeps=substeps)
    out = hh_step_pallas(*args, dt=0.1, substeps=substeps, interpret=True)
    for a, b in zip(out, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
ATTN_CASES = [
    # b, hq, hkv, tq, tk, d, causal, window, softcap, prefix
    (1, 4, 2, 256, 256, 64, True, None, None, None),
    (2, 2, 1, 128, 256, 32, True, 64, None, None),
    (1, 2, 2, 256, 256, 64, True, None, 30.0, None),
    (1, 2, 2, 256, 256, 64, True, None, None, 100),
    (2, 4, 4, 200, 200, 64, False, None, None, None),
]


@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_pallas_matches_ref(case):
    b, hq, hkv, tq, tk, d, causal, window, softcap, prefix = case
    q = jnp.asarray(RNG.standard_normal((b, hq, tq, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, hkv, tk, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, hkv, tk, d)), jnp.float32)
    qoff = tk - tq
    ref = R.flash_attention_ref(q, k, v, causal=causal, window=window,
                                softcap=softcap, prefix=prefix,
                                q_offset=qoff)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 softcap=softcap, prefix=prefix,
                                 q_offset=qoff, q_block=128, k_block=128,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case", ATTN_CASES[:3])
def test_flash_xla_grads_match_autodiff(case):
    b, hq, hkv, tq, tk, d, causal, window, softcap, prefix = case
    tq = min(tq, 96)
    tk = min(tk, 96)
    q = jnp.asarray(RNG.standard_normal((b, hq, tq, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((b, hkv, tk, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((b, hkv, tk, d)), jnp.float32)

    def f_ref(q, k, v):
        return R.flash_attention_ref(
            q, k, v, causal=causal, window=window, softcap=softcap,
            prefix=prefix).sum()

    def f_fl(q, k, v):
        return flash_attention_xla(q, k, v, causal, window, None, 0,
                                   softcap, prefix, 32, 32).sum()

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(f_fl, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-5)


# ---------------------------------------------------------------------------
@pytest.mark.parametrize("b,t,h,dh,ds,chunk", [
    (2, 128, 4, 16, 16, 32), (1, 256, 8, 32, 32, 64), (2, 64, 2, 8, 64, 64),
])
def test_ssd_chunked_and_pallas_match_naive(b, t, h, dh, ds, chunk):
    x = jnp.asarray(RNG.standard_normal((b, t, h, dh)), jnp.float32)
    dt = jnp.asarray(0.001 + 0.1 * RNG.random((b, t, h)), jnp.float32)
    A = jnp.asarray(-np.exp(RNG.uniform(0, 2, h)), jnp.float32)
    B = jnp.asarray(RNG.standard_normal((b, t, 1, ds)), jnp.float32)
    C = jnp.asarray(RNG.standard_normal((b, t, 1, ds)), jnp.float32)
    D = jnp.asarray(RNG.standard_normal(h), jnp.float32)
    ref = R.ssd_scan_ref(x, dt, A, B, C, D)
    chk = ssd_chunked(x, dt, A, B, C, D, chunk=chunk)
    pls = ssd_scan_pallas(x, dt, A, B, C, D, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(chk), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(pls), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ssd_state_carry_matches_two_halves():
    """Chunked SSD with initial_state == running the halves back to back."""
    b, t, h, dh, ds = 1, 128, 2, 8, 16
    x = jnp.asarray(RNG.standard_normal((b, t, h, dh)), jnp.float32)
    dt = jnp.asarray(0.01 + 0.05 * RNG.random((b, t, h)), jnp.float32)
    A = jnp.asarray([-1.0, -2.0], jnp.float32)
    B = jnp.asarray(RNG.standard_normal((b, t, 1, ds)), jnp.float32)
    C = jnp.asarray(RNG.standard_normal((b, t, 1, ds)), jnp.float32)
    full = ssd_chunked(x, dt, A, B, C, None, chunk=32)
    y1, s1 = ssd_chunked(x[:, :64], dt[:, :64], A, B[:, :64], C[:, :64],
                         None, chunk=32, return_final_state=True)
    y2 = ssd_chunked(x[:, 64:], dt[:, 64:], A, B[:, 64:], C[:, 64:],
                     None, chunk=32, initial_state=s1)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(full), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# occupancy-based tile selection for the ELL spmv (paper §3)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_pre,k,n_post,b", [
    (16, 4, 32, 1), (1000, 100, 1000, 1), (1000, 1000, 1000, 16),
    (100_000, 100, 100_000, 1), (8192, 512, 8192, 8),
    (50_000, 1000, 50_000, 4),
])
def test_spmv_chosen_tiles_are_vmem_feasible(n_pre, k, n_post, b):
    """The autotuned (bp, bn) must fit VMEM with Mosaic's double buffering
    and stay hardware-aligned — for every shape, including the paper's
    scalability-study sizes."""
    from repro.kernels.autotune import (V5E, choose_block_spmv,
                                        spmv_block_bytes)
    cfg = choose_block_spmv(n_pre, k, n_post, b)
    assert cfg["feasible"]
    assert cfg["bn"] % V5E.lane == 0
    assert cfg["bp"] % V5E.sublane_f32 == 0
    need = spmv_block_bytes(cfg["bp"], cfg["bn"], k, b) * V5E.double_buffer
    assert need <= V5E.vmem_bytes, (cfg, need)


def test_spmv_wide_k_chunks_to_feasible_tiles():
    """K beyond the kernel's full-row VMEM limit must be flagged
    infeasible and split into chunks whose tiling fits (e.g. the row widths
    FixedProbability produces at p=0.2, n_post=100k)."""
    from repro.kernels.autotune import (V5E, choose_block_spmv,
                                        spmv_block_bytes)
    from repro.kernels.ell_spmv import feasible_k_chunk
    wide = choose_block_spmv(10_000, 20_000, 100_000, 1)
    assert not wide["feasible"]
    kc, cfg = feasible_k_chunk(10_000, 20_000, 100_000, 1)
    assert kc < 20_000 and cfg["feasible"]
    need = spmv_block_bytes(cfg["bp"], cfg["bn"], kc, 1) * V5E.double_buffer
    assert need <= V5E.vmem_bytes


def test_spmv_pallas_wide_k_correct():
    """Interpret-mode end to end through the K-chunked launch path."""
    from repro.kernels.ell_spmv import feasible_k_chunk
    n_pre, k, n_post, b = 24, 20_000, 64, 2
    assert feasible_k_chunk(n_pre, k, n_post, b)[0] < k
    g = RNG.standard_normal((n_pre, k)).astype(np.float32)
    idx = RNG.integers(0, n_post, (n_pre, k)).astype(np.int32)
    valid = RNG.random((n_pre, k)) < 0.5
    spk = (RNG.random((b, n_pre)) < 0.4).astype(np.float32)
    ref = R.ell_spmv_ref(jnp.asarray(g), jnp.asarray(idx),
                         jnp.asarray(valid), jnp.asarray(spk), n_post)
    out = ell_spmv_pallas(jnp.asarray(g), jnp.asarray(idx),
                          jnp.asarray(valid), jnp.asarray(spk),
                          n_post=n_post, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_default_blocks_routes_through_autotune():
    from repro.kernels.autotune import choose_block_spmv
    from repro.kernels.ell_spmv import default_blocks
    for shape in [(64, 16, 100, 4), (4096, 128, 4096, 2)]:
        cfg = choose_block_spmv(*shape)
        assert default_blocks(*shape) == (cfg["bp"], cfg["bn"])


def test_spmv_pallas_correct_with_autotuned_blocks():
    """End to end: interpret-mode kernel with the chosen tiles == oracle."""
    n_pre, k, n_post, b = 96, 24, 260, 3
    g = RNG.standard_normal((n_pre, k)).astype(np.float32)
    idx = RNG.integers(0, n_post, (n_pre, k)).astype(np.int32)
    valid = RNG.random((n_pre, k)) < 0.7
    spk = (RNG.random((b, n_pre)) < 0.3).astype(np.float32)
    ref = R.ell_spmv_ref(jnp.asarray(g), jnp.asarray(idx),
                         jnp.asarray(valid), jnp.asarray(spk), n_post)
    out = ell_spmv_pallas(jnp.asarray(g), jnp.asarray(idx),
                          jnp.asarray(valid), jnp.asarray(spk),
                          n_post=n_post, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_tpu_limits_looked_up_by_device_kind():
    """On a TPU the tiles follow the chip's device_kind and an unknown kind
    is an error; off the TPU they are a v5e's, the chip they model."""
    import types

    from repro.kernels.autotune import (V5E, UnknownTPUError,
                                        device_limits)
    dev = lambda platform, kind: types.SimpleNamespace(
        platform=platform, device_kind=kind)
    assert device_limits(dev("tpu", "TPU v5 lite")) is V5E
    assert device_limits(dev("cpu", "cpu")) is V5E
    assert device_limits() is V5E                 # this CPU-only process
    with pytest.raises(UnknownTPUError, match="TPU v99"):
        device_limits(dev("tpu", "TPU v99"))


def test_interpret_mode_on_tpu_is_refused(monkeypatch):
    """REPRO_USE_PALLAS=interpret would run the kernels through the
    interpreter on the chip: the dispatch refuses by name instead."""
    from repro.kernels import ops
    monkeypatch.setenv("REPRO_USE_PALLAS", "interpret")
    assert ops.backend() == "interpret"           # CPU: validation mode
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "tpu")
    with pytest.raises(ops.InterpretOnTPUError, match="REPRO_USE_PALLAS"):
        ops.backend()
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    assert ops.backend() == "pallas"
