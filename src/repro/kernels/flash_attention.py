"""Pallas TPU kernel: flash attention (forward) with causal/window/prefix
masks, GQA, and logit soft-capping.

Tiling: grid (B*Hq, nq, nk) with the k-block axis minor, so each q-tile's
(m, l, acc) online-softmax state lives in VMEM scratch across the k sweep
(init at ki==0, emit at ki==nk-1).  K/V tiles are indexed through the GQA
head map (q head -> kv head) inside the BlockSpec index_map, so grouped
heads never materialize repeated KV.

Block sizes default from the occupancy model (paper §3): the (qb x kb)
logits tile is the VMEM driver; qb/kb multiples of the 128-lane MXU dims.

The backward pass on TPU would follow kernels/flash_xla.py's recompute
schedule; training on this CPU container uses that XLA path, so only the
forward kernel is provided here (validated in interpret mode against
ref.flash_attention_ref).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.autotune import TPULimits, device_limits

__all__ = ["flash_attention_pallas", "default_blocks"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, causal, window, prefix, softcap, q_offset, qb, kb, nk,
            tk_real):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)            # [qb, d]
    k = k_ref[0].astype(jnp.float32)            # [kb, d]
    v = v_ref[0].astype(jnp.float32)            # [kb, d]

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale     # [qb, kb]
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)

    qpos = qi * qb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 0) \
        + q_offset
    kpos = ki * kb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
    mask = kpos < tk_real          # padded key positions contribute nothing
    if causal:
        cm = kpos <= qpos
        if prefix is not None:
            cm = jnp.logical_or(cm, jnp.logical_and(kpos < prefix,
                                                    qpos < prefix))
        mask = jnp.logical_and(mask, cm)
    if window is not None:
        mask = jnp.logical_and(mask, kpos > qpos - window)
    logits = jnp.where(mask, logits, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_cur = jnp.max(logits, axis=1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new[:, None])
    p = jnp.where(mask, p, 0.0)
    l_new = l_prev * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _emit():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-37)[:, None]
                    ).astype(o_ref.dtype)


def default_blocks(tq: int, tk: int, d: int,
                   lim: TPULimits | None = None) -> tuple[int, int]:
    """qb/kb from the occupancy model: working set = q + k + v + logits +
    acc tiles (x2 double-buffered) under the VMEM budget, dims 128-aligned.
    ``lim`` defaults to the attached device's (autotune.device_limits)."""
    lim = device_limits() if lim is None else lim
    qb = min(512, max(128, tq))
    kb = min(1024, max(128, tk))
    while (qb * d + 2 * kb * d + qb * kb + qb * d) * 4 * lim.double_buffer \
            > lim.vmem_bytes and kb > 128:
        kb //= 2
    while (qb * d + 2 * kb * d + qb * kb + qb * d) * 4 * lim.double_buffer \
            > lim.vmem_bytes and qb > 128:
        qb //= 2
    return qb, kb


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "q_offset", "softcap", "prefix",
    "q_block", "k_block", "interpret"))
def flash_attention_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window: Optional[int] = None,
    scale: Optional[float] = None, q_offset: int = 0,
    softcap: Optional[float] = None, prefix: Optional[int] = None,
    q_block: Optional[int] = None, k_block: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """q [B,Hq,Tq,D]; k,v [B,Hkv,Tk,D] -> [B,Hq,Tq,D]."""
    b, hq, tq, d = q.shape
    hkv, tk = k.shape[1], k.shape[2]
    rep = hq // hkv
    s = scale if scale is not None else 1.0 / math.sqrt(d)

    qb, kb = default_blocks(tq, tk, d)
    qb = q_block or min(qb, tq)
    kb = k_block or min(kb, tk)
    # pad sequence dims to block multiples
    pq = math.ceil(tq / qb) * qb
    pk = math.ceil(tk / kb) * kb
    q3 = q.reshape(b * hq, tq, d)
    k3 = k.reshape(b * hkv, tk, d)
    v3 = v.reshape(b * hkv, tk, d)
    if pq != tq:
        q3 = jnp.pad(q3, ((0, 0), (0, pq - tq), (0, 0)))
    if pk != tk:
        k3 = jnp.pad(k3, ((0, 0), (0, pk - tk), (0, 0)))
        v3 = jnp.pad(v3, ((0, 0), (0, pk - tk), (0, 0)))
        # padded keys are masked: their kpos > every real qpos under causal;
        # for non-causal we mask via window=None ... guard with explicit
        # validity below by folding into the causal/window mask using kpos.
    nq, nk = pq // qb, pk // kb

    kernel = functools.partial(
        _kernel, scale=s, causal=causal, window=window, prefix=prefix,
        softcap=softcap, q_offset=q_offset, qb=qb, kb=kb, nk=nk,
        tk_real=tk)

    out = pl.pallas_call(
        kernel,
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, qb, d), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, kb, d),
                         lambda bh, qi, ki, rep=rep: (bh // rep, ki, 0)),
            pl.BlockSpec((1, kb, d),
                         lambda bh, qi, ki, rep=rep: (bh // rep, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, qb, d), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, pq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qb,), jnp.float32),
            pltpu.VMEM((qb,), jnp.float32),
            pltpu.VMEM((qb, d), jnp.float32),
        ],
        interpret=interpret,
    )(q3, k3, v3)
    return out[:, :tq].reshape(b, hq, tq, d)