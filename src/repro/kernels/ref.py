"""Pure-jnp oracles for every Pallas kernel.

These are the semantics; kernels must match them to float tolerance.  They are
also the implementations used for CPU dry-runs/rooflines (the CPU backend
cannot compile Mosaic TPU custom-calls) — see DESIGN.md §3.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = [
    "ell_spmv_ref", "ell_spmv_delay_ref", "izhikevich_step_ref",
    "hh_step_ref", "flash_attention_ref", "ssd_scan_ref",
]


_LANE = 128


def _lane_pad(*rows: jax.Array) -> tuple:
    """Pad [n_pre, K] row arrays with zero slots up to a K that is a
    multiple of 128 before they are flattened.  On a TPU, flattening a K
    that is not lane-aligned is a relayout whose compile time grows with
    n_pre*K (over a minute at 2*10^7 slots); the aligned flatten compiles
    in about a second.  The padded slots add +0.0, which changes no sum."""
    pad = -rows[0].shape[1] % _LANE
    if not pad:
        return rows
    return tuple(jnp.pad(r, ((0, 0), (0, pad))) for r in rows)


def ell_spmv_ref(g: jax.Array, post_ind: jax.Array, valid: jax.Array,
                 spikes: jax.Array, n_post: int) -> jax.Array:
    """Batched ELL scatter-accumulate.

    g, post_ind, valid: [n_pre, K];  spikes: [B, n_pre]  ->  [B, n_post]
    out[b, j] = sum_{i,k} spikes[b,i] * g[i,k] * valid[i,k] * (post_ind[i,k]==j)
    """
    gm, post_ind = _lane_pad(jnp.where(valid, g, 0.0), post_ind)
    contrib = spikes[:, :, None] * gm[None, :, :]          # [B, n_pre, K]
    flat_idx = post_ind.reshape(-1)                        # [n_pre*K]
    flat = contrib.reshape(contrib.shape[0], -1)           # [B, n_pre*K]
    out = jnp.zeros((spikes.shape[0], n_post), flat.dtype)
    return out.at[:, flat_idx].add(flat)


def ell_spmv_delay_ref(g: jax.Array, post_ind: jax.Array, valid: jax.Array,
                       delay: jax.Array, spikes: jax.Array, n_post: int,
                       n_slots: int) -> jax.Array:
    """Fused delay-scatter: one pass over the ELL slots lands every synapse's
    contribution at its own (delay_slot, post) coordinate.

    g, post_ind, valid, delay: [n_pre, K];  spikes: [B, n_pre]
    ->  [B, n_slots, n_post]
    out[b, d, j] = sum_{i,k} spikes[b,i] * g[i,k] * valid[i,k]
                             * (delay[i,k]==d) * (post_ind[i,k]==j)

    Per (d, j) the contributing slots are visited in the same row-major
    (i, k) order as a masked single-delay ell_spmv_ref pass, so replacing
    the max_delay+1 masked passes with one fused scatter is bit-exact.
    """
    gm, dm, post_ind = _lane_pad(jnp.where(valid, g, 0.0),
                                 jnp.where(valid, delay, 0), post_ind)
    contrib = spikes[:, :, None] * gm[None, :, :]          # [B, n_pre, K]
    dflat = dm.reshape(-1)                                 # [n_pre*K]
    pflat = post_ind.reshape(-1)
    flat = contrib.reshape(contrib.shape[0], -1)
    out = jnp.zeros((spikes.shape[0], n_slots, n_post), flat.dtype)
    return out.at[:, dflat, pflat].add(flat)


def izhikevich_step_ref(v, u, isyn, a, b, c, d, dt):
    """Fused Izhikevich update (two half-steps on V), matching
    repro.core.snn.neurons.IZHIKEVICH semantics."""
    v1 = v + 0.5 * dt * (0.04 * v * v + 5.0 * v + 140.0 - u + isyn)
    v2 = v1 + 0.5 * dt * (0.04 * v1 * v1 + 5.0 * v1 + 140.0 - u + isyn)
    u2 = u + dt * a * (b * v2 - u)
    v2 = jnp.minimum(v2, 30.0)
    spiked = v2 >= 29.99
    v_out = jnp.where(spiked, c, v2)
    u_out = jnp.where(spiked, u2 + d, u2)
    return v_out, u_out, spiked


def _vtrap(x):
    """x / (exp(x) - 1), guarded at the pole (Taylor: 1 - x/2)."""
    return jnp.where(jnp.abs(x) > 1e-4,
                     x / (jnp.exp(x) - 1.0), 1.0 - x / 2.0)


def hh_step_ref(v, m, h, n, isyn, dt, substeps=5, gNa=7.15, ENa=50.0,
                gK=1.43, EK=-95.0, gl=0.02672, El=-63.563, C=0.143):
    """Fused Traub-Miles HH update, matching make_traubmiles(substeps)."""
    hdt = dt / substeps
    for _ in range(substeps):
        imem = -(m * m * m * h * gNa * (v - ENa) + n ** 4 * gK * (v - EK)
                 + gl * (v - El) - isyn)
        v = v + hdt * imem / C
        a_m = 1.28 * _vtrap((-52.0 - v) / 4.0)
        b_m = 1.4 * _vtrap((v + 25.0) / 5.0)
        a_h = 0.128 * jnp.exp((-48.0 - v) / 18.0)
        b_h = 4.0 / (jnp.exp((-25.0 - v) / 5.0) + 1.0)
        a_n = 0.16 * _vtrap((-50.0 - v) / 5.0)
        b_n = 0.5 * jnp.exp((-55.0 - v) / 40.0)
        m = jnp.clip(m + hdt * (a_m * (1.0 - m) - b_m * m), 0.0, 1.0)
        h = jnp.clip(h + hdt * (a_h * (1.0 - h) - b_h * h), 0.0, 1.0)
        n = jnp.clip(n + hdt * (a_n * (1.0 - n) - b_n * n), 0.0, 1.0)
    return v, m, h, n


def flash_attention_ref(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window: Optional[int] = None,
    scale: Optional[float] = None, q_offset: int = 0,
    softcap: Optional[float] = None, prefix: Optional[int] = None,
) -> jax.Array:
    """Plain softmax attention.

    q: [B, Hq, Tq, D]; k, v: [B, Hkv, Tk, D] with Hq % Hkv == 0 (GQA).
    window: if set, query position p attends keys in (p-window, p].
    q_offset: absolute position of q[0] (for decode: q_offset = Tk - Tq).
    """
    b, hq, tq, d = q.shape
    hkv = k.shape[1]
    rep = hq // hkv
    kk = jnp.repeat(k, rep, axis=1)
    vv = jnp.repeat(v, rep, axis=1)
    s = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(q.dtype)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, kk).astype(jnp.float32) * s
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    qpos = jnp.arange(tq) + q_offset
    kpos = jnp.arange(k.shape[2])
    mask = jnp.ones((tq, k.shape[2]), bool)
    if causal:
        cmask = kpos[None, :] <= qpos[:, None]
        if prefix is not None:   # prefix-LM: bidirectional inside prefix
            cmask |= (kpos[None, :] < prefix) & (qpos[:, None] < prefix)
        mask &= cmask
    if window is not None:
        mask &= kpos[None, :] > (qpos[:, None] - window)
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows -> 0
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(vv.dtype), vv)


def flash_attention_xla(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window=None, scale: Optional[float] = None,
    q_offset: int = 0, softcap: Optional[float] = None,
    prefix: Optional[int] = None, q_chunk: int = 512, k_chunk: int = 1024,
) -> jax.Array:
    """Flash-style attention in plain XLA: online softmax over k-chunks inside
    a scan, q-chunks via lax.map.  Same semantics as flash_attention_ref but
    with O(q_chunk * k_chunk) temporaries — this is the production path for
    long sequences on backends without the Pallas kernel, and what the
    dry-run/roofline lowers.  Accepts a traced `window`."""
    b, hq, tq, d = q.shape
    hkv = k.shape[1]
    rep = hq // hkv
    tk = k.shape[2]
    s = scale if scale is not None else 1.0 / math.sqrt(d)

    qc = min(q_chunk, tq)
    while tq % qc:
        qc //= 2
    kc = min(k_chunk, tk)
    while tk % kc:
        kc //= 2
    nq, nk = tq // qc, tk // kc

    # [b, hkv, rep, t, d] grouped views; fold q-chunks into the batch of map
    qg = q.reshape(b, hkv, rep, nq, qc, d)
    qg = jnp.moveaxis(qg, 3, 0)                      # [nq, b, hkv, rep, qc, d]
    kg = k.reshape(b, hkv, nk, kc, d)
    vg = v.reshape(b, hkv, nk, kc, d)
    kpos_all = jnp.arange(tk).reshape(nk, kc)

    def do_q_chunk(args):
        qi, qblk = args                               # [], [b,hkv,rep,qc,d]
        qpos = qi * qc + jnp.arange(qc) + q_offset

        def body(carry, inp):
            m, l, acc = carry
            kblk, vblk, kpos = inp                    # [b,hkv,kc,d] x2, [kc]
            logits = jnp.einsum(
                "bgrqd,bgkd->bgrqk", qblk, kblk,
                preferred_element_type=jnp.float32) * s
            if softcap is not None:
                logits = softcap * jnp.tanh(logits / softcap)
            mask = jnp.ones((qc, kc), bool)
            if causal:
                cm = kpos[None, :] <= qpos[:, None]
                if prefix is not None:
                    cm |= (kpos[None, :] < prefix) & (qpos[:, None] < prefix)
                mask &= cm
            if window is not None:
                mask &= kpos[None, :] > (qpos[:, None] - window)
            logits = jnp.where(mask[None, None, None], logits, -jnp.inf)
            m_new = jnp.maximum(m, logits.max(-1))
            m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
            p = jnp.exp(logits - m_safe[..., None])
            p = jnp.where(mask[None, None, None], p, 0.0)
            alpha = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_safe))
            l_new = l * alpha + p.sum(-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bgrqk,bgkd->bgrqd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, hkv, rep, qc), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, hkv, rep, qc), jnp.float32)
        a0 = jnp.zeros((b, hkv, rep, qc, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            body, (m0, l0, a0),
            (jnp.moveaxis(kg, 2, 0), jnp.moveaxis(vg, 2, 0), kpos_all))
        out = acc / jnp.maximum(l, 1e-37)[..., None]
        return out                                    # [b,hkv,rep,qc,d]

    outs = jax.lax.map(do_q_chunk, (jnp.arange(nq), qg))
    out = jnp.moveaxis(outs, 0, 3)                    # [b,hkv,rep,nq,qc,d]
    return out.reshape(b, hq, tq, d).astype(q.dtype)


def ssd_scan_ref(x, dt, A, B, C, D=None):
    """Mamba2 SSD reference: naive sequential state-space recurrence.

    x:  [b, t, h, dh]   inputs (already gated/projected)
    dt: [b, t, h]       softplus'd step sizes (>0)
    A:  [h]             negative decay rates (A < 0)
    B:  [b, t, g, ds]   input projections (g state groups, broadcast to h)
    C:  [b, t, g, ds]   output projections
    D:  [h] or None     skip connection
    Returns y: [b, t, h, dh].
    State: s[h, dh, ds];   s' = exp(dt*A) * s + dt * x ⊗ B;   y = s · C
    """
    b, t, h, dh = x.shape
    g = B.shape[2]
    ds = B.shape[3]
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2)  # [b, t, h, ds]
    Ch = jnp.repeat(C, rep, axis=2)

    def step(s, inp):
        xt, dtt, bt, ct = inp  # [b,h,dh], [b,h], [b,h,ds], [b,h,ds]
        decay = jnp.exp(dtt * A[None, :])[:, :, None, None]   # [b,h,1,1]
        ds_new = s * decay + (dtt[:, :, None] * xt)[..., None] * bt[:, :, None, :]
        y = jnp.einsum("bhds,bhs->bhd", ds_new, ct)
        return ds_new, y

    s0 = jnp.zeros((b, h, dh, ds), x.dtype)
    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(Bh, 1, 0), jnp.moveaxis(Ch, 1, 0))
    _, ys = jax.lax.scan(step, s0, xs)
    y = jnp.moveaxis(ys, 0, 1)  # [b, t, h, dh]
    if D is not None:
        y = y + x * D[None, None, :, None]
    return y
