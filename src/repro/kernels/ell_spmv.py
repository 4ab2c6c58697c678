"""Pallas TPU kernel: batched ELL sparse synaptic accumulation.

This is the paper's GPU hot loop (sparse spike propagation) re-thought for
TPU.  GeNN's CUDA kernel assigns one thread per (spike, synapse) and uses
atomics into shared memory.  TPUs have neither per-lane scatter nor atomics;
the kernel instead rebuilds each (post-block x pre-block) tile of the dense
weight matrix in VMEM from the ELL slots and contracts the spike tile
against it on the MXU:

    W^T[j, i] = sum_k g[i, k] * [post_ind[i, k] == j]
    out[b, j] = sum_i spikes[b, i] * W^T[j, i]

The ELL rows arrive transposed, ``[K, n_pre]``, so slot k of every pre
neuron in the tile is one sublane row: comparing it against a row iota of
the post tile is a 2-D broadcast, with no rank-changing reshape inside the
kernel (Mosaic refuses those).  Spike tiles are ``[B, BP]`` with BP a
multiple of 128 lanes.  The batch dimension B (gScale candidates, served
streams) makes the contraction a real matmul.

Pre blocks without a spike are skipped: a scalar-prefetched flag per block
gates the weight build and the contraction, and the row tiles of an empty
block map to the block fetched last, so the pipeline issues no DMA for them.
Event-driven propagation compacts the spiking rows to the front, which makes
every tail block empty.

Grid layout: (post_blocks, pre_blocks) — pre is the minor (fastest) axis so
each output tile stays resident in VMEM while all pre-blocks accumulate into
it (revisiting pattern, init at pre_block==0).

Block sizes come from repro.kernels.autotune (occupancy model, paper §3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.autotune import TPULimits, choose_block_spmv

__all__ = ["ell_spmv_pallas", "ell_spmv_delay_pallas", "default_blocks"]

_SUB = 8          # ELL slots per loop trip: one f32 sublane tile of rows


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _accumulate_weights(w_ref, g_ref, idx_ref, dly_ref, *, post0: int,
                        bn: int) -> None:
    """w_ref[r, i] = sum_k g[k, i] * [row(k, i) == r] over this pre block's
    K slots, where row is the local post index (plain kernel) or the
    combined (delay_slot * bn + local_post) index (delay kernel)."""
    kp = g_ref.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, w_ref.shape, 0)
    w_ref[...] = jnp.zeros_like(w_ref)

    def body(c, carry):
        r0 = pl.multiple_of(c * _SUB, _SUB)
        g = g_ref[pl.ds(r0, _SUB), :]                      # [8, BP]
        local = idx_ref[pl.ds(r0, _SUB), :] - post0        # [8, BP]
        if dly_ref is not None:
            # a slot whose post lies outside this tile must not fold into a
            # neighbouring delay band of the combined index
            inb = (local >= 0) & (local < bn)
            local = jnp.where(inb, dly_ref[pl.ds(r0, _SUB), :] * bn + local,
                              -1)
        acc = w_ref[...]
        for s in range(_SUB):
            acc = acc + jnp.where(rows == local[s:s + 1, :],
                                  g[s:s + 1, :], 0.0)
        w_ref[...] = acc
        return carry

    jax.lax.fori_loop(0, kp // _SUB, body, 0)


def _contract(spk: jax.Array, w_t: jax.Array) -> jax.Array:
    """spk [B, BP] x W^T [R, BP] -> [B, R] in full f32 precision."""
    return jax.lax.dot_general(
        spk, w_t, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _kernel(nz_ref, src_ref, spk_ref, g_ref, idx_ref, out_ref, w_ref, *,
            bn: int):
    jb, pb = pl.program_id(0), pl.program_id(1)

    @pl.when(pb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(nz_ref[pb] != 0)
    def _accumulate():
        _accumulate_weights(w_ref, g_ref, idx_ref, None, post0=jb * bn,
                            bn=bn)
        out_ref[...] += _contract(spk_ref[...], w_ref[...])


def _delay_kernel(nz_ref, src_ref, spk_ref, g_ref, idx_ref, dly_ref, out_ref,
                  w_ref, *, bn: int, n_slots: int):
    """Fused delay-scatter variant: the weight tile's row index is the
    combined (delay_slot, local_post) coordinate, so one contraction lands
    every synapse's contribution in its own dendritic-ring slot."""
    jb, pb = pl.program_id(0), pl.program_id(1)

    @pl.when(pb == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(nz_ref[pb] != 0)
    def _accumulate():
        _accumulate_weights(w_ref, g_ref, idx_ref, dly_ref, post0=jb * bn,
                            bn=bn)
        acc = _contract(spk_ref[...], w_ref[...])          # [B, S*BN]
        for d in range(n_slots):
            out_ref[d] += acc[:, d * bn:(d + 1) * bn]


def default_blocks(n_pre: int, k: int, n_post: int, b: int,
                   lim: TPULimits | None = None) -> tuple[int, int]:
    """(pre_block, post_block) from the occupancy-based block-size
    determination (paper §3, repro.kernels.autotune.choose_block_spmv)."""
    cfg = choose_block_spmv(n_pre, k, n_post, b, lim=lim)
    return cfg["bp"], cfg["bn"]


def feasible_k_chunk(n_pre: int, k: int, n_post: int, b: int,
                     lim: TPULimits | None = None,
                     n_slots: int = 1) -> tuple[int, dict]:
    """Largest K-chunk whose chosen tiling fits VMEM (the kernel loads
    full-K row tiles, so very wide rows must be split and the partial
    currents summed).  Returns (k_chunk, block config for that chunk)."""
    kc = k
    while True:
        cfg = choose_block_spmv(n_pre, kc, n_post, b, lim=lim,
                                n_slots=n_slots)
        if cfg["feasible"] or kc == 1:
            return kc, cfg
        kc = (kc + 1) // 2


def _launch(kernel, spikes, row_arrays, *, n_post: int, out_rows: int,
            pre_block: int, post_block: int, interpret: bool) -> jax.Array:
    """Pad and transpose the operands, then run `kernel` over the
    (post-block, pre-block) grid.  Returns [out_rows, B_pad, n_post_pad]
    (out_rows = 1 for the plain kernel, n_slots for the delay kernel)."""
    b, n_pre = spikes.shape
    k = row_arrays[0].shape[1]
    bpad = _round_up(b, 8)
    pp = _round_up(n_pre, pre_block)
    pj = _round_up(n_post, post_block)
    kp = _round_up(k, _SUB)
    spk = jnp.pad(spikes.astype(jnp.float32),
                  ((0, bpad - b), (0, pp - n_pre)))
    # padded rows and slots carry g = 0: they add nothing anywhere
    rows_t = [jnp.pad(a, ((0, pp - n_pre), (0, kp - k))).T
              for a in row_arrays]                          # [kp, pp] each

    n_blocks = pp // pre_block
    nz = jnp.any(spk.reshape(bpad, n_blocks, pre_block) != 0,
                 axis=(0, 2)).astype(jnp.int32)
    # an empty block points at the last non-empty one before it, so the
    # pipeline sees a repeated block index and skips the row-tile DMA
    src = jax.lax.cummax(jnp.where(nz > 0, jnp.arange(n_blocks), 0))

    row_spec = pl.BlockSpec((kp, pre_block),
                            lambda jb, pb, nz, src: (0, src[pb]))
    if out_rows == 1:
        out_spec = pl.BlockSpec((bpad, post_block),
                                lambda jb, pb, nz, src: (0, jb))
        out_shape = (bpad, pj)
    else:
        out_spec = pl.BlockSpec((out_rows, bpad, post_block),
                                lambda jb, pb, nz, src: (0, 0, jb))
        out_shape = (out_rows, bpad, pj)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(pj // post_block, n_blocks),
            in_specs=[pl.BlockSpec((bpad, pre_block),
                                   lambda jb, pb, nz, src: (0, pb))]
                     + [row_spec] * len(rows_t),
            out_specs=out_spec,
            scratch_shapes=[pltpu.VMEM((out_rows * post_block, pre_block),
                                       jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(nz, src, spk, *rows_t)
    return out.reshape((out_rows,) + out_shape[-2:])


@functools.partial(
    jax.jit,
    static_argnames=("n_post", "pre_block", "post_block", "interpret"))
def ell_spmv_pallas(
    g: jax.Array, post_ind: jax.Array, valid: jax.Array, spikes: jax.Array,
    *, n_post: int, pre_block: int | None = None,
    post_block: int | None = None, interpret: bool = False,
) -> jax.Array:
    """Batched ELL spmv on TPU.  g/post_ind/valid: [n_pre, K];
    spikes: [B, n_pre] -> [B, n_post].

    When no (bp, bn) tiling of the full K width fits VMEM (K beyond several
    thousand slots), the rows are split into feasible K-chunks, each
    launched separately, and the partial currents summed."""
    n_pre, k = g.shape
    b = spikes.shape[0]

    if pre_block is None and post_block is None:
        kc, cfg = feasible_k_chunk(n_pre, k, n_post, b)
        if kc < k:
            out = jnp.zeros((b, n_post), jnp.float32)
            for lo in range(0, k, kc):
                out = out + ell_spmv_pallas(
                    g[:, lo:lo + kc], post_ind[:, lo:lo + kc],
                    valid[:, lo:lo + kc], spikes, n_post=n_post,
                    pre_block=cfg["bp"], post_block=cfg["bn"],
                    interpret=interpret)
            return out
        pre_block, post_block = cfg["bp"], cfg["bn"]
    elif pre_block is None or post_block is None:
        dbp, dbn = default_blocks(n_pre, k, n_post, b)
        pre_block = pre_block or dbp
        post_block = post_block or dbn

    gm = jnp.where(valid, g, 0.0).astype(jnp.float32)
    out = _launch(functools.partial(_kernel, bn=post_block), spikes,
                  [gm, post_ind.astype(jnp.int32)], n_post=n_post,
                  out_rows=1, pre_block=pre_block, post_block=post_block,
                  interpret=interpret)
    return out[0, :b, :n_post]


@functools.partial(
    jax.jit,
    static_argnames=("n_post", "n_slots", "pre_block", "post_block",
                     "interpret"))
def ell_spmv_delay_pallas(
    g: jax.Array, post_ind: jax.Array, valid: jax.Array, delay: jax.Array,
    spikes: jax.Array, *, n_post: int, n_slots: int,
    pre_block: int | None = None, post_block: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused delay-scatter ELL spmv on TPU: one pass over the slots lands
    each synapse's contribution at its (delay_slot, post) ring coordinate.

    g/post_ind/valid/delay: [n_pre, K]; spikes: [B, n_pre]
    -> [B, n_slots, n_post].  Semantics: repro.kernels.ref.ell_spmv_delay_ref.
    Replaces n_slots masked single-delay passes with one kernel launch."""
    n_pre, k = g.shape
    b = spikes.shape[0]

    if pre_block is None and post_block is None:
        kc, cfg = feasible_k_chunk(n_pre, k, n_post, b, n_slots=n_slots)
        if kc < k:
            out = jnp.zeros((b, n_slots, n_post), jnp.float32)
            for lo in range(0, k, kc):
                out = out + ell_spmv_delay_pallas(
                    g[:, lo:lo + kc], post_ind[:, lo:lo + kc],
                    valid[:, lo:lo + kc], delay[:, lo:lo + kc], spikes,
                    n_post=n_post, n_slots=n_slots,
                    pre_block=cfg["bp"], post_block=cfg["bn"],
                    interpret=interpret)
            return out
        pre_block, post_block = cfg["bp"], cfg["bn"]
    elif pre_block is None or post_block is None:
        cfg = choose_block_spmv(n_pre, k, n_post, b, n_slots=n_slots)
        pre_block = pre_block or cfg["bp"]
        post_block = post_block or cfg["bn"]

    gm = jnp.where(valid, g, 0.0).astype(jnp.float32)
    dly = jnp.where(valid, delay, 0).astype(jnp.int32)
    out = _launch(
        functools.partial(_delay_kernel, bn=post_block, n_slots=n_slots),
        spikes, [gm, post_ind.astype(jnp.int32), dly], n_post=n_post,
        out_rows=n_slots, pre_block=pre_block, post_block=post_block,
        interpret=interpret)                      # [n_slots, B_pad, pj]
    return jnp.transpose(out[:, :b, :n_post], (1, 0, 2))
