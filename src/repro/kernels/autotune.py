"""Occupancy-based block-size determination, adapted from paper §3 to TPU.

The paper picks CUDA block sizes by computing *occupancy* — resident warps per
SM limited by four bottlenecks (threads, blocks/SM, shared memory, registers)
— and choosing the smallest block size that still hides memory latency.

TPU has no warps/SMs, but the same shape of reasoning applies to Pallas tiles:

  bottleneck (CUDA)            ->  bottleneck (TPU / Pallas)
  threads per block            ->  lane/sublane alignment (last dim % 128,
                                   second-minor % 8 for f32, % 16 bf16)
  shared memory per SM         ->  VMEM working set per grid step (incl. the
                                   x2 for Mosaic's automatic double-buffering)
  registers                    ->  VREGs; proxied by the per-block footprint
  blocks per SM / grid width   ->  grid steps per TensorCore: enough grid
                                   parallelism to hide HBM->VMEM latency

`occupancy()` scores a candidate tile; `choose_block*` enumerate aligned
candidates and pick the max-occupancy one (ties -> larger tile, fewer grid
steps).  The same calculator drives every kernel in this package and is
exported as a benchmark table (bench_occupancy_blocksize).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Sequence, Tuple

import jax

from repro.obs import trace as _trace

__all__ = [
    "TPULimits", "V5E", "TPU_LIMITS", "UnknownTPUError", "device_limits",
    "occupancy", "choose_block_elementwise",
    "choose_block_matmul", "choose_block_spmv", "spmv_block_bytes",
    "choose_propagation", "occupancy_report",
]


@dataclasses.dataclass(frozen=True)
class TPULimits:
    """Per-core resource limits of one TPU generation (defaults: v5e)."""

    vmem_bytes: int = 16 * 1024 * 1024     # usable VMEM per core
    lane: int = 128                        # vector lane count
    sublane_f32: int = 8                   # sublanes per vreg (f32)
    mxu: int = 128                         # MXU systolic dim
    min_grid_per_core: int = 2             # grid steps to overlap DMA/compute
    double_buffer: int = 2                 # Mosaic pipelines in/out buffers


V5E = TPULimits()

#: limits by ``jax.Device.device_kind``; a TPU kind missing here is an error
TPU_LIMITS: Dict[str, TPULimits] = {"TPU v5 lite": V5E}


class UnknownTPUError(LookupError):
    """The attached TPU's device_kind has no TPULimits entry."""


def device_limits(device=None) -> TPULimits:
    """Tile limits for `device` (default: the first JAX device).

    On a TPU they are looked up by ``device_kind``: a kind without an entry
    in TPU_LIMITS raises UnknownTPUError rather than tiling for another
    chip.  Off the TPU (CPU runs, Pallas interpret mode, compiles for a
    described topology) the tiles are those of a v5e, the chip they model.
    """
    dev = jax.devices()[0] if device is None else device
    if dev.platform != "tpu":
        return V5E
    try:
        return TPU_LIMITS[dev.device_kind]
    except KeyError:
        raise UnknownTPUError(
            f"no TPULimits for TPU device_kind {dev.device_kind!r}; known "
            f"kinds: {sorted(TPU_LIMITS)} (add its VMEM and tile limits to "
            "repro.kernels.autotune.TPU_LIMITS)") from None


def _align_penalty(shape: Tuple[int, ...], dtype_bytes: int,
                   lim: TPULimits) -> float:
    """1.0 when hardware-aligned, <1 when padding would waste lanes."""
    if not shape:
        return 1.0
    last = shape[-1]
    sub = shape[-2] if len(shape) >= 2 else 1
    lane_eff = min(1.0, last / math.ceil(last / lim.lane) / lim.lane)
    sublane_quota = lim.sublane_f32 * (4 // max(1, dtype_bytes))
    sub_eff = min(1.0, sub / math.ceil(sub / sublane_quota) / sublane_quota)
    return lane_eff * sub_eff


def occupancy(
    block_bytes: int, grid_steps: int, shapes: Sequence[Tuple[int, ...]],
    dtype_bytes: int = 4, lim: TPULimits = V5E,
) -> float:
    """Occupancy in [0, 1]: how well this tiling hides memory latency.

    block_bytes: total VMEM working set of ONE grid step (all operands+outputs)
    grid_steps:  number of grid steps the kernel launches on this core
    shapes:      per-operand block shapes (for alignment scoring)
    """
    need = block_bytes * lim.double_buffer
    if need > lim.vmem_bytes:
        return 0.0
    # VMEM term: fraction of VMEM left as headroom counts *against* nothing,
    # but being able to hold >=2 in-flight buffers is required (double_buffer)
    # and >=2 grid steps are needed so DMA for step i+1 overlaps compute of i.
    grid_term = min(1.0, grid_steps / lim.min_grid_per_core)
    align_term = 1.0
    for s in shapes:
        align_term = min(align_term, _align_penalty(s, dtype_bytes, lim))
    # Prefer tiles that use a healthy fraction of VMEM (big tiles amortize
    # control overhead) without exceeding it — mirrors "enough resident
    # warps" without "register spill".
    util = need / lim.vmem_bytes
    util_term = min(1.0, 0.25 + util)  # soft ramp; full credit at 75%+ usage
    return grid_term * align_term * util_term


def _pow2s(lo: int, hi: int):
    v = lo
    while v <= hi:
        yield v
        v *= 2


def choose_block_elementwise(
    n: int, arrays: int, dtype_bytes: int = 4, lim: TPULimits = V5E,
) -> Tuple[int, int]:
    """Tile a length-n elementwise op reshaped to (rows, 128).

    Returns (block_rows, grid_steps). `arrays` counts ins+outs resident."""
    rows = math.ceil(n / lim.lane)
    best = (lim.sublane_f32, 1, -1.0)
    for br in _pow2s(lim.sublane_f32, max(lim.sublane_f32, 1 << 14)):
        if br > rows and br != lim.sublane_f32:
            break
        grid = math.ceil(rows / br)
        bytes_ = br * lim.lane * dtype_bytes * arrays
        occ = occupancy(bytes_, grid, [(br, lim.lane)], dtype_bytes, lim)
        score = (occ, br)  # ties -> bigger block
        if score > (best[2], best[0]):
            best = (br, grid, occ)
    return best[0], best[1]


def choose_block_matmul(
    m: int, n: int, k: int, dtype_bytes: int = 4, lim: TPULimits = V5E,
    candidates: Sequence[int] = (128, 256, 512, 1024, 2048),
) -> Dict[str, int]:
    """Pick (bm, bn, bk) for a tiled matmul C[m,n] += A[m,k] B[k,n]."""
    best = None
    for bm in candidates:
        if bm > max(m, lim.mxu):
            continue
        for bn in candidates:
            if bn > max(n, lim.mxu):
                continue
            for bk in candidates:
                if bk > max(k, lim.mxu):
                    continue
                blk = (bm * bk + bk * bn + bm * bn) * dtype_bytes
                grid = (math.ceil(m / bm) * math.ceil(n / bn)
                        * math.ceil(k / bk))
                occ = occupancy(blk, grid, [(bm, bk), (bk, bn), (bm, bn)],
                                dtype_bytes, lim)
                # secondary objective: arithmetic intensity ~ 1/(1/bm+1/bn)
                ai = 1.0 / (1.0 / bm + 1.0 / bn)
                key = (occ, ai)
                if best is None or key > best[0]:
                    best = (key, {"bm": bm, "bn": bn, "bk": bk,
                                  "occupancy": occ, "grid": grid})
    assert best is not None
    return best[1]


def spmv_block_bytes(bp: int, bn: int, k: int, b: int,
                     dtype_bytes: int = 4, n_slots: int = 1) -> int:
    """VMEM working set of one ELL-spmv grid step (repro.kernels.ell_spmv):
    spike tile [B, BP], transposed g + idx row tiles [K, BP], output tile
    [B, BN], plus the weight tile [BN, BP] the kernel rebuilds in a scratch
    buffer and the row-index iota and select temporary of the same shape —
    the weight tile and the row tiles are the VMEM drivers.

    n_slots > 1 describes the fused-delay variant: a third [K, BP] row tile
    (the delay slots) and a (delay, post)-combined weight tile/output whose
    post extent is n_slots * BN."""
    row_tiles = 2 if n_slots == 1 else 3
    return (b * bp + row_tiles * k * bp + b * bn * n_slots
            + 3 * bn * n_slots * bp) * dtype_bytes


def choose_block_spmv(
    n_pre: int, k: int, n_post: int, b: int, dtype_bytes: int = 4,
    lim: TPULimits | None = None, tag: str = "", n_slots: int = 1,
) -> Dict[str, int]:
    """Pick (bp, bn) tiles for the ELL spmv kernel via the occupancy model
    (paper §3: smallest block that still hides latency; ties prefer larger
    tiles / fewer grid steps).  Both are multiples of 128: bp is the lane
    dimension of the spike and row tiles, bn that of the output tile.

    The kernel loads full-K row tiles, so for very wide rows (K beyond
    several thousand slots) *no* (bp, bn) fits VMEM: the result then carries
    ``feasible: False`` and the minimum (128, 128) tiling — callers
    (repro.kernels.ell_spmv) split K into feasible chunks and sum.

    Every decision is recorded as a ``choose_block_spmv`` trace instant
    (repro.obs.trace) carrying the problem shape, chosen tile, occupancy
    and VMEM footprint; ``tag`` attributes it (e.g. a synapse group name).

    n_slots > 1 sizes the fused-delay variant (repro.kernels.ell_spmv.
    ell_spmv_delay_pallas): output and weight tiles grow by the number of
    dendritic-delay ring slots.  ``lim`` defaults to device_limits().
    """
    lim = device_limits() if lim is None else lim
    bn_candidates = [bn for bn in (128, 256, 512, 1024)
                     if bn <= max(128, math.ceil(n_post / lim.lane)
                                  * lim.lane)]
    best = None
    for bn in bn_candidates:
        bp = lim.lane
        while bp <= max(lim.lane, 1 << 14):
            if bp > n_pre and bp != lim.lane:
                break
            grid = math.ceil(n_post / bn) * math.ceil(n_pre / bp)
            blk = spmv_block_bytes(bp, bn, k, b, dtype_bytes, n_slots)
            occ = occupancy(blk, grid,
                            [(k, bp), (b, bp), (b, bn * n_slots),
                             (bn * n_slots, bp)],
                            dtype_bytes, lim)
            key = (occ, bp * bn)           # ties -> bigger tile
            if best is None or key > best[0]:
                best = (key, {"bp": bp, "bn": bn, "occupancy": occ,
                              "grid": grid,
                              "block_bytes": blk, "feasible": occ > 0.0})
            bp *= 2
    if best is None or best[0][0] <= 0.0:
        blk = spmv_block_bytes(lim.lane, lim.lane, k, b, dtype_bytes,
                               n_slots)
        cfg = {"bp": lim.lane, "bn": lim.lane, "occupancy": 0.0,
               "grid": (math.ceil(n_post / lim.lane)
                        * math.ceil(n_pre / lim.lane)),
               "block_bytes": blk,
               "feasible": blk * lim.double_buffer <= lim.vmem_bytes}
    else:
        cfg = best[1]
    _trace.instant("choose_block_spmv", tag=tag, n_pre=n_pre, k=k,
                   n_post=n_post, b=b, n_slots=n_slots, **cfg)
    return cfg


def choose_propagation(
    n_pre: int, k: int, n_post: int, b: int = 1, activity: float = 0.1,
    capacity: int | None = None, n_slots: int = 1, dtype_bytes: int = 4,
    lim: TPULimits | None = None, tag: str = "",
) -> Dict[str, object]:
    """Occupancy/activity-model crossover: dense full-matrix spmv vs
    event-driven row gathering for one synapse group (paper's sparse
    synapse-connection representation; cf. GeNN's sparse spike delivery).

    Dense traverses all n_pre*K ELL slots every step.  Event-driven compacts
    the spiking pre-neuron index list into a fixed-capacity buffer (overflow
    falls back to dense at runtime) and gathers only those rows, paying an
    O(n_pre) compaction sweep per step.  ``activity`` is the modelled mean
    firing fraction per step; the capacity gets ~2.5x headroom over it so
    typical fluctuations stay on the fast path, rounded up to the sublane
    quantum and clamped to n_pre.

    Picks "event" only when (a) the modelled event slot traffic is at most
    half the dense traffic — the compaction/gather overhead needs a clear
    win — (b) the matrix is big enough (>= 32768 slots) to amortize the
    fixed per-step compaction cost, and (c) the compacted problem still has
    a feasible spmv tiling.  Returns mode, capacity, both block configs and
    the modelled slot counts; records a ``choose_propagation`` trace
    instant.
    """
    lim = device_limits() if lim is None else lim
    if capacity is None:
        q = lim.sublane_f32
        cap = math.ceil(n_pre * activity * 2.5 / q) * q
        cap = int(min(n_pre, max(q, cap)))
    else:
        cap = int(min(n_pre, max(1, capacity)))
    dense_slots = n_pre * k
    event_slots = cap * k + n_pre      # gathered rows + compaction sweep
    dense_cfg = choose_block_spmv(n_pre, k, n_post, b, dtype_bytes, lim,
                                  tag=f"{tag}:dense", n_slots=n_slots)
    event_cfg = choose_block_spmv(cap, k, n_post, b, dtype_bytes, lim,
                                  tag=f"{tag}:event", n_slots=n_slots)
    worthwhile = (dense_slots >= 32768
                  and 2 * event_slots <= dense_slots
                  and event_cfg["feasible"])
    mode = "event" if worthwhile else "dense"
    cfg = {"mode": mode, "capacity": cap, "activity": activity,
           "dense_slots": dense_slots, "event_slots": event_slots,
           "dense_occupancy": dense_cfg["occupancy"],
           "event_occupancy": event_cfg["occupancy"]}
    _trace.instant("choose_propagation", tag=tag, n_pre=n_pre, k=k,
                   n_post=n_post, b=b, n_slots=n_slots, **cfg)
    return cfg


def occupancy_report(lim: TPULimits = V5E) -> str:
    """The paper-style block-size table (benchmarked in bench_occupancy)."""
    lines = ["workload,block,grid,occupancy"]
    for n in (1 << 12, 1 << 16, 1 << 20):
        br, grid = choose_block_elementwise(n, arrays=6, lim=lim)
        occ = occupancy(br * lim.lane * 4 * 6, grid, [(br, lim.lane)], 4, lim)
        lines.append(f"elementwise_n={n},({br}x128),{grid},{occ:.3f}")
    for m, n, k in ((512, 512, 512), (4096, 4096, 4096), (8192, 1024, 8192)):
        cfg = choose_block_matmul(m, n, k, 2, lim)
        lines.append(
            f"matmul_{m}x{n}x{k},({cfg['bm']}x{cfg['bn']}x{cfg['bk']}),"
            f"{cfg['grid']},{cfg['occupancy']:.3f}")
    return "\n".join(lines)
