"""Device-resident connectivity construction (the build-time hot path).

The host-side initializers in `repro.sparse.formats` materialize the synapse
graph with a python loop over pre-neuron rows — at the paper's scalability-
study sizes the *construction*, not the step loop, becomes the ceiling
(minutes of host time and host RAM for graphs whose simulation step is
milliseconds).  Following "Runtime Construction of Large-Scale Spiking
Neuronal Network Models on GPU Devices" (Golosio et al., 2023), this module
generates connectivity *on device, in parallel*, emitting `ELLSynapses`
directly in O(nnz) memory.

Design rules:

* **Counter-based randomness.**  Every row draws from
  ``fold_in(base_key, global_row_index)`` — a pure function of (seed, row).
  The graph is therefore bit-deterministic for a fixed seed and *identical*
  regardless of device count or row chunking: generating rows [0, n) in one
  call equals concatenating any partition of the rows (`rows=` argument).
* **O(nnz) memory.**  Fixed-fanout sampling without replacement uses a
  dedup-redraw loop over the K slots (exactly the "collect first K distinct
  values of an iid stream" construction of a uniform K-subset), never a
  dense [n_pre, n_post] mask.  Only when K > n_post/2 — where O(n_post) per
  row *is* O(K) — does it switch to a per-row top-k permutation.
* **Same declarations.**  The dispatcher `device_resolve` consumes the very
  same `ConnectivityInit` dataclasses the host path uses; weights come from
  the dual-backend `WeightSnippet`s (scalars and None also work).

`partition_ell_by_post` repacks a built ELL into post-sharded per-device
blocks for the sharded engine (`repro.core.snn.engine`): slot (i, k) goes to
the shard owning post neuron post_ind[i, k], compacted to K_local slots with
the original slot order preserved (so scatter-accumulation order — and hence
bit-exact currents — is preserved per post neuron).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import snn_axis
from repro.obs import trace
from repro.sparse import formats as F

__all__ = [
    "device_resolve", "device_fixed_fanout", "device_fixed_probability",
    "device_one_to_one", "device_dense", "partition_ell_by_post",
    "as_device_weight", "as_device_delay", "device_delays",
    "device_init_local", "LocalInitPlan", "construction_peak_model",
]

_JTriple = Tuple[jax.Array, jax.Array, jax.Array]  # post_ind, g, valid

_MAX_REDRAW_ROUNDS = 64  # residual-duplicate probability < 2**-64 per slot
_LANE = 128              # TPU vector lanes: the slot axis is padded to these


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def as_device_weight(weight) -> F.WeightSnippet:
    """Normalize a ModelSpec weight declaration to a device-capable snippet.

    None -> ConstantWeight(1); scalars -> ConstantWeight(x); WeightSnippet
    passes through.  Raw numpy callables cannot be traced under jit — raise
    with the fix spelled out.
    """
    if weight is None:
        return F.ConstantWeight(1.0)
    if isinstance(weight, F.WeightSnippet):
        return weight
    if isinstance(weight, (int, float)):
        return F.ConstantWeight(float(weight))
    raise TypeError(
        f"device-side construction needs a dual-backend weight initializer "
        f"(ConstantWeight / UniformWeight / NormalWeight, or a scalar), got "
        f"{weight!r}; host-only numpy callables cannot run under jit — "
        "declare the weight as a WeightSnippet or build with init='host'")


def as_device_delay(delay) -> F.DelaySnippet:
    """Normalize a delay declaration to a device-capable snippet.

    Ints -> ConstantDelay(x); DelaySnippet passes through.  Raw numpy
    callables cannot be traced under jit — raise with the fix spelled out.
    """
    if isinstance(delay, F.DelaySnippet):
        return delay
    if isinstance(delay, int) and not isinstance(delay, bool):
        return F.ConstantDelay(delay)
    raise TypeError(
        f"device-side construction needs a dual-backend delay initializer "
        f"(ConstantDelay / UniformIntDelay, or an int), got {delay!r}; "
        "host-only numpy callables cannot run under jit — declare the delay "
        "as a DelaySnippet or build with init='host'")


def _row_keys(key: jax.Array, rows: jax.Array) -> jax.Array:
    return jax.vmap(lambda r: jax.random.fold_in(key, r))(rows)


def _row_weights(weight: F.WeightSnippet, key: jax.Array, rows: jax.Array,
                 k: int) -> jax.Array:
    """Per-row keyed weight draws: w[r] depends only on (seed, global row)."""
    wkey = jax.random.fold_in(key, 0x5EED)
    return jax.vmap(lambda rk: weight.device(rk, (k,)))(_row_keys(wkey, rows))


def device_delays(key: jax.Array, n_pre: int, k: int, delay,
                  rows: Optional[jax.Array] = None) -> jax.Array:
    """[len(rows), k] int32 per-synapse dendritic delays, generated on
    device with the same counter-based key schedule as connectivity and
    weights: row r draws from fold_in(fold_in(key, 0xDE1A), r), a pure
    function of (seed, global row) — so the delay matrix is seed-
    deterministic and independent of device count or row chunking."""
    snip = as_device_delay(delay)
    rows = _rows_or_default(rows, n_pre)
    dkey = jax.random.fold_in(key, 0xDE1A)
    return jax.vmap(lambda rk: snip.device(rk, (k,)))(
        _row_keys(dkey, rows)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# distinct sampling: k targets per row, uniform without replacement
# ---------------------------------------------------------------------------

def _distinct_topk(rk: jax.Array, n_post: int, k: int) -> jax.Array:
    """Uniform k-subset via the k smallest of n_post iid uniforms.
    O(n_post) per row — used only when k > n_post/2, where that *is* O(k)."""
    u = jax.random.uniform(rk, (n_post,))
    _, idx = jax.lax.top_k(-u, k)
    return jnp.sort(idx.astype(jnp.int32))


def _distinct_redraw(rk: jax.Array, n_post: int, k: int) -> jax.Array:
    """Uniform k-subset in O(k) memory: draw k iid values, redraw duplicate
    slots with fresh counters until all distinct.  Keeping first occurrences
    and redrawing the rest is exactly "first k distinct values of an iid
    uniform stream" — i.e. sequential sampling without replacement."""

    def dup_mask(sorted_vals):
        return jnp.concatenate([jnp.zeros((1,), bool),
                                sorted_vals[1:] == sorted_vals[:-1]])

    def cond(carry):
        i, _, has_dup = carry
        return has_dup & (i < _MAX_REDRAW_ROUNDS)

    def body(carry):
        i, vals, _ = carry
        fresh = jax.random.randint(jax.random.fold_in(rk, i), (k,), 0,
                                   n_post, jnp.int32)
        vals = jnp.sort(jnp.where(dup_mask(vals), fresh, vals))
        return i + 1, vals, dup_mask(vals).any()

    v0 = jnp.sort(jax.random.randint(jax.random.fold_in(rk, 0), (k,), 0,
                                     n_post, jnp.int32))
    _, vals, _ = jax.lax.while_loop(cond, body, (1, v0, dup_mask(v0).any()))
    return vals


@functools.partial(jax.jit, static_argnames=("n_post", "k"))
def _sample_distinct_rows(key: jax.Array, rows: jax.Array, n_post: int,
                          k: int) -> jax.Array:
    """[len(rows), k] int32, each row a uniform k-subset of [0, n_post),
    sorted ascending, keyed by the *global* row index."""
    if k > n_post:
        raise ValueError(f"k={k} > n_post={n_post}")
    if k == n_post:
        return jnp.broadcast_to(jnp.arange(n_post, dtype=jnp.int32),
                                (rows.shape[0], n_post))
    rks = _row_keys(key, rows)
    one = _distinct_topk if k > n_post // 2 else _distinct_redraw
    return jax.vmap(lambda rk: one(rk, n_post, k))(rks)


# ---------------------------------------------------------------------------
# initializer kernels
# ---------------------------------------------------------------------------

def _rows_or_default(rows, n_pre: int) -> jax.Array:
    if rows is None:
        return jnp.arange(n_pre, dtype=jnp.int32)
    return jnp.asarray(rows, jnp.int32)


def device_fixed_fanout(key: jax.Array, n_pre: int, n_post: int,
                        n_conn: int, weight=None,
                        rows: Optional[jax.Array] = None) -> _JTriple:
    """Exactly n_conn distinct random targets per pre row, on device."""
    rows = _rows_or_default(rows, n_pre)
    post = _sample_distinct_rows(jax.random.fold_in(key, 0xC0), rows,
                                 n_post, n_conn)
    g = _row_weights(as_device_weight(weight), key, rows, n_conn)
    return post, g.astype(jnp.float32), jnp.ones_like(post, bool)


def _binomial_slots(n_post: int, p: float) -> int:
    """Static slot count covering Binomial(n_post, p) row degrees: mean plus
    six standard deviations (residual clamp probability < 1e-9 per row)."""
    mean = n_post * p
    std = math.sqrt(max(n_post * p * (1.0 - p), 0.0))
    return int(min(n_post, max(1, math.ceil(mean + 6.0 * std + 1.0))))


@functools.partial(jax.jit, static_argnames=("n_post", "k"))
def _fixed_probability_rows(
    key: jax.Array, rows: jax.Array, n_post: int, p: float, k: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(post [R, k], counts [R], overflow [R] bool): per-row
    Binomial(n_post, p) degrees, then a uniform degree-subset of targets (a
    k-subset randomly permuted, first `count` slots valid) — the per-pair
    Bernoulli model, marginalized.  A raw degree draw above the static slot
    padding `k` is clamped, and the row is flagged in `overflow` so callers
    can surface the clamp instead of silently dropping synapses."""
    ckey = jax.random.fold_in(key, 0xDE)

    def one(rk):
        raw = jax.random.binomial(jax.random.fold_in(rk, 1), n_post,
                                  p).astype(jnp.int32)
        cnt = jnp.clip(raw, 0, k)
        vals = (_distinct_topk if k > n_post // 2 else _distinct_redraw)(
            jax.random.fold_in(rk, 2), n_post, k)
        perm = jnp.argsort(
            jax.random.uniform(jax.random.fold_in(rk, 3), (k,)))
        return vals[perm], cnt, raw > k

    return jax.vmap(one)(_row_keys(ckey, rows))


def _report_overflow(n_rows, *, n_pre: int, n_post: int, p: float,
                     k: int) -> None:
    """Surface clamped FixedProbability rows through the trace timeline.

    Under jit/shard_map `n_rows` is a tracer — the count cannot be read at
    trace time, so reporting is skipped here and done by the caller that owns
    the host sync (`device_init_local` reports from its count pass)."""
    if isinstance(n_rows, jax.core.Tracer):
        return
    n = int(jax.device_get(n_rows))
    if n > 0:
        trace.instant("device_init.overflow", kind="fixed_probability",
                      rows_clamped=n, rows=n_pre, n_post=n_post, p=float(p),
                      max_k=k)


def device_fixed_probability(key: jax.Array, n_pre: int, n_post: int,
                             p: float, weight=None,
                             rows: Optional[jax.Array] = None) -> _JTriple:
    """Each (pre, post) pair connected independently with probability p."""
    if not 0.0 <= p <= 1.0:
        raise ValueError(f"FixedProbability p={p} outside [0, 1]")
    rows = _rows_or_default(rows, n_pre)
    k = _binomial_slots(n_post, p)
    post, counts, over = _fixed_probability_rows(key, rows, n_post, p, k)
    _report_overflow(jnp.sum(over.astype(jnp.int32)), n_pre=n_pre,
                     n_post=n_post, p=p, k=k)
    valid = jnp.arange(k, dtype=jnp.int32)[None, :] < counts[:, None]
    g = _row_weights(as_device_weight(weight), key, rows, k)
    g = jnp.where(valid, g, 0.0).astype(jnp.float32)
    return jnp.where(valid, post, 0).astype(jnp.int32), g, valid


def device_one_to_one(key: jax.Array, n_pre: int, n_post: int, weight=None,
                      rows: Optional[jax.Array] = None) -> _JTriple:
    if n_pre != n_post:
        raise ValueError(
            f"OneToOne requires n_pre == n_post, got {n_pre} != {n_post}")
    rows = _rows_or_default(rows, n_pre)
    post = rows[:, None]
    g = _row_weights(as_device_weight(weight), key, rows, 1)
    return post, g.astype(jnp.float32), jnp.ones_like(post, bool)


def device_dense(key: jax.Array, n_pre: int, n_post: int, weight=None,
                 rows: Optional[jax.Array] = None) -> _JTriple:
    rows = _rows_or_default(rows, n_pre)
    post = jnp.broadcast_to(jnp.arange(n_post, dtype=jnp.int32),
                            (rows.shape[0], n_post))
    g = _row_weights(as_device_weight(weight), key, rows, n_post)
    return post, g.astype(jnp.float32), jnp.ones_like(post, bool)


def device_resolve(connect: F.ConnectivityInit, key: jax.Array, n_pre: int,
                   n_post: int, weight=None,
                   rows: Optional[jax.Array] = None) -> _JTriple:
    """Dispatch a ConnectivityInit declaration to its device kernel."""
    if isinstance(connect, F.FixedFanout):
        return device_fixed_fanout(key, n_pre, n_post, connect.n_conn,
                                   weight, rows)
    if isinstance(connect, F.FixedProbability):
        return device_fixed_probability(key, n_pre, n_post, connect.p,
                                        weight, rows)
    if isinstance(connect, F.OneToOne):
        return device_one_to_one(key, n_pre, n_post, weight, rows)
    if isinstance(connect, F.DenseInit):
        return device_dense(key, n_pre, n_post, weight, rows)
    raise NotImplementedError(
        f"no device-side kernel for {connect.describe()}; build with "
        "init='host' or add a kernel to repro.sparse.device_init")


# ---------------------------------------------------------------------------
# post-sharding: repack a built ELL into per-device blocks
# ---------------------------------------------------------------------------

def _shard_counts(post_ind: jax.Array, valid: jax.Array, n_shards: int,
                  shard_size: int) -> jax.Array:
    """[rows, n_shards] int32 slot counts per (pre row, post shard).

    Computed from the sorted shard ids via searchsorted boundaries:
    O(rows * D log K), never an [rows, K, D] one-hot temporary (which would
    be O(nnz * D) — the very blowup this module exists to avoid).  Every op
    is per-row independent, so counts over any row chunk equal the matching
    rows of the full-matrix call — the property `device_init_local` leans on.
    """
    shard = jnp.where(valid, post_ind // shard_size, n_shards)
    shard_s = jnp.sort(shard, axis=1)
    bounds = jnp.arange(n_shards + 1, dtype=shard_s.dtype)
    edges = jax.vmap(
        lambda row: jnp.searchsorted(row, bounds, side="left"))(shard_s)
    return jnp.diff(edges, axis=1)


def _partition_rows(
    g: jax.Array, post_ind: jax.Array, valid: jax.Array,
    delay: Optional[jax.Array], n_shards: int, shard_size: int, k_local: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, Optional[jax.Array]]:
    """Repack ELL rows into [n_shards, rows, k_local] post-shard blocks.

    Slot (i, k) goes to the shard owning post neuron post_ind[i, k],
    compacted left and re-indexed to shard-local post ids; the within-row
    slot order is preserved (stable sort), so per-post-neuron scatter
    accumulation order — and hence bit-exact currents — matches the input
    slot order.  All ops are per-row independent: partitioning a chunk of
    rows bit-matches the corresponding rows of a full-matrix partition.

    The slot axis is first padded with invalid slots to whole 128-lane
    tiles, and the rows are sorted by shard with their payload in one
    multi-operand sort, then gathered per shard: compiled for a TPU,
    gathering or scattering along an unaligned K (1,000) is a relayout
    whose compile time grows with the slot count.
    """
    pad = -g.shape[1] % _LANE
    if pad:
        g, post_ind, valid, delay = (
            None if a is None else jnp.pad(a, ((0, 0), (0, pad)))
            for a in (g, post_ind, valid, delay))
    shard = jnp.where(valid, post_ind // shard_size, n_shards)
    payload = (post_ind.astype(jnp.int32),
               jnp.where(valid, g, 0.0).astype(jnp.float32))
    if delay is not None:
        payload += (jnp.where(valid, delay, 0).astype(jnp.int32),)
    shard_s, *sorted_ = jax.lax.sort((shard,) + payload, dimension=1,
                                     num_keys=1, is_stable=True)
    bounds = jnp.arange(n_shards + 1, dtype=shard_s.dtype)
    edges = jax.vmap(
        lambda row: jnp.searchsorted(row, bounds, side="left"))(shard_s)
    j = jnp.arange(k_local, dtype=edges.dtype)[None, :]
    blocks = []                    # per shard: (g, post, valid[, delay])
    for d in range(n_shards):
        idx = edges[:, d:d + 1] + j                 # [n_rows, k_local]
        ok = idx < edges[:, d + 1:d + 2]
        idx = jnp.where(ok, idx, 0)
        post_d, g_d, *delay_d = (
            jnp.where(ok, jnp.take_along_axis(a, idx, axis=1), 0)
            for a in sorted_)
        blocks.append((g_d, jnp.where(ok, post_d - d * shard_size, 0), ok,
                       *delay_d))
    g_out, post_out, valid_out, *delay_out = (jnp.stack(a) for a in
                                              zip(*blocks))
    return g_out, post_out, valid_out, (delay_out[0] if delay_out else None)


def partition_ell_by_post(
    ell: F.ELLSynapses, n_shards: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, Optional[jax.Array], int, int]:
    """Split an ELL column-wise into `n_shards` post-neuron shards.

    Returns (g, post_local, valid, delay_local, shard_size, k_local) with
    the array outputs shaped [n_shards, n_pre, k_local]: shard d holds, for
    every pre row, the slots whose post neuron lives in
    [d*shard_size, (d+1)*shard_size), compacted left and re-indexed to
    shard-local post ids.  The within-row slot order is preserved (stable
    sort), so per-post-neuron scatter accumulation order — and hence
    bit-exact currents — matches the global ELL.  The per-synapse dendritic
    delay slot (when present) rides along through the identical permutation;
    delay_local is None for delay-free ELLs.  Total memory across shards
    ~= nnz (k_local ~= K / n_shards).

    This materializes the *full* ELL first — every device pays O(nnz).  For
    builds where that does not fit, `device_init_local` fuses generation and
    partitioning per device at O(nnz / n_devices) peak, bit-exactly.
    """
    n_post = ell.n_post
    shard_size = -(-n_post // n_shards)  # ceil
    counts = _shard_counts(ell.post_ind, ell.valid, n_shards, shard_size)
    k_local = max(1, int(counts.max()))           # build-time host sync
    g_out, post_out, valid_out, delay_out = _partition_rows(
        ell.g, ell.post_ind, ell.valid, ell.delay, n_shards, shard_size,
        k_local)
    return g_out, post_out, valid_out, delay_out, shard_size, k_local


# ---------------------------------------------------------------------------
# fused local construction: generate only the rows you own, partition in
# place, exchange slots — peak memory O(nnz / device)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LocalInitPlan:
    """Everything `device_init_local` needs to rebuild one synapse group's
    post-sharded blocks without the full ELL: the declaration, its key, and
    the generation-space geometry.  `n_post_total` is the *generation* post
    space (the concatenated post-population window); `post_window` restricts
    to one concrete group's [lo, hi) slice of it (None = the whole space)."""
    connect: F.ConnectivityInit
    key: jax.Array
    n_pre: int
    n_post_total: int
    weight: object = None
    delay: object = None
    post_window: Optional[Tuple[int, int]] = None


def _fp_row_overflow(key: jax.Array, rows: jax.Array, n_post: int,
                     p: float) -> jax.Array:
    """[rows] int32 flags: FixedProbability rows whose raw Binomial degree
    draw exceeds the static ELL slot padding (mirrors the key schedule of
    `_fixed_probability_rows` without materializing targets)."""
    k = _binomial_slots(n_post, p)
    ckey = jax.random.fold_in(key, 0xDE)

    def one(rk):
        raw = jax.random.binomial(jax.random.fold_in(rk, 1), n_post,
                                  p).astype(jnp.int32)
        return (raw > k).astype(jnp.int32)

    return jax.vmap(one)(_row_keys(ckey, rows))


def device_init_local(
    connect: F.ConnectivityInit, key: jax.Array, n_pre: int, n_post: int,
    mesh, weight=None, delay=None, axis: Optional[str] = None,
    post_window: Optional[Tuple[int, int]] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array, Optional[jax.Array], int, int]:
    """Fused `device_resolve` + `partition_ell_by_post` under `shard_map`.

    Each device generates *only* its own ceil(n_pre / D) pre rows (via the
    counter-based `rows=` argument, so the draws bit-match a global
    generation), partitions them into post-shard blocks locally, and trades
    slots with an `all_to_all` — no device ever materializes the full ELL,
    so peak construction memory is O(nnz / device) instead of O(nnz).

    Returns (g, post_local, valid, delay_local, shard_size, k_local) with
    arrays shaped [n_shards, n_pre, k_local] exactly like
    `partition_ell_by_post` (sharded along axis 0 over the mesh) and
    bit-identical to the generate-then-partition path at any device count.

    `n_post` is the total generation post space; `post_window=(lo, hi)`
    restricts the output to one post-population window of it (matching the
    multi-post-population split in `ModelSpec._build`).
    """
    axis = snn_axis(mesh) if axis is None else axis
    D = int(mesh.shape[axis])
    if post_window is None:
        lo, hi = 0, int(n_post)
    else:
        lo, hi = int(post_window[0]), int(post_window[1])
    n_local_post = hi - lo
    shard_size = -(-n_local_post // D)   # == engine's per-device post shard
    R = -(-n_pre // D)                   # padded pre rows per device
    has_delay = delay is not None
    is_fp = isinstance(connect, F.FixedProbability)

    def _generate(k):
        """This device's row chunk, masked to the post window.  Rows past
        n_pre (pre-axis padding) are generated then invalidated — their
        draws never reach the output, so padding cannot break exactness."""
        d = jax.lax.axis_index(axis)
        rows = d * R + jnp.arange(R, dtype=jnp.int32)
        post, g, valid = device_resolve(connect, k, n_pre, n_post, weight,
                                        rows=rows)
        valid = valid & (rows < n_pre)[:, None]
        dd = None
        if has_delay:
            dd = device_delays(k, n_pre, post.shape[1], delay, rows=rows)
            dd = jnp.where(valid, dd, 0).astype(jnp.int32)
        if post_window is not None:
            mask = (post >= lo) & (post < hi) & valid
            post = jnp.where(mask, post - lo, 0).astype(jnp.int32)
            g = jnp.where(mask, g, 0.0).astype(jnp.float32)
            dd = None if dd is None else jnp.where(mask, dd, 0)
            valid = mask
        return rows, post, g, valid, dd

    def count_fn(k):
        rows, post, _, valid, _ = _generate(k)
        counts = _shard_counts(post, valid, D, shard_size)
        # reduce across the axis so the outputs are replicated: in a
        # multi-host mesh each process can only read its own shards, but
        # every process needs the same k_local to build the same program
        kmax = jax.lax.pmax(jnp.max(counts).astype(jnp.int32), axis)
        if is_fp:
            over = _fp_row_overflow(k, rows, n_post, connect.p)
            osum = jax.lax.psum(
                jnp.sum(jnp.where(rows < n_pre, over, 0)), axis)
        else:
            osum = jnp.zeros((), jnp.int32)
        return kmax.reshape(1), osum.reshape(1)

    counted = jax.jit(jax.shard_map(
        count_fn, mesh=mesh, in_specs=(P(),),
        out_specs=(P(), P()), check_vma=False))(key)
    k_local = max(1, int(jax.device_get(counted[0])[0]))
    if is_fp:
        overflow = int(jax.device_get(counted[1])[0])
        if overflow > 0:
            trace.instant("device_init.overflow", kind="fixed_probability",
                          rows_clamped=overflow, rows=n_pre, n_post=n_post,
                          p=float(connect.p),
                          max_k=_binomial_slots(n_post, connect.p))

    def fill_fn(k):
        _, post, g, valid, dd = _generate(k)
        parts = _partition_rows(g, post, valid, dd, D, shard_size, k_local)
        out = []
        for arr in parts:
            if arr is None:
                continue
            # [D, R, kl] where [s] = slots for shard s from this device's
            # rows; all_to_all makes [s] = device s's rows for *this* shard,
            # so the reshape recovers global row order for the local block
            blk = jax.lax.all_to_all(arr, axis, split_axis=0, concat_axis=0)
            out.append(blk.reshape(D * R, k_local)[None])
        return tuple(out)

    n_out = 4 if has_delay else 3
    outs = jax.jit(jax.shard_map(
        fill_fn, mesh=mesh, in_specs=(P(),),
        out_specs=tuple(P(axis, None, None) for _ in range(n_out)),
        check_vma=False))(key)
    g_out = outs[0][:, :n_pre]
    post_out = outs[1][:, :n_pre]
    valid_out = outs[2][:, :n_pre]
    delay_out = outs[3][:, :n_pre] if has_delay else None
    return g_out, post_out, valid_out, delay_out, shard_size, k_local


def construction_peak_model(n_pre: int, k: int, n_devices: int, k_local: int,
                            has_delay: bool = False) -> dict:
    """Analytic peak construction bytes per device for one synapse group:
    generate-then-partition (every device materializes the full [n_pre, k]
    ELL plus sort temporaries plus the full [D, n_pre, k_local] block stack)
    vs. the fused local path (only ceil(n_pre / D) rows resident, plus the
    partitioned blocks, their all_to_all receive buffer, and the final
    block).  Used by `ModelSpec.plan` and the scaling bench — the fused
    number is the O(nnz / device) claim, stated in bytes."""
    slot_b = F.ell_slot_bytes(has_delay)
    # shard ids (i4) + sorted shard ids (i4) + sorted copies of each slot
    # array: the transient working set of `_partition_rows` per source slot
    tmp_b = 8 + slot_b
    rows_local = -(-n_pre // n_devices)
    block_b = n_devices * k_local * slot_b       # [D, ., k_local] per row
    gen = n_pre * (k * (slot_b + tmp_b) + block_b)
    fused = rows_local * (k * (slot_b + tmp_b) + 3 * block_b)
    return {"generate_partition_bytes": int(gen),
            "fused_local_bytes": int(fused)}
