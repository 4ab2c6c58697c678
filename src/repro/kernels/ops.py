"""Jit'd dispatching wrappers around the Pallas kernels.

Dispatch policy (``REPRO_USE_PALLAS``, parsed by repro.flags.pallas_mode):
  REPRO_USE_PALLAS=1          -> compiled Pallas kernels (real TPU)
  REPRO_USE_PALLAS=interpret  -> Pallas interpret mode (CPU validation);
                                 refused with InterpretOnTPUError on a TPU
  unset/0                     -> pure-jnp reference (the default everywhere,
                                 the TPU included)

The backend is read when a wrapper is traced, so a jitted program keeps the
backend it was traced with.  The public functions keep one signature
regardless of backend so the rest of the system never branches.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro import flags as _flags
from repro.kernels import ref as _ref

__all__ = [
    "backend", "use_pallas", "InterpretOnTPUError", "ell_spmv",
    "ell_spmv_batched", "ell_spmv_delay", "ell_spmv_delay_batched",
    "ell_spmv_event", "ell_spmv_event_delay", "izhikevich_step", "hh_step",
    "flash_attention", "ssd_scan",
]


class InterpretOnTPUError(RuntimeError):
    """REPRO_USE_PALLAS=interpret while JAX's default backend is a TPU."""


def backend() -> str:
    """Kernel backend: 'ref' | 'pallas' | 'interpret'.  The env parse lives
    in repro.flags.pallas_mode (one site; misspellings raise).  Interpret
    mode on a TPU raises InterpretOnTPUError: the chip would run the kernels
    through the interpreter, slowly and without saying so."""
    mode = _flags.pallas_mode()
    if mode is _flags.PallasMode.ON:
        return "pallas"
    if mode is _flags.PallasMode.INTERPRET:
        if jax.default_backend() == "tpu":
            raise InterpretOnTPUError(
                "REPRO_USE_PALLAS=interpret on a TPU would interpret the "
                "Pallas kernels instead of compiling them; set "
                "REPRO_USE_PALLAS=1 for the compiled kernels or unset it for "
                "the reference path")
        return "interpret"
    return "ref"


def use_pallas() -> bool:
    return backend() != "ref"


# -- sparse synaptic accumulation -------------------------------------------

def ell_spmv_batched(ell, spikes: jax.Array) -> jax.Array:
    """spikes [B, n_pre] -> currents [B, n_post]."""
    be = backend()
    if be == "ref":
        return _ref.ell_spmv_ref(ell.g, ell.post_ind, ell.valid, spikes,
                                 ell.n_post)
    from repro.kernels.ell_spmv import ell_spmv_pallas
    return ell_spmv_pallas(ell.g, ell.post_ind, ell.valid, spikes,
                           n_post=ell.n_post,
                           interpret=(be == "interpret"))


def _per_sample(fn):
    """Decorate `fn(ell, spikes, *static)` of one sample so that under vmap
    it runs once per sample in a loop.

    Serving vmaps the step over streams (sweep_gscale over values).
    Batched, the reference scatter puts the batch in the minor dimension of
    its updates (32x padding on a TPU), and a batched ``lax.cond``
    predicate turns event propagation into a select that also runs the
    dense fallback for every sample.  In the loop each sample runs the
    offline program, so a served stream stays bit-exact against its
    offline run on every backend.  One custom_vmap per static arguments:
    a jaxpr names its batching rule by identity, so equal programs keep
    equal jaxprs."""
    @functools.lru_cache(maxsize=None)
    def looped(*static):
        return jax.custom_batching.sequential_vmap(
            lambda ell, spikes: fn(ell, spikes, *static))

    @functools.wraps(fn)
    def wrapper(ell, spikes: jax.Array, *static) -> jax.Array:
        return looped(*static)(ell, spikes)
    return wrapper


@_per_sample
def ell_spmv(ell, spikes: jax.Array) -> jax.Array:
    """spikes [n_pre] -> currents [n_post]."""
    return ell_spmv_batched(ell, spikes[None, :])[0]


def ell_spmv_delay_batched(ell, spikes: jax.Array, n_slots: int) -> jax.Array:
    """Fused delay-scatter: spikes [B, n_pre] -> ring contributions
    [B, n_slots, n_post] (slot d = contributions arriving d steps from now,
    before cursor rotation).  Requires ell.delay."""
    be = backend()
    if be == "ref":
        return _ref.ell_spmv_delay_ref(ell.g, ell.post_ind, ell.valid,
                                       ell.delay, spikes, ell.n_post, n_slots)
    from repro.kernels.ell_spmv import ell_spmv_delay_pallas
    return ell_spmv_delay_pallas(ell.g, ell.post_ind, ell.valid, ell.delay,
                                 spikes, n_post=ell.n_post, n_slots=n_slots,
                                 interpret=(be == "interpret"))


@_per_sample
def ell_spmv_delay(ell, spikes: jax.Array, n_slots: int) -> jax.Array:
    """spikes [n_pre] -> ring contributions [n_slots, n_post]."""
    return ell_spmv_delay_batched(ell, spikes[None, :], n_slots)[0]


# -- event-driven propagation -------------------------------------------------

def _compact_rows(ell, spikes: jax.Array, capacity: int):
    """Compact the spiking pre-neuron rows of an ELL matrix.

    Returns (ell_c, spk_c, count): a capacity-row ELL holding the spiking
    rows in ascending pre order (dead tail rows invalidated), the matching
    spike values, and the true spike count.  Ascending order + exact-zero
    contributions from dropped rows keep the per-post accumulation sequence
    identical to the dense pass, so the result is bit-exact."""
    n_pre = ell.n_pre
    hits = spikes != 0
    count = jnp.sum(hits.astype(jnp.int32))
    (idx,) = jnp.nonzero(hits, size=capacity, fill_value=n_pre)
    safe = jnp.minimum(idx, n_pre - 1)
    live = idx < n_pre
    ell_c = type(ell)(
        g=ell.g[safe], post_ind=ell.post_ind[safe],
        valid=ell.valid[safe] & live[:, None], n_post=ell.n_post,
        delay=None if ell.delay is None else ell.delay[safe])
    spk = jnp.asarray(spikes, jnp.float32)
    spk_c = jnp.where(live, spk[safe], 0.0)
    return ell_c, spk_c, count


def _scoped(name: str, fn, *args):
    """A ``lax.cond`` branch: ``fn(*args)`` under the named scope
    ``name`` (``event`` within capacity, ``fallback`` over it)."""
    def branch():
        with jax.named_scope(name):
            return fn(*args)
    return branch


@_per_sample
def ell_spmv_event(ell, spikes: jax.Array, capacity: int) -> jax.Array:
    """Event-driven spmv: gather only the spiking rows (fixed capacity);
    more than `capacity` simultaneous spikes falls back to the dense pass.
    spikes [n_pre] -> currents [n_post], bit-exact vs ell_spmv."""
    with jax.named_scope("compact"):
        ell_c, spk_c, count = _compact_rows(ell, spikes, capacity)
    spk = jnp.asarray(spikes, jnp.float32)
    return jax.lax.cond(
        count <= capacity,
        _scoped("event", ell_spmv, ell_c, spk_c),
        _scoped("fallback", ell_spmv, ell, spk))


@_per_sample
def ell_spmv_event_delay(ell, spikes: jax.Array, n_slots: int,
                         capacity: int) -> jax.Array:
    """Event-driven fused delay-scatter: spikes [n_pre] ->
    [n_slots, n_post], bit-exact vs ell_spmv_delay; overflow falls back to
    the dense fused pass."""
    with jax.named_scope("compact"):
        ell_c, spk_c, count = _compact_rows(ell, spikes, capacity)
    spk = jnp.asarray(spikes, jnp.float32)
    return jax.lax.cond(
        count <= capacity,
        _scoped("event", ell_spmv_delay, ell_c, spk_c, n_slots),
        _scoped("fallback", ell_spmv_delay, ell, spk, n_slots))


# -- fused neuron updates -----------------------------------------------------

def izhikevich_step(v, u, isyn, a, b, c, d, dt: float):
    be = backend()
    if be == "ref":
        return _ref.izhikevich_step_ref(v, u, isyn, a, b, c, d, dt)
    from repro.kernels.izhikevich_step import izhikevich_step_pallas
    n = v.shape[0]
    bcast = lambda x: jnp.broadcast_to(jnp.asarray(x, jnp.float32), (n,))
    return izhikevich_step_pallas(
        v, u, isyn, bcast(a), bcast(b), bcast(c), bcast(d), dt=dt,
        interpret=(be == "interpret"))


def hh_step(v, m, h, n, isyn, dt: float, **params):
    be = backend()
    if be == "ref":
        return _ref.hh_step_ref(v, m, h, n, isyn, dt, **params)
    from repro.kernels.hh_step import hh_step_pallas
    return hh_step_pallas(v, m, h, n, isyn, dt=dt,
                          interpret=(be == "interpret"), **params)


# -- LM kernels ---------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    scale: Optional[float] = None, q_offset: int = 0,
                    softcap: Optional[float] = None,
                    prefix: Optional[int] = None):
    from repro import flags
    be = backend()
    if flags.ROOFLINE_NO_ATTN:
        # identity-shaped stand-in: costs of projections remain, core gone
        rep = q.shape[1] // k.shape[1]
        return q * (scale or 1.0) + jnp.repeat(v, rep, axis=1).mean(
            axis=2, keepdims=True)
    if flags.ROOFLINE_NAIVE_ATTN:
        return _ref.flash_attention_ref(
            q, k, v, causal=causal, window=window, scale=scale,
            q_offset=q_offset, softcap=softcap, prefix=prefix)
    if isinstance(window, jax.core.Tracer):
        # traced window (not produced by the built-in archs): masked XLA path
        return _ref.flash_attention_xla(
            q, k, v, causal=causal, window=window, scale=scale,
            q_offset=q_offset, softcap=softcap, prefix=prefix)
    if be == "ref":
        if q.shape[2] * k.shape[2] <= 1024 * 1024:
            return _ref.flash_attention_ref(
                q, k, v, causal=causal, window=window, scale=scale,
                q_offset=q_offset, softcap=softcap, prefix=prefix)
        from repro.kernels.flash_xla import flash_attention_xla
        return flash_attention_xla(q, k, v, causal, window, scale,
                                   q_offset, softcap, prefix)
    if prefix is not None:
        # prefix-LM masking not in the Pallas kernel (VLM prefill only)
        return _ref.flash_attention_xla(
            q, k, v, causal=causal, window=window, scale=scale,
            q_offset=q_offset, softcap=softcap, prefix=prefix)
    from repro.kernels.flash_attention import flash_attention_pallas
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, scale=scale,
        q_offset=q_offset, softcap=softcap,
        interpret=(be == "interpret"))


def ssd_scan(x, dt, A, B, C, D=None):
    from repro import flags
    if flags.ROOFLINE_NO_SSD:
        return x * dt[..., None] + C.mean(axis=(2, 3))[..., None, None]
    be = backend()
    if be == "ref":
        from repro.models.ssm import ssd_chunked  # chunked jnp (production)
        return ssd_chunked(x, dt, A, B, C, D)
    from repro.kernels.ssd_scan import ssd_scan_pallas
    return ssd_scan_pallas(x, dt, A, B, C, D,
                           interpret=(be == "interpret"))
