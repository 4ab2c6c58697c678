"""Compile the main path's Pallas kernels for a described TPU v5e.

Interpret mode (tests/test_kernels.py) checks the kernels' numbers on the
CPU but not whether Mosaic accepts them: block shapes off the (8, 128)
tiling and in-kernel reshapes pass the interpreter and fail on the chip.
These tests compile each kernel for one chip of a ``v5e:2x2`` topology that
is described, not attached, at the widths of chip_smoke.py's network
(``IzhikevichNetConfig(n_total=100_000, n_conn=1_000)``: 80,000 excitatory
and 20,000 inhibitory neurons, K = 1,000 slots per row), at its event-
capacity widths, at one fused-delay width and at a width the interpret-mode
tests use.  The reference backend's event propagation, the chip's default,
is compiled too, offline and as four served streams run it.  Each compile
takes about two seconds; nothing runs.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.ell_spmv import ell_spmv_delay_pallas, ell_spmv_pallas
from repro.sparse.formats import ELLSynapses

K = 1_000
N_EXC, N_INH = 80_000, 20_000
CAP_EXC, CAP_INH = 20_000, 5_000     # choose_propagation's event capacities


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: a compile for a described chip cannot be read back."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _ell_shapes(sharding, n_pre, k, b):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return (s((n_pre, k), jnp.float32), s((n_pre, k), jnp.int32),
            s((n_pre, k), jnp.bool_), s((n_pre, k), jnp.int32),
            s((b, n_pre), jnp.float32))


def _assert_kernel_compiled(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_pre,k,n_post,b", [
    (N_EXC, K, N_EXC, 1),            # exc -> exc
    (N_EXC, K, N_INH, 1),            # exc -> inh
    (N_INH, K, N_EXC, 1),            # inh -> exc
    (N_INH, K, N_INH, 1),            # inh -> inh
    # the excitatory groups' event shapes (CAP_EXC rows) are inh_* above
    (CAP_INH, K, N_EXC, 1),          # inh -> exc event capacity
    (CAP_INH, K, N_INH, 1),          # inh -> inh event capacity
    (N_EXC, K, N_EXC, 4),            # four served streams
    (200, 50, 333, 2),               # an interpret-mode test width
], ids=["exc_exc", "exc_inh", "inh_exc", "inh_inh", "event_inh_exc",
        "event_inh_inh", "streams4", "small"])
def test_ell_spmv_compiles_for_v5e(one_chip, n_pre, k, n_post, b):
    g, post, valid, _, spk = _ell_shapes(one_chip, n_pre, k, b)
    _assert_kernel_compiled(
        ell_spmv_pallas.lower(g, post, valid, spk, n_post=n_post).compile())


@pytest.mark.parametrize("n_pre,k,n_post,b,n_slots", [
    (N_INH, K, N_INH, 1, 16),        # chip_smoke.py's delay shape
    (48, 6, 40, 1, 5),               # an interpret-mode test width
], ids=["inh_inh_16slots", "small"])
def test_ell_spmv_delay_compiles_for_v5e(one_chip, n_pre, k, n_post, b,
                                         n_slots):
    g, post, valid, delay, spk = _ell_shapes(one_chip, n_pre, k, b)
    _assert_kernel_compiled(ell_spmv_delay_pallas.lower(
        g, post, valid, delay, spk, n_post=n_post,
        n_slots=n_slots).compile())


@pytest.mark.parametrize("streams", [0, 4], ids=["offline", "streams4"])
def test_reference_event_spmv_compiles_for_v5e(one_chip, monkeypatch,
                                               streams):
    """exc -> exc event propagation on the reference backend (XLA scatter),
    one spike vector or four served streams under vmap.  Batched, the
    streams' scatter updates took a [slots, streams] layout padded 32x on
    the chip, more than its 16 GB; the per-stream loop stays within the
    unbatched program's footprint."""
    monkeypatch.delenv("REPRO_USE_PALLAS", raising=False)
    g, post, valid, _, spk = _ell_shapes(one_chip, N_EXC, K, max(streams, 1))

    def event(g, post, valid, spk):
        ell = ELLSynapses(g=g, post_ind=post, valid=valid, n_post=N_EXC)
        one = lambda s: ops.ell_spmv_event(ell, s, CAP_EXC)
        return jax.vmap(one)(spk) if streams else one(spk[0])

    compiled = jax.jit(event).lower(g, post, valid, spk).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 1024 ** 3
