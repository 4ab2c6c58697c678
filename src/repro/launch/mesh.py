"""Production mesh construction.

Single pod: 16x16 = 256 chips, axes ("data", "model").
Multi-pod:  2x16x16 = 512 chips, axes ("pod", "data", "model") — "pod" is an
outer data/FSDP axis crossing the inter-pod (DCN/ICI) links.

Functions, not module constants: importing this module must never touch JAX
device state (device count is locked at first backend init; the dry-run sets
XLA_FLAGS before importing anything).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh",
           "make_snn_mesh", "snn_axis", "batch_axes", "MeshPlan",
           "init_distributed"]

#: mesh axis the SNN engine partitions neuron populations over
SNN_AXIS = "neuron"

# process-wide: jax.distributed.initialize may run exactly once
_DISTRIBUTED = {"initialized": False}


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     **kwargs) -> Tuple[int, int]:
    """Wire this process into a multi-host JAX runtime and return
    (process_index, process_count).

    Call once per process before building any mesh; afterwards
    `jax.devices()` spans every host, so `make_snn_mesh()` returns a
    mesh crossing hosts and `ModelSpec.build(init="device", mesh=...)`
    constructs each host's connectivity shards locally
    (`device_init_local`) — no host ever materializes the full ELL.

    With no arguments the coordinator/rank come from the environment
    (JAX_COORDINATOR_ADDRESS etc. / the cluster plugin); pass
    `coordinator_address="host:port"`, `num_processes`, `process_id`
    explicitly for bare multi-process launches.  Idempotent: a second
    call (or an already-initialized runtime) is a no-op."""
    if not _DISTRIBUTED["initialized"]:
        # CPU cross-process collectives use gloo, the installed JAX's
        # default for jax_cpu_collectives_implementation
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id, **kwargs)
        except RuntimeError as e:
            # tolerate double-init (ours or a framework's): the runtime
            # is already up, which is all this function guarantees
            if "already" not in str(e).lower():
                raise
        _DISTRIBUTED["initialized"] = True
    return jax.process_index(), jax.process_count()


def make_mesh(shape, axes):
    """A mesh over the first prod(shape) devices with Auto (compiler-
    propagated) sharding on every axis."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_parallel: int = 1):
    """Mesh over whatever devices exist (tests / CPU runs)."""
    n = len(jax.devices())
    mp = max(1, min(model_parallel, n))
    return make_mesh((n // mp, mp), ("data", "model"))


def make_snn_mesh(n_devices: Optional[int] = None):
    """1-D mesh for the sharded SNN engine: populations are partitioned
    along the neuron axis (`SNN_AXIS`) over `n_devices` (default: all)."""
    n = len(jax.devices()) if n_devices is None else int(n_devices)
    return make_mesh((n,), (SNN_AXIS,))


def snn_axis(mesh) -> str:
    """The neuron-partition axis of a mesh: `SNN_AXIS` when present, else a
    single-axis mesh's only axis (so plain 1-D meshes work unrenamed)."""
    if SNN_AXIS in mesh.axis_names:
        return SNN_AXIS
    if len(mesh.axis_names) == 1:
        return mesh.axis_names[0]
    raise ValueError(
        f"mesh axes {mesh.axis_names} have no {SNN_AXIS!r} axis; build the "
        "mesh with make_snn_mesh or name one axis 'neuron'")


def batch_axes(mesh) -> Tuple[str, ...]:
    """Axes the global batch (and FSDP shards) ride on."""
    return tuple(a for a in mesh.axis_names if a != "model")


class MeshPlan:
    """Mesh + axis bookkeeping passed through launch entry points."""

    def __init__(self, mesh):
        self.mesh = mesh
        self.batch = batch_axes(mesh)
        self.model = "model" if "model" in mesh.axis_names else None

    @property
    def n_devices(self) -> int:
        return self.mesh.devices.size

    def __repr__(self) -> str:
        axes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        return f"MeshPlan({axes})"
