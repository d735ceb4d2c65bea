"""Device-mesh construction.

The mesh is the TPU-native replacement for Horovod's rank/size world
(SURVEY.md §2 #7-#9): axis ``data`` is the gradient-allreduce axis
(BASELINE.json:5 "psum over ICI"); ``fsdp`` shards parameters along the same
data-parallel family; ``model``/``seq``/``expert``/``pipeline`` host tensor,
sequence, expert, and pipeline parallelism. Size-1 axes are free, so every
program is written against the full six-axis mesh and collapses cleanly to
single-chip.

Axis order puts ``model``/``seq`` innermost so tensor/sequence collectives
(all-gather, ppermute rings) land on the fastest ICI neighbours, while pure-DP
psums tolerate the outer (slower, possibly DCN) dimensions — the standard
TPU mesh layout recipe.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from distributeddeeplearning_tpu.config import ParallelConfig

MESH_AXES: tuple[str, ...] = (
    "pipeline", "data", "fsdp", "expert", "seq", "model")


def backend_devices(backend: Optional[str] = None) -> list[jax.Device]:
    """The devices a run on ``backend`` is placed on.

    ``"tpu"`` means TPUs: it raises when JAX's devices are anything else,
    instead of quietly training on the CPU and exiting 0. ``"cpu"`` forces
    the host's CPU devices (tests, ``--backend cpu``). ``None`` takes JAX's
    default devices as they are (library callers that placed the process
    themselves, e.g. with ``JAX_PLATFORMS``).
    """
    if backend not in (None, "tpu", "cpu"):
        raise ValueError(f"unknown backend {backend!r} (tpu | cpu)")
    if backend == "cpu":
        return jax.devices("cpu")
    devices = jax.devices()
    if backend == "tpu" and devices[0].platform != "tpu":
        raise RuntimeError(
            f"backend 'tpu' was asked for but JAX found only "
            f"{devices[0].platform} devices; run on a machine with a TPU, "
            f"or say --backend cpu (JAX_PLATFORMS=cpu) to mean the CPU")
    return devices


def make_mesh(parallel: ParallelConfig,
              devices: Optional[Sequence[jax.Device]] = None,
              backend: Optional[str] = None) -> Mesh:
    """Build a Mesh matching ``parallel``'s axis sizes on ``devices``, or on
    :func:`backend_devices` of ``backend`` (``TrainConfig.backend``, the
    library-level counterpart of ``train.py --backend``).

    Uses ``mesh_utils.create_device_mesh`` on real TPU platforms so the mesh
    axes align with the physical ICI torus; falls back to a reshape for CPU
    test devices (where topology is fake anyway).
    """
    if devices is None:
        devices = backend_devices(backend)
    sizes = parallel.axis_sizes()
    shape = tuple(sizes[a] for a in MESH_AXES)
    n = int(np.prod(shape))
    if n > len(devices):
        raise ValueError(
            f"mesh axes {dict(zip(MESH_AXES, shape))} need {n} devices, "
            f"have {len(devices)}")
    devices = list(devices)[:n]  # sub-mesh on the first n devices
    if devices[0].platform == "tpu":
        num_slices = len({getattr(d, "slice_index", 0) for d in devices})
        if num_slices > 1:
            # Multi-slice pod: slices are joined by DCN (the InfiniBand role —
            # SURVEY.md §5.8), so the gradient-allreduce axes must span
            # slices while tensor/sequence collectives stay on intra-slice
            # ICI. create_hybrid_device_mesh lays devices out exactly so.
            per_slice, dcn = _hybrid_shapes(shape, num_slices)
            dev_array = mesh_utils.create_hybrid_device_mesh(
                per_slice, dcn, devices=list(devices))
        else:
            dev_array = mesh_utils.create_device_mesh(
                shape, devices=list(devices))
    elif parallel.emulate_slices > 1:
        # Emulated multi-slice layout (validation): treat device blocks of
        # size n/num_slices as slices and arrange each global axis
        # DCN-major / per-slice-minor — the same arrangement
        # create_hybrid_device_mesh produces on a real pod, so the sharding
        # rules and collectives compile against the hybrid layout without
        # multi-slice hardware.
        per_slice, dcn = _hybrid_shapes(shape, parallel.emulate_slices)
        k = len(shape)
        arr = np.asarray(list(devices)).reshape(tuple(dcn) + tuple(per_slice))
        perm = [x for i in range(k) for x in (i, k + i)]
        dev_array = arr.transpose(perm).reshape(shape)
    else:
        dev_array = np.asarray(list(devices)).reshape(shape)
    return Mesh(dev_array, MESH_AXES)


def _hybrid_shapes(shape: tuple[int, ...],
                   num_slices: int) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """Split a global mesh shape into (per-slice ICI shape, DCN shape).

    DCN (slow, inter-slice) carries the outermost axes in MESH_AXES order —
    ``pipeline`` first, then ``data`` — because pipeline stage boundaries and
    gradient allreduces tolerate DCN latency, while ``model``/``seq``
    collectives are per-layer and must stay on ICI. Each consumed axis size
    must be divisible by its DCN share.
    """
    per_slice, dcn = list(shape), [1] * len(shape)
    remaining = num_slices
    for i, axis in enumerate(MESH_AXES):
        if remaining == 1:
            break
        if axis not in ("pipeline", "data"):
            continue
        take = np.gcd(per_slice[i], remaining)
        if take > 1:
            dcn[i] = int(take)
            per_slice[i] //= int(take)
            remaining //= int(take)
    if remaining != 1:
        raise ValueError(
            f"cannot distribute {num_slices} slices over the "
            f"pipeline/data axes of mesh {dict(zip(MESH_AXES, shape))}; "
            f"make pipeline*data divisible by the slice count")
    return tuple(per_slice), tuple(dcn)


def data_axis_names(parallel: ParallelConfig) -> tuple[str, ...]:
    """Mesh axes over which the global batch is split (and grads psummed)."""
    del parallel  # size-1 axes are no-ops, so both are always safe to name
    return ("data", "fsdp")


def data_parallel_degree(parallel: ParallelConfig) -> int:
    """Number of data shards (product of the data-parallel family axes).

    This is the degree the elastic launcher re-plans on host loss/gain
    (launch.py --elastic): gradients are allreduce-MEANS over the data axes
    at a fixed global batch, so the degree can change between attempts while
    the optimizer trajectory stays bitwise (docs/fault_tolerance.md).
    """
    return int(parallel.data) * int(parallel.fsdp)


def use_mesh(mesh: Mesh):
    """Ambient-mesh context manager, so ``with_sharding_constraint``/flax
    logical constraints can resolve bare PartitionSpecs during tracing."""
    return jax.sharding.set_mesh(mesh)


def local_mesh_description(mesh: Mesh) -> str:
    return ", ".join(f"{a}={s}" for a, s in mesh.shape.items() if s > 1) or "1 device"
