"""Bucketed (fused) gradient all-reduce for the explicit-DP path.

Horovod's tensor fusion exists because reducing a CNN's gradient pytree
leaf-by-leaf issues one collective per parameter tensor — ResNet50 has ~160
leaves, many under 10 KB, so launch/latency overhead dominates the wire time
(Horovod, PAPERS.md:5). Batching small tensors into a few size-targeted
buckets amortizes that overhead and is the enabler for overlapping the
reduction with the tail of the backward pass (CUDA-aware-MPI DNN training,
PAPERS.md:6). This module is the XLA-native port of that idea for the
``shard_map`` DP path (train/steps.py):

- :func:`plan_buckets` flattens the gradient tree into deterministic,
  size-targeted fusion buckets. Assignment is keyed by the leaf's *tree
  path* (sorted), not by flatten order, so the plan is stable under dict
  insertion-order churn — the same leaf always lands in the same bucket.
- :func:`all_reduce` performs ONE collective per bucket: ``psum``, or the
  bandwidth-optimal ring form ``psum_scatter`` + ``all_gather``. Buckets
  are independent dataflow, so XLA's scheduler is free to start a bucket's
  collective the moment its last leaf's gradient is produced, overlapping
  communication with the remaining backward computation — the role of
  Horovod's background fusion-buffer thread, collapsed into one XLA
  program.
- A dtype policy (``payload_dtype``) optionally compresses the reduction
  payload to bf16 (half the wire bytes); results are immediately restored
  to each leaf's own dtype, so fp32 master params/optimizer state never
  see bf16 accumulation error beyond the documented reduce tolerance
  (docs/fused_allreduce.md).

Per-leaf reduction (``bucket_bytes=0``) is kept as the A/B reference path —
bench.py's ``ar_fused`` vs ``ar_perleaf`` suite rows measure exactly this
module's win on chip.

The bucket independence noted above is ALSO what the ZeRO-2/3 overlapped
schedules (parallel/zero.py) exploit: each fusion bucket gets its own
``custom_vjp`` boundary so its reduce-scatter depends only on that bucket's
cotangents, letting XLA issue it while earlier layers' backward is still
running.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Union

import jax
import jax.numpy as jnp

from distributeddeeplearning_tpu.observability import telemetry

AxisNames = Union[str, tuple[str, ...]]

DEFAULT_BUCKET_MB = 4.0
_MB = 1024 * 1024


def _numel(shape: Sequence[int]) -> int:
    n = 1
    for d in shape:
        n *= int(d)
    return n


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """A deterministic leaf -> fusion-bucket assignment for ONE tree shape.

    ``buckets`` holds groups of indices into the *flatten-order* leaf list;
    group order and membership derive only from (path, shape, dtype), never
    from flatten order, so two trees with the same leaves produce the same
    plan regardless of container insertion order.
    """

    treedef: Any
    paths: tuple[str, ...]                 # per flatten-order leaf
    shapes: tuple[tuple[int, ...], ...]
    dtypes: tuple[Any, ...]
    buckets: tuple[tuple[int, ...], ...]   # flatten-order indices per bucket
    bucket_bytes: int

    @property
    def num_leaves(self) -> int:
        return len(self.paths)

    def bucket_of(self, path: str) -> int:
        """Bucket index holding the leaf at ``path`` (stability tests)."""
        i = self.paths.index(path)
        for b, members in enumerate(self.buckets):
            if i in members:
                return b
        raise KeyError(path)  # pragma: no cover - every leaf is assigned

    def describe(self) -> str:
        sizes = [sum(_numel(self.shapes[i]) for i in members)
                 for members in self.buckets]
        return (f"{len(self.buckets)} bucket(s) over {self.num_leaves} "
                f"leaves, elems/bucket={sizes}")


def plan_buckets(tree, bucket_bytes: Optional[int] = None) -> BucketPlan:
    """Assign the leaves of ``tree`` (arrays OR shape/dtype structs — works
    on tracers at trace time) to size-targeted fusion buckets.

    Leaves are visited in sorted-path order and packed greedily: a bucket
    closes when adding the next leaf would push it past ``bucket_bytes``
    (a single oversized leaf still gets its own bucket). ``bucket_bytes``
    <= 0 degenerates to one bucket per leaf — the unfused reference plan.
    """
    if bucket_bytes is None:
        bucket_bytes = int(DEFAULT_BUCKET_MB * _MB)
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    paths = tuple(jax.tree_util.keystr(p) for p, _ in flat)
    if len(set(paths)) != len(paths):  # pragma: no cover - pytrees keys are
        raise ValueError("duplicate leaf paths in gradient tree")  # unique
    shapes = tuple(tuple(leaf.shape) for _, leaf in flat)
    dtypes = tuple(jnp.dtype(leaf.dtype) for _, leaf in flat)
    order = sorted(range(len(flat)), key=lambda i: paths[i])

    buckets: list[tuple[int, ...]] = []
    cur: list[int] = []
    cur_bytes = 0
    for i in order:
        nbytes = _numel(shapes[i]) * dtypes[i].itemsize
        if cur and (bucket_bytes <= 0 or cur_bytes + nbytes > bucket_bytes):
            buckets.append(tuple(cur))
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
    if cur:
        buckets.append(tuple(cur))
    return BucketPlan(treedef=treedef, paths=paths, shapes=shapes,
                      dtypes=dtypes, buckets=tuple(buckets),
                      bucket_bytes=int(bucket_bytes))


def _leaf_sizes(plan: BucketPlan, members: Sequence[int]) -> list[int]:
    return [_numel(plan.shapes[i]) for i in members]


def _reduce_flat(vec, axis_names: AxisNames, algorithm: str, axis_size: int):
    """One fused collective over a flat payload vector (shard-local view).

    ``psum``: a single all-reduce. ``ring``: reduce-scatter + all-gather —
    the two-phase form whose per-chip traffic is the 2(n-1)/n optimum on a
    ring; the payload is padded to a multiple of the axis size so every
    chip owns an equal scatter chunk.
    """
    if algorithm == "psum" or axis_size <= 1:
        return jax.lax.psum(vec, axis_names)
    if algorithm != "ring":
        raise ValueError(f"unknown all-reduce algorithm {algorithm!r}; "
                         f"expected 'psum' or 'ring'")
    pad = (-vec.size) % axis_size
    if pad:
        vec = jnp.pad(vec, (0, pad))
    chunk = jax.lax.psum_scatter(vec, axis_names, scatter_dimension=0,
                                 tiled=True)
    full = jax.lax.all_gather(chunk, axis_names, tiled=True)
    return full[:full.size - pad] if pad else full


def all_reduce(tree, axis_names: AxisNames, *, axis_size: int,
               bucket_bytes: Optional[int] = None,
               payload_dtype=None, algorithm: str = "psum",
               plan: Optional[BucketPlan] = None):
    """Cross-shard SUM of every leaf of ``tree`` (call inside shard_map).

    One collective per fusion bucket instead of one per leaf. Each bucket
    concatenates its leaves' raveled values — cast to ``payload_dtype``
    when set (bf16 compression) — reduces once, then splits/reshapes/casts
    back to each leaf's own dtype. Leaves keep their exact per-element
    reduction semantics: bucketing changes how many collectives are
    launched, never which values are summed together.

    ``bucket_bytes=0`` (or a plan built that way) reduces per leaf — the
    unfused reference path for A/B measurement.
    """
    if plan is None:
        plan = plan_buckets(tree, bucket_bytes)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if len(leaves) != plan.num_leaves:
        raise ValueError(
            f"plan was built for {plan.num_leaves} leaves, tree has "
            f"{len(leaves)}")
    out: list[Any] = [None] * len(leaves)
    tele = telemetry.get()
    for b, members in enumerate(plan.buckets):
        sizes = _leaf_sizes(plan, members)
        # named_scope labels this bucket's collective in device profiles
        # (jax.profiler / XLA HLO names); the telemetry span runs at TRACE
        # time (once per compile, cat="trace") and carries the bucket's
        # shape metadata into the Chrome trace alongside the runtime
        # phases. Runtime per-bucket device timing lives in the profiler
        # trace — a host-side span cannot see inside one XLA program.
        scope = f"allreduce/bucket{b:02d}"
        with tele.span(f"collective:{scope}", cat="trace",
                       leaves=len(members), elems=sum(sizes)), \
                jax.named_scope(scope):
            if len(members) == 1 and payload_dtype is None:
                # Single-leaf bucket with no dtype policy: skip the
                # ravel/concat round-trip entirely.
                i = members[0]
                out[i] = _reduce_flat(leaves[i].ravel(), axis_names,
                                      algorithm,
                                      axis_size).reshape(plan.shapes[i])
                continue
            # Concatenation needs one dtype; with no explicit payload
            # policy, promote to the bucket's widest member so mixed-dtype
            # buckets never silently downcast a leaf's payload.
            common = (jnp.dtype(payload_dtype) if payload_dtype is not None
                      else jnp.result_type(
                          *(plan.dtypes[i] for i in members)))
            parts = [leaves[i].ravel().astype(common) for i in members]
            buf = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
            red = _reduce_flat(buf, axis_names, algorithm, axis_size)
            offset = 0
            for i, n in zip(members, sizes):
                piece = jax.lax.dynamic_slice_in_dim(red, offset, n, 0)
                out[i] = piece.reshape(plan.shapes[i]).astype(plan.dtypes[i])
                offset += n
    return jax.tree_util.tree_unflatten(treedef, out)


def all_reduce_gradients(grads, axis_names: AxisNames, *, axis_size: int,
                         options=None):
    """The train-step entry point: SUM ``grads`` across ``axis_names`` per
    the run's :class:`~distributeddeeplearning_tpu.config.AllReduceConfig`
    (``options``; None = defaults). The caller divides by ``axis_size`` to
    turn the Horovod-style ring sum into the gradient average."""
    bucket_mb = getattr(options, "bucket_mb", DEFAULT_BUCKET_MB)
    dtype_name = getattr(options, "dtype", "float32") or "float32"
    algorithm = getattr(options, "algorithm", "psum") or "psum"
    payload = None
    if dtype_name not in ("float32", "bfloat16"):
        raise ValueError(
            f"allreduce dtype {dtype_name!r} not supported; use 'float32' "
            f"(reduce in the gradients' own dtype) or 'bfloat16' "
            f"(compressed payload, fp32 master restored after the reduce)")
    if dtype_name == "bfloat16":
        payload = jnp.bfloat16
    return all_reduce(grads, axis_names, axis_size=axis_size,
                      bucket_bytes=int(float(bucket_mb) * _MB),
                      payload_dtype=payload, algorithm=algorithm)
