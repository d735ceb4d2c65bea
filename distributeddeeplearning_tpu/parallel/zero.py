"""ZeRO sharding ladder (stages 1-3) for the explicit-DP path.

The bucketed ring all-reduce (parallel/collectives.py) already materializes
the ZeRO-1 partition as its intermediate: after ``psum_scatter`` each shard
holds the reduced 1/N chunk of every bucket, and the trailing ``all_gather``
throws that structure away so every shard can run the SAME full optimizer
update. ZeRO-1 (ZeRO stage 1, Rajbhandari et al.) keeps it instead: the
optimizer update runs on each shard's chunk only, optimizer state lives
permanently 1/N-sharded, and the ``all_gather`` moves the *updated
parameters* rather than the summed gradients — identical communication
volume (one reduce-scatter + one all-gather of the parameter bytes per
step), optimizer HBM and update FLOPs divided by the DP degree.

The higher stages extend the SAME chunk layout (train/steps.py selects the
schedule per ``TrainConfig.optimizer_sharding``):

- **ZeRO-2** — gradients are born reduce-scattered: a per-bucket identity
  ``custom_vjp`` (:func:`assemble_params_overlapped`) makes the loss
  differentiate w.r.t. this shard's parameter CHUNKS, its backward rule
  reduce-scattering each bucket's parameter cotangents the moment backward
  produces them. The full gradient tree is never materialized as a live
  whole and the collectives overlap the remaining backward compute —
  update arithmetic identical to zero1 (same packed per-bucket
  ``psum_scatter``, same chunk update).
- **ZeRO-3 / FSDP-unified** — parameters themselves live 1/N-chunked and
  are all-gathered on demand per fusion bucket for forward/backward
  (:func:`gather_params_overlapped`); the backward rule of that gather is
  the bucket reduce-scatter, so gradient chunks come out of autodiff
  already reduced, overlapped with backward. This folds the GSPMD
  ``fsdp`` parameter-sharding rule (parallel/sharding.py) into the
  explicit path's bucket planner — an image config with ``fsdp > 1`` plus
  ``zero3`` shards chunks over BOTH dp axes.

Layout: per-leaf chunking that PRESERVES the parameter treedef. Every leaf
is raveled, zero-padded to a multiple of the axis size N, and split into N
contiguous chunks; shard k owns elements ``[k*c, (k+1)*c)`` of every leaf.
Keeping one chunk per leaf (instead of slicing the concatenated bucket)
means the chunk tree has the same structure and relative magnitudes as the
parameter tree, so path-keyed weight-decay masks apply unchanged and
per-layer trust-ratio norms (LARS/LAMB) need only a cross-shard ``psum`` of
squared sums (train/optim.py) to be exact. Bucket fusion is kept at the
collective level: each fusion bucket's member leaves are packed into ONE
``(N, row)`` payload — row k carrying every member's chunk k — so one
``psum_scatter``/``all_gather`` launches per bucket, exactly like the fused
all-reduce.

Padding is benign through every supported optimizer: padded gradient
elements are zero on all shards, so momentum/Adam moments stay zero, the
update there is zero, and squared-sum norms gain nothing.

Checkpoint compatibility (train/checkpoint.py): :class:`Zero1StateConverter`
gathers the chunked optimizer state into the CANONICAL layout — each leaf
restored to its parameter's shape, padding stripped — before save, and pads
and re-shards on restore. The canonical layout is byte-identical to what the
replicated path saves, so zero1 checkpoints restore replicated, replicated
checkpoints restore into zero1, and the DP degree may change between save
and resume (the pad is a function of N and is never persisted).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from distributeddeeplearning_tpu.observability import flight, telemetry
from distributeddeeplearning_tpu.parallel.collectives import (
    _MB, AxisNames, BucketPlan, DEFAULT_BUCKET_MB, _numel, plan_buckets)


@dataclasses.dataclass(frozen=True)
class Zero1Layout:
    """Chunk assignment for ONE parameter tree shape on ONE axis size.

    ``chunk_sizes[i]`` is the per-shard chunk length of flatten-order leaf
    i: ``ceil(numel_i / axis_size)``; the leaf's padded flat length is
    ``chunk_sizes[i] * axis_size``. Bucket membership reuses the
    deterministic path-keyed planner, so the payload layout is stable under
    dict insertion-order churn exactly like the fused all-reduce.
    """

    plan: BucketPlan
    axis_size: int
    chunk_sizes: tuple[int, ...]

    @property
    def num_leaves(self) -> int:
        return self.plan.num_leaves

    def padded_size(self, i: int) -> int:
        return self.chunk_sizes[i] * self.axis_size

    def describe(self) -> str:
        total = sum(_numel(s) for s in self.plan.shapes)
        padded = sum(self.padded_size(i) for i in range(self.num_leaves))
        return (f"1/{self.axis_size} per shard over "
                f"{len(self.plan.buckets)} bucket(s), "
                f"{self.num_leaves} leaves, pad {padded - total} elems")


def stage_index(optimizer_sharding: Optional[str]) -> int:
    """The ZeRO stage number of an ``--optimizer-sharding`` mode (none -> 0,
    zero1 -> 1, ...). Used by cross-axis elastic re-formation to describe a
    stage change (``zero2 -> none``) in resume announcements and sidecars —
    the canonical on-disk layout is stage-agnostic, so any pair is legal."""
    mode = (optimizer_sharding or "none").strip().lower()
    if mode in ("", "none"):
        return 0
    if mode.startswith("zero") and mode[4:].isdigit():
        return int(mode[4:])
    raise ValueError(f"unknown optimizer-sharding mode {optimizer_sharding!r}")


def build_layout(tree, axis_size: int,
                 bucket_bytes: Optional[int] = None) -> Zero1Layout:
    """Plan the ZeRO-1 chunk layout for ``tree`` (arrays or shape structs —
    shapes are static, so this works on tracers at trace time)."""
    if axis_size < 1:
        raise ValueError(f"axis_size must be >= 1 (got {axis_size})")
    plan = plan_buckets(tree, bucket_bytes)
    chunk_sizes = tuple(-(-_numel(s) // axis_size) for s in plan.shapes)
    return Zero1Layout(plan=plan, axis_size=axis_size,
                       chunk_sizes=chunk_sizes)


def payload_dtype_from_options(options=None) -> Optional[Any]:
    """Gradient-scatter payload dtype per the run's AllReduceConfig (None =
    reduce in the gradients' own dtype, ``jnp.bfloat16`` = compressed
    wire payload). Shared by every stage's scatter path."""
    dtype_name = getattr(options, "dtype", "float32") or "float32"
    if dtype_name not in ("float32", "bfloat16"):
        raise ValueError(
            f"allreduce dtype {dtype_name!r} not supported; use 'float32' "
            f"(reduce in the gradients' own dtype) or 'bfloat16' "
            f"(compressed payload, fp32 master restored after the reduce)")
    return jnp.bfloat16 if dtype_name == "bfloat16" else None


def layout_from_options(tree, axis_size: int, options=None
                        ) -> tuple[Zero1Layout, Optional[Any]]:
    """(layout, scatter payload dtype) per the run's AllReduceConfig —
    the same bucket-size/dtype policy knobs the fused all-reduce reads.
    The payload dtype applies to the gradient reduce-scatter only; the
    parameter all-gather always moves the parameters' own dtype."""
    bucket_mb = getattr(options, "bucket_mb", DEFAULT_BUCKET_MB)
    payload = payload_dtype_from_options(options)
    return build_layout(tree, axis_size,
                        int(float(bucket_mb) * _MB)), payload


def modeled_grad_bytes(layout: Zero1Layout, *, chunked: bool) -> int:
    """Per-device gradient residency MODEL for the memory-ladder accounting
    (gradients are transient, so unlike params/opt-state they cannot be
    measured off a held state tree): full leaf bytes for schedules that
    materialize the whole gradient tree (replicated, zero1, overlap-off
    zero2/zero3), chunk bytes when gradients only ever exist
    reduce-scattered (overlapped zero2/zero3)."""
    plan = layout.plan
    if chunked:
        return sum(c * jnp.dtype(plan.dtypes[i]).itemsize
                   for i, c in enumerate(layout.chunk_sizes))
    return sum(_numel(s) * jnp.dtype(plan.dtypes[i]).itemsize
               for i, s in enumerate(plan.shapes))


def _check_leaves(layout: Zero1Layout, n: int) -> None:
    if n != layout.num_leaves:
        raise ValueError(f"layout was built for {layout.num_leaves} leaves, "
                         f"tree has {n}")


def _pad_flat(leaf, padded: int):
    flat = leaf.ravel()
    pad = padded - flat.size
    return jnp.pad(flat, (0, pad)) if pad else flat


# ---------------------------------------------------------------------------
# Global (full-array) layout conversions — used for optimizer-state init and
# checkpoint reshard, OUTSIDE shard_map. The chunked global form of a leaf is
# its zero-padded ravel of length chunk*N; placed with P(data, fsdp) on dim 0
# it is exactly the concatenation of the shards' chunks.
# ---------------------------------------------------------------------------

def to_chunked(tree, layout: Zero1Layout):
    """Each leaf -> its padded flat ``(chunk * N,)`` global form."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    _check_leaves(layout, len(leaves))
    out = [_pad_flat(leaf, layout.padded_size(i))
           for i, leaf in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


def from_chunked(tree, layout: Zero1Layout):
    """Inverse of :func:`to_chunked`: strip padding, restore leaf shapes."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    _check_leaves(layout, len(leaves))
    out = []
    for i, leaf in enumerate(leaves):
        shape = layout.plan.shapes[i]
        out.append(leaf[:_numel(shape)].reshape(shape))
    return jax.tree_util.tree_unflatten(treedef, out)


def chunked_struct(tree, layout: Zero1Layout):
    """ShapeDtypeStruct tree of the chunked global form (for eval_shape)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    _check_leaves(layout, len(leaves))
    out = [jax.ShapeDtypeStruct((layout.padded_size(i),),
                                jnp.dtype(layout.plan.dtypes[i]))
           for i in range(len(leaves))]
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Shard-local ops — call INSIDE shard_map.
# ---------------------------------------------------------------------------

def local_chunks(tree, layout: Zero1Layout, axis_names: AxisNames):
    """This shard's contiguous 1/N chunk of every (padded, raveled) leaf."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    _check_leaves(layout, len(leaves))
    idx = jax.lax.axis_index(axis_names)
    out = []
    for i, leaf in enumerate(leaves):
        c = layout.chunk_sizes[i]
        flat = _pad_flat(leaf, layout.padded_size(i))
        out.append(jax.lax.dynamic_slice_in_dim(flat, idx * c, c, 0))
    return jax.tree_util.tree_unflatten(treedef, out)


def _scatter_members(fulls, layout: Zero1Layout, axis_names: AxisNames,
                     b: int, payload_dtype=None, scope_prefix: str = "zero1",
                     overlapped: bool = False) -> tuple:
    """One bucket's reduce-scatter: full-shaped member leaves (ordered as
    ``layout.plan.buckets[b]``) -> that bucket's reduced chunk leaves.

    The bucket's members are packed as an ``(N, row)`` matrix whose row k
    holds every member's chunk k, so the tiled ``psum_scatter`` over the
    raveled payload hands shard k exactly row k — its own chunk of every
    member — already reduced. ``overlapped=True`` marks the trace-time
    span for :func:`telemetry.overlap_fraction` — it is set only by the
    custom_vjp backward rules, where the scatter is issued inside backward.
    """
    members = layout.plan.buckets[b]
    n = layout.axis_size
    tele = telemetry.get()
    # Same per-bucket annotation scheme as collectives.all_reduce:
    # named_scope for device profiles, a trace-time telemetry span
    # (cat="trace") for the Chrome trace.
    scope = f"{scope_prefix}/reduce_scatter/bucket{b:02d}"
    span_args = {"cat": "trace", "leaves": len(members)}
    if overlapped:
        span_args["overlapped"] = True
    # Flight-record mirror of the trace span: this body runs once per
    # COMPILE (trace time), so the record gets a one-shot collective-plan
    # event per bucket, never a per-step fsync.
    flight.get().record("collective", phase="reduce_scatter", scope=scope,
                        bucket=b, leaves=len(members),
                        overlapped=bool(overlapped))
    with tele.span(f"collective:{scope}", **span_args), \
            jax.named_scope(scope):
        common = (jnp.dtype(payload_dtype) if payload_dtype is not None
                  else jnp.result_type(
                      *(layout.plan.dtypes[i] for i in members)))
        parts = []
        for j, i in enumerate(members):
            flat = _pad_flat(fulls[j].astype(common), layout.padded_size(i))
            parts.append(flat.reshape(n, layout.chunk_sizes[i]))
        row = (parts[0] if len(parts) == 1
               else jnp.concatenate(parts, axis=1))
        chunk = jax.lax.psum_scatter(row.reshape(-1), axis_names,
                                     scatter_dimension=0, tiled=True)
        out = []
        off = 0
        for i in members:
            c = layout.chunk_sizes[i]
            piece = jax.lax.dynamic_slice_in_dim(chunk, off, c, 0)
            out.append(piece.astype(layout.plan.dtypes[i]))
            off += c
    return tuple(out)


def _gather_wire_dtypes(layout: Zero1Layout, members, out_dtype) -> list:
    """What each member of a bucket travels as, and comes back as.

    ``out_dtype`` None: every member in the bucket's common (master) dtype,
    restored to its own afterwards. ``out_dtype`` set (zero3 under a bf16
    policy): a leaf of rank >= 2 (a dense or conv kernel, an embedding
    table) travels in ``out_dtype``, which is the cast its layer makes
    anyway, so the layer reads the same bits from half the bytes; a leaf of
    rank < 2 (a norm scale, a bias) travels as its master, because the norm
    layers consume those in float32 and a bf16 wire would round what no
    other stage rounds: zero3 alone would leave the ladder's trajectory
    (``tests/test_mixed_precision.py::test_mixed_zero_ladder_parity_band``
    holds every stage to the same bits)."""
    if out_dtype is None:
        common = jnp.result_type(*(layout.plan.dtypes[i] for i in members))
        return [common] * len(members)
    return [jnp.dtype(out_dtype) if len(layout.plan.shapes[i]) >= 2
            else jnp.dtype(layout.plan.dtypes[i]) for i in members]


def _gather_members(chunks, layout: Zero1Layout, axis_names: AxisNames,
                    b: int, scope_prefix: str = "zero1",
                    out_dtype=None) -> tuple:
    """One bucket's all-gather: chunk member leaves (ordered as
    ``layout.plan.buckets[b]``) -> full-shaped member leaves. The gathered
    ``(N*row,)`` payload reshapes to ``(N, row)`` with row k = shard k's
    chunks; slicing a member's column block and raveling row-major
    restores its padded flat leaf in natural order.

    ``out_dtype`` (mixed precision, zero3): each chunk is cast to its wire
    dtype (:func:`_gather_wire_dtypes`) BEFORE the collective and the
    gathered full leaf stays in it. Where a bucket's members travel in
    dtypes of two widths, the payload is their bits side by side (as
    unsigned integers of the narrower width), so that it is still one
    collective a bucket and every member moves exactly its own bytes."""
    members = layout.plan.buckets[b]
    n = layout.axis_size
    tele = telemetry.get()
    scope = f"{scope_prefix}/all_gather/bucket{b:02d}"
    flight.get().record("collective", phase="all_gather", scope=scope,
                        bucket=b, leaves=len(members))
    with tele.span(f"collective:{scope}", cat="trace",
                   leaves=len(members)), jax.named_scope(scope):
        wire = _gather_wire_dtypes(layout, members, out_dtype)
        carrier = wire[0]
        if len(set(wire)) > 1:
            carrier = jnp.dtype(
                f"uint{8 * min(w.itemsize for w in wire)}")
        parts = []
        for j, w in enumerate(wire):
            part = chunks[j].astype(w)
            if w != carrier:  # (c,) -> (c, k) narrower words -> (c * k,)
                part = jax.lax.bitcast_convert_type(part, carrier).ravel()
            parts.append(part)
        row = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        full = jax.lax.all_gather(row, axis_names, tiled=True)
        mat = full.reshape(n, -1)
        out = []
        off = 0
        for i, w in zip(members, wire):
            c = layout.chunk_sizes[i]
            shape = layout.plan.shapes[i]
            k = w.itemsize // carrier.itemsize  # carrier words an element
            piece = jax.lax.slice_in_dim(mat, off, off + c * k, axis=1)
            if w != carrier:
                piece = jax.lax.bitcast_convert_type(
                    piece.reshape((n, c, k) if k > 1 else (n, c)), w)
            leaf_dtype = w if out_dtype is not None else layout.plan.dtypes[i]
            out.append(piece.reshape(n * c)[:_numel(shape)]
                       .reshape(shape).astype(leaf_dtype))
            off += c * k
    return tuple(out)


def reduce_scatter(tree, layout: Zero1Layout, axis_names: AxisNames, *,
                   payload_dtype=None):
    """Cross-shard SUM of every leaf, each shard keeping only its chunk.

    One ``psum_scatter`` per fusion bucket (see :func:`_scatter_members`) —
    the first half of the ring all-reduce with the all-gather elided.

    ``payload_dtype`` (bf16 compression) applies to the scatter payload
    only; chunks are restored to each leaf's own dtype immediately after.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    _check_leaves(layout, len(leaves))
    out: list[Any] = [None] * len(leaves)
    for b, members in enumerate(layout.plan.buckets):
        pieces = _scatter_members([leaves[i] for i in members], layout,
                                  axis_names, b, payload_dtype)
        for i, piece in zip(members, pieces):
            out[i] = piece
    return jax.tree_util.tree_unflatten(treedef, out)


def all_gather_chunks(chunks, layout: Zero1Layout, axis_names: AxisNames,
                      *, out_dtype=None):
    """Reassemble full leaves from per-shard chunks (updated parameters).

    One ``all_gather`` per fusion bucket (see :func:`_gather_members`) —
    the second half of the ring all-reduce, moved AFTER the optimizer
    update. ``out_dtype`` casts the matrices before the wire and leaves
    them in it (mixed-precision zero3 forward gathers).
    """
    leaves, treedef = jax.tree_util.tree_flatten(chunks)
    _check_leaves(layout, len(leaves))
    out: list[Any] = [None] * len(leaves)
    for b, members in enumerate(layout.plan.buckets):
        pieces = _gather_members([leaves[i] for i in members], layout,
                                 axis_names, b, out_dtype=out_dtype)
        for i, piece in zip(members, pieces):
            out[i] = piece
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Backward/collective overlap (ZeRO-2/3). Each fusion bucket gets its OWN
# custom_vjp boundary, so in the backward pass bucket b's reduce-scatter
# depends only on bucket b's parameter cotangents — XLA issues it the moment
# those are produced, while backward continues through earlier layers. A
# single tree-level vjp (or the post-backward reduce_scatter above) would
# serialize every collective after the last cotangent instead.
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _gather_vjp(layout: Zero1Layout, axis_names, b: int, payload_dtype,
                scope_prefix: str, out_dtype=None):
    """ZeRO-3 bucket primitive: fwd all-gathers this shard's chunks into
    full leaves (the matrices in ``out_dtype`` when set — bf16 compute
    params from fp32 masters, cast before the wire); bwd reduce-scatters
    the full-shaped cotangents back to chunk cotangents in the plan
    (master) dtypes (the exact transpose of a tiled all-gather whose output
    feeds every shard's loss term)."""

    def _primal(*chunks):
        return _gather_members(chunks, layout, axis_names, b, scope_prefix,
                               out_dtype=out_dtype)

    def _fwd(*chunks):
        return _primal(*chunks), None

    def _bwd(_, cts):
        return _scatter_members(cts, layout, axis_names, b, payload_dtype,
                                scope_prefix, overlapped=True)

    fn = jax.custom_vjp(_primal)
    fn.defvjp(_fwd, _bwd)
    return fn


@functools.lru_cache(maxsize=None)
def _assemble_vjp(layout: Zero1Layout, axis_names, b: int, payload_dtype):
    """ZeRO-2 bucket primitive: fwd is the IDENTITY on the already-
    replicated full leaves (the chunk operands are unused — parameters are
    not sharded at stage 2, so no forward gather is owed); bwd
    reduce-scatters the full-shaped cotangents into the CHUNK operands'
    cotangent slots. Differentiating a loss w.r.t. the chunks therefore
    yields already-reduce-scattered gradients without the full gradient
    tree ever forming, at zero forward cost. The full-leaf operands get
    zero cotangents — they enter as non-differentiated closure constants
    in train/steps.py, so those zeros are dead code XLA eliminates."""
    members = layout.plan.buckets[b]
    nm = len(members)

    def _primal(*args):
        return args[:nm]

    def _fwd(*args):
        return args[:nm], None

    def _bwd(_, cts):
        gchunks = _scatter_members(cts, layout, axis_names, b, payload_dtype,
                                   "zero2", overlapped=True)
        zeros = tuple(jnp.zeros(layout.plan.shapes[i],
                                layout.plan.dtypes[i]) for i in members)
        return zeros + gchunks

    fn = jax.custom_vjp(_primal)
    fn.defvjp(_fwd, _bwd)
    return fn


def _as_axis_key(axis_names: AxisNames):
    return axis_names if isinstance(axis_names, str) else tuple(axis_names)


def _dtype_key(dtype):
    """Hashable, canonical form of an optional dtype for the lru_cached
    vjp factories (np scalar types and jnp.dtype objects must alias)."""
    return None if dtype is None else jnp.dtype(dtype).name


def gather_params_overlapped(pchunks, layout: Zero1Layout,
                             axis_names: AxisNames, *, payload_dtype=None,
                             scope_prefix: str = "zero3", out_dtype=None):
    """ZeRO-3 on-demand parameter materialization with backward overlap.

    Assembles the full parameter tree from this shard's chunk tree, one
    custom_vjp all-gather per fusion bucket. Differentiating a loss through
    the result w.r.t. ``pchunks`` yields ALREADY reduce-scattered chunk
    gradients (cross-shard SUM — divide by N for the average), each
    bucket's scatter issued inside backward as its cotangents complete.
    """
    leaves, treedef = jax.tree_util.tree_flatten(pchunks)
    _check_leaves(layout, len(leaves))
    out: list[Any] = [None] * len(leaves)
    key = _as_axis_key(axis_names)
    for b, members in enumerate(layout.plan.buckets):
        fn = _gather_vjp(layout, key, b, payload_dtype, scope_prefix,
                         _dtype_key(out_dtype))
        fulls = fn(*[leaves[i] for i in members])
        for i, full in zip(members, fulls):
            out[i] = full
    return jax.tree_util.tree_unflatten(treedef, out)


def assemble_params_overlapped(params, pchunks, layout: Zero1Layout,
                               axis_names: AxisNames, *, payload_dtype=None):
    """ZeRO-2 gradient-scatter boundary: returns ``params`` unchanged
    (identity forward — parameters stay replicated at stage 2) wired so
    that differentiating a loss through the result w.r.t. ``pchunks``
    yields reduce-scattered bucket gradients issued during backward.
    ``params`` must enter as a non-differentiated constant of the loss."""
    pleaves, treedef = jax.tree_util.tree_flatten(params)
    cleaves, _ = jax.tree_util.tree_flatten(pchunks)
    _check_leaves(layout, len(pleaves))
    _check_leaves(layout, len(cleaves))
    out: list[Any] = [None] * len(pleaves)
    key = _as_axis_key(axis_names)
    for b, members in enumerate(layout.plan.buckets):
        fn = _assemble_vjp(layout, key, b, payload_dtype)
        fulls = fn(*([pleaves[i] for i in members]
                     + [cleaves[i] for i in members]))
        for i, full in zip(members, fulls):
            out[i] = full
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Optimizer-state layout derivation. Which opt-state leaves mirror a
# parameter leaf (momentum, Adam moments — chunked and sharded) vs carry
# their own shape (step counters — replicated) is decided STRUCTURALLY: init
# the optimizer abstractly against two probe trees with different leaf sizes
# and mark the leaves whose shape follows the probe. Flatten order is
# identical across inits of the same treedef, so index i of the chunked
# template, the canonical template, and a live opt state all name the same
# leaf — no shape-based guessing (a 1-D bias can collide with its own
# padded-chunk length).
# ---------------------------------------------------------------------------

def _struct_tree(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(tuple(x.shape), jnp.dtype(x.dtype)),
        tree)


def _probe_struct(tree, layout: Zero1Layout):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = [jax.ShapeDtypeStruct(
        (layout.padded_size(i) + layout.axis_size,),
        jnp.dtype(layout.plan.dtypes[i])) for i in range(len(leaves))]
    return jax.tree_util.tree_unflatten(treedef, out)


def _opt_templates(tx, params_struct, layout: Zero1Layout):
    """(canonical flat, chunked flat, treedef, per-leaf chunked? mask)."""
    params_struct = _struct_tree(params_struct)
    canonical = jax.eval_shape(tx.init, params_struct)
    chunked = jax.eval_shape(tx.init, chunked_struct(params_struct, layout))
    probe = jax.eval_shape(tx.init, _probe_struct(params_struct, layout))
    flat_canon, tdef_c = jax.tree_util.tree_flatten(canonical)
    flat_chunk, tdef_k = jax.tree_util.tree_flatten(chunked)
    flat_probe, _ = jax.tree_util.tree_flatten(probe)
    if tdef_c != tdef_k:
        raise ValueError(
            "optimizer state structure depends on parameter leaf shapes; "
            "the ZeRO-1 chunked<->canonical correspondence needs it to be "
            f"shape-independent (canonical {tdef_c} vs chunked {tdef_k})")
    mask = tuple(k.shape != p.shape
                 for k, p in zip(flat_chunk, flat_probe))
    return flat_canon, flat_chunk, tdef_c, mask


def opt_state_specs(tx, params_struct, layout: Zero1Layout,
                    chunk_spec, replicated_spec):
    """Per-leaf PartitionSpec tree for the optimizer state: ``chunk_spec``
    on chunked (parameter-mirroring) leaves, ``replicated_spec`` elsewhere
    (step counters). Feeds shard_map in/out_specs and jit out_shardings."""
    _, _, treedef, mask = _opt_templates(tx, params_struct, layout)
    return jax.tree_util.tree_unflatten(
        treedef, [chunk_spec if m else replicated_spec for m in mask])


class ZeroStateConverter:
    """Gather-on-save / reshard-on-restore between a stage's live layout
    and the CANONICAL (replicated-path) checkpoint layout.

    ``to_canonical`` strips padding and restores each chunked leaf to its
    parameter's shape — the exact layout the replicated path saves, so
    checkpoints are interchangeable across ``none``/``zero1``/``zero2``/
    ``zero3`` and across DP degrees (the pad is a function of N and never
    persisted). ``from_canonical`` re-pads for the CURRENT layout and
    places chunk leaves sharded over the DP axes. ``canonical_abstract``
    describes the on-disk layout for orbax's structure-matched restore
    (replicated placement; the reshard happens in ``from_canonical`` right
    after).

    ``stage`` selects WHICH trees are chunked in the live layout: the
    optimizer state for every stage (1-3 share the zero1 opt layout;
    stage 2's difference — never-materialized gradients — is transient and
    has no checkpoint footprint), plus params/ema_params at stage 3, where
    parameters live in the chunked global form.
    """

    def __init__(self, tx, params_struct, layout: Zero1Layout, mesh,
                 axis_names: AxisNames, stage: int = 1):
        if stage not in (1, 2, 3):
            raise ValueError(f"stage must be 1, 2 or 3 (got {stage})")
        self.layout = layout
        self.stage = stage
        self._params_struct = _struct_tree(params_struct)
        self._flat_canon, self._flat_chunk, self._treedef, self._mask = (
            _opt_templates(tx, params_struct, layout))
        self._rep = NamedSharding(mesh, P())
        self._chunk_shd = NamedSharding(mesh, P(axis_names))
        self._full_params_jit = None

    def _flat(self, opt_state):
        flat, treedef = jax.tree_util.tree_flatten(opt_state)
        if treedef != self._treedef:
            raise ValueError(
                f"optimizer state structure does not match the converter's "
                f"template: {treedef} vs {self._treedef}")
        return flat

    def _opt_to_canonical(self, opt_state):
        out = []
        for leaf, m, canon in zip(self._flat(opt_state), self._mask,
                                  self._flat_canon):
            out.append(leaf[:_numel(canon.shape)].reshape(canon.shape)
                       if m else leaf)
        return jax.tree_util.tree_unflatten(self._treedef, out)

    def _opt_from_canonical(self, opt_state):
        out = []
        for leaf, m, chunk in zip(self._flat(opt_state), self._mask,
                                  self._flat_chunk):
            out.append(_pad_flat(leaf, chunk.shape[0]) if m else leaf)
        return jax.tree_util.tree_unflatten(self._treedef, out)

    def opt_shardings(self):
        return jax.tree_util.tree_unflatten(
            self._treedef,
            [self._chunk_shd if m else self._rep for m in self._mask])

    def param_shardings(self, tree):
        """Chunk shardings for a params-shaped tree (stage-3 live layout)."""
        return jax.tree_util.tree_map(lambda _: self._chunk_shd, tree)

    @property
    def _params_chunked(self) -> bool:
        return self.stage >= 3

    def _live_to_canonical(self, s):
        s = s.replace(opt_state=self._opt_to_canonical(s.opt_state))
        if self._params_chunked:
            s = s.replace(params=from_chunked(s.params, self.layout))
            if s.ema_params is not None:
                s = s.replace(
                    ema_params=from_chunked(s.ema_params, self.layout))
        return s

    def to_canonical(self, state):
        """TrainState with every chunked tree gathered to canonical layout."""
        if self._params_chunked:
            # Pin EVERY output replicated — canonical means full shapes,
            # opt state included; without out_shardings the pad-strip
            # reshape could keep a sharded placement that the canonical
            # (on-disk) layout does not admit.
            shardings = jax.tree_util.tree_map(lambda _: self._rep, state)
            return jax.jit(self._live_to_canonical,
                           out_shardings=shardings)(state)
        return jax.jit(self._live_to_canonical)(state)

    def from_canonical(self, state):
        """TrainState re-padded + sharded for this stage's live layout."""
        shardings = jax.tree_util.tree_map(lambda _: self._rep, state)
        shardings = shardings.replace(opt_state=self.opt_shardings())
        if self._params_chunked:
            shardings = shardings.replace(
                params=self.param_shardings(state.params))
            if state.ema_params is not None:
                shardings = shardings.replace(
                    ema_params=self.param_shardings(state.ema_params))

        def _pad(s):
            s = s.replace(opt_state=self._opt_from_canonical(s.opt_state))
            if self._params_chunked:
                s = s.replace(params=to_chunked(s.params, self.layout))
                if s.ema_params is not None:
                    s = s.replace(
                        ema_params=to_chunked(s.ema_params, self.layout))
            return s

        return jax.jit(_pad, out_shardings=shardings)(state)

    def full_params_state(self, state):
        """``state`` with FULL-shape (canonical) params/ema for consumers
        that need the whole model resident — evaluation, export. Identity
        below stage 3; at stage 3 a cached jit gathers the chunked global
        form back to parameter shapes (pure reshape+slice: the chunked
        global form holds every element, just padded and raveled)."""
        if not self._params_chunked:
            return state
        if self._full_params_jit is None:
            def _full(s):
                s = s.replace(params=from_chunked(s.params, self.layout))
                if s.ema_params is not None:
                    s = s.replace(
                        ema_params=from_chunked(s.ema_params, self.layout))
                return s
            self._full_params_jit = jax.jit(_full)
        return self._full_params_jit(state)

    def _abstract_full(self, tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(tuple(x.shape),
                                           jnp.dtype(x.dtype),
                                           sharding=self._rep), tree)

    def canonical_abstract(self, state_like):
        """``state_like`` with every chunked tree replaced by the canonical
        (on-disk) layout as sharding-carrying ShapeDtypeStructs."""
        out = []
        for leaf, m, canon in zip(self._flat(state_like.opt_state),
                                  self._mask, self._flat_canon):
            if m:
                out.append(jax.ShapeDtypeStruct(canon.shape, canon.dtype,
                                                sharding=self._rep))
            else:
                out.append(jax.ShapeDtypeStruct(
                    tuple(leaf.shape), leaf.dtype,
                    sharding=getattr(leaf, "sharding", self._rep)))
        state_like = state_like.replace(
            opt_state=jax.tree_util.tree_unflatten(self._treedef, out))
        if self._params_chunked:
            state_like = state_like.replace(
                params=self._abstract_full(self._params_struct))
            if state_like.ema_params is not None:
                state_like = state_like.replace(
                    ema_params=self._abstract_full(self._params_struct))
        return state_like


# Name retained from the ZeRO-1-only era (PR 2); external callers and
# checkpoints are agnostic to which stage produced a canonical save.
Zero1StateConverter = ZeroStateConverter
