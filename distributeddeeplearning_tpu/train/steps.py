"""Compiled train/eval steps — the heart of the port.

Two parallel execution styles, both single XLA programs per step
(BASELINE.json:5: "replace hvd.DistributedOptimizer / hvd.allreduce with
jax.pmap/pjit emitting XLA psum over ICI"):

1. ``make_dp_train_step`` — ``shard_map`` over the (data, fsdp) mesh axes
   with replicated parameters and an explicit bucketed all-reduce on
   gradients (parallel/collectives.py). This is the literal
   Horovod-semantics path for the CNN configs: local BatchNorm (per-shard
   statistics, like per-GPU BN under Horovod), gradient averaging across
   shards, identical parameter update everywhere. Horovod's backward-hook +
   background-thread + fusion-buffer machinery maps onto the bucket planner:
   leaves fuse into size-targeted buckets, one collective each, which XLA
   overlaps with the remaining backward compute (SURVEY.md §3.1).

2. ``make_gspmd_train_step`` — ``jit`` + ``NamedSharding`` with logical-axis
   rules (parallel/sharding.py). Used for transformer workloads where
   parameters themselves shard (tp/fsdp) and activations shard over batch
   and sequence (dp/sp); XLA inserts every collective.

Both donate the input state (in-place update in HBM, no copy).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributeddeeplearning_tpu import compat
from distributeddeeplearning_tpu.analysis import anatomy
from distributeddeeplearning_tpu.config import TrainConfig, resolve_precision
from distributeddeeplearning_tpu.models import moe
from distributeddeeplearning_tpu.models.hyper_connections import MHC_METRICS
from distributeddeeplearning_tpu.ops import kda, ssd
from distributeddeeplearning_tpu.parallel import collectives
from distributeddeeplearning_tpu.parallel import sharding as shardlib
from distributeddeeplearning_tpu.parallel import zero
from distributeddeeplearning_tpu.parallel.mesh import use_mesh
from distributeddeeplearning_tpu.observability import telemetry
from distributeddeeplearning_tpu.perf import aot as aotlib
from distributeddeeplearning_tpu.robustness import faults
from distributeddeeplearning_tpu.train import losses
from distributeddeeplearning_tpu.train.state import TrainState

DATA_AXES = ("data", "fsdp")

# Scope names inside the compiled step, shared by both step builders below
# and read back out of the executable's HLO by analysis/anatomy.py (its
# ``part_of`` holds the same strings). Names are compile-time metadata: they
# change no schedule and cost nothing at run time.
STEP_SCOPES = ("grads", "grad_reduce", "loss_scale", "optimizer", "ema",
               "guard")
GRADS, GRAD_REDUCE, LOSS_SCALE, OPTIMIZER, EMA, GUARD = STEP_SCOPES
LOSS_SCOPE = "loss"  # round each loss closure's loss, inside ``grads``

# Trace-time counters, keyed by step name. A step function's Python body
# runs only while jax is TRACING it, so each counter increments exactly once
# per (re)trace — the probe tests use to assert that a warm restart loads
# its executable from the AOT cache without tracing at all.
TRACE_COUNTS: collections.Counter = collections.Counter()


def _aot_acquire(aot, name: str, jitted, args):
    """Resolve an ahead-of-time executable for ``jitted`` at ``args``' avals.

    Fingerprint hit: deserialize the saved executable (phase ``aot_load``)
    — zero tracing. Miss: ``lower().compile()`` cold (phase ``compile``)
    and serialize for the next attempt (phase ``aot_save``). The lowered
    ``Compiled`` object must be called directly — invoking the jit wrapper
    afterwards would re-trace, since AOT compilation bypasses jit's
    internal cache.
    """
    key = aot.key(name, args)
    fn = aot.load(name, key)
    if fn is not None:
        return fn
    with telemetry.phase("compile", program=name):
        compiled_exec = aotlib.compile_lowered(jitted.lower(*args))
    aot.save(name, key, compiled_exec)
    return compiled_exec


def _anatomy_of(executable) -> dict[str, str]:
    """``{instruction name: op_name}`` of the executable a step runs
    (analysis/anatomy.py) — ``train_step.anatomy()`` of both builders. A
    step holds its executable from its first call on, and only when it
    resolved one through the AOT cache; a plain ``jit`` keeps its own."""
    if not hasattr(executable, "as_text"):
        raise RuntimeError(
            "this train step holds no executable to read: call it once "
            "first, with the compile cache on (TrainConfig.compile_cache)")
    return anatomy.table(executable.as_text())


def _inject_nan_grads(grads, step, nan_steps):
    """Fault injection (robustness/faults.py): poison the gradients of the
    updates whose pre-update ``state.step`` is in ``nan_steps``. Compiled in
    ONLY when a fault plan asks for it — the plan-free hot path carries no
    injection ops."""
    hit = jnp.zeros((), jnp.bool_)
    for s in nan_steps:
        hit = jnp.logical_or(hit, step == jnp.int32(s))
    return jax.tree_util.tree_map(
        lambda g: jnp.where(hit, jnp.full_like(g, jnp.nan), g), grads)


def _tree_sq_norm(tree):
    """Squared norm of a tree in f32 (finite iff every leaf is; values big
    enough to overflow the f32 sum also flag — such a step is equally
    unusable)."""
    leaves = jax.tree_util.tree_leaves(tree)
    return sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in leaves)


def _skip_if_bad(bad, new_tree, old_tree):
    """Bad-step guard: keep the pre-update value on every leaf when ``bad``.
    The select passes the already-computed new values through unchanged on
    good steps, so good-step numerics are value-identical."""
    if new_tree is None:
        return None
    return jax.tree_util.tree_map(
        lambda n, o: jnp.where(bad, o, n), new_tree, old_tree)


def _guard_config(config: TrainConfig):
    """(nan_steps, guard_on) for this build. The guard is compiled in only
    when asked for — explicitly (``bad_step_guard``) or implicitly by a plan
    that injects NaN gradients. It cannot be unconditionally on: keeping the
    pre-update state alive for the skip-select blocks the donated buffers'
    in-place reuse, which re-fuses the surrounding XLA program and drifts
    the trajectory ~1 ULP — breaking the zero1<->replicated bitwise pin
    (tests/test_zero1.py). Guard-free builds compile the exact seed program."""
    nan_steps = faults.resolve(config).nan_grad_steps()
    guard = bool(nan_steps) or bool(getattr(config, "bad_step_guard", False))
    return nan_steps, guard


def init_loss_scale(config: TrainConfig):
    """Initial dynamic-loss-scale state for ``TrainState.loss_scale``:
    ``{"scale", "good_steps"}`` device scalars when the precision policy
    arms scaling, None otherwise (the None keeps the state pytree — and
    therefore every existing checkpoint and sharding-spec derivation —
    byte-identical for policy-free configs)."""
    policy = resolve_precision(config)
    if policy.loss_scale <= 0:
        return None
    return {"scale": jnp.float32(policy.loss_scale),
            "good_steps": jnp.zeros((), jnp.int32)}


def _next_loss_scale(policy, scale, good_steps, overflow):
    """The dynamic-scale automaton, shared by both train-step paths:
    overflow -> halve (floored at loss_scale_min), ``growth_interval``
    consecutive good steps -> double (capped at loss_scale_max). Returns
    (new_state_dict, metrics_dict); the caller applies the update skip."""
    good = good_steps + jnp.int32(1)
    grow = good >= jnp.int32(policy.loss_scale_growth_interval)
    new_scale = jnp.where(
        overflow,
        jnp.maximum(scale * jnp.float32(0.5),
                    jnp.float32(policy.loss_scale_min)),
        jnp.where(grow,
                  jnp.minimum(scale * jnp.float32(2.0),
                              jnp.float32(policy.loss_scale_max)),
                  scale))
    new_good = jnp.where(jnp.logical_or(overflow, grow), jnp.int32(0), good)
    # ``loss_scale_skip`` is deliberately NOT ``bad_step``: a backoff is
    # the scaler doing its job, and the bad-step anomaly tracker
    # (train/loop.py) must never count one as a run anomaly.
    return ({"scale": new_scale, "good_steps": new_good},
            {"loss_scale": new_scale,
             "loss_scale_skip": overflow.astype(jnp.float32)})


def _ema_update(ema, new_params, decay: float):
    """Shadow-param EMA: e <- d*e + (1-d)*p. None stays None (off)."""
    if ema is None:
        return None
    d = jnp.float32(decay)
    return jax.tree_util.tree_map(
        lambda e, p: (d * e + (1.0 - d) * p).astype(p.dtype),
        ema, new_params)


# ---------------------------------------------------------------------------
# Forward/loss closures per input kind
# ---------------------------------------------------------------------------

def _image_loss_fn(model, config: TrainConfig):
    smoothing = config.optimizer.label_smoothing

    def loss_fn(params, batch_stats, batch, rng):
        variables = {"params": params}
        if batch_stats is not None:
            variables["batch_stats"] = batch_stats
        # rngs is harmless for dropout-free CNNs and required for image
        # transformers (models/vit.py); per-shard/per-step folding happens in
        # the calling step fn.
        out, mutated = model.apply(
            variables, batch["image"], train=True, mutable=["batch_stats"],
            rngs={"dropout": rng})
        with jax.named_scope(LOSS_SCOPE):
            loss = losses.smoothed_softmax_ce(out, batch["label"], smoothing)
            metrics = {"loss": loss,
                       "accuracy": losses.top1_accuracy(out, batch["label"])}
        return loss, (mutated.get("batch_stats"), metrics)

    return loss_fn


def _token_loss_fn(model, config: TrainConfig):
    del config
    # MoE models sow per-layer load-balance losses into "moe_losses"
    # (models/moe.py); weight comes from the model's own config so dense
    # models pay nothing.
    aux_weight = getattr(getattr(model, "cfg", None), "moe_aux_weight", 0.0)

    def loss_fn(params, batch_stats, batch, rng):
        del batch_stats
        kw = {}
        if "masked_positions" in batch:  # gather-mode head (BertMLM)
            kw["masked_positions"] = batch["masked_positions"]
        logits, mutated = model.apply(
            {"params": params}, batch["input_ids"],
            attention_mask=batch.get("attention_mask"),
            train=True, rngs={"dropout": rng}, mutable=["moe_losses"], **kw)
        with jax.named_scope(LOSS_SCOPE):
            loss = losses.mlm_loss(
                logits, batch.get("masked_labels", batch.get("labels")))
        metrics = {"loss": loss}
        aux_leaves = jax.tree_util.tree_leaves(mutated.get("moe_losses", {}))
        if aux_leaves:
            aux = sum(aux_leaves) / len(aux_leaves)
            loss = loss + aux_weight * aux
            metrics["moe_aux"] = aux
        return loss, (None, metrics)

    return loss_fn


def model_state(variables):
    """What a model keeps besides its parameters and the step must carry:
    BatchNorm's running statistics, or the routed experts' selection biases
    (models/moe.py), or None. It rides in ``TrainState.batch_stats``, which
    holds ONE collection: a model that keeps both is refused here rather than
    trained with one of them silently left behind."""
    kept = [c for c in ("batch_stats", moe.ROUTER_STATE) if c in variables]
    if len(kept) > 1:
        raise ValueError(
            f"the model keeps {kept}: TrainState.batch_stats carries one "
            f"collection of model state, not both")
    return variables[kept[0]] if kept else None


def _moe_metrics(sown) -> dict:
    """The step's four expert counters from what each RoutedExperts layer
    sowed: assignments that landed on this chip's experts (all layers), the
    largest share of a layer's assignments that one expert took,
    assignments that found no room in a row buffer (which is sized so that
    there are none), and the layers in which more landed than the buffer
    sized to what is expected holds, so that the worst-case body ran."""
    by_name: dict = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(sown):
        by_name.setdefault(path[-2].key, []).append(leaf)
    return {"moe_tokens_here": sum(by_name["tokens_here"]),
            "moe_max_expert_share": jnp.max(
                jnp.stack(by_name["max_expert_share"])),
            "moe_dropped": sum(by_name["dropped"]),
            "moe_worst_case_layers": sum(by_name["worst_case"])}


# What layers sow for the step's metrics beside the experts' counters: the
# collection, the metric's name, and how the layers' values become one: the
# least cumulative gate inside a chunk over the delta-rule layers (ops/kda.py)
# and the least summed decay of a chunk over the state-space mixers
# (ops/ssd.py); the largest |row sum - 1| of any residual mixing matrix after
# its Sinkhorn iterations, over tokens and hyper-connections (mhc).
_SOWN = ((kda.KDA_METRICS, "kda_min_chunk_log_decay", jnp.min),
         (ssd.SSD_METRICS, "ssd_min_chunk_log_decay", jnp.min),
         (MHC_METRICS, "mhc_max_row_sum_gap", jnp.max))


def _sown_metrics(mutated) -> dict:
    """One number a collection of `_SOWN` that the model sowed into."""
    return {name: merge(jnp.stack(jax.tree_util.tree_leaves(mutated[col])))
            for col, name, merge in _SOWN if col in mutated}


def _causal_loss_fn(model, config: TrainConfig):
    del config

    def loss_fn(params, batch_stats, batch, rng):
        variables, mutable = {"params": params}, [c for c, _, _ in _SOWN]
        if batch_stats is not None:
            # routed experts: the selection biases move, with no gradient
            variables[moe.ROUTER_STATE] = batch_stats
            mutable += [moe.ROUTER_STATE, moe.MOE_METRICS]
        logits, mutated = model.apply(
            variables, batch["input_ids"],
            attention_mask=batch.get("attention_mask"),
            train=True, rngs={"dropout": rng}, mutable=mutable)
        with jax.named_scope(LOSS_SCOPE):
            loss = losses.causal_lm_loss(
                logits, batch["input_ids"], batch.get("attention_mask"))
        metrics = {"loss": loss, **_sown_metrics(mutated)}
        if batch_stats is None:
            return loss, (None, metrics)
        return loss, (mutated[moe.ROUTER_STATE],
                      {**metrics, **_moe_metrics(mutated[moe.MOE_METRICS])})

    return loss_fn


def loss_fn_for(model, input_kind: str, config: TrainConfig,
                objective: str = "classify"):
    if input_kind == "image":
        return _image_loss_fn(model, config)
    if input_kind == "tokens":
        if objective == "causal":
            return _causal_loss_fn(model, config)
        return _token_loss_fn(model, config)
    raise ValueError(f"unknown input kind {input_kind!r}")


# ---------------------------------------------------------------------------
# Gradient accumulation (config 5: batch=32k on any mesh — VERDICT r1 #3)
# ---------------------------------------------------------------------------

def accumulated_grads(loss_fn, params, batch_stats, batch, rng, accum: int):
    """Gradients for ``batch``, optionally microbatched via ``lax.scan``.

    With ``accum > 1`` the leading batch dim splits into ``accum`` equal
    microbatches; per-microbatch gradients are summed in a scan carry and
    divided once at the end — mathematically the big-batch *mean* gradient
    (exact for any loss that is a mean over examples, hence for SGD/LARS
    updates up to fp summation order). Activation memory drops by ~accum
    while the optimizer still sees one batch=32k update, which is what lets
    the LARS recipe execute on an 8-chip (or 8-fake-CPU) mesh.

    BatchNorm statistics are updated sequentially through the scan (each
    microbatch normalizes with its own statistics, exactly like running the
    microbatches as separate steps); metrics are averaged over microbatches.
    Returns ``(grads, new_batch_stats, metrics)``.
    """
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    if accum <= 1:
        (_, (new_bn, metrics)), grads = grad_fn(params, batch_stats, batch, rng)
        return grads, new_bn, metrics

    micro = jax.tree_util.tree_map(
        lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]), batch)

    def body(carry, xs):
        grads_acc, bn = carry
        mb, idx = xs
        (_, (new_bn, metrics)), grads = grad_fn(
            params, bn, mb, jax.random.fold_in(rng, idx))
        grads_acc = jax.tree_util.tree_map(jnp.add, grads_acc, grads)
        if new_bn is None:
            new_bn = bn
        return (grads_acc, new_bn), metrics

    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    (grads_sum, new_bn), metrics = jax.lax.scan(
        body, (zeros, batch_stats), (micro, jnp.arange(accum)))
    grads = jax.tree_util.tree_map(lambda g: g / accum, grads_sum)
    metrics = jax.tree_util.tree_map(jnp.mean, metrics)
    return grads, new_bn, metrics


# ---------------------------------------------------------------------------
# Path 1: explicit-collective DP (shard_map + psum) — Horovod semantics
# ---------------------------------------------------------------------------

def make_dp_train_step(model, tx: optax.GradientTransformation, mesh: Mesh,
                       config: TrainConfig, input_kind: str = "image",
                       objective: str = "classify",
                       state_like: Optional[TrainState] = None,
                       aot=None, zero_layout=None, params_struct=None
                       ) -> Callable[[TrainState, Any, jax.Array],
                                     tuple[TrainState, dict]]:
    """Build the jitted data-parallel train step.

    state: fully replicated. batch: leading dim sharded over (data, fsdp).
    Per-shard gradients are summed across the DP axes by the bucketed fused
    all-reduce (``config.allreduce``: bucket size / payload dtype /
    psum-vs-ring) and divided by the shard count — the exact
    allreduce-average Horovod performs — so parameters stay bit-identical
    on every shard. BN running-stat updates are ``pmean``-ed likewise.

    ``config.optimizer_sharding`` climbs the ZeRO ladder (parallel/zero.py):

    - ``zero1`` — the gradient sync stops at the ring's halfway point: one
      ``psum_scatter`` per fusion bucket leaves each shard the reduced 1/N
      chunk of every leaf, the optax update runs on that chunk against
      permanently 1/N-sharded optimizer state, and the trailing
      ``all_gather`` moves the *updated parameters* — same wire bytes as
      the ring all-reduce, optimizer HBM/compute divided by N.
    - ``zero2`` — same update math, but the loss is differentiated w.r.t.
      the parameter CHUNKS through a per-bucket identity ``custom_vjp``
      whose backward rule IS the bucket reduce-scatter: gradients are born
      reduce-scattered during backward (overlapping remaining backward
      compute) and the full gradient tree never materializes.
    - ``zero3`` — parameters themselves live in the chunked global form
      (``state.params`` leaves are padded flat ``(chunk*N,)`` arrays
      sharded over the DP axes) and are all-gathered on demand per bucket
      in forward, the gather's backward rule again the bucket
      reduce-scatter. No parameter all-gather after the update — the
      chunks ARE the persistent state.

    ``config.overlap_collectives=False`` downgrades zero2/zero3 to the
    serialized schedule (full grads after backward, one scatter pass) for
    A/B measurement; update arithmetic is unchanged.

    For any sharded stage ``state_like`` (the initialized TrainState) is
    required — it supplies per-leaf partition specs for shard_map. Under
    zero3 its params are chunked, so the FULL-shape ``params_struct`` and
    the ``zero_layout`` built from it must be passed in (train/loop.py
    does); other stages can derive both from ``state_like.params``.

    ``aot`` (a perf.aot.StepExecutableCache) switches the first call to the
    ahead-of-time path: load the serialized executable for this config
    fingerprint, or ``lower().compile()`` once and serialize it so the next
    launch / restart attempt skips tracing entirely (docs/compile_cache.md).
    """
    loss_fn = loss_fn_for(model, input_kind, config, objective)
    dp_size = mesh.shape["data"] * mesh.shape["fsdp"]
    accum = config.grad_accum_steps

    # Precision policy (config.resolve_precision). With no explicit policy
    # every derived value below collapses to the legacy behavior —
    # ar_options IS config.allreduce, no loss scaling, fp32 gathers — so
    # policy-free configs compile the exact seed program (and keep the
    # zero1<->replicated parity pin). An explicit policy re-points the
    # reduction payload at policy.reduce_dtype and, for bf16 compute,
    # gathers zero3's matrices on the wire in bf16 (norm scales and biases
    # as they are: zero._gather_wire_dtypes) while the persistent chunks
    # (the masters the optimizer updates) stay fp32.
    policy = resolve_precision(config)
    scaling = config.precision is not None and policy.loss_scale > 0
    ar_options = (dataclasses.replace(config.allreduce,
                                      dtype=policy.reduce_dtype)
                  if config.precision is not None else config.allreduce)
    gather_dtype = (jnp.bfloat16
                    if (config.precision is not None
                        and policy.compute_dtype == "bfloat16")
                    else None)

    nan_steps, guard = _guard_config(config)
    stage = getattr(config, "optimizer_sharding", "none") or "none"
    sharded = stage in ("zero1", "zero2", "zero3")
    overlap = (stage in ("zero2", "zero3")
               and getattr(config, "overlap_collectives", True))
    layout = payload = None
    if sharded:
        if state_like is None:
            raise ValueError(
                f"optimizer_sharding={stage!r} requires state_like= (the "
                "initialized TrainState) so the step can derive the chunk "
                "layout and per-leaf optimizer-state partition specs")
        if params_struct is None:
            if stage == "zero3":
                raise ValueError(
                    "optimizer_sharding='zero3' requires params_struct= "
                    "(full parameter shapes) — state_like.params is already "
                    "chunked and cannot seed the layout")
            params_struct = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(tuple(x.shape), x.dtype),
                state_like.params)
        if zero_layout is not None:
            layout = zero_layout
            payload = zero.payload_dtype_from_options(ar_options)
        else:
            layout, payload = zero.layout_from_options(
                params_struct, dp_size, options=ar_options)

    def step_fn(state: TrainState, batch, rng):
        TRACE_COUNTS["dp_train_step"] += 1  # trace-time only, not per call
        # Per-shard RNG: fold in the linearized DP coordinate.
        idx = jax.lax.axis_index(DATA_AXES)
        rng = jax.random.fold_in(jax.random.fold_in(rng, idx), state.step)

        # Dynamic loss scaling: scale the differentiated scalar only — the
        # aux metrics (including metrics["loss"]) stay unscaled, and the
        # gradients come out uniformly multiplied by the scale, which the
        # unscale below divides back out after the cross-shard reduction.
        if scaling:
            ls_scale = state.loss_scale["scale"]

            def lfn(p, bn, b, r):
                loss, aux = loss_fn(p, bn, b, r)
                return loss * ls_scale, aux
        else:
            lfn = loss_fn

        # Per-shard microbatching: the reshape is shard-local (free), and the
        # sum-over-examples gradient is grouping-invariant, so accum-N here
        # equals the one-shot big-batch gradient. (With the overlapped
        # zero2/zero3 schedules each microbatch issues its own per-bucket
        # scatters, so the cross-shard sum order differs from zero1's single
        # post-accumulation scatter — same math, not bitwise; accum=1 is.)
        gchunks = pchunks = None
        with jax.named_scope(GRADS):
            if stage == "zero3":
                # Inside shard_map the P(DATA_AXES) in_spec on the chunked
                # global form means state.params leaves ARE this shard's
                # local (chunk,) slices — no dynamic_slice needed.
                pchunks = state.params
                if overlap:
                    def chunk_loss(pc, bn, b, r):
                        full = zero.gather_params_overlapped(
                            pc, layout, DATA_AXES, payload_dtype=payload,
                            out_dtype=gather_dtype)
                        return lfn(full, bn, b, r)
                    gchunks, new_bn, metrics = accumulated_grads(
                        chunk_loss, pchunks, state.batch_stats, batch, rng,
                        accum)
                else:
                    full = zero.all_gather_chunks(pchunks, layout, DATA_AXES,
                                                  out_dtype=gather_dtype)
                    grads, new_bn, metrics = accumulated_grads(
                        lfn, full, state.batch_stats, batch, rng, accum)
            elif stage == "zero2" and overlap:
                pchunks = zero.local_chunks(state.params, layout, DATA_AXES)

                def chunk_loss(pc, bn, b, r):
                    # state.params enters as a closure CONSTANT (the
                    # identity forward), so only the chunk cotangents
                    # survive — the full gradient tree is never a live value.
                    full = zero.assemble_params_overlapped(
                        state.params, pc, layout, DATA_AXES,
                        payload_dtype=payload)
                    return lfn(full, bn, b, r)

                gchunks, new_bn, metrics = accumulated_grads(
                    chunk_loss, pchunks, state.batch_stats, batch, rng, accum)
            else:
                grads, new_bn, metrics = accumulated_grads(
                    lfn, state.params, state.batch_stats, batch, rng, accum)

        if nan_steps:
            if gchunks is not None:
                gchunks = _inject_nan_grads(gchunks, state.step, nan_steps)
            else:
                grads = _inject_nan_grads(grads, state.step, nan_steps)

        with jax.named_scope(GRAD_REDUCE):
            metrics = jax.lax.pmean(metrics, DATA_AXES)
            if new_bn is not None:
                # Sync running statistics (cheap; normalization itself stayed
                # local per shard, matching per-GPU BN under Horovod).
                new_bn = jax.lax.pmean(new_bn, DATA_AXES)

        if sharded:
            # Shard-local optimizer update on this shard's 1/N chunk of
            # every leaf. `tx` was built with shard_axes=DATA_AXES
            # (train/optim.py), so any cross-leaf norms (global clip,
            # LARS/LAMB trust ratios) psum their squared sums and the
            # chunked update matches the replicated one per element.
            with jax.named_scope(GRAD_REDUCE):
                if gchunks is None:
                    # zero1 / overlap-off schedules: full gradient tree was
                    # materialized; run the ring's first half now.
                    gchunks = zero.reduce_scatter(grads, layout, DATA_AXES,
                                                  payload_dtype=payload)
                gchunks = jax.tree_util.tree_map(
                    lambda g: g / dp_size, gchunks)
            if scaling:
                # Overflow check on the still-scaled chunks, then unscale.
                # Each shard holds 1/N of every leaf, so the squared norm
                # needs one psum to make the verdict shard-consistent.
                with jax.named_scope(LOSS_SCALE):
                    overflow = ~jnp.isfinite(
                        jax.lax.psum(_tree_sq_norm(gchunks), DATA_AXES))
                    gchunks = jax.tree_util.tree_map(
                        lambda g: g / ls_scale, gchunks)
            with jax.named_scope(OPTIMIZER):
                if pchunks is None:
                    pchunks = zero.local_chunks(state.params, layout,
                                                DATA_AXES)
                updates, new_opt = tx.update(gchunks, state.opt_state,
                                             pchunks)
                new_pchunks = optax.apply_updates(pchunks, updates)
                if stage == "zero3":
                    # Chunks ARE the persistent parameter layout — no gather.
                    new_params = new_pchunks
                else:
                    new_params = zero.all_gather_chunks(new_pchunks, layout,
                                                        DATA_AXES)
        else:
            # The allreduce. compat.shard_map runs with replication checking
            # OFF, so autodiff does NOT auto-psum gradients for the
            # replicated params — `grads` arrives here shard-LOCAL, and this
            # train step owns the reduction schedule: leaves fuse into
            # size-targeted buckets, one collective per bucket (Horovod
            # tensor fusion), with each bucket an independent dataflow edge
            # XLA can overlap with remaining backward compute. Dividing the
            # sum by the shard count turns the ring-allreduce-sum into the
            # gradient *average* hvd applies.
            with jax.named_scope(GRAD_REDUCE):
                grads = collectives.all_reduce_gradients(
                    grads, DATA_AXES, axis_size=dp_size,
                    options=ar_options)
                grads = jax.tree_util.tree_map(lambda g: g / dp_size, grads)
            if scaling:
                # Post-all-reduce gradients are shard-identical, so the
                # overflow verdict is shard-consistent without a collective.
                with jax.named_scope(LOSS_SCALE):
                    overflow = ~jnp.isfinite(_tree_sq_norm(grads))
                    grads = jax.tree_util.tree_map(
                        lambda g: g / ls_scale, grads)
            with jax.named_scope(OPTIMIZER):
                updates, new_opt = tx.update(grads, state.opt_state,
                                             state.params)
                new_params = optax.apply_updates(state.params, updates)

        with jax.named_scope(EMA):
            new_ema = _ema_update(state.ema_params, new_params,
                                  config.optimizer.ema_decay)
        new_ls = state.loss_scale
        if scaling:
            # Loss-scale skip-on-overflow: same select machinery as the
            # bad-step guard but applied FIRST and accounted separately
            # (``loss_scale_skip``, never ``bad_step``) — a scale backoff is
            # normal mixed-precision operation, not a run anomaly, and the
            # guard below must see the already-restored (finite) state so a
            # backoff can never double-count.
            with jax.named_scope(LOSS_SCALE):
                new_params = _skip_if_bad(overflow, new_params, state.params)
                new_opt = _skip_if_bad(overflow, new_opt, state.opt_state)
                new_bn = _skip_if_bad(overflow, new_bn, state.batch_stats)
                new_ema = _skip_if_bad(overflow, new_ema, state.ema_params)
                new_ls, ls_metrics = _next_loss_scale(
                    policy, ls_scale, state.loss_scale["good_steps"],
                    overflow)
            metrics.update(ls_metrics)
        if guard:
            # Bad-step guard (docs/fault_tolerance.md). The decision must be
            # identical on every shard, so derive it ONLY from values that
            # already are: the pmean'd loss and the post-update params
            # (post-all-reduce here, post-all-gather under zero1).
            # Non-finite grads on ANY shard propagate through the reduction
            # and the optimizer into the params, so checking the result
            # catches them — one local (collective-free) reduction per step,
            # except under zero3 where new_params is this shard's chunks
            # only and the norm needs a psum to stay shard-consistent.
            with jax.named_scope(GUARD):
                sq = _tree_sq_norm(new_params)
                if stage == "zero3":
                    sq = jax.lax.psum(sq, DATA_AXES)
                bad = jnp.logical_or(~jnp.isfinite(metrics["loss"]),
                                     ~jnp.isfinite(sq))
                if scaling:
                    # An overflow step already skipped above; even if its loss
                    # was non-finite, the scaler owns it — not the anomaly
                    # budget.
                    bad = jnp.logical_and(bad, jnp.logical_not(overflow))
                # Skip-on-bad: the step index still advances (the batch is
                # consumed; a skip is a skip, not a retry), but params/opt/BN/
                # EMA keep their pre-update values so one poisoned batch can't
                # wreck the run.
                new_params = _skip_if_bad(bad, new_params, state.params)
                new_opt = _skip_if_bad(bad, new_opt, state.opt_state)
                new_bn = _skip_if_bad(bad, new_bn, state.batch_stats)
                new_ema = _skip_if_bad(bad, new_ema, state.ema_params)
            metrics["bad_step"] = bad.astype(jnp.float32)
        new_state = TrainState(step=state.step + 1, params=new_params,
                               opt_state=new_opt, batch_stats=new_bn,
                               ema_params=new_ema, loss_scale=new_ls)
        return new_state, metrics

    batch_spec = P(DATA_AXES)
    if sharded:
        # Everything replicated EXCEPT the chunked leaves, which shard dim 0
        # over the DP axes (each shard sees its chunk): the opt state at
        # every stage, plus params/ema at zero3.
        opt_spec = zero.opt_state_specs(tx, params_struct, layout,
                                        P(DATA_AXES), P())
        state_spec = jax.tree_util.tree_map(lambda _: P(), state_like)
        state_spec = state_spec.replace(opt_state=opt_spec)
        if stage == "zero3":
            state_spec = state_spec.replace(
                params=jax.tree_util.tree_map(lambda _: P(DATA_AXES),
                                              state_like.params))
            if state_like.ema_params is not None:
                state_spec = state_spec.replace(
                    ema_params=jax.tree_util.tree_map(
                        lambda _: P(DATA_AXES), state_like.ema_params))
    else:
        state_spec = P()
    mapped = compat.shard_map(
        step_fn, mesh=mesh,
        in_specs=(state_spec, batch_spec, P()),
        out_specs=(state_spec, P()))
    jitted = jax.jit(mapped, donate_argnums=0)
    aot_exec = {"fn": None, "resolved": aot is None or not aot.enabled}

    def compiled(state, batch, rng):
        if not aot_exec["resolved"]:
            # First call: bind the AOT executable at these argument avals.
            # Donation (argnums=0) is baked into the lowering, so the
            # Compiled object updates state in place exactly like the jit.
            aot_exec["resolved"] = True
            aot_exec["fn"] = _aot_acquire(aot, "dp_train_step", jitted,
                                          (state, batch, rng))
        if aot_exec["fn"] is not None:
            return aot_exec["fn"](state, batch, rng)
        return jitted(state, batch, rng)

    def warm(state_struct, batch, rng) -> bool:
        """Resolve the step executable from abstract avals without
        executing. The elastic restore/compile overlap (train/loop.py) runs
        this on a background thread while ``restore_latest`` deserializes
        the checkpoint, so a re-formed attempt pays max(restore, compile)
        instead of their sum. ``state_struct`` must carry the live state's
        shardings (ShapeDtypeStruct with sharding=) — same contract as the
        evaluator's warm_compile_async. Returns False (cold path intact) on
        any failure; warm-up is optional."""
        if aot_exec["fn"] is not None:
            return True
        try:
            if aot is not None and aot.enabled:
                fn = _aot_acquire(aot, "dp_train_step", jitted,
                                  (state_struct, batch, rng))
            else:
                with telemetry.phase("compile", program="dp_train_step"):
                    fn = jitted.lower(state_struct, batch, rng).compile()
            aot_exec["fn"] = fn
            aot_exec["resolved"] = True
            return True
        except Exception:  # noqa: BLE001 - warm-up is optional
            return False

    compiled.warm = warm
    # The step program as jax lowers it for these arguments — for reading
    # what was compiled (kernels, collectives, memory), not for running.
    compiled.lower = jitted.lower
    compiled.anatomy = lambda: _anatomy_of(aot_exec["fn"])
    # Raw traceable step for the fused multi-step loop
    # (make_fused_train_loop): shard_map composes under an outer jit+scan.
    compiled.raw_step = mapped
    compiled.zero_layout = layout
    compiled.zero_stage = stage if sharded else None
    compiled.overlap = overlap
    # Per-device gradient residency for the memory-ladder accounting
    # (train/loop.py, bench.py): gradients are transient, so this is a
    # model, not a measurement — see zero.modeled_grad_bytes.
    if layout is not None:
        compiled.grad_bytes_per_device = zero.modeled_grad_bytes(
            layout, chunked=overlap)
    elif state_like is not None:
        compiled.grad_bytes_per_device = zero.modeled_grad_bytes(
            zero.build_layout(state_like.params, 1), chunked=False)
    else:
        compiled.grad_bytes_per_device = None
    return compiled


def make_token_eval_step(model, mesh: Mesh, config: TrainConfig,
                         state_shardings, objective: str = "mlm"):
    """Held-out LM eval (GSPMD): per-batch (loss_sum, token_count) with
    dropout off — exact aggregation across any sharding, so perplexity is
    identical to a single-device pass (the token analogue of the image
    path's psum'd correct-counts, SURVEY.md §3.5)."""

    def eval_fn(state: TrainState, batch):
        TRACE_COUNTS["token_eval_step"] += 1
        kw = {}
        if objective != "causal" and "masked_positions" in batch:
            kw["masked_positions"] = batch["masked_positions"]
        variables = {"params": state.params}
        if objective == "causal" and state.batch_stats is not None:
            variables[moe.ROUTER_STATE] = state.batch_stats
        with _unreplicated_rules_ctx(config):
            logits = model.apply(
                variables, batch["input_ids"],
                attention_mask=batch.get("attention_mask"), train=False, **kw)
        if objective == "causal":
            s, n = losses.causal_lm_loss_sums(
                logits, batch["input_ids"], batch.get("attention_mask"))
        else:
            s, n = losses.mlm_loss_sums(
                logits, batch.get("masked_labels", batch.get("labels")))
        return {"loss_sum": s, "count": n}

    jit_cache: dict = {}

    def compiled(state, batch):
        key = jax.tree_util.tree_structure(batch)
        if key not in jit_cache:
            jit_cache[key] = jax.jit(
                eval_fn,
                in_shardings=(state_shardings, None),
                out_shardings=NamedSharding(mesh, P()))
        with use_mesh(mesh):
            return jit_cache[key](state, batch)

    def lower_for(state, batch):
        """AOT entry for the eval warm-compile overlap (train/loop.py):
        lower at abstract avals without executing. The caller keeps the
        returned Lowered's ``compile()`` result and must call IT — jit's
        internal cache is not populated by AOT compilation."""
        key = jax.tree_util.tree_structure(batch)
        if key not in jit_cache:
            jit_cache[key] = jax.jit(
                eval_fn,
                in_shardings=(state_shardings, None),
                out_shardings=NamedSharding(mesh, P()))
        with use_mesh(mesh):
            return jit_cache[key].lower(state, batch)

    compiled.lower_for = lower_for
    return compiled


def make_dp_eval_step(model, mesh: Mesh, config: TrainConfig):
    """Eval: per-shard correct-count, psum before dividing (SURVEY.md §3.5)."""
    del config

    def eval_fn(state: TrainState, batch):
        TRACE_COUNTS["dp_eval_step"] += 1
        variables = {"params": state.params}
        if state.batch_stats is not None:
            variables["batch_stats"] = state.batch_stats
        logits = model.apply(variables, batch["image"], train=False)
        correct = (jnp.argmax(logits, -1) == batch["label"]).sum()
        total = jnp.asarray(batch["label"].shape[0], jnp.int32)
        correct = jax.lax.psum(correct, DATA_AXES)
        total = jax.lax.psum(total, DATA_AXES)
        return {"correct": correct, "total": total}

    mapped = compat.shard_map(
        eval_fn, mesh=mesh, in_specs=(P(), P(DATA_AXES)),
        out_specs=P())
    return jax.jit(mapped)


# ---------------------------------------------------------------------------
# Path 2: GSPMD (jit + NamedSharding) — tp/sp/fsdp for transformers
# ---------------------------------------------------------------------------

def _unreplicated_rules_ctx(config: TrainConfig):
    return nn.logical_axis_rules(list(shardlib.logical_rules(config.parallel)))


def _batch_leaf_shardings(mesh: Mesh, batch_shd, batch):
    """Leading-dim batch sharding for array leaves, replicated for scalars —
    the one rule both the per-step GSPMD jit and the fused loop use."""
    rep = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(
        lambda x: batch_shd if getattr(x, "ndim", 0) >= 1 else rep, batch)


def _zero2_opt_state_shardings(mesh: Mesh, abstract_opt, shardings_opt):
    """ZeRO-2 composition for the GSPMD *pipelined* path: re-spec each
    optimizer-state leaf to also shard over the DP axes on its first free
    (unsharded, divisible) dimension. Stage/tp dims keep their axes, so a
    moment chunk lives inside its stage's DP group — XLA then lowers the
    gradient reduction feeding the update into a reduce-scatter per group
    and all-gathers the applied updates, the per-bucket dataflow the
    explicit shard_map path builds by hand in parallel/zero.py
    (docs/pipeline.md "Composing with ZeRO-2"). Leaves with no divisible
    free dim (scalars, odd shapes) stay on their param spec — partial
    sharding, same rule as the explicit layout planner."""
    dp_axes = tuple(a for a in ("data", "fsdp") if mesh.shape.get(a, 1) > 1)
    if not dp_axes:
        return shardings_opt
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]

    def shard_leaf(aval, shd):
        shape = getattr(aval, "shape", ())
        if not shape or not isinstance(shd, NamedSharding):
            return shd
        spec = list(shd.spec) + [None] * (len(shape) - len(shd.spec))
        for d, size in enumerate(shape):
            if spec[d] is None and size and size % dp == 0:
                spec[d] = dp_axes if len(dp_axes) > 1 else dp_axes[0]
                return NamedSharding(mesh, P(*spec))
        return shd

    return jax.tree_util.tree_map(
        shard_leaf, nn.meta.unbox(abstract_opt), shardings_opt)


def init_sharded_state(model, tx, mesh: Mesh, config: TrainConfig,
                       example_batch: Any, rng: jax.Array,
                       input_kind: str = "tokens", aot=None):
    """Initialize a TrainState whose params/opt-state are laid out per the
    logical sharding rules, created directly on-device via jit out_shardings
    (no host-side full materialization).

    With an ``aot`` cache the init program itself is fingerprint-keyed like
    the train step: a re-formed elastic attempt (or any warm boot of an
    identical config) deserializes it instead of re-compiling — the init
    values are overwritten by the checkpoint restore anyway, so the compile
    it skips was pure outage time (reconfiguration ``spawn_s``)."""

    def init_fn(rng):
        with _unreplicated_rules_ctx(config):
            if input_kind == "tokens":
                variables = model.init(
                    {"params": rng, "dropout": rng},
                    example_batch["input_ids"], train=False)
            else:
                variables = model.init(
                    {"params": rng}, example_batch["image"], train=False)
        params = variables["params"]
        opt_state = tx.init(params)
        return TrainState.create(
            params=params, opt_state=opt_state,
            batch_stats=model_state(variables),
            ema_params=(params if config.optimizer.ema_decay > 0
                        else None),
            loss_scale=init_loss_scale(config))

    with use_mesh(mesh):  # model may embed mesh-dependent shard_maps (ring)
        abstract = jax.eval_shape(init_fn, rng)
    with _unreplicated_rules_ctx(config):
        specs = nn.logical_to_mesh(nn.get_partition_spec(abstract))
    shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), specs,
        is_leaf=lambda x: isinstance(x, P))
    if (config.optimizer_sharding == "zero2"
            and getattr(getattr(model, "cfg", None), "pipeline_stages", 1)
            > 1):
        shardings = shardings.replace(opt_state=_zero2_opt_state_shardings(
            mesh, abstract.opt_state, shardings.opt_state))
    with use_mesh(mesh):
        jitted = jax.jit(init_fn, out_shardings=shardings)
        if aot is not None and aot.enabled:
            jitted = _aot_acquire(aot, "gspmd_init", jitted, (rng,))
        state = jitted(rng)
    return state, shardings


def make_gspmd_train_step(model, tx, mesh: Mesh, config: TrainConfig,
                          state_shardings, input_kind: str = "tokens",
                          objective: str = "mlm", aot=None):
    loss_fn = loss_fn_for(model, input_kind, config, objective)
    nan_steps, bad_guard = _guard_config(config)
    policy = resolve_precision(config)
    scaling = config.precision is not None and policy.loss_scale > 0
    # Token batches are (B, S): dim 0 over the DP axes, dim 1 over `seq`.
    seq_dim = 1 if input_kind == "tokens" else None
    batch_shd = shardlib.batch_sharding(mesh, seq_dim=seq_dim)

    def step_fn(state: TrainState, batch, rng):
        TRACE_COUNTS["gspmd_train_step"] += 1
        rng = jax.random.fold_in(rng, state.step)
        if scaling:
            ls_scale = state.loss_scale["scale"]

            def lfn(p, bn, b, r):
                loss, aux = loss_fn(p, bn, b, r)
                return loss * ls_scale, aux
        else:
            lfn = loss_fn
        with _unreplicated_rules_ctx(config), jax.named_scope(GRADS):
            # Microbatching under GSPMD: the (B,) -> (A, B/A) reshape crosses
            # the dp sharding, so XLA may insert a small resharding collective
            # on the *batch* (token batches are tiny; image configs use the
            # shard-local DP path above instead). Caveat: SPMD propagation
            # has been observed (jax 0.4.37) to realize this contiguous
            # split as the shard-local grouping — for a loss that is a plain
            # per-example mean the accumulated gradient is grouping-
            # invariant, but it is NOT guaranteed mesh-stable for
            # group-normalized losses; the pipeline conveyor hit the same
            # pattern and moved to a strided split (models/pipeline.py).
            grads, new_bn, metrics = accumulated_grads(
                lfn, state.params, state.batch_stats, batch, rng,
                config.grad_accum_steps)
        if nan_steps:
            grads = _inject_nan_grads(grads, state.step, nan_steps)
        if scaling:
            # One logical program: XLA inserts whatever cross-shard
            # reduction the norm needs, so the verdict is globally
            # consistent without an explicit psum.
            with jax.named_scope(LOSS_SCALE):
                overflow = ~jnp.isfinite(_tree_sq_norm(grads))
                grads = jax.tree_util.tree_map(lambda g: g / ls_scale, grads)
        with jax.named_scope(OPTIMIZER):
            updates, new_opt = tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
        with jax.named_scope(EMA):
            new_ema = _ema_update(state.ema_params, new_params,
                                  config.optimizer.ema_decay)
        new_ls = state.loss_scale
        if scaling:
            with jax.named_scope(LOSS_SCALE):
                new_params = _skip_if_bad(overflow, new_params, state.params)
                new_opt = _skip_if_bad(overflow, new_opt, state.opt_state)
                new_bn = _skip_if_bad(overflow, new_bn, state.batch_stats)
                new_ema = _skip_if_bad(overflow, new_ema, state.ema_params)
                new_ls, ls_metrics = _next_loss_scale(
                    policy, ls_scale, state.loss_scale["good_steps"],
                    overflow)
            metrics.update(ls_metrics)
        if bad_guard:
            # Bad-step guard on the post-update params (same placement as
            # the DP path). One logical program: XLA inserts any cross-shard
            # reduction the norm needs, so the scalar is globally
            # consistent without an explicit psum.
            with jax.named_scope(GUARD):
                bad = jnp.logical_or(
                    ~jnp.isfinite(metrics["loss"]),
                    ~jnp.isfinite(_tree_sq_norm(new_params)))
                if scaling:
                    bad = jnp.logical_and(bad, jnp.logical_not(overflow))
                new_params = _skip_if_bad(bad, new_params, state.params)
                new_opt = _skip_if_bad(bad, new_opt, state.opt_state)
                new_bn = _skip_if_bad(bad, new_bn, state.batch_stats)
                new_ema = _skip_if_bad(bad, new_ema, state.ema_params)
            metrics["bad_step"] = bad.astype(jnp.float32)
        new_state = TrainState(step=state.step + 1, params=new_params,
                               opt_state=new_opt, batch_stats=new_bn,
                               ema_params=new_ema, loss_scale=new_ls)
        return new_state, metrics

    batch_shardings = functools.partial(_batch_leaf_shardings, mesh, batch_shd)

    jit_cache: dict = {}

    def jit_for(batch):
        return jax.jit(
            step_fn,
            in_shardings=(state_shardings, batch_shardings(batch),
                          NamedSharding(mesh, P())),
            out_shardings=(state_shardings, NamedSharding(mesh, P())),
            donate_argnums=0)

    def compiled(state, batch, rng):
        # One jit wrapper per batch structure — recreating the wrapper per
        # call would discard the compilation cache. With an AOT cache the
        # wrapper resolves once to an executable (same contract as the dp
        # path): fingerprint hit deserializes it — zero retraces, so a
        # pipelined warm boot skips the whole schedule trace — and a miss
        # lower().compile()s and saves it for the next attempt.
        key = jax.tree_util.tree_structure(batch)
        if key not in jit_cache:
            jitted = jit_for(batch)
            if aot is not None and aot.enabled:
                with use_mesh(mesh):
                    jitted = _aot_acquire(aot, "gspmd_train_step", jitted,
                                          (state, batch, rng))
            jit_cache[key] = jitted
        with use_mesh(mesh):
            return jit_cache[key](state, batch, rng)

    def warm(state_struct, batch, rng) -> bool:
        """GSPMD twin of the DP path's ``warm``: populate the per-structure
        cache from abstract avals (elastic restore/compile overlap). The
        explicit in_shardings make struct lowering exact — the executable
        the first real call would have built."""
        key = jax.tree_util.tree_structure(batch)
        if key in jit_cache:
            return True
        try:
            jitted = jit_for(batch)
            with use_mesh(mesh):
                if aot is not None and aot.enabled:
                    jitted = _aot_acquire(aot, "gspmd_train_step", jitted,
                                          (state_struct, batch, rng))
                else:
                    with telemetry.phase("compile",
                                         program="gspmd_train_step"):
                        jitted = jitted.lower(state_struct, batch,
                                              rng).compile()
            jit_cache[key] = jitted
            return True
        except Exception:  # noqa: BLE001 - warm-up is optional
            return False

    def lower(state, batch, rng):
        """The step program as jax lowers it for these arguments — for
        reading what was compiled, not for running (same as the DP path)."""
        with use_mesh(mesh):
            return jit_for(batch).lower(state, batch, rng)

    compiled.warm = warm
    compiled.lower = lower
    compiled.anatomy = lambda: _anatomy_of(
        next(iter(jit_cache.values()), None))
    compiled.raw_step = step_fn
    compiled.state_shardings = state_shardings
    return compiled


# ---------------------------------------------------------------------------
# Fused multi-step loop (steps_per_loop) — dispatch-latency amortization
# ---------------------------------------------------------------------------

def make_fused_train_loop(train_step, source, batch_shd, mesh: Mesh):
    """Fuse K train steps + on-device batch generation into ONE XLA program.

    The TPU analogue of TF/TPUEstimator's ``iterations_per_loop``: when the
    batch is a pure on-device function of ``(seed, step)`` (synthetic
    sources), a ``lax.scan`` over K steps removes K-1 host dispatches per
    loop — worth it only where per-step host dispatch, not the device,
    gates throughput.

    Numerics are mathematically identical to the per-step path — the step fn
    derives its RNG from ``state.step`` and the scan feeds each step the
    same ``gen_fn(key, step)`` batch ``source.batch(step)`` would have
    produced — but NOT bitwise: XLA fuses/reassociates the two programs
    differently (~1e-6/step fp drift, which BN+ReLU training chaotically
    amplifies; see tests/test_fused_loop.py).

    Returns ``runner(state, rng, start, n) -> (state, last_step_metrics)``
    with a per-``n`` compile cache, or None when ``train_step`` exposes no
    raw traceable step. ``start`` is traced, so every same-length block
    reuses one executable.
    """
    raw_step = getattr(train_step, "raw_step", None)
    gen_fn = getattr(source, "gen_fn", None)
    if raw_step is None or gen_fn is None:
        return None
    state_shardings = getattr(train_step, "state_shardings", None)
    rep = NamedSharding(mesh, P())

    def batch_constraint(batch):
        return jax.lax.with_sharding_constraint(
            batch, _batch_leaf_shardings(mesh, batch_shd, batch))

    def make(n: int):
        def fused(state, rng, key, start):
            def body(st, i):
                batch = batch_constraint(gen_fn(key, start + i))
                return raw_step(st, batch, rng)

            # Full unroll: a rolled while-loop body pins one conservative
            # layout for every iteration (XLA layout assignment can't
            # specialize across loop trips), measured 43% slower than
            # per-step dispatch for ResNet50; unrolled, XLA optimizes the
            # straight-line program like K consecutive steps.
            state2, stacked = jax.lax.scan(
                body, state, jnp.arange(n, dtype=jnp.int32), unroll=True)
            return state2, jax.tree_util.tree_map(lambda m: m[-1], stacked)

        kw = {}
        if state_shardings is not None:
            kw = dict(in_shardings=(state_shardings, rep, rep, rep),
                      out_shardings=(state_shardings, rep))
        return jax.jit(fused, donate_argnums=0, **kw)

    cache: dict[int, Any] = {}
    key = jax.random.key(source.seed)

    def runner(state, rng, start: int, n: int):
        if n not in cache:
            cache[n] = make(n)
        with use_mesh(mesh):
            return cache[n](state, rng, key, jnp.int32(start))

    return runner
