"""The model-agnostic training loop behind ``train.py``.

One loop serves every acceptance config (BASELINE.json:6-12): it selects the
parallel execution style (explicit-collective DP for CNNs, GSPMD for
transformer workloads with tp/sp), builds the data source, and drives the
compiled step with JSONL metrics — the role the reference's per-framework
``src/train-script.py`` files played (SURVEY.md §2 #1-#3), minus the
framework forks.
"""

from __future__ import annotations

import os
import signal
import sys
import threading
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp

from distributeddeeplearning_tpu.config import (TrainConfig,
                                                resolve_mlm_max_predictions,
                                                resolve_precision)
from distributeddeeplearning_tpu import data as datalib
from distributeddeeplearning_tpu.data import synthetic
from distributeddeeplearning_tpu.models import model_spec
from distributeddeeplearning_tpu.observability import anomaly as anomalylib
from distributeddeeplearning_tpu.observability import flight as flightlib
from distributeddeeplearning_tpu.observability import health, sidecars, telemetry
from distributeddeeplearning_tpu.observability import metrics as metricslib
from distributeddeeplearning_tpu.observability import straggler as stragglib
from distributeddeeplearning_tpu.parallel import mesh as meshlib
from distributeddeeplearning_tpu.parallel import sharding as shardlib
from distributeddeeplearning_tpu.parallel import zero as zerolib
from distributeddeeplearning_tpu.perf import aot as aotlib
from distributeddeeplearning_tpu.perf import compile_cache as cachelib
from distributeddeeplearning_tpu.robustness import faults as faultslib
from distributeddeeplearning_tpu.train import checkpoint as ckptlib
from distributeddeeplearning_tpu.train import optim, steps
from distributeddeeplearning_tpu.train import state as statelib
from distributeddeeplearning_tpu.train.state import TrainState
from distributeddeeplearning_tpu.utils.logging import MetricLogger


def _dtype(config: TrainConfig):
    # The model's compute dtype comes from the precision policy; with no
    # explicit policy this resolves to config.dtype (legacy behavior).
    compute = resolve_precision(config).compute_dtype
    return jnp.bfloat16 if compute == "bfloat16" else jnp.float32


def steps_per_epoch(config: TrainConfig) -> Optional[int]:
    """Explicit ``config.steps_per_epoch``, else derived from the dataset's
    train-split size (ImageNet: 1,281,167; an imagefolder ``data_dir``:
    counted once from disk), else None (step-based runs)."""
    if config.steps_per_epoch:
        return config.steps_per_epoch
    if config.data.data_dir:
        # Imagefolder layout (train/<class>/<files>): count the actual
        # corpus — it wins over the canonical ImageNet constant, which is
        # only right for the full dataset (a TFRecord data_dir has train-*
        # shards, no train/ dir, and raises here). folder_index is the
        # loaders' own lru_cached, extension-filtered listing, so the
        # derived epoch length agrees with the batches they yield and the
        # walk is shared, not repeated. Epoch-cadenced eval then works on
        # any on-disk corpus (the graded-corpus convergence leg needs it).
        try:
            from distributeddeeplearning_tpu.data.imagenet import (
                folder_index)
            n = len(folder_index(config.data.data_dir, "train")[0])
            return max(n // config.global_batch_size, 1)
        except FileNotFoundError:
            pass
    if config.data.dataset == "imagenet":
        from distributeddeeplearning_tpu.data.imagenet import TRAIN_SPLIT_SIZE
        return max(TRAIN_SPLIT_SIZE // config.global_batch_size, 1)
    return None


def uses_gspmd(config: TrainConfig, input_kind: str) -> bool:
    """Transformers (or any config with tp/sp axes) take the GSPMD path;
    pure-DP CNNs take the explicit shard_map+psum path. An ``fsdp`` axis
    alone forces GSPMD *unless* ``optimizer_sharding='zero3'`` — zero3 folds
    the GSPMD fsdp parameter-sharding rule into the explicit path's bucket
    planner (parallel/zero.py), chunk-sharding params over BOTH dp axes."""
    p = config.parallel
    if input_kind == "tokens" or p.model > 1 or p.seq > 1:
        return True
    return p.fsdp > 1 and config.optimizer_sharding != "zero3"


@telemetry.phase("build")
def build(config: TrainConfig, total_steps: int):
    """Construct (mesh, model, batch sharding, state, train_step, sched, rng)
    for a config. The data source is NOT built here — real pipelines must be
    positioned at the post-restore start step, so ``run`` creates it after
    checkpoint restore."""
    spec = model_spec(config.model)
    _ = config.per_device_batch  # early, friendly divisibility error
    if config.optimizer_sharding not in ("none", "zero1", "zero2", "zero3"):
        raise ValueError(
            f"unknown optimizer_sharding {config.optimizer_sharding!r}; "
            f"expected one of 'none', 'zero1', 'zero2', 'zero3'")
    if (config.optimizer_sharding != "none"
            and uses_gspmd(config, spec.input_kind)
            and not (config.optimizer_sharding == "zero2"
                     and config.parallel.pipeline > 1)):
        raise ValueError(
            f"optimizer_sharding={config.optimizer_sharding!r} applies to "
            "the explicit-DP shard_map path only (image model, no tp/sp "
            "axes — and no fsdp axis except under zero3, which absorbs "
            "it); the GSPMD path shards state via NamedSharding rules "
            "instead. Exception: zero2 composes with a pipelined model "
            "(parallel.pipeline > 1), sharding optimizer state over each "
            "stage's DP group (docs/pipeline.md)")
    if config.attention_impl == "flash" and config.parallel.seq > 1:
        raise ValueError(
            "attention_impl='flash' is incompatible with seq-axis "
            "parallelism (it needs the full sequence per device); use "
            "attention_impl='ring' for seq>1")
    mesh = meshlib.make_mesh(config.parallel, backend=config.backend)
    dtype = _dtype(config)
    if spec.input_kind == "tokens":
        kw: dict = dict(vocab_size=config.data.vocab_size, dtype=dtype,
                        seq_len=config.data.seq_len)
    else:
        kw = dict(num_classes=config.data.num_classes, dtype=dtype)
    # Attention/remat knobs apply to any transformer (BERT/GPT/ViT); CNN
    # builders reject them loudly (TypeError names the kwarg) rather than
    # silently ignoring the flag.
    if config.attention_impl:
        kw["attention_impl"] = config.attention_impl
    if config.remat:
        kw["remat"] = True
    if config.fused_bn:
        kw["fused_bn"] = True
    if config.fused_block:
        kw["fused_block"] = True
    if config.sync_bn:
        # Cross-replica BN needs the named mesh axes of the explicit
        # shard_map path; the GSPMD path has no manual axes to pmean over.
        if uses_gspmd(config, spec.input_kind):
            raise ValueError(
                "sync_bn requires the pure-DP shard_map path (image model, "
                "no tp/sp/fsdp axes); this config takes the GSPMD path")
        import inspect
        if "bn_axis_name" not in inspect.signature(spec.build).parameters:
            raise ValueError(
                f"--sync-bn: model {config.model!r} has no BatchNorm to "
                f"synchronize (supported: resnet*/densenet* families)")
        kw["bn_axis_name"] = steps.DATA_AXES
    if config.pipeline_microbatches:
        kw["pipeline_microbatches"] = config.pipeline_microbatches
    if config.pipeline_schedule != "gpipe":
        kw["pipeline_schedule"] = config.pipeline_schedule
    if config.pipeline_virtual_stages != 1:
        kw["pipeline_virtual_stages"] = config.pipeline_virtual_stages
    model = spec.build(**kw)

    # A mesh axis nothing maps onto silently duplicates compute across its
    # groups (devices wasted, no error from XLA) — reject up front, like the
    # flash/seq check above.
    mcfg = getattr(model, "cfg", None)
    stages = getattr(mcfg, "pipeline_stages", 1)
    experts = getattr(mcfg, "num_experts", 0)
    if config.pipeline_microbatches is not None:
        if config.pipeline_microbatches < 1:
            raise ValueError(
                f"pipeline_microbatches={config.pipeline_microbatches} "
                f"must be >= 1")
        if stages <= 1:
            # Same loud-reject rule as the CNN builders for attn/remat:
            # a knob nothing consumes must not silently do nothing.
            raise ValueError(
                f"pipeline_microbatches set but model {config.model!r} is "
                f"not pipelined (pipeline_stages={stages}); use a *_pp "
                f"model")
    if (config.pipeline_schedule != "gpipe"
            or config.pipeline_virtual_stages != 1) and stages <= 1:
        raise ValueError(
            f"pipeline_schedule={config.pipeline_schedule!r} / "
            f"pipeline_virtual_stages={config.pipeline_virtual_stages} set "
            f"but model {config.model!r} is not pipelined "
            f"(pipeline_stages={stages}); use a *_pp model")
    if config.parallel.pipeline > 1 and stages % config.parallel.pipeline:
        raise ValueError(
            f"parallel.pipeline={config.parallel.pipeline} but model "
            f"{config.model!r} has pipeline_stages={stages}; use a pipelined "
            f"model (e.g. bert_base_pp) whose stage count is divisible by "
            f"the mesh axis")
    if config.parallel.expert > 1 and (experts == 0
                                       or experts % config.parallel.expert):
        raise ValueError(
            f"parallel.expert={config.parallel.expert} but model "
            f"{config.model!r} has num_experts={experts}; use an MoE model "
            f"(e.g. bert_base_moe) whose expert count is divisible by the "
            f"mesh axis")

    stage = config.optimizer_sharding
    sharded = stage in ("zero1", "zero2", "zero3")
    # Under any ZeRO stage the optimizer sees 1/N chunks, so its norm-based
    # pieces (global clip, LARS/LAMB trust ratios) must psum over the DP
    # axes — on the explicit shard_map path only. The GSPMD zero2+pipeline
    # composition is one logical program with no manual axes to psum over;
    # XLA inserts any cross-shard reduction the update math needs.
    explicit_sharded = sharded and not uses_gspmd(config, spec.input_kind)
    tx, sched = optim.make_optimizer(
        config.optimizer, config.global_batch_size, total_steps,
        steps_per_epoch(config),
        shard_axes=steps.DATA_AXES if explicit_sharded else None)
    bn_batch = config.per_device_batch // max(config.grad_accum_steps, 1)
    if config.sync_bn:
        # SyncBN pools statistics across the DP shards: the effective
        # statistics batch is the whole (micro)batch, not the shard's.
        bn_batch *= config.parallel.data * config.parallel.fsdp
    if (spec.input_kind == "image" and jax.process_index() == 0
            and (bn_batch == 1
                 or (config.grad_accum_steps > 1 and bn_batch < 32))):
        import warnings

        # warnings.warn (not a raw stderr print): dedupes across repeat
        # builds and lets deliberate small-batch harnesses filter it.
        # bn_batch == 1 is a measured failure mode, not hypothetical:
        # single-sample BN with a 1x1 final feature map normalizes every
        # feature to exactly beta, collapsing logits to uniform (loss pins
        # at ln(num_classes), BN grads go to zero). Per-shard BN is
        # intentional (per-GPU BN under Horovod); the fix is a bigger
        # per-shard batch, not synced statistics.
        detail = ("training can silently stall at uniform logits; increase "
                  "--batch-size, reduce the data-parallel axis, or pool "
                  "statistics across shards with --sync-bn"
                  if bn_batch == 1 else "consider lowering --accum")
        warnings.warn(
            f"BatchNorm statistics will be computed over only {bn_batch} "
            f"example(s) (per_device_batch={config.per_device_batch}, "
            f"grad_accum_steps={config.grad_accum_steps}); {detail}",
            UserWarning, stacklevel=2)
    rng = jax.random.key(config.seed)

    seq_dim = 1 if spec.input_kind == "tokens" else None
    batch_shd = shardlib.batch_sharding(mesh, seq_dim=seq_dim)

    if uses_gspmd(config, spec.input_kind):
        # Shapes-only example for init; synthetic regardless of data mode.
        example = synthetic.make_source(
            config, spec.input_kind, sharding=batch_shd,
            objective=spec.objective).batch(0)
        # Same AOT executable cache as the explicit-DP path below: a warm
        # boot of an identical config (pipelined runs included — the
        # schedule is part of the fingerprint) deserializes the step with
        # zero retraces instead of re-tracing the whole tick loop. Created
        # BEFORE init so the init program rides the same cache — on a
        # re-formed elastic attempt the init compile is pure spawn_s
        # outage (restore overwrites its values), so it loads warm too.
        aot = aotlib.StepExecutableCache.for_config(
            config, mesh.devices.flat, total_steps=total_steps)
        state, shardings = steps.init_sharded_state(
            model, tx, mesh, config, example, rng, spec.input_kind,
            aot=aot)
        train_step = steps.make_gspmd_train_step(
            model, tx, mesh, config, shardings, spec.input_kind,
            spec.objective, aot=aot)
        train_step.aot = aot
    else:
        def variables_fn(rng):
            if spec.input_kind == "tokens":
                return model.init(
                    {"params": rng, "dropout": rng},
                    jnp.zeros((1, config.data.seq_len), jnp.int32),
                    train=False)
            size = config.data.image_size
            return model.init(
                {"params": rng}, jnp.zeros((1, size, size, 3), dtype),
                train=False)

        replicated = shardlib.replicated(mesh)
        layout = converter = params_struct = None
        if sharded:
            dp_size = mesh.shape["data"] * mesh.shape["fsdp"]
            params_struct = jax.eval_shape(variables_fn, rng)["params"]
            layout, _ = zerolib.layout_from_options(
                params_struct, dp_size, options=config.allreduce)
            converter = zerolib.ZeroStateConverter(
                tx, params_struct, layout, mesh, steps.DATA_AXES,
                stage=3 if stage == "zero3" else 1)

        def init_fn(rng):
            variables = variables_fn(rng)
            params = variables["params"]
            # ZeRO: optimizer state is born in the chunked global layout
            # (each leaf padded+raveled to chunk*N); out_shardings below
            # then scatter it 1/N per device — it is never materialized
            # replicated. Under zero3 the params (and EMA) themselves are
            # born in that layout too.
            opt_params = (zerolib.to_chunked(params, layout) if sharded
                          else params)
            if stage == "zero3":
                params = opt_params
            return TrainState.create(
                params=params, opt_state=tx.init(opt_params),
                batch_stats=steps.model_state(variables),
                ema_params=(params if config.optimizer.ema_decay > 0
                            else None),
                loss_scale=steps.init_loss_scale(config))

        if sharded:
            abstract = jax.eval_shape(init_fn, rng)
            out_shd = jax.tree_util.tree_map(lambda _: replicated, abstract)
            out_shd = out_shd.replace(opt_state=converter.opt_shardings())
            if stage == "zero3":
                out_shd = out_shd.replace(
                    params=converter.param_shardings(abstract.params))
                if abstract.ema_params is not None:
                    out_shd = out_shd.replace(
                        ema_params=converter.param_shardings(
                            abstract.ema_params))
        else:
            out_shd = replicated
        state = jax.jit(init_fn, out_shardings=out_shd)(rng)
        # AOT executable cache (perf/aot.py): keyed by the config
        # fingerprint + total_steps (the LR schedule bakes the horizon into
        # the program), so a restart attempt or re-launch of the same config
        # deserializes the step instead of retracing it.
        aot = aotlib.StepExecutableCache.for_config(
            config, mesh.devices.flat, total_steps=total_steps)
        train_step = steps.make_dp_train_step(
            model, tx, mesh, config, spec.input_kind, spec.objective,
            state_like=state, aot=aot, zero_layout=layout,
            params_struct=params_struct)
        train_step.zero_converter = converter
        train_step.aot = aot

    return mesh, model, batch_shd, state, train_step, sched, rng


def _run_ramp(config: TrainConfig, stages, *, total_steps, logger,
              warmup_steps, eval_batches, return_state,
              restore_for_eval) -> dict[str, Any]:
    """Staged global-batch ramp (arXiv 1711.04325 recipe): run each stage
    as its own segment at the stage batch — the per-stage LR follows for
    free from the linear-scaling rule, because ``make_optimizer`` scales
    the base LR by stage_batch / reference_batch when each segment builds.

    Stages chain through the checkpoint dir when one is configured (every
    boundary lands on the checkpoint cadence by construction, so a stage
    transition IS an ordinary resume — elastic re-formation and
    cross-degree resume compose unchanged), or by carrying the final state
    in process when there is none (quick benches). The returned summary is
    the final stage's — steady state at the target batch — plus a
    ``batch_ramp`` block describing the staging."""
    live = [st for st in stages if st.start_step < total_steps]
    if not live:
        live = stages[-1:]
    carried = None
    summary: dict[str, Any] = {}
    stage_meta = []
    for k, st in enumerate(live):
        end = total_steps if st.end_step is None else min(st.end_step,
                                                          total_steps)
        cfg_s = config.replace(global_batch_size=st.batch)
        if k > 0 and config.checkpoint_dir:
            cfg_s = cfg_s.replace(resume=True)
        last = k == len(live) - 1
        want_state = (return_state and last) or (
            not config.checkpoint_dir and not last)
        summary = run(cfg_s, total_steps=end, logger=logger,
                      warmup_steps=warmup_steps, eval_batches=eval_batches,
                      return_state=want_state,
                      restore_for_eval=restore_for_eval,
                      _ramp_stage=True, _carried_state=carried)
        carried = summary.get("state")
        if not (return_state and last):
            summary.pop("state", None)
        stage_meta.append({
            "batch": int(st.batch),
            "start_step": int(st.start_step),
            "end_step": int(end),
            "examples_per_sec": summary.get("examples_per_sec"),
        })
    summary["batch_ramp"] = {"spec": config.batch_ramp,
                             "stages": stage_meta}
    return summary


def run(config: TrainConfig, *, total_steps: int,
        logger: Optional[MetricLogger] = None,
        warmup_steps: int = 0, eval_batches: int = 0,
        return_state: bool = False,
        restore_for_eval: bool = False,
        _ramp_stage: bool = False,
        _carried_state: Optional[TrainState] = None) -> dict[str, Any]:
    """Train for ``total_steps``; returns a summary with throughput.

    ``warmup_steps`` are excluded from timing (compile + first-step cost),
    matching the reference benchmark harness semantics (SURVEY.md §3.4).
    With ``config.checkpoint_dir`` set, saves every
    ``checkpoint_every_steps`` (async) plus a final save, and — when
    ``config.resume`` — restores the newest checkpoint and continues from
    its step, replaying the deterministic data stream from there.
    ``eval_batches > 0`` enables periodic + final held-out eval
    (SURVEY.md §3.5): sharded top-1 for image models, mean per-token loss
    (perplexity) for token models.
    """
    t_origin = time.perf_counter()  # time_to_first_step_s measures from here
    if not _ramp_stage and not restore_for_eval:
        # Stage segments re-enter run() with a per-stage batch size that
        # deliberately differs from the ramp's final batch — only the
        # top-level call parses (and validates) the schedule.
        ramp = optim.parse_batch_ramp(
            getattr(config, "batch_ramp", None),
            final_batch=config.global_batch_size,
            checkpoint_every=(config.checkpoint_every_steps
                              if config.checkpoint_dir else 0))
        if ramp is not None:
            return _run_ramp(config, ramp, total_steps=total_steps,
                             logger=logger, warmup_steps=warmup_steps,
                             eval_batches=eval_batches,
                             return_state=return_state,
                             restore_for_eval=restore_for_eval)
    owns_logger = logger is None
    logger = logger or MetricLogger()
    # A caller-reused logger (in-process restart harnesses) must not turn
    # the wall time spent between runs — teardown, restore, recompile —
    # into this run's first throughput sample.
    logger.reset_throughput()
    # Telemetry is configured BEFORE the first compile so the collective
    # layers' trace-time bucket spans land in the buffer; export runs in the
    # finally below, so a faulting run (crash/SIGTERM/abort) still writes
    # its trace — the runs a post-mortem needs most.
    tele = telemetry.configure(
        trace_dir=config.trace_dir, trace_steps=config.trace_steps,
        max_events=config.trace_max_events,
        process_index=jax.process_index())
    # Flight recorder (observability/flight.py): the crash-surviving half
    # of observability. config.flight_dir overrides the launcher-exported
    # DDL_FLIGHT_DIR; with neither set the disabled singleton makes every
    # record() a no-op. Configured before the first compile so the
    # collective layers' one-shot plan events land in the record.
    flight = flightlib.configure_from_env(
        host=jax.process_index(),
        directory=getattr(config, "flight_dir", None))
    metricslib.configure(run_id=flight.run_id)
    # Persistent compile cache (perf/compile_cache.py): switched on (or
    # off) BEFORE any compile; where it lives is decided from outside.
    cachelib.activate(config.compile_cache)
    spec = model_spec(config.model)
    mesh, model, batch_shd, state, train_step, sched, rng = build(
        config, total_steps)
    # Roofline denominators for every log-cadence record and the summary:
    # analytic FLOPs/example x job peak (per-chip spec x device count) —
    # the %-of-peak axis of observability/perf_report.py. A model without
    # a FLOPs entry or a device that is not a TPU leaves the logger without
    # a roofline; a TPU kind missing from the peak table is an error.
    from distributeddeeplearning_tpu.models import flops as flopslib
    mlm_pred = (resolve_mlm_max_predictions(
        config.data.mlm_max_predictions, config.data.seq_len,
        spec.objective) if spec.input_kind == "tokens" else 0)
    _per_ex = flopslib.train_flops_per_example(
        config.model, seq_len=config.data.seq_len, mlm_positions=mlm_pred)
    _peak = flopslib.peak_flops(
        mesh.devices.flat[0].device_kind,
        resolve_precision(config).compute_dtype)
    logger.set_roofline(_per_ex, _peak * mesh.size if _peak else None)

    ckpt = ckptlib.Checkpointer.create(
        config, converter=getattr(train_step, "zero_converter", None))
    try:
        return _run_inner(
            config, spec, mesh, model, batch_shd, state, train_step, sched,
            rng, ckpt, logger, total_steps=total_steps,
            warmup_steps=warmup_steps, eval_batches=eval_batches,
            return_state=return_state, restore_for_eval=restore_for_eval,
            t_origin=t_origin, carried_state=_carried_state)
    except BaseException as exc:
        # Fsync'd BEFORE teardown: even if the finally below wedges, the
        # flight record already explains how the run ended (SIGKILL skips
        # this too, of course — but then the last fault/step event stands).
        flight.record("abort", error=type(exc).__name__,
                      detail=str(exc)[:300])
        raise
    finally:
        if ckpt is not None:
            ckpt.close()  # releases the async-checkpointing executor
        if owns_logger:
            logger.close()  # guaranteed JSONL/TB handle release
        trace_file = tele.export()
        if trace_file is not None:
            print(f"# telemetry trace written to {trace_file}",
                  file=sys.stderr, flush=True)
        flight.close()


def _run_inner(config, spec, mesh, model, batch_shd, state, train_step, sched,
               rng, ckpt, logger, *, total_steps, warmup_steps, eval_batches,
               return_state, restore_for_eval=False,
               t_origin=None, carried_state=None) -> dict[str, Any]:
    if t_origin is None:
        t_origin = time.perf_counter()
    # Fault plan (robustness/faults.py): config.fault_plan + the per-child
    # DDL_FAULT_PLAN env + the legacy fail_at_step shim, filtered to this
    # restart attempt. Empty plan (the default) => injector is None and the
    # hot loop runs zero fault-injection code.
    fault_plan = faultslib.resolve(config)
    fault_plan.validate(total_steps, checkpoint_dir=config.checkpoint_dir)
    start_step = 0
    if carried_state is not None:
        # In-process batch-ramp chaining (no checkpoint dir): adopt the
        # previous stage's final state — same mesh, model, and state
        # structure; only the batch shape and LR scale changed — and pick
        # the loop position up from its step counter.
        state = carried_state
        start_step = int(jax.device_get(state.step))
    resolved_loader = datalib.resolve_loader(config, spec.input_kind)
    live_degree = meshlib.data_parallel_degree(config.parallel)
    # The explicit-DP step carries its stage as an attribute; the GSPMD
    # zero2∘pipeline composition shards via NamedSharding rules and has no
    # such attribute, so fall back to the configured stage — the stream
    # metadata (and the cross-axis announcement below) must name the stage
    # that actually ran, whichever path built the step.
    live_stage = (getattr(train_step, "zero_stage", None)
                  or config.optimizer_sharding or "none")
    live_pp = int(config.parallel.pipeline)
    prior_meta: dict = {}
    if ckpt is not None:
        # Pin the environment-dependent loader resolution to the checkpoint:
        # a resume that would silently switch pipelines (different shuffle
        # order) fails loudly instead (ADVICE r1 #1).
        # opt_state_layout documents the on-disk optimizer-state format:
        # ALWAYS canonical (parameter-shaped leaves) — zero1 runs gather on
        # save (parallel/zero.py) — which is what makes checkpoints
        # interchangeable across optimizer-sharding modes and DP degrees. A
        # future layout change would clash here loudly instead of silently
        # mis-restoring.
        # global_batch_size is the fixed point of elastic re-formation: the
        # DEGREE may change between attempts (mesh_degree below is
        # informational, rewritten each run), but the global batch must not
        # — gradients are allreduce-means, so a fixed batch keeps the
        # trajectory bitwise across degrees, while a changed batch silently
        # changes the optimization problem. Eval-only consumers are exempt
        # (they feed no optimizer).
        meta = {"loader": resolved_loader, "opt_state_layout": "canonical"}
        if not restore_for_eval:
            # Under a batch ramp the strict key is the ramp's FINAL batch
            # (constant across every stage segment, and equal to a plain
            # unramped config's global_batch_size): a mid-ramp stage resume
            # and an unramped continuation at the target batch both pass,
            # while resuming at a genuinely different problem still fails
            # loudly. The ramp spec itself rides in the informational set.
            meta["global_batch_size"] = int(optim.ramp_final_batch(config))
        # optimizer_sharding / pipeline_degree join mesh_degree as
        # informational (rewritten each run): the canonical layout makes
        # checkpoints interchangeable across ZeRO stages and pipeline
        # degrees, so a cross-axis re-formation is announced, not refused.
        prior_meta = ckpt.verify_or_record_stream_meta(
            meta, update={"mesh_degree": live_degree,
                          "optimizer_sharding": live_stage,
                          "pipeline_degree": live_pp,
                          "batch_ramp": optim.ramp_describe(config)})
    # The membership event of a re-formed elastic attempt (exported by the
    # launcher as DDL_ELASTIC_EVENT): detect_t is CLOCK_MONOTONIC at fault
    # detection, the same clock telemetry.now_s() reads in this process, so
    # the first post-resume step closes the reconfiguration_time_s span.
    # Read BEFORE restore: a re-formed attempt overlaps its warm compile
    # against the restore below.
    elastic_event = health.read_elastic_event()
    if ckpt is not None and config.resume:
        warm_thread = None
        if (elastic_event is not None and not restore_for_eval
                and getattr(train_step, "warm", None) is not None):
            # Re-formation fast path: kick the train-step compile off on a
            # background thread (abstract avals from the pre-restore state
            # template + one throwaway batch at the latest-step hint) while
            # orbax restores — the detect->first-step outage then pays
            # max(restore, compile), not their sum. Failures silently leave
            # the cold path in place, like the evaluator's warm compile.
            hint = ckpt.latest_step()
            if hint is not None and int(hint) < total_steps:
                try:
                    warm_src = datalib.make_source(
                        config, spec.input_kind, batch_shd,
                        start_step=int(hint), objective=spec.objective)
                    warm_batch = warm_src.batch(int(hint))
                    state_struct = jax.tree_util.tree_map(
                        lambda x: jax.ShapeDtypeStruct(
                            x.shape, x.dtype, sharding=x.sharding), state)
                    warm_thread = threading.Thread(
                        target=train_step.warm,
                        args=(state_struct, warm_batch, rng),
                        daemon=True, name="ddl-reform-warm-compile")
                    warm_thread.start()
                except Exception:  # noqa: BLE001 - warm-up is optional
                    warm_thread = None
        # restore_for_eval: params/BN/step only, fresh optimizer state — an
        # eval-only consumer must not have to repeat the training run's
        # optimizer flags to satisfy the full-state structure match.
        restored = (ckpt.restore_latest_for_eval(state) if restore_for_eval
                    else ckpt.restore_latest(state))
        if restored is not None:
            # Warm-restart aliasing safety, for EVERY restore: on CPU,
            # orbax-restored arrays can ALIAS host memory the restore
            # machinery owns (zero-copy device_put). A step that donates
            # them then produces outputs aliasing memory orbax later frees
            # and reuses — the live state (and every checkpoint saved from
            # it) silently turns to garbage a few steps into the resumed
            # run. Observed through the plain jit path too, not just a
            # directly-called AOT executable (perf/aot.py), so the copy is
            # unconditional: one bitwise-identical device copy breaks the
            # alias and the buffers are XLA-owned, like a fresh init's.
            state = ckptlib.device_copy(restored)
            start_step = int(jax.device_get(state.step))
            prior_degree = prior_meta.get("mesh_degree")
            if (prior_degree is not None
                    and int(prior_degree) != live_degree):
                # Elastic cross-degree resume (launch.py --elastic): the
                # checkpoint was written at another DP degree; the
                # converter's canonical layout already restored it bitwise
                # onto THIS mesh. Loud, because a degree change outside
                # elastic mode is operator error worth noticing.
                if jax.process_index() == 0:
                    print(f"# elastic: resumed a degree-{prior_degree} "
                          f"checkpoint onto a degree-{live_degree} mesh "
                          f"(canonical layout; global batch unchanged)",
                          file=sys.stderr, flush=True)
                telemetry.get().instant(
                    "elastic:cross_degree_resume", step=start_step,
                    degree_before=int(prior_degree),
                    degree_after=live_degree)
            # Cross-AXIS resume: the previous attempt ran a different ZeRO
            # stage and/or pipeline degree. The canonical (parameter-shaped)
            # on-disk layout restored bitwise onto this plan; announce so an
            # operator reading the log sees the axes crossed, not just the
            # degree.
            prior_stage = prior_meta.get("optimizer_sharding")
            prior_pp = prior_meta.get("pipeline_degree")
            axis_changes = []
            if prior_stage is not None and str(prior_stage) != live_stage:
                axis_changes.append(
                    f"optimizer sharding {prior_stage} -> {live_stage}")
            if prior_pp is not None and int(prior_pp) != live_pp:
                axis_changes.append(f"pipeline {int(prior_pp)} -> {live_pp}")
            if axis_changes:
                if jax.process_index() == 0:
                    print("# elastic: cross-axis resume — "
                          + ", ".join(axis_changes)
                          + " (canonical layout; trajectory preserved "
                            "through the converter)",
                          file=sys.stderr, flush=True)
                telemetry.get().instant(
                    "elastic:cross_axis_resume", step=start_step,
                    optimizer_sharding=live_stage, pipeline_degree=live_pp)
        if warm_thread is not None:
            # Join before the first dispatch: either the executable is
            # ready (the dispatch below hits the warm cache) or the warm
            # compile failed and the dispatch compiles cold — never both.
            warm_thread.join()
    flight = flightlib.get()
    flight.record("run_start", step=start_step, total_steps=int(total_steps),
                  degree=live_degree, model=config.model,
                  resumed=bool(start_step))
    if start_step:
        flight.record("restore", step=start_step)
    # Source is created here — after restore — so a real (streaming) pipeline
    # starts at the resume step rather than replaying from zero. A run with
    # no steps left skips pipeline construction entirely.
    source = (datalib.make_source(
        config, spec.input_kind, batch_shd, start_step=start_step,
        objective=spec.objective)
        if start_step < total_steps else None)
    # A resumed run may have fewer than warmup_steps left to execute (or
    # none at all, when the checkpoint already passed total_steps).
    warmup_steps = min(warmup_steps, max(total_steps - start_step - 1, 0))
    end_step = max(total_steps, start_step)

    if jax.process_index() == 0:
        # stderr so harness consumers (bench.py) keep a clean stdout
        ar = ("" if uses_gspmd(config, spec.input_kind)
              else f" | allreduce: {config.allreduce.describe()}")
        zl = getattr(train_step, "zero_layout", None)
        if zl is not None:
            _stage = getattr(train_step, "zero_stage", None) or "zero1"
            _ov = "+overlap" if getattr(train_step, "overlap", False) else ""
            ar += f" | opt-sharding: {_stage}{_ov} ({zl.describe()})"
        if config.precision is not None:
            ar += f" | precision: {resolve_precision(config).describe()}"
        if getattr(config, "batch_ramp", None):
            ar += f" | batch-ramp: {config.batch_ramp}"
        print(f"# mesh: {meshlib.local_mesh_description(mesh)} | "
              f"model={config.model} global_batch={config.global_batch_size} "
              f"dtype={config.dtype} loader={resolved_loader}" + ar
              + (f" | resumed@{start_step}" if start_step else ""),
              file=sys.stderr, flush=True)

    # Periodic in-training eval (SURVEY.md §3.5: "train N epochs → periodic
    # eval → top-1"). eval_batches > 0 enables it; cadence is
    # config.eval_every_epochs converted to steps.
    evaluator = None
    eval_every_steps = 0
    evals: list[tuple[int, float]] = []
    # Under zero3 the live params are chunked; evaluation needs the full
    # model, so eval consumers go through the converter's cached gather
    # (identity below stage 3 / without sharding).
    _zconv = getattr(train_step, "zero_converter", None)

    def _eval_state(st):
        return _zconv.full_params_state(st) if _zconv is not None else st

    if eval_batches > 0:
        if spec.input_kind == "image":
            evaluator = _Evaluator(config, mesh, model, batch_shd,
                                   eval_batches)
        else:
            evaluator = _TokenEvaluator(config, spec, mesh, model, batch_shd,
                                        eval_batches, state)
        if config.eval_every_epochs > 0:
            spe = steps_per_epoch(config)
            if spe is not None:
                eval_every_steps = max(int(config.eval_every_epochs * spe), 1)
        if start_step < total_steps:
            # Overlap: warm-compile the eval step on a background thread
            # while the first training steps run, so the first
            # epoch-boundary eval doesn't stall the loop on a cold compile.
            evaluator.warm_compile_async(
                _eval_state(state), aot=getattr(train_step, "aot", None))

    # Fused multi-step blocks (config.steps_per_loop > 1): only when batches
    # are generated on-device (synthetic sources expose gen_fn) — a streaming
    # host pipeline needs a dispatch per step anyway. Blocks are split at
    # every step where host-side action fires (logging, checkpoint, eval,
    # warmup timer, profiling span edges, fault injection), so cadence
    # semantics are identical to the per-step path.
    fused_runner = None
    if config.steps_per_loop > 1 and source is not None:
        fused_runner = steps.make_fused_train_loop(
            train_step, source, batch_shd, mesh)
        if fused_runner is None and jax.process_index() == 0:
            print(f"# warning: steps_per_loop={config.steps_per_loop} ignored "
                  f"— loader {resolved_loader!r} streams from the host, so "
                  f"each step needs its own dispatch (fusion requires an "
                  f"on-device synthetic source)", file=sys.stderr, flush=True)

    def _next_boundary(pos: int) -> int:
        """Smallest action step (in completed-steps space) > pos."""
        cands = [total_steps]
        cadences = [config.log_every]
        if eval_every_steps:
            cadences.append(eval_every_steps)
        if ckpt is not None:
            cadences.append(config.checkpoint_every_steps)
        for c in cadences:
            if c > 0:
                cands.append((pos // c + 1) * c)
        points = [start_step + warmup_steps, *fault_plan.boundary_steps()]
        if config.profile_steps is not None:
            points.extend(config.profile_steps)
        if config.trace_steps is not None:
            # Fused blocks split at the telemetry window's edges, so its
            # step-tagged spans cover exactly the requested steps.
            points.extend(config.trace_steps)
        cands.extend(a for a in points if a is not None and a > pos)
        return min(c for c in cands if c > pos)

    # Preemption-aware checkpointing (SURVEY.md §5.3/5.4 extension): Cloud
    # TPU preemption delivers SIGTERM with a grace window, and the in-repo
    # launcher's fail-whole path does the same (_terminate_all). Instead of
    # losing everything since the last cadence save, note the signal and
    # save synchronously at the next step boundary, then exit nonzero so a
    # restart wrapper resumes from that exact step. Orbax saves are
    # collective, so this completes when every process got the signal
    # (whole-job preemption — the normal case); a partially-signaled job
    # falls back to the launcher's SIGKILL escalation, no worse than before.
    preempted: dict[str, Any] = {"signum": None}
    prev_sigterm = None
    install_handler = (ckpt is not None and threading.current_thread()
                       is threading.main_thread())
    if install_handler:
        def _on_sigterm(signum, frame):
            preempted["signum"] = signum
        prev_sigterm = signal.signal(signal.SIGTERM, _on_sigterm)

    injector = faultslib.make_injector(fault_plan, ckpt,
                                       config.checkpoint_dir)
    bad_tracker = _BadStepTracker(config.bad_step_limit)
    # Online anomaly detection (observability/anomaly.py) over the chief's
    # log-cadence records: host-side medians only, so the cost is noise.
    # Flags become flight-recorder events + trace instants, and non-finite
    # signals feed bad_tracker so a diverged run still aborts when the
    # compiled guard is off.
    detector = (anomalylib.AnomalyDetector(
        straggler_ratio=(config.straggler_threshold
                         if config.straggler_threshold > 0 else 1.5))
        if getattr(config, "anomaly_detection", True)
        and jax.process_index() == 0 else None)
    mreg = metricslib.get()
    metrics = {}
    timed_examples = 0
    profile = _Profiler(config)
    # Phase telemetry (observability/telemetry.py; configured in run()):
    # host-side monotonic timestamps only — no device fetches on non-log
    # steps, and the disabled singleton makes record_span a single attribute
    # check. The heartbeat writer (observability/health.py) exists iff the
    # launcher exported DDL_HEARTBEAT_DIR; the straggler monitor
    # (observability/straggler.py) iff the job is multi-process.
    tele = telemetry.get()
    heartbeat = health.HeartbeatWriter.from_env()
    straggler = stragglib.make_monitor(config)
    phase_clock = tele.enabled or straggler is not None
    data_wait_acc = 0.0             # seconds in source.batch since last log
    data_wait_total = 0.0           # seconds in source.batch, whole run
    t_last_log = telemetry.now_s()  # log-interval origin for straggler math
    steps_at_last_log = start_step
    if heartbeat is not None:
        heartbeat.beat(start_step)  # arm the watchdog before compile
    # warmup_steps == 0 means "time everything" (incl. compile).
    t_timed = time.perf_counter() if warmup_steps == 0 else None
    # Cold-start measurement (docs/compile_cache.md): the first dispatch's
    # host-blocking wall time is the trace+compile (or AOT load) cost;
    # time_to_first_step_s is run() entry -> first step's results fetched.
    compile_time_s: Optional[float] = None
    time_to_first_step_s: Optional[float] = None
    compile_pending: Optional[float] = None
    overlap_frac: Optional[float] = None
    pipeline_bubble: Optional[float] = None
    reconfig_time_s: Optional[float] = None
    reconfig_phases: Optional[dict] = None
    try:
        i = start_step  # steps completed so far
        while i < total_steps:
            if preempted["signum"] is not None:
                tele.instant("preempted", step=i,
                             signum=preempted["signum"])
                flight.record("preempted", step=int(i),
                              signum=preempted["signum"])
                ckpt.maybe_save(i, state, force=True)
                ckpt.wait()
                raise SystemExit(
                    f"preempted (signal {preempted['signum']}): "
                    f"checkpoint saved at step {i}")
            if heartbeat is not None:
                # Rendezvous membership (launch.py --elastic): the launcher
                # raised the reform barrier — a host joined, announced a
                # drain, or was lost. Exit EXIT_DRAIN voluntarily at this
                # step boundary so the job re-forms WITHOUT any survivor
                # being torn down. A barrier at our own epoch (the one that
                # formed us) reads as None.
                barrier = health.poll_drain()
                if barrier is not None:
                    saved = False
                    if ckpt is not None and barrier.get("save", True):
                        # Every member is alive (the launcher only marks
                        # save-capable barriers when the membership is
                        # whole), so the collective save completes and the
                        # re-formed attempt resumes from THIS step instead
                        # of the last cadence save.
                        ckpt.maybe_save(i, state, force=True)
                        ckpt.wait()
                        saved = True
                    tele.instant("elastic:drain", step=int(i),
                                 epoch=barrier.get("epoch"))
                    flight.record("drain", step=int(i),
                                  epoch=barrier.get("epoch"),
                                  trigger=barrier.get("trigger"),
                                  saved=saved)
                    if jax.process_index() == 0:
                        print(f"# elastic: reform barrier (epoch "
                              f"{barrier.get('epoch')}, trigger "
                              f"{barrier.get('trigger')}) — draining at "
                              f"step {i}"
                              + (" after a collective save" if saved else
                                 " without saving (a member is already "
                                 "gone)"),
                              file=sys.stderr, flush=True)
                    raise SystemExit(health.EXIT_DRAIN)
            n = (min(config.steps_per_loop, _next_boundary(i) - i)
                 if fused_runner is not None else 1)
            profile.before_step(i)
            t_step0 = (time.perf_counter() if compile_time_s is None
                       else None)
            if n == 1:
                # The data-wait clock runs UNCONDITIONALLY (two monotonic
                # reads per step — noise): data_wait_frac must be present
                # on every log record even when ~0, so the anomaly
                # detector's loader-stall dominance test and the input-
                # pipeline headroom claim read the same always-on signal.
                t0 = telemetry.now_s()
                batch = source.batch(i)
                t1 = telemetry.now_s()
                data_wait_acc += t1 - t0
                if phase_clock:
                    tele.record_span("data_wait", t0, t1, step=i)
                    state, metrics = train_step(state, batch, rng)
                    tele.record_span("dispatch", t1, telemetry.now_s(),
                                     step=i)
                else:
                    state, metrics = train_step(state, batch, rng)
            else:
                if phase_clock:
                    t1 = telemetry.now_s()
                    state, metrics = fused_runner(state, rng, i, n)
                    tele.record_span("dispatch", t1, telemetry.now_s(),
                                     step=i, fused_steps=n)
                else:
                    state, metrics = fused_runner(state, rng, i, n)
            i += n
            if t_step0 is not None:
                # First step of this run. The dispatch above blocked the
                # host for the trace+compile (or AOT load); the fetch below
                # is a true execution barrier, so the pair gives cold-start
                # latency. One extra sync on step one only — numerics and
                # steady-state timing are untouched.
                compile_time_s = time.perf_counter() - t_step0
                t_fetch0 = time.perf_counter()
                jax.device_get(metrics)
                first_step_exec_s = time.perf_counter() - t_fetch0
                time_to_first_step_s = time.perf_counter() - t_origin
                compile_pending = compile_time_s
                tele.gauge("compile_time_s", round(compile_time_s, 3),
                           step=int(i))
                tele.gauge("time_to_first_step_s",
                           round(time_to_first_step_s, 3), step=int(i))
                if elastic_event is not None and isinstance(
                        elastic_event.get("detect_t"), (int, float)):
                    # Reconfiguration span: launcher-side fault detection ->
                    # this first post-resume step, both ends on the shared
                    # local CLOCK_MONOTONIC. Covers teardown, relaunch,
                    # restore, and recompile — the operator-visible outage.
                    detect_t = float(elastic_event["detect_t"])
                    reconfig_time_s = telemetry.now_s() - detect_t
                    tele.gauge("reconfiguration_time_s",
                               round(reconfig_time_s, 3), step=int(i))
                    # Phase breakdown of the outage (all on the shared
                    # CLOCK_MONOTONIC): detect -> last member drained
                    # (launcher clock), restore (orbax wall time), compile
                    # (first dispatch host-block — near zero when the warm
                    # overlap landed), first-step execution; spawn_s is the
                    # remainder (relaunch + imports + device init). With
                    # the restore/compile overlap the parts can overlap in
                    # wall time, so they need not sum to total_s.
                    drain_done = elastic_event.get("drain_done_t")
                    drain_s = (max(0.0, float(drain_done) - detect_t)
                               if isinstance(drain_done, (int, float))
                               else None)
                    restore_s = (ckpt.last_restore_s
                                 if ckpt is not None else None)
                    known = sum(v for v in (drain_s, restore_s,
                                            compile_time_s,
                                            first_step_exec_s)
                                if v is not None)
                    reconfig_phases = {
                        "total_s": round(reconfig_time_s, 3),
                        "drain_s": (round(drain_s, 3)
                                    if drain_s is not None else None),
                        "restore_s": (round(restore_s, 3)
                                      if restore_s is not None else None),
                        "compile_s": round(compile_time_s, 3),
                        "first_step_s": round(first_step_exec_s, 3),
                        "spawn_s": round(
                            max(0.0, reconfig_time_s - known), 3),
                    }
                    for k, v in reconfig_phases.items():
                        if k != "total_s" and v is not None:
                            tele.gauge(f"reconfiguration_{k}", v,
                                       step=int(i))
                    # The outage span, closed: the launcher recorded the
                    # re-formation *plan*; this records it *landed*.
                    flight.record(
                        "reconfiguration", step=int(i),
                        trigger=elastic_event.get("trigger"),
                        degree_before=elastic_event.get("degree_before"),
                        degree_after=elastic_event.get("degree_after"),
                        epoch=elastic_event.get("epoch"),
                        reconfiguration_time_s=round(reconfig_time_s, 3),
                        phases=reconfig_phases,
                        resume_step=start_step)
                if tele.enabled and getattr(train_step, "zero_stage", None):
                    # Backward/collective overlap gauge: fraction of the
                    # step's reduce-scatter spans issued INSIDE backward
                    # (the custom_vjp bucket boundaries mark theirs
                    # overlapped=True). Spans are trace-time, so an AOT
                    # cache hit (zero retraces) leaves no spans and the
                    # gauge honestly reads 0 — docs/zero_sharding.md.
                    overlap_frac = telemetry.overlap_fraction(
                        tele.snapshot())
                    tele.gauge("backward_collective_overlap",
                               round(overlap_frac, 4), step=int(i))
                if tele.enabled and config.parallel.pipeline > 1:
                    # Measured pipeline bubble: idle/total stage-ticks from
                    # the per-tick `pipeline_tick` instants the schedule
                    # emits at trace time. Like the overlap gauge these are
                    # trace-time events, so an AOT cache hit leaves none —
                    # the helper returns None then (not a fake 0.0) and the
                    # gauge is simply skipped. docs/pipeline.md has the
                    # analytic curve this is compared against in bench.
                    pipeline_bubble = telemetry.pipeline_bubble_fraction(
                        tele.snapshot())
                    if pipeline_bubble is not None:
                        tele.gauge("pipeline_bubble_fraction",
                                   round(pipeline_bubble, 4), step=int(i))
            profile.after_step(i - 1, metrics)
            bad_tracker.push(metrics)
            done = i - start_step
            if done == warmup_steps:
                # Dispatch is asynchronous: wait for the last warmup step's
                # outputs so the timing window opens with the device idle.
                jax.block_until_ready(metrics)
                t_timed = time.perf_counter()
            if i % config.log_every == 0 or i == total_steps:
                extra = {}
                t_log = telemetry.now_s()
                interval_steps = max(i - steps_at_last_log, 1)
                if straggler is not None:
                    # One small allgather per log step, on EVERY process at
                    # the same step — a collective, like the eval syncs.
                    # compile_s rides along exactly once (the first log
                    # after the program was built — the same step on every
                    # host), surfacing compile stragglers.
                    extra = straggler.collect(
                        int(i), (t_log - t_last_log) / interval_steps,
                        data_wait_acc / interval_steps,
                        compile_s=compile_pending)
                if compile_pending is not None:
                    extra["compile_time_s"] = round(compile_pending, 3)
                    extra["time_to_first_step_s"] = round(
                        time_to_first_step_s, 3)
                    compile_pending = None
                # Always-present loader-stall share of the interval (0.0
                # when the pipeline kept up — fused on-device blocks fetch
                # nothing and honestly read 0). The logger mirrors every
                # numeric field into telemetry gauges, so this lands in
                # the JSONL record, the gauge stream, and the registry.
                extra["data_wait_frac"] = round(
                    data_wait_acc / (t_log - t_last_log), 6) \
                    if t_log - t_last_log > 1e-9 else 0.0
                # logger floats every metric (a true fetch barrier); no
                # separate block needed. Its span is therefore the device
                # time of the steps still in flight — log-cadence only, so
                # telemetry adds no fetch of its own.
                with tele.span("fetch_barrier", step=int(i)):
                    # now_s=t_log: the logger's step-time window uses the
                    # SAME clock reading the straggler skew math above
                    # used — one timestamp per log step, not two
                    # (utils/logging.py mirrors the record into telemetry
                    # gauges, closing the duplicated emit path).
                    log_rec = logger.log(
                        int(i), metrics,
                        examples_per_step=config.global_batch_size,
                        now_s=t_log,
                        lr=float(sched(i - 1)), **extra)
                flight.record("step", step=int(i),
                              loss=log_rec.get("loss"),
                              examples_per_sec=log_rec.get(
                                  "examples_per_sec"))
                if jax.process_index() == 0:
                    _observe_and_detect(log_rec, int(i), mreg, detector,
                                        flight, tele, bad_tracker,
                                        overlap_frac=overlap_frac,
                                        pipeline_bubble=pipeline_bubble,
                                        data_wait_s=data_wait_acc,
                                        interval_s=t_log - t_last_log)
                if heartbeat is not None:
                    heartbeat.beat(int(i))
                if tele.enabled:
                    _record_hbm_gauges(tele, int(i))
                t_last_log, steps_at_last_log = telemetry.now_s(), i
                data_wait_total += data_wait_acc
                data_wait_acc = 0.0
            if done > warmup_steps:
                # Blocks never straddle the warmup edge (it is a boundary),
                # so the whole block counts toward the timed window.
                timed_examples += config.global_batch_size * n
            if ckpt is not None:
                t_ck = telemetry.now_s() if tele.enabled else 0.0
                if ckpt.maybe_save(i, state):
                    # Recorded only when a save actually launched (async:
                    # the span is the launch + state-gather cost, not the
                    # full write).
                    if tele.enabled:
                        tele.record_span("checkpoint_save", t_ck,
                                         telemetry.now_s(), step=int(i))
                    flight.record("save", step=int(i))
            if (eval_every_steps and i % eval_every_steps == 0
                    and i < total_steps):
                t_eval = time.perf_counter()
                with tele.span("eval", step=int(i)):
                    val = evaluator(_eval_state(state))
                evals.append((i, val))
                logger.log(int(i), {evaluator.metric_name: val})
                if t_timed is not None:
                    # Keep throughput numbers about training: shift the
                    # timing origin past the eval pause.
                    t_timed += time.perf_counter() - t_eval
            if injector is not None:
                # Scheduled fault injection (SURVEY.md §5.3, robustness/
                # faults.py): crash/sigterm/sigkill/corrupt after completing
                # step i — AFTER maybe_save, so a cadence save at i is
                # already (async-)launched when the fault lands, exactly the
                # race a real preemption exposes.
                injector(i)
        # End-of-run sync: steps run in order on the device, so the final
        # step's metrics and step counter being ready means the whole
        # dispatch queue has drained — no need to walk the state tree.
        jax.block_until_ready((metrics, state.step))
        bad_tracker.drain()
    finally:
        # prev may be None when the prior handler was installed from C (not
        # visible to Python) — restoring None would raise inside finally and
        # mask the propagating exception; SIG_DFL is the honest fallback.
        if install_handler:
            signal.signal(signal.SIGTERM,
                          prev_sigterm if prev_sigterm is not None
                          else signal.SIG_DFL)
        profile.finish()
    if ckpt is not None:
        if total_steps > start_step:
            if ckpt.maybe_save(total_steps, state, force=True):
                flight.record("save", step=int(total_steps), final=True)
        ckpt.wait()

    summary: dict[str, Any] = {
        "final_step": end_step,
        "start_step": start_step,
        "final_metrics": {k: float(v) for k, v in metrics.items()},
        "bad_steps": bad_tracker.total,
    }
    if compile_time_s is not None:
        summary["compile_time_s"] = round(compile_time_s, 3)
        summary["time_to_first_step_s"] = round(time_to_first_step_s, 3)
    if elastic_event is not None:
        summary["elastic_event"] = {
            k: elastic_event.get(k)
            for k in ("trigger", "degree_before", "degree_after", "epoch")}
        if reconfig_time_s is not None:
            summary["reconfiguration_time_s"] = round(reconfig_time_s, 3)
        if reconfig_phases is not None:
            summary["reconfiguration_phases"] = reconfig_phases
        _write_elastic_sidecar(elastic_event, reconfig_time_s, start_step,
                               phases=reconfig_phases)
    if getattr(train_step, "zero_stage", None) is not None:
        summary["optimizer_sharding"] = {
            "stage": train_step.zero_stage,
            "overlap": bool(getattr(train_step, "overlap", False)),
            "overlap_fraction": overlap_frac,
        }
    if config.parallel.pipeline > 1:
        summary["pipeline"] = {
            "schedule": config.pipeline_schedule,
            "virtual_stages": config.pipeline_virtual_stages,
            "bubble_fraction": pipeline_bubble,
        }
    _write_sharding_sidecar(config, train_step, overlap_frac,
                            pipeline_bubble)
    aot = getattr(train_step, "aot", None)
    if aot is not None and aot.enabled:
        summary["compile_cache"] = aot.stats()
        aot.flush_stats()  # counters land next to the cache for doctor.py
    hbm = _device_memory_stats(state, train_step)
    if hbm:
        summary["memory"] = hbm
        if jax.process_index() == 0:
            for k in ("resident_bytes_per_device", "peak_bytes_in_use"):
                if k in hbm:
                    mreg.observe(k, hbm[k], step=end_step)
            parts = []
            if "peak_bytes_in_use" in hbm:
                parts.append(
                    f"peak_hbm={hbm['peak_bytes_in_use'] / 2**20:.1f}MiB")
            for k in ("params_bytes_per_device",
                      "grads_bytes_per_device",
                      "opt_state_bytes_per_device",
                      "ema_params_bytes_per_device",
                      "resident_bytes_per_device"):
                if k in hbm:
                    parts.append(f"{k.split('_bytes')[0]}/dev="
                                 f"{hbm[k] / 2**20:.2f}MiB")
            if parts:
                print("# memory: " + " ".join(parts),
                      file=sys.stderr, flush=True)
    # Input-pipeline headroom (docs/perf_measurement.md): whole-run seconds
    # spent blocked in source.batch, and — when a timed window exists — the
    # share of that window they represent. ~0 means the loader kept ahead
    # of the device at this batch size; the large-batch claim ("still ~0
    # at 2x the batch") reads THIS field off the stamped record.
    data_wait_total += data_wait_acc
    summary["input_pipeline"] = {
        "loader": resolved_loader,
        "prefetch_depth": int(datalib.effective_prefetch_depth(config)),
        "data_wait_s": round(data_wait_total, 4),
    }
    if t_timed is not None and timed_examples:
        elapsed = time.perf_counter() - t_timed
        summary["examples_per_sec"] = timed_examples / elapsed
        summary["examples_per_sec_per_chip"] = (
            summary["examples_per_sec"] / jax.device_count())
        summary["steps_per_sec"] = (
            total_steps - start_step - warmup_steps) / elapsed
        if elapsed > 1e-9:
            # Approximate on purpose: the wait accumulator spans the whole
            # run while the clock window excludes warmup — headroom is a
            # capacity signal, not a benchmark metric.
            summary["input_pipeline"]["data_wait_frac"] = round(
                min(data_wait_total / elapsed, 1.0), 6)
    # Run summaries emit into the perf_report schema: this summary was
    # measured by THIS process on the backend below — provenance fresh —
    # and, on a TPU, carries the roofline %-of-peak (absent off TPU and
    # for a model with no FLOPs entry; never from an assumed peak).
    from distributeddeeplearning_tpu.observability import perf_report
    roof = perf_report.roofline(
        summary.get("examples_per_sec_per_chip"), config.model,
        seq_len=config.data.seq_len,
        mlm_positions=(resolve_mlm_max_predictions(
            config.data.mlm_max_predictions, config.data.seq_len,
            spec.objective) if spec.input_kind == "tokens" else 0),
        device_kind=mesh.devices.flat[0].device_kind,
        compute_dtype=resolve_precision(config).compute_dtype)
    if "pct_of_peak" in roof:
        summary["pct_of_peak"] = roof["pct_of_peak"]
    perf_report.annotate(summary, provenance="fresh",
                         config=config, total_steps=total_steps)
    if evaluator is not None:
        final_val = evaluator(_eval_state(state))
        evals.append((end_step, final_val))
        summary[evaluator.metric_name] = final_val
        best = evaluator.best(t for _, t in evals)
        summary["best_" + evaluator.metric_name.removeprefix("eval_")] = best
        summary["evals"] = evals
        if evaluator.metric_name == "eval_loss":
            import math

            summary["eval_ppl"] = math.exp(min(final_val, 30.0))
    if return_state:
        summary["state"] = state
    flight.record("run_end", step=end_step, bad_steps=bad_tracker.total)
    if flight.enabled and jax.process_index() == 0:
        # Final metrics export next to the flight record — the aggregate
        # snapshot a post-mortem (or a textfile scraper) picks up.
        mreg.write_prometheus(os.path.join(flight.directory, "metrics.prom"))
        mreg.write_snapshot(
            os.path.join(flight.directory, "metrics_snapshot.json"))
    return summary


class _BadStepTracker:
    """Host-side circuit breaker over the compiled step's ``bad_step`` flag.

    The guard in train/steps.py skips non-finite updates on-device; this
    tracker counts the skips and aborts the run after ``limit`` CONSECUTIVE
    skips (a run whose every step is bad is diverged, not unlucky). Flags
    are fetched LAGGED — a flag is only ``float()``-ed once two newer steps
    have been dispatched, by which time its program has executed — so the
    breaker never synchronizes the async dispatch pipeline; the remainder
    drains at end of run. Fused multi-step blocks report their last step's
    flag only, so under ``steps_per_loop`` the count is per-block (blocks
    split at injected-fault boundaries, keeping chaos tests exact).
    """

    _LAG = 2

    def __init__(self, limit: int):
        self.limit = max(int(limit), 1)
        self.total = 0
        self._consecutive = 0
        self._window: list = []

    def push(self, metrics) -> None:
        flag = metrics.get("bad_step")
        if flag is None:
            return
        self._window.append(flag)
        if len(self._window) > self._LAG:
            self._check(self._window.pop(0))

    def drain(self) -> None:
        while self._window:
            self._check(self._window.pop(0))

    def note_anomaly(self) -> None:
        """Anomaly-detector feed (observability/anomaly.py): a non-finite
        loss/grad signal on the log cadence counts like a bad-step skip,
        so a run pinned at NaN aborts through the SAME breaker even when
        the compiled guard was never built into the step."""
        self._bump()

    def _check(self, flag) -> None:
        if float(jax.device_get(flag)) > 0:
            self._bump()
        else:
            self._consecutive = 0

    def _bump(self) -> None:
        self.total += 1
        self._consecutive += 1
        if self._consecutive >= self.limit:
            raise RuntimeError(
                f"aborting: {self._consecutive} consecutive non-finite "
                f"update steps (bad_step_limit={self.limit}) — the run "
                f"is diverging, not hitting stray bad batches; lower "
                f"the learning rate or inspect the data shards. "
                f"{self.total} update(s) were skipped in total.")


def _observe_and_detect(log_rec, step, mreg, detector, flight, tele,
                        bad_tracker, *, overlap_frac, pipeline_bubble=None,
                        data_wait_s, interval_s) -> None:
    """Chief-side log-cadence fan-out: feed the metrics registry and the
    anomaly detector from the record ``MetricLogger.log`` just built.

    The straggler monitor's per-host fields ride inside ``log_rec`` (they
    were passed to ``log`` as extras), so host skew needs no second
    allgather here. The registry export refreshes every log step when a
    flight dir exists — cheap (two small atomic writes) and it means a
    killed run leaves a current snapshot, not just a final one.
    """
    mreg.observe_many(log_rec, step=step)
    if overlap_frac is not None:
        mreg.observe("backward_collective_overlap", overlap_frac, step=step)
    if pipeline_bubble is not None:
        mreg.observe("pipeline_bubble_fraction", pipeline_bubble, step=step)
    skew = None
    if log_rec.get("host_step_time_mean"):
        skew = (log_rec.get("host_step_time_max", 0.0)
                / log_rec["host_step_time_mean"])
        mreg.observe("host_step_time_skew", skew, step=step)
    if detector is not None:
        wait_frac = (data_wait_s / interval_s) if interval_s > 1e-9 else None
        anomalies = detector.update(
            step, loss=log_rec.get("loss"),
            grad_norm=log_rec.get("grad_norm"),
            examples_per_sec=log_rec.get("examples_per_sec"),
            data_wait_frac=wait_frac, straggler_ratio=skew,
            bad_step=log_rec.get("bad_step"))
        anomalylib.report(anomalies, flight_rec=flight, tele=tele,
                          bad_tracker=bad_tracker)
    if flight.enabled:
        mreg.write_prometheus(os.path.join(flight.directory, "metrics.prom"))
        mreg.write_snapshot(
            os.path.join(flight.directory, "metrics_snapshot.json"))


def _record_hbm_gauges(tele, step: int) -> None:
    """Periodic HBM telemetry (log cadence, telemetry on): allocator stats
    straight from ``memory_stats()`` — host-side bookkeeping, no device
    fetch. Backends without allocator stats (CPU) record nothing."""
    try:
        for d, dev in enumerate(jax.local_devices()):
            stats = dev.memory_stats() or {}
            for key in ("bytes_in_use", "peak_bytes_in_use"):
                if key in stats:
                    tele.gauge(f"hbm_{key}/d{d}", int(stats[key]), step=step)
    except Exception:
        pass


def _sharding_sidecar_path() -> str:
    # Indirection kept monkeypatchable (tests redirect it off-repo); the
    # write itself goes through the shared helper (observability/sidecars).
    return sidecars.path_for("last_run_sharding")


def _write_sharding_sidecar(config, train_step, overlap_frac,
                            pipeline_bubble=None) -> None:
    """Record the run's active sharding stage + overlap status where
    tools/doctor.py looks (best-effort, like the compile-cache stats)."""
    if jax.process_index() != 0:
        return
    rec = {
        "optimizer_sharding": config.optimizer_sharding,
        "overlap_collectives": bool(
            getattr(config, "overlap_collectives", True)),
        "overlap": bool(getattr(train_step, "overlap", False)),
        "overlap_fraction": overlap_frac,
        "dp": config.parallel.data * config.parallel.fsdp,
        "model": config.model,
        # Active precision policy + ramp, for tools/doctor.py check_precision
        # — which policy actually ran, not which one the flags implied.
        "precision": resolve_precision(config).describe(),
        "precision_explicit": config.precision is not None,
        "batch_ramp": optim.ramp_describe(config),
    }
    if config.parallel.pipeline > 1:
        # Pipeline block for tools/doctor.py check_pipeline: what schedule
        # the run used and the bubble it measured (None on AOT warm boots
        # where no trace-time tick instants existed to measure from).
        rec["pipeline"] = {
            "stages": config.parallel.pipeline,
            "schedule": config.pipeline_schedule,
            "virtual_stages": config.pipeline_virtual_stages,
            "bubble_fraction": pipeline_bubble,
        }
    sidecars.write(_sharding_sidecar_path(), rec)


def _elastic_sidecar_path() -> str:
    return sidecars.path_for("last_elastic_event")


def _write_elastic_sidecar(event, reconfig_time_s, resume_step,
                           phases=None) -> None:
    """Record the re-formation this attempt resumed under where
    tools/doctor.py looks (best-effort, like the sharding sidecar)."""
    if jax.process_index() != 0:
        return
    sidecars.write(_elastic_sidecar_path(), {
        "trigger": event.get("trigger"),
        "degree_before": event.get("degree_before"),
        "degree_after": event.get("degree_after"),
        "epoch": event.get("epoch"),
        "reconfiguration_time_s": (round(reconfig_time_s, 3)
                                   if reconfig_time_s is not None
                                   else None),
        "phases": phases,
        "resume_step": int(resume_step),
    })


def _device_memory_stats(state=None, train_step=None) -> Optional[dict]:
    """Peak/current HBM on local device 0 (where the backend reports it;
    CPU doesn't) plus — given the final ``state`` — the per-device resident
    bytes of params / optimizer state / EMA, computed from the arrays'
    actual shard placement, and — given the ``train_step`` — the MODELED
    per-device gradient bytes (zero.modeled_grad_bytes: gradients are
    transient, so residency is a schedule property, not a measurement).
    ``resident_bytes_per_device`` sums the components into the per-device
    memory-ladder number the ZeRO acceptance test asserts decreases
    replicated→zero1→zero2→zero3. The state breakdown works on EVERY
    backend, so the win is measurable even on the CPU/fake-device path
    where allocator peaks are unavailable. The observability counterpart
    of nvidia-smi in the reference's stack."""
    out: dict = {}
    try:
        stats = jax.local_devices()[0].memory_stats() or {}
    except Exception:
        stats = {}
    for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
        if key in stats:
            out[key] = int(stats[key])
    if state is not None:
        try:
            dev = jax.local_devices()[0]
            for name, tree in (("params", state.params),
                               ("opt_state", state.opt_state),
                               ("ema_params", state.ema_params)):
                if tree is not None:
                    out[f"{name}_bytes_per_device"] = (
                        statelib.resident_bytes(tree, dev))
        except Exception:
            pass
    gb = getattr(train_step, "grad_bytes_per_device", None)
    if gb is not None:
        out["grads_bytes_per_device"] = int(gb)
    resident = [out.get(k) for k in ("params_bytes_per_device",
                                     "grads_bytes_per_device",
                                     "opt_state_bytes_per_device",
                                     "ema_params_bytes_per_device")]
    if any(v is not None for v in resident):
        out["resident_bytes_per_device"] = sum(v or 0 for v in resident)
    return out or None


class _Profiler:
    """Hot-loop tracing hook (SURVEY.md §5.1) — the TPU replacement for
    Horovod's HOROVOD_TIMELINE Chrome trace. ``config.profile_steps=(a, b)``
    captures a ``jax.profiler`` trace of steps [a, b) into
    ``config.profile_dir`` (TensorBoard-loadable), process 0 only."""

    def __init__(self, config: TrainConfig):
        self.span = config.profile_steps
        self.dir = config.profile_dir or "/tmp/ddl_tpu_profile"
        self.active = False
        self.enabled = self.span is not None and jax.process_index() == 0

    def before_step(self, step: int) -> None:
        if not self.enabled:
            return
        lo, hi = self.span
        if not self.active and lo <= step < hi:
            jax.profiler.start_trace(self.dir)
            self.active = True

    def after_step(self, step: int, metrics) -> None:
        # Stop only after the last profiled step's device work completes —
        # dispatch is async, so stopping without blocking would trace host
        # activity only.
        if self.active and step + 1 >= self.span[1]:
            jax.block_until_ready(metrics)
            self._stop()

    def finish(self) -> None:
        if self.active:
            self._stop()

    def _stop(self) -> None:
        jax.profiler.stop_trace()
        self.active = False
        print(f"# profiler trace written to {self.dir}",
              file=sys.stderr, flush=True)


class _EvaluatorBase:
    """Shared held-out-eval plumbing (SURVEY.md §3.5).

    Built once per run — the compiled eval step is reused across every
    periodic (epoch-boundary) and final invocation. The synthetic source is
    indexable and reused, evaluating at a fixed huge batch-index offset
    (``SYNTHETIC_EVAL_OFFSET``) disjoint from any training step index, so
    eval batches never replay training batches and every eval scores the
    same held-out set. A real validation split is a *finite ordered
    stream*, so a fresh source is built per invocation (each eval reads the
    split from its start) with prefetch_depth=0 — construction must not
    eagerly decode lookahead batches a short eval would throw away.

    Subclasses set ``metric_name``/``best``, build ``self.eval_step``, and
    implement ``_accumulate`` over the per-batch eval-step outputs.
    """

    SYNTHETIC_EVAL_OFFSET = 1 << 30
    input_kind: str
    objective: str = "classify"

    def __init__(self, config: TrainConfig, batch_shd, num_batches: int):
        self.num_batches = num_batches
        self.synthetic = config.data.synthetic or not config.data.data_dir
        self._config, self._batch_shd = config, batch_shd
        self._synth_source = (
            datalib.make_source(config, self.input_kind, batch_shd,
                                objective=self.objective)
            if self.synthetic else None)
        self._warm_thread: Optional[threading.Thread] = None
        self._warm_exec = None

    def warm_compile_async(self, state, aot=None) -> None:
        """Compile the eval step on a background thread while the first
        training steps run (overlap — the loop's hot path never blocks on
        this). The executable is built ahead-of-time from abstract avals
        (``lower().compile()``): the live ``state`` buffers are donated by
        the next train step, so only their ShapeDtypeStructs are captured.
        The first eval joins the thread and calls the prepared executable;
        any failure here silently leaves the cold path in place.

        ``aot`` (perf/aot.StepExecutableCache) additionally persists the
        executable, so the next launch of this config skips even the
        overlapped compile.
        """
        if self._warm_thread is not None or self._warm_exec is not None:
            return
        if state.ema_params is not None:
            state = state.replace(params=state.ema_params)
        state_struct = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding), state)

        def work():
            try:
                with telemetry.phase("warm_compile"):
                    # One throwaway batch fixes the eval batch avals;
                    # synthetic sources are indexable (nothing is consumed)
                    # and real sources are rebuilt fresh per eval
                    # invocation anyway.
                    source, offset = self._source_and_offset()
                    batch = source.batch(offset)
                    fn = None
                    key = None
                    if aot is not None and aot.enabled:
                        key = aot.key("eval_step", (state_struct, batch))
                        fn = aot.load("eval_step", key)
                    if fn is None:
                        lower = getattr(self.eval_step, "lower_for",
                                        None) or self.eval_step.lower
                        fn = aotlib.compile_lowered(
                            lower(state_struct, batch))
                        if key is not None:
                            aot.save("eval_step", key, fn)
                self._warm_exec = fn
            except Exception:  # noqa: BLE001 - warm-up is optional
                self._warm_exec = None

        self._warm_thread = threading.Thread(
            target=work, daemon=True, name="ddl-eval-warm-compile")
        self._warm_thread.start()

    def _eval_fn(self):
        """The step callable for this invocation: the warm-compiled
        executable when the overlap produced one, else the cold jit."""
        if self._warm_thread is not None:
            self._warm_thread.join()
            self._warm_thread = None
        return self._warm_exec if self._warm_exec is not None \
            else self.eval_step

    def _source_and_offset(self):
        if self.synthetic:
            return self._synth_source, self.SYNTHETIC_EVAL_OFFSET
        import dataclasses
        cfg = self._config.replace(data=dataclasses.replace(
            self._config.data, prefetch_depth=0))
        return datalib.make_source(
            cfg, self.input_kind, self._batch_shd, train=False,
            objective=self.objective), 0

    def __call__(self, state) -> float:
        if state.ema_params is not None:
            # EMA evaluation: score the shadow weights (the reason the
            # EMA exists); training params continue unaffected.
            state = state.replace(params=state.ema_params)
        source, offset = self._source_and_offset()
        # Multi-process: the exhaustion decision must be GLOBAL — eval
        # steps are cross-process collectives, so one process breaking
        # while another proceeds would deadlock the job. When the source
        # can size itself up front (``batches_hint`` — the imagefolder val
        # splits of all three loaders), the processes agree ONCE on
        # min(local hints) before the loop (ADVICE r4: one collective, not
        # one per batch); otherwise every iteration carries the per-batch
        # agreement below.
        num_batches = self.num_batches
        per_batch_sync = jax.process_count() > 1
        if per_batch_sync:
            hint = getattr(source, "batches_hint", None)
            if hint is not None:
                import numpy as np
                from jax.experimental import multihost_utils

                hints = multihost_utils.process_allgather(
                    np.asarray([hint], np.int64))
                num_batches = min(num_batches, int(hints.min()))
                per_batch_sync = False
                if num_batches < self.num_batches:
                    import warnings

                    warnings.warn(
                        f"validation split holds {num_batches} of the "
                        f"{self.num_batches} requested eval batches; "
                        f"scoring the available ones")
                if num_batches == 0:
                    raise RuntimeError(
                        f"validation split yields no full batch on some "
                        f"process (global batch "
                        f"{self._config.global_batch_size}); shrink the "
                        f"batch or provide more validation images")
        outs = []
        eval_fn = self._eval_fn()
        for j in range(num_batches):
            try:
                batch = source.batch(offset + j)
            except StopIteration:
                if not per_batch_sync and jax.process_count() > 1:
                    # The upfront agreement promised this batch existed;
                    # running dry here means the hint was wrong, and a
                    # silent per-process break would deadlock the
                    # collective eval step on the others. Die loudly.
                    raise RuntimeError(
                        f"eval source exhausted at batch {j} despite "
                        f"batches_hint promising {num_batches}; the "
                        f"loader's sharding and its hint disagree")
                batch = None
            # Per-batch agreement (unknown-size streams): if ANY shard ran
            # dry (imagefolder files rarely divide evenly), all stop here
            # and the fetched batches of the others are discarded.
            if per_batch_sync:
                import numpy as np
                from jax.experimental import multihost_utils

                have = multihost_utils.process_allgather(
                    np.asarray([batch is not None], np.int32))
                if not have.all():
                    batch = None
            if batch is None:
                # A real validation split is finite; a short one must yield
                # a result over what exists, not a crash mid-training.
                if not outs:
                    raise RuntimeError(
                        f"validation split yielded no full batch (global "
                        f"batch {self._config.global_batch_size}); shrink "
                        f"the batch or provide more validation images")
                import warnings

                warnings.warn(
                    f"validation split exhausted after {j} of "
                    f"{self.num_batches} eval batches; scoring the "
                    f"available ones")
                break
            try:
                outs.append(jax.device_get(eval_fn(state, batch)))
            except Exception:  # noqa: BLE001
                if eval_fn is self.eval_step:
                    raise
                # The warm executable's avals disagree with the live batch
                # (e.g. a real loader emitted a different structure than
                # the warm-up batch). Eval steps don't donate, so retrying
                # through the cold jit is safe.
                eval_fn = self.eval_step
                self._warm_exec = None
                outs.append(jax.device_get(eval_fn(state, batch)))
        return self._accumulate(outs)


class _TokenEvaluator(_EvaluatorBase):
    """Held-out LM eval for token models: mean per-token loss over
    ``num_batches`` (perplexity = exp(loss)), computed with dropout off and
    exact (loss_sum, token_count) aggregation — identical to a
    single-device pass under any sharding. ``best`` is ``min``."""

    metric_name = "eval_loss"
    best = staticmethod(min)
    input_kind = "tokens"

    def __init__(self, config: TrainConfig, spec, mesh, model, batch_shd,
                 num_batches: int, state):
        self.objective = spec.objective
        super().__init__(config, batch_shd, num_batches)
        shardings = jax.tree_util.tree_map(lambda x: x.sharding, state)
        self.eval_step = steps.make_token_eval_step(
            model, mesh, config, shardings, spec.objective)

    def _accumulate(self, outs) -> float:
        loss_sum = count = 0.0
        for out in outs:
            loss_sum += float(out["loss_sum"])
            count += float(out["count"])
        return loss_sum / max(count, 1.0)


class _Evaluator(_EvaluatorBase):
    """Sharded top-1 over ``num_batches``: per-shard correct counts are
    psummed across the DP axes before dividing, so the result is identical
    to a single-device pass over the global batch."""

    metric_name = "eval_top1"
    best = staticmethod(max)
    input_kind = "image"

    def __init__(self, config: TrainConfig, mesh, model, batch_shd,
                 num_batches: int):
        super().__init__(config, batch_shd, num_batches)
        self.eval_step = steps.make_dp_eval_step(model, mesh, config)

    def _accumulate(self, outs) -> float:
        correct = total = 0
        for out in outs:
            correct += int(out["correct"])
            total += int(out["total"])
        return correct / max(total, 1)
