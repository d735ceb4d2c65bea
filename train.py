#!/usr/bin/env python
"""Training entrypoint — the reference's ``train.py`` CLI surface, TPU-native.

BASELINE.json:5 requires "the existing train.py entrypoints and benchmark
harness run unchanged from the CLI with --backend=tpu"; this is that CLI.
Pick an acceptance config by name (``--config``, see BASELINE.json:6-12) or
assemble one from flags.

Examples:
    python train.py --config resnet50_synthetic --steps 100
    python train.py --model resnet50 --batch-size 256 --dp 8 --backend tpu
    python train.py --config bert_base_mlm --steps 50 --tp 2 --sp 2
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__,
                                formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("--config", default=None,
                   help="acceptance-config preset name (see --list-configs)")
    p.add_argument("--list-configs", action="store_true")
    p.add_argument("--backend", default="tpu", choices=["tpu", "cpu"],
                   help="device backend (BASELINE.json:5)")
    p.add_argument("--model", default=None, help="model registry name")
    p.add_argument("--batch-size", type=int, default=None,
                   help="global batch size")
    p.add_argument("--steps", type=int, default=None,
                   help="total train steps (overrides --epochs)")
    p.add_argument("--epochs", type=float, default=None)
    p.add_argument("--synthetic", action="store_true", default=None,
                   help="on-device synthetic data (config 1)")
    p.add_argument("--data-dir", default=None)
    p.add_argument("--image-size", type=int, default=None,
                   help="decode/augment target side length for image "
                        "pipelines (default 224; small-corpus runs avoid "
                        "upscaling cost by matching their JPEG size)")
    p.add_argument("--loader", default=None,
                   choices=["auto", "tf", "native", "grain"],
                   help="input pipeline for image datasets")
    p.add_argument("--dp", type=int, default=None, help="data-parallel size")
    p.add_argument("--accum", type=int, default=None,
                   help="gradient-accumulation microbatches per optimizer "
                        "step (config 5's batch=32k on small meshes)")
    p.add_argument("--steps-per-loop", type=int, default=None,
                   help="fuse N train steps into one XLA program (lax.scan) "
                        "when data is generated on-device — amortizes "
                        "per-step host dispatch latency")
    p.add_argument("--fsdp", type=int, default=None)
    p.add_argument("--tp", type=int, default=None, help="tensor-parallel size")
    p.add_argument("--sp", type=int, default=None, help="sequence-parallel size")
    p.add_argument("--ep", type=int, default=None,
                   help="expert-parallel size (MoE models)")
    p.add_argument("--pp", type=int, default=None,
                   help="pipeline-parallel size (pipelined models)")
    p.add_argument("--attn", default=None,
                   choices=["dense", "ring", "flash", "zigzag"],
                   help="attention impl for transformer models")
    p.add_argument("--remat", action="store_true", default=None,
                   help="rematerialize transformer layers in backward "
                        "(less activation HBM, ~1/3 more FLOPs)")
    p.add_argument("--fused-bn", action="store_true", default=None,
                   help="Pallas fused BN(+residual)+ReLU kernels for CNNs "
                        "(ops/fused_batchnorm.py)")
    p.add_argument("--fused-block", action="store_true", default=None,
                   help="conv-epilogue fusion: bottleneck 1x1 convs as "
                        "Pallas matmul+BN (ops/fused_linear_bn.py; "
                        "resnet50/101/152)")
    p.add_argument("--ema-decay", type=float, default=None,
                   help="exponential-moving-average of params (e.g. "
                        "0.9999); evals score the EMA weights")
    p.add_argument("--allreduce-bucket-mb", type=float, default=None,
                   help="gradient tensor-fusion bucket size in MB for the "
                        "explicit-DP path (parallel/collectives.py); one "
                        "collective per bucket instead of per parameter "
                        "leaf. 0 = per-leaf reduction (the unfused A/B "
                        "baseline); default 4")
    p.add_argument("--allreduce-dtype", default=None,
                   choices=["float32", "bfloat16"],
                   help="gradient all-reduce payload dtype: bfloat16 halves "
                        "the wire bytes and restores fp32 masters after the "
                        "reduce (documented tolerance, docs/"
                        "fused_allreduce.md)")
    p.add_argument("--allreduce-algo", default=None,
                   choices=["psum", "ring"],
                   help="per-bucket collective: one psum, or the "
                        "bandwidth-optimal psum_scatter+all_gather ring "
                        "form")
    p.add_argument("--optimizer-sharding", default=None,
                   choices=["none", "zero1", "zero2", "zero3"],
                   help="ZeRO sharding ladder for the explicit-DP path "
                        "(parallel/zero.py): zero1 = 1/N-sharded optimizer "
                        "state (reduce-scatter grads, chunk update, "
                        "all-gather updated params); zero2 = + gradients "
                        "born reduce-scattered during backward, full grad "
                        "tree never materialized; zero3 = + parameters "
                        "themselves 1/N-sharded, all-gathered on demand "
                        "per fusion bucket (FSDP unified with the bucket "
                        "planner)")
    p.add_argument("--no-overlap-collectives", dest="overlap_collectives",
                   action="store_false", default=None,
                   help="zero2/zero3: disable backward/collective overlap "
                        "(serialize every bucket's reduce-scatter after "
                        "backward) — the A/B baseline schedule; update "
                        "math is unchanged")
    p.add_argument("--sync-bn", action="store_true", default=None,
                   help="cross-replica BatchNorm statistics (psum over the "
                        "data axis, torch SyncBatchNorm semantics; pure-DP "
                        "CNN configs only)")
    p.add_argument("--pp-microbatches", type=int, default=None,
                   help="pipeline microbatch count for *_pp models; the "
                        "fill/drain bubble wastes (P-1)/(M*V+P-1) of each "
                        "step, so use M >= 4*(P-1) (or shrink V's "
                        "denominator with --pipeline-schedule 1f1b)")
    p.add_argument("--pipeline-schedule", default=None,
                   choices=["gpipe", "1f1b"],
                   help="pipeline schedule for *_pp models "
                        "(models/pipeline.py): gpipe = fill/drain; 1f1b = "
                        "interleaved one-forward-one-backward over "
                        "--pipeline-virtual-stages chunks per stage, "
                        "shrinking the bubble to (P-1)/(M*V+P-1) "
                        "(docs/pipeline.md)")
    p.add_argument("--pipeline-virtual-stages", type=int, default=None,
                   help="virtual chunks per stage for --pipeline-schedule "
                        "1f1b; must divide layers-per-stage, and M must be "
                        "a multiple of P when V > 1")
    p.add_argument("--seq-len", type=int, default=None,
                   help="sequence length for token models")
    p.add_argument("--mlm-max-predictions", type=int, default=None,
                   help="gather-mode MLM head: project only this many masked "
                        "positions to vocab; -1 = auto (round(0.15*seq_len), "
                        "the canonical BERT recipe); 0/unset = dense "
                        "full-sequence logits")
    p.add_argument("--optimizer", default=None, choices=["sgd", "lars", "adamw", "lamb"])
    p.add_argument("--lr", type=float, default=None)
    p.add_argument("--dtype", default=None, choices=["bfloat16", "float32"])
    p.add_argument("--precision", default=None, choices=["fp32", "mixed"],
                   help="explicit precision policy: 'mixed' = bf16 compute + "
                        "fp32 master weights + dynamic loss scaling, 'fp32' = "
                        "everything float32; subsumes --dtype "
                        "(docs/mixed_precision.md)")
    p.add_argument("--batch-ramp", default=None, metavar="SPEC",
                   help="staged global-batch ramp, e.g. '8192:600,16384:600,"
                        "32768' — 600 steps at 8192, 600 at 16384, then the "
                        "configured batch; every boundary must land on the "
                        "checkpoint cadence and the last stage must equal "
                        "--batch-size (docs/mixed_precision.md)")
    p.add_argument("--seed", type=int, default=None)
    p.add_argument("--log-every", type=int, default=None)
    p.add_argument("--warmup-steps", type=int, default=2,
                   help="steps excluded from throughput timing")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--eval-batches", type=int, default=0,
                   help="periodic + final held-out eval over N batches "
                        "(top-1 for image models, loss/perplexity for "
                        "token models)")
    p.add_argument("--eval-every-epochs", type=float, default=None,
                   help="periodic-eval cadence in epochs (default 1.0; "
                        "needs --eval-batches and a sized dataset)")
    p.add_argument("--eval-only", action="store_true",
                   help="restore the newest checkpoint and run held-out "
                        "eval without training (requires --checkpoint-dir "
                        "and --eval-batches)")
    p.add_argument("--no-resume", action="store_true",
                   help="ignore existing checkpoints in --checkpoint-dir")
    p.add_argument("--profile-steps", default=None, metavar="A,B",
                   help="capture a jax.profiler trace of steps [A,B)")
    p.add_argument("--profile-dir", default=None,
                   help="trace output dir (default /tmp/ddl_tpu_profile)")
    p.add_argument("--trace-dir", default=None,
                   help="always-on phase telemetry: per-step phase spans, "
                        "per-bucket collective spans, fault/restart "
                        "instants, HBM gauges exported here as Chrome-trace "
                        "JSON (one file per process; read with "
                        "tools/summarize_trace.py or chrome://tracing)")
    p.add_argument("--trace-steps", default=None, metavar="A,B",
                   help="restrict step-tagged telemetry events to steps "
                        "[A,B) (default: the whole run)")
    p.add_argument("--flight-dir", default=None,
                   help="flight recorder: crash-surviving fsync'd JSONL "
                        "event log (steps, saves/restores, faults, "
                        "anomalies, re-formations) written here, one file "
                        "per host, plus Prometheus-text + JSON metric "
                        "exports; read with tools/postmortem.py (default: "
                        "$DDL_FLIGHT_DIR from launch.py --flight-dir, else "
                        "off)")
    p.add_argument("--no-anomaly-detection", action="store_true",
                   help="disable the online anomaly detector (loss spikes, "
                        "grad-norm drift, throughput collapse, straggler "
                        "trending on the log cadence)")
    p.add_argument("--straggler-threshold", type=float, default=None,
                   help="multi-host: warn when a host's log-cadence step "
                        "time exceeds this multiple of the cross-host mean "
                        "(default 1.5; 0 disables the per-log allgather)")
    p.add_argument("--fail-at-step", type=int, default=None,
                   help="DEPRECATED alias for --fault-plan crash@K "
                        "(fires on every restart attempt)")
    p.add_argument("--fault-plan", default=None, metavar="PLAN",
                   help="deterministic fault injection: comma-separated "
                        "kind@step[:qualifier] terms, e.g. "
                        "'sigkill@20,corrupt_latest_ckpt@20'; grammar and "
                        "kinds in docs/fault_tolerance.md")
    p.add_argument("--bad-step-guard", action="store_true",
                   help="compile the non-finite-update skip guard into the "
                        "train step (auto-enabled when --fault-plan injects "
                        "nan_grads); costs ~1 ULP of trajectory drift vs "
                        "the guard-free program, see docs/fault_tolerance.md")
    p.add_argument("--bad-step-limit", type=int, default=None,
                   help="abort after K consecutive non-finite update steps "
                        "(skipped, not applied; default 10)")
    p.add_argument("--loader-timeout", type=float, default=None,
                   help="data watchdog: seconds to wait per host batch "
                        "before retrying (0 = watchdog off, the default)")
    p.add_argument("--loader-retries", type=int, default=None,
                   help="data watchdog: retries per batch before declaring "
                        "the loader stalled (default 2)")
    p.add_argument("--checkpoint-every", type=int, default=None,
                   help="save a checkpoint every N steps")
    p.add_argument("--no-compile-cache", action="store_true",
                   help="run without the persistent compile cache + AOT "
                        "step executables (docs/compile_cache.md); where "
                        "the cache lives is $JAX_COMPILATION_CACHE_DIR, "
                        "else <repo>/.cache/jax_compile")
    p.add_argument("--tensorboard-dir", default=None,
                   help="mirror metrics into TF summaries at this dir")
    return p.parse_args(argv)


def build_config(args: argparse.Namespace):
    from distributeddeeplearning_tpu import config as cfglib

    cfg = cfglib.preset(args.config) if args.config else cfglib.TrainConfig()
    if args.model:
        cfg = cfg.replace(model=args.model)
    if args.batch_size:
        cfg = cfg.replace(global_batch_size=args.batch_size)
    if args.epochs:
        cfg = cfg.replace(num_epochs=args.epochs)
    if args.dtype:
        cfg = cfg.replace(dtype=args.dtype)
    if args.precision:
        pol = (cfglib.PrecisionPolicy.mixed() if args.precision == "mixed"
               else cfglib.PrecisionPolicy.fp32())
        cfg = cfg.replace(precision=pol, dtype=pol.compute_dtype)
    if args.batch_ramp:
        cfg = cfg.replace(batch_ramp=args.batch_ramp)
    if args.seed is not None:
        cfg = cfg.replace(seed=args.seed)
    if args.log_every:
        cfg = cfg.replace(log_every=args.log_every)
    if args.checkpoint_dir:
        cfg = cfg.replace(checkpoint_dir=args.checkpoint_dir)
    if args.no_resume:
        cfg = cfg.replace(resume=False)
    if args.fail_at_step is not None:
        if args.fail_at_step <= 0:
            raise SystemExit(
                f"--fail-at-step must be positive (got {args.fail_at_step})")
        cfg = cfg.replace(fail_at_step=args.fail_at_step)
    if args.fault_plan:
        from distributeddeeplearning_tpu.robustness import faults
        try:
            faults.parse_plan(args.fault_plan)  # fail fast on grammar errors
        except ValueError as e:
            raise SystemExit(f"--fault-plan: {e}")
        cfg = cfg.replace(fault_plan=args.fault_plan)
    if args.bad_step_limit is not None:
        if args.bad_step_limit <= 0:
            raise SystemExit(
                f"--bad-step-limit must be positive (got {args.bad_step_limit})")
        cfg = cfg.replace(bad_step_limit=args.bad_step_limit)
    if args.bad_step_guard:
        cfg = cfg.replace(bad_step_guard=True)
    if args.loader_timeout is not None:
        if args.loader_timeout < 0:
            raise SystemExit(
                f"--loader-timeout must be >= 0 (got {args.loader_timeout})")
        cfg = cfg.replace(data=dataclasses.replace(
            cfg.data, loader_timeout_s=args.loader_timeout))
    if args.loader_retries is not None:
        if args.loader_retries < 0:
            raise SystemExit(
                f"--loader-retries must be >= 0 (got {args.loader_retries})")
        cfg = cfg.replace(data=dataclasses.replace(
            cfg.data, loader_retries=args.loader_retries))
    if args.checkpoint_every is not None:
        if args.checkpoint_every <= 0:
            raise SystemExit(
                f"--checkpoint-every must be positive (got {args.checkpoint_every})")
        cfg = cfg.replace(checkpoint_every_steps=args.checkpoint_every)
    if args.no_compile_cache:
        cfg = cfg.replace(compile_cache=False)
    if args.accum is not None:
        if args.accum <= 0:
            raise SystemExit(f"--accum must be positive (got {args.accum})")
        cfg = cfg.replace(grad_accum_steps=args.accum)
    if args.steps_per_loop is not None:
        if args.steps_per_loop <= 0:
            raise SystemExit(
                f"--steps-per-loop must be positive (got {args.steps_per_loop})")
        cfg = cfg.replace(steps_per_loop=args.steps_per_loop)
    cfg = cfg.replace(backend=args.backend)
    if args.profile_steps:
        try:
            lo, hi = (int(x) for x in args.profile_steps.split(","))
        except ValueError:
            raise SystemExit(
                f"--profile-steps expects A,B (got {args.profile_steps!r})")
        if not 0 <= lo < hi:
            raise SystemExit(
                f"--profile-steps needs 0 <= A < B (got {lo},{hi})")
        cfg = cfg.replace(profile_steps=(lo, hi))
    if args.profile_dir:
        cfg = cfg.replace(profile_dir=args.profile_dir)
    if args.trace_dir:
        cfg = cfg.replace(trace_dir=args.trace_dir)
    if args.flight_dir:
        cfg = cfg.replace(flight_dir=args.flight_dir)
    if args.no_anomaly_detection:
        cfg = cfg.replace(anomaly_detection=False)
    if args.trace_steps:
        try:
            lo, hi = (int(x) for x in args.trace_steps.split(","))
        except ValueError:
            raise SystemExit(
                f"--trace-steps expects A,B (got {args.trace_steps!r})")
        if not 0 <= lo < hi:
            raise SystemExit(
                f"--trace-steps needs 0 <= A < B (got {lo},{hi})")
        cfg = cfg.replace(trace_steps=(lo, hi))
    if args.straggler_threshold is not None:
        if args.straggler_threshold < 0:
            raise SystemExit(f"--straggler-threshold must be >= 0 "
                             f"(got {args.straggler_threshold})")
        cfg = cfg.replace(straggler_threshold=args.straggler_threshold)

    par = cfg.parallel
    updates = {}
    if args.dp is not None:
        updates["data"] = args.dp
    if args.fsdp is not None:
        updates["fsdp"] = args.fsdp
    if args.tp is not None:
        updates["model"] = args.tp
    if args.sp is not None:
        updates["seq"] = args.sp
    if args.ep is not None:
        updates["expert"] = args.ep
    if args.pp is not None:
        updates["pipeline"] = args.pp
    if updates:
        cfg = cfg.replace(parallel=dataclasses.replace(par, **updates))

    if args.attn:
        cfg = cfg.replace(attention_impl=args.attn)
    if args.remat:
        cfg = cfg.replace(remat=True)
    if args.eval_every_epochs is not None:
        if args.eval_every_epochs <= 0:
            raise SystemExit(f"--eval-every-epochs must be positive "
                             f"(got {args.eval_every_epochs})")
        cfg = cfg.replace(eval_every_epochs=args.eval_every_epochs)
    if args.fused_bn:
        cfg = cfg.replace(fused_bn=True)
    if args.fused_block:
        cfg = cfg.replace(fused_block=True)
    if args.sync_bn:
        cfg = cfg.replace(sync_bn=True)
    ar_updates = {}
    if args.allreduce_bucket_mb is not None:
        if args.allreduce_bucket_mb < 0:
            raise SystemExit(f"--allreduce-bucket-mb must be >= 0 "
                             f"(got {args.allreduce_bucket_mb}); 0 selects "
                             f"per-leaf reduction")
        ar_updates["bucket_mb"] = args.allreduce_bucket_mb
    if args.allreduce_dtype:
        ar_updates["dtype"] = args.allreduce_dtype
    if args.allreduce_algo:
        ar_updates["algorithm"] = args.allreduce_algo
    if ar_updates:
        cfg = cfg.replace(
            allreduce=dataclasses.replace(cfg.allreduce, **ar_updates))
    if args.optimizer_sharding:
        cfg = cfg.replace(optimizer_sharding=args.optimizer_sharding)
    if args.overlap_collectives is not None:
        cfg = cfg.replace(overlap_collectives=args.overlap_collectives)
    if args.ema_decay is not None:
        cfg = cfg.replace(optimizer=dataclasses.replace(
            cfg.optimizer, ema_decay=args.ema_decay))
    if args.pp_microbatches is not None:
        cfg = cfg.replace(pipeline_microbatches=args.pp_microbatches)
    if args.pipeline_schedule:
        cfg = cfg.replace(pipeline_schedule=args.pipeline_schedule)
    if args.pipeline_virtual_stages is not None:
        if args.pipeline_virtual_stages < 1:
            raise SystemExit(
                f"--pipeline-virtual-stages must be >= 1 "
                f"(got {args.pipeline_virtual_stages})")
        cfg = cfg.replace(pipeline_virtual_stages=args.pipeline_virtual_stages)
    if cfg.pipeline_virtual_stages > 1 and cfg.pipeline_schedule != "1f1b":
        raise SystemExit(
            "--pipeline-virtual-stages > 1 requires --pipeline-schedule "
            "1f1b (gpipe has no virtual chunks)")

    data_updates = {}
    if args.synthetic is not None:
        data_updates["synthetic"] = True
    if args.seq_len:
        data_updates["seq_len"] = args.seq_len
    if args.mlm_max_predictions is not None:
        from distributeddeeplearning_tpu.models import model_spec
        spec = model_spec(cfg.model)
        data_updates["mlm_max_predictions"] = \
            cfglib.resolve_mlm_max_predictions(
                args.mlm_max_predictions,
                data_updates.get("seq_len", cfg.data.seq_len),
                spec.objective)
    if args.data_dir:
        data_updates["data_dir"] = args.data_dir
        data_updates["synthetic"] = False
    if args.loader:
        data_updates["loader"] = args.loader
    if args.image_size is not None:
        if args.image_size <= 0:
            raise SystemExit(
                f"--image-size must be positive (got {args.image_size})")
        data_updates["image_size"] = args.image_size
    if data_updates:
        cfg = cfg.replace(data=dataclasses.replace(cfg.data, **data_updates))

    opt_updates = {}
    if args.optimizer:
        opt_updates["name"] = args.optimizer
    if args.lr is not None:
        opt_updates["learning_rate"] = args.lr
    if opt_updates:
        cfg = cfg.replace(
            optimizer=dataclasses.replace(cfg.optimizer, **opt_updates))
    return cfg


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.list_configs:
        from distributeddeeplearning_tpu import config as cfglib
        print("\n".join(cfglib.PRESETS))
        return 0

    import os
    if args.backend == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"

    # Join a multi-host job if the launcher (launch.py) configured one —
    # the MPI_Init moment of the reference's stack (SURVEY.md §3.1).
    from distributeddeeplearning_tpu import launch as launchlib
    launchlib.maybe_initialize_distributed()

    cfg = build_config(args)
    from distributeddeeplearning_tpu.train import loop

    from distributeddeeplearning_tpu.models import model_spec

    total_steps = args.steps
    if args.eval_only:
        if not (args.checkpoint_dir and args.eval_batches > 0):
            raise SystemExit(
                "--eval-only needs --checkpoint-dir (the model to restore) "
                "and a positive --eval-batches (how much of the held-out "
                "split to score)")
        if args.no_resume:
            raise SystemExit(
                "--eval-only with --no-resume would score freshly "
                "initialized weights; drop --no-resume")
        if total_steps is not None or args.epochs:
            raise SystemExit(
                "--eval-only trains nothing; drop --steps/--epochs "
                "(or drop --eval-only to train then eval)")
        # Refuse an empty/typo'd directory BEFORE paying for compile + a
        # full eval of randomly initialized weights.
        from distributeddeeplearning_tpu.train import checkpoint as ckptlib
        ck = ckptlib.Checkpointer.create(cfg)
        try:
            if ck.latest_step() is None:
                raise SystemExit(
                    f"--eval-only: no checkpoint found in "
                    f"{cfg.checkpoint_dir!r}; refusing to score randomly "
                    f"initialized weights")
        finally:
            ck.close()
        # total_steps=0 with resume: the restored step lands past the
        # (empty) training range, so the loop skips straight to final eval.
        total_steps = 0
    elif total_steps is None:
        if model_spec(cfg.model).input_kind == "tokens":
            # MLM pretraining is step-based (no canonical "epoch"); require
            # an explicit step budget rather than inventing one.
            raise SystemExit(
                "token models have no epoch semantics; pass --steps")
        steps_per_epoch = loop.steps_per_epoch(cfg)
        if steps_per_epoch is None:
            raise SystemExit(
                f"dataset {cfg.data.dataset!r} has no known epoch size; "
                "pass --steps or set steps_per_epoch in the config")
        total_steps = int(cfg.num_epochs * steps_per_epoch)

    logger_cm = contextlib.nullcontext(None)
    if args.tensorboard_dir:
        from distributeddeeplearning_tpu.utils.logging import MetricLogger
        # Context manager: the TB writer / JSONL handle is released even
        # when the loop raises (preemption SystemExit, injected faults).
        logger_cm = MetricLogger(tensorboard_dir=args.tensorboard_dir)

    with logger_cm as logger:
        summary = loop.run(cfg, total_steps=total_steps,
                           warmup_steps=min(args.warmup_steps,
                                            total_steps - 1)
                           if total_steps > 1 else 0,
                           eval_batches=args.eval_batches, logger=logger,
                           restore_for_eval=args.eval_only)
    if args.eval_only and summary["start_step"] == 0:
        # Backstop for a checkpoint that vanished between the pre-check and
        # the restore: never report a random-init score as a valid summary.
        raise SystemExit(
            f"--eval-only: no checkpoint found in {cfg.checkpoint_dir!r}; "
            "refusing to score randomly initialized weights")
    import jax
    if jax.process_index() == 0:
        print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
