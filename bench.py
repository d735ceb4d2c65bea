#!/usr/bin/env python
"""Benchmark harness — prints JSON metric lines; the LAST line is the result.

Metric of record (BASELINE.json:2): ResNet50/ImageNet images/sec/chip,
measured on the headline single-chip synthetic config (config 1 scaled to a
throughput-class batch), bfloat16, compile/warmup excluded — the protocol the
reference's harness used for its images/sec tables (SURVEY.md §3.4).

``vs_baseline``: BASELINE.json captured no published reference numbers
("published": {}), so the denominator is the north-star target expressed
per-chip: 8xV100 ResNet50 ImageNet aggregate on a v5e-8, i.e. one V100's
mixed-precision throughput per chip. We pin that at 1450 images/sec/chip
(NVIDIA's commonly-published V100 ResNet50 AMP figure — a literature
stand-in, NOT a measured reference value; every metric line says so in its
``baseline_denominator`` field). vs_baseline > 1.0 means beating the target.

Output contract: the child compiles ONCE, then emits a valid metric line
after a short quick window (3 warmup + 8 timed steps) and a refined line
after the full-protocol window (the 11 steps already run count as warmup,
then 30 timed steps); the parent relays each line to stdout the moment it
appears, and the last parseable line wins. Every line is a measurement THIS
run made on the devices it names: the harness never prints a cached value,
needs a TPU unless ``--platform cpu`` says otherwise, and exits non-zero
when no fresh measurement landed or the child failed (an ``error`` record is
then the last line). Attempts share the persistent compile cache
(perf/compile_cache.py), so a retry skips straight past compilation.

``--suite`` measures every acceptance config (BASELINE.json:6-12) plus the
beyond-scope families in one child process (backend init amortized), one
metric line per config — used to (re)populate BASELINE.md's measured tables.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import subprocess
import sys
import threading
import time

V100_AMP_RESNET50_IMAGES_PER_SEC = 1450.0
BASELINE_DENOMINATOR_NOTE = (
    "V100 AMP ResNet50 1450 img/s — literature stand-in per chip for the "
    "8xV100-on-v5e-8 north star; BASELINE.json published={}")
RETRY_BACKOFF_SEC = (5, 15)  # sleeps between attempts

# --suite rows: (name, model, overrides, est_s) in VALUE-PER-MINUTE order —
# a run that is cut mid-suite yields the most valuable prefix. Rows are
# SELECTED BY NAME (--suite-rows): names are stable under reorders and
# insertions, unlike positional indices. est_s is the expected on-chip wall
# cost of the row (compile with warm persistent cache + measure; a guess
# from the sessions BASELINE.md records) and gates row admission
# against the remaining --suite-budget; it is NOT a hard per-row kill (the
# row deadline handles that). Batch sizes are the measured sweet spots from
# BASELINE.md's round-2 sweeps; S=2048 rows need flash+remat to fit.
SUITE = (
    # Headline family first: its compile cache is warm from the headline
    # run, and the acceptance metric of record is this row.
    ("resnet50", "resnet50", {}, 90),
    # Fused-vs-per-leaf gradient all-reduce A/B (parallel/collectives.py):
    # same model/batch as the headline (warm cache), differing ONLY in the
    # reduction schedule — ar_fused buckets leaves at the default 4 MB,
    # ar_perleaf (bucket_mb=0) reduces leaf-by-leaf, the pre-fusion
    # behavior. Never measured on chip — the tensor-fusion win this PR
    # exists to quantify.
    ("ar_fused", "resnet50", {"allreduce_bucket_mb": 4.0}, 90),
    ("ar_perleaf", "resnet50", {"allreduce_bucket_mb": 0.0}, 90),
    # ZeRO-1 optimizer sharding vs the fused all-reduce it replaces
    # (parallel/zero.py): reduce-scatter + shard-local update + param
    # all-gather, same wire volume, opt state 1/N per chip. Paired with
    # ar_fused (same model/batch/bucket) so the throughput delta isolates
    # the schedule change; the memory win shows in the per-device
    # opt_state bytes every record now carries. Never measured on chip.
    ("zero1", "resnet50", {"allreduce_bucket_mb": 4.0,
                           "optimizer_sharding": "zero1"}, 90),
    # ZeRO-2/3 complete the ladder (same pairing discipline as zero1):
    # zero2 keeps grads reduce-scattered per bucket (never materializing
    # the full grad tree), zero3 stores params 1/N-chunked and all-gathers
    # them per bucket on demand — both with the backward/collective
    # overlapped schedule on by default. Never measured on chip.
    ("zero2", "resnet50", {"allreduce_bucket_mb": 4.0,
                           "optimizer_sharding": "zero2"}, 90),
    ("zero3", "resnet50", {"allreduce_bucket_mb": 4.0,
                           "optimizer_sharding": "zero3"}, 90),
    # Never measured on chip under the gather-head protocol (r2 protocol
    # change) — the two highest-value unknown rows.
    ("bert512_flash", "bert_base", {"batch_size": 32, "seq_len": 512,
                                    "attention_impl": "flash"}, 120),
    ("gpt2_1024", "gpt2_small", {"batch_size": 16, "seq_len": 1024}, 120),
    ("bert512", "bert_base", {"batch_size": 32, "seq_len": 512}, 120),
    ("resnet152", "resnet152", {"batch_size": 256}, 120),
    ("densenet121", "densenet121", {"batch_size": 256}, 120),
    ("vit_b16", "vit_b16", {"batch_size": 256}, 120),
    # Long-context last: largest compile, slowest steps, and its CPU-side
    # evidence (flash==dense parity) is the strongest of the set.
    ("bert2048_flash", "bert_base", {"batch_size": 32, "seq_len": 2048,
                                     "attention_impl": "flash",
                                     "remat": True}, 180),
    # Large-batch %-of-peak A/B (ISSUE 20), reached by NAME
    # (--suite-rows): identical model at 2x the headline
    # per-chip batch, differing ONLY in precision policy. The fp32 arm
    # scores against the fp32 roof and the mixed arm (bf16 compute + fp32
    # masters + dynamic loss scaling) against the bf16 roof, so the pair
    # reads as distance-from-own-speed-of-light (arXiv 1711.04325); each
    # emits under its own _<precision> metric name.
    ("largebatch_fp32", "resnet50", {"batch_size": 1024,
                                     "precision": "fp32"}, 120),
    ("largebatch_bf16", "resnet50", {"batch_size": 1024,
                                     "precision": "mixed"}, 120),
    # Pipeline-schedule A/B (models/pipeline.py), after the value-per-minute
    # prefix — reached by NAME (--suite-rows), never by budget order.
    # Fill/drain GPipe vs
    # interleaved 1f1b at IDENTICAL geometry — same model, batch, seq_len,
    # stages (pp=2) and microbatches (M=4, registry); the ONLY delta is the
    # schedule (1f1b adds V=2 virtual chunks per stage). Each record
    # carries the measured pipeline_bubble_fraction from the trace-time
    # tick instants next to the analytic (P-1)/(M*V+P-1), so the pair IS
    # the bubble-kill verdict: 1f1b's measured bubble must land strictly
    # below gpipe's and within 1.5x its analytic value (docs/pipeline.md).
    ("pp_gpipe", "bert_tiny_pp4", {"batch_size": 4, "seq_len": 128,
                                   "pp": 2, "pipeline_schedule": "gpipe",
                                   "pipeline_virtual_stages": 1}, 90),
    ("pp_1f1b", "bert_tiny_pp4", {"batch_size": 4, "seq_len": 128,
                                  "pp": 2, "pipeline_schedule": "1f1b",
                                  "pipeline_virtual_stages": 2}, 90),
)


def _metric_name_unit(args) -> tuple[str, str]:
    """One source of truth for the metric identity, shared by the success
    and error paths (parent + child processes). Consults the model registry
    for the input kind; registry import touches no device backend."""
    objective = None
    try:
        from distributeddeeplearning_tpu.models import model_spec
        spec = model_spec(args.model)
        if spec.input_kind == "tokens":
            objective = spec.objective
    except Exception:
        name = args.model  # best effort when the registry import fails
        if "bert" in name:
            objective = "mlm"
        elif "gpt" in name or "llama" in name:
            objective = "causal"
    if objective:
        # The head mode is part of the measurement protocol: gN = gather
        # head over N positions (canonical BERT), no suffix = dense logits.
        # Keeps gather-mode rows from being compared against the dense-head
        # numbers recorded under the unsuffixed name.
        from distributeddeeplearning_tpu.config import (
            resolve_mlm_max_predictions)
        mp = resolve_mlm_max_predictions(
            args.mlm_max_predictions, args.seq_len, objective)
    # Per-leaf gradient all-reduce (bucket_mb=0) is the fusion A/B's
    # reference schedule, NOT the production path: give it its own metric
    # name so its (expected-slower) number can never evict the headline's
    # last-good entry under the same key.
    perleaf = ("_perleaf_ar"
               if getattr(args, "allreduce_bucket_mb", None) == 0 else "")
    # ZeRO rows likewise get their own metric name per stage: each sharded
    # schedule is a different measurement protocol and its number must not
    # evict the replicated headline's last-good entry.
    stage = getattr(args, "optimizer_sharding", None)
    if stage and stage != "none":
        perleaf += f"_{stage}"
    # Pipeline rows likewise: each (stages, schedule, virtual-stage) tuple
    # is its own measurement protocol — the gpipe and 1f1b A/B rows must
    # never evict each other's (or the non-pipelined model's) last-good
    # entries under a shared key.
    pp = getattr(args, "pp", 1) or 1
    if pp > 1:
        sched = getattr(args, "pipeline_schedule", "gpipe") or "gpipe"
        vv = getattr(args, "pipeline_virtual_stages", 1) or 1
        perleaf += f"_pp{pp}_{sched}" + (f"v{vv}" if vv > 1 else "")
    # Precision-policy A/B rows (ISSUE 20): the fp32 reference arm and the
    # mixed (bf16 compute + fp32 masters + dynamic loss scaling) arm are
    # different measurement protocols scoring against different rooflines —
    # each gets its own metric name so neither can evict the other's (or
    # the default row's) last-good entry.
    prec = getattr(args, "precision", None)
    if prec:
        perleaf += f"_{prec}"
    # Tracing adds per-step clock reads inside the timed window — protocol
    # drift by design (it's how the overhead A/B measures itself), so traced
    # numbers live under their own metric name and can never evict an
    # untraced last-good entry.
    if getattr(args, "trace_dir", None):
        perleaf += "_tele"
    if objective:
        gather = f"_g{mp}" if mp > 0 else ""
        return (f"{args.model}{perleaf}_{objective}_s{args.seq_len}{gather}"
                f"_seqs_per_sec_per_chip", "sequences/sec/chip")
    return (f"{args.model}{perleaf}_imagenet_images_per_sec_per_chip",
            "images/sec/chip")


def _protocol_suffix(args) -> str:
    """Measurement-protocol qualifiers that are not part of the metric name
    (attention kernel, remat) — without them the dense and flash suite rows
    would be indistinguishable."""
    parts = []
    if args.attention_impl:
        parts.append(args.attention_impl)
    if args.remat:
        parts.append("remat")
    if getattr(args, "fused_bn", False):
        parts.append("fusedbn")
    if getattr(args, "fused_block", False):
        parts.append("fusedblock")
    ar_mb = getattr(args, "allreduce_bucket_mb", None)
    if ar_mb is not None:
        # Reduction schedule is protocol: default (no flag) is the fused
        # path at AllReduceConfig's default bucket size; an explicit value
        # is marked so the A/B rows stay distinguishable in the record.
        parts.append("perleaf-ar" if ar_mb == 0 else f"ar{ar_mb:g}mb")
    if getattr(args, "allreduce_dtype", None) == "bfloat16":
        parts.append("ar-bf16")
    stage = getattr(args, "optimizer_sharding", None)
    if stage and stage != "none":
        parts.append(stage)
        if stage in ("zero2", "zero3") and \
                getattr(args, "overlap_collectives", True) is False:
            parts.append("no-overlap")
    prec = getattr(args, "precision", None)
    if prec:
        # Spell the policy out (compute/param/reduce + loss scale) so the
        # record says WHAT "mixed" meant when it was measured, not just
        # that it was.
        try:
            from distributeddeeplearning_tpu.config import PrecisionPolicy
            pol = (PrecisionPolicy.mixed() if prec == "mixed"
                   else PrecisionPolicy.fp32())
            parts.append(pol.describe())
        except Exception:
            parts.append(f"prec-{prec}")
    elif getattr(args, "dtype", None):
        parts.append(args.dtype)
    pp = getattr(args, "pp", 1) or 1
    if pp > 1:
        parts.append(f"pp{pp}-{getattr(args, 'pipeline_schedule', 'gpipe')}"
                     f"-v{getattr(args, 'pipeline_virtual_stages', 1) or 1}")
    if getattr(args, "trace_dir", None):
        parts.append("tele")
    return (" " + "+".join(parts)) if parts else ""


def _mfu_fields(args, value: float) -> dict:
    """tflops_per_sec + mfu_pct for a rate of ``value`` examples/sec/chip.
    Model FLOPs are the analytic fwd+bwd enumeration (models/flops.py,
    2-flops-per-MAC convention, validated against XLA cost analysis by
    tests/test_flops.py); the peak is the detected chip's spec number at the
    arm's compute dtype. Child-only (it asks jax for the device). A model
    with no FLOPs entry omits the fields and a device that is not a TPU
    omits the peak ones; a TPU kind missing from the peak table raises."""
    from distributeddeeplearning_tpu.config import (
        resolve_mlm_max_predictions)
    from distributeddeeplearning_tpu.models import flops as flopslib
    from distributeddeeplearning_tpu.models import model_spec
    try:
        spec = model_spec(args.model)
    except KeyError:
        return {}
    mlm_pred = (resolve_mlm_max_predictions(
        args.mlm_max_predictions, args.seq_len, spec.objective)
        if spec.input_kind == "tokens" else 0)
    per_ex = flopslib.train_flops_per_example(
        args.model, seq_len=args.seq_len, mlm_positions=mlm_pred)
    if per_ex is None:
        return {}
    out = {"tflops_per_sec": round(value * per_ex / 1e12, 2)}
    import jax
    # %-of-peak scores against the roof of the arm's OWN compute dtype
    # (models/flops.py peak tables): the fp32 reference arm vs the fp32
    # roof, the mixed/bf16 arm vs the bf16 roof — each measures distance
    # from its own speed of light (arXiv 1711.04325 axis).
    prec = getattr(args, "precision", None)
    compute = ("float32"
               if prec == "fp32" or (prec is None and
                                     getattr(args, "dtype", None)
                                     == "float32")
               else "bfloat16")
    peak = flopslib.peak_flops(jax.devices()[0].device_kind, compute)
    if peak:
        out["mfu_pct"] = round(100.0 * value * per_ex / peak, 1)
        out["peak_dtype"] = compute
    return out


def _emit_metric(args, value: float, protocol: str,
                 extra: dict | None = None) -> None:
    metric, unit = _metric_name_unit(args)
    # The 1450 img/s denominator is specifically the V100 ResNet50 AMP
    # figure — comparing any other model against it would be meaningless,
    # so vs_baseline is emitted only for the metric of record.
    rec = {
        "metric": metric,
        "value": round(value, 2),
        "unit": unit,
        "vs_baseline": (round(value / V100_AMP_RESNET50_IMAGES_PER_SEC, 4)
                        if args.model == "resnet50" else None),
        "protocol": protocol + _protocol_suffix(args),
        "baseline_denominator": BASELINE_DENOMINATOR_NOTE,
    }
    rec.update(_mfu_fields(args, value))
    # Roofline %-of-peak is ALWAYS present (null when model FLOPs or the
    # chip's spec peak are unknown): the suite table's comparability
    # column must exist on every row, not only the lucky ones
    # (docs/perf_measurement.md; large-batch baselines of arXiv
    # 1711.04325 compare on this axis).
    rec["pct_of_peak"] = rec.get("mfu_pct")
    # Structured kernel-config marker: consumers can filter fused-kernel
    # records without parsing the protocol string.
    if getattr(args, "fused_block", False):
        rec["fused_block"] = True
    if extra:
        rec.update(extra)
    # This line is a live measurement by THIS process. Runs in the child,
    # so the backend identity block reflects the devices that answered.
    from distributeddeeplearning_tpu.observability import perf_report
    perf_report.annotate(rec, provenance="fresh")
    rec["attempt"] = int(os.environ.get("DDL_BENCH_ATTEMPT", "1") or 1)
    print(json.dumps(rec), flush=True)


def _note(msg: str) -> None:
    """Child heartbeat on stderr: reaches error records, never stdout."""
    print(f"# bench: {msg}", file=sys.stderr, flush=True)


def _child_measure(args, emit_quick: bool = True,
                   emit_final: bool = True,
                   deadline: float | None = None) -> float:
    """One config: compile once, emit quick then full-protocol lines;
    returns the full-protocol rate.

    ``emit_quick=False`` (suite mode) keeps the quick window as pure warmup
    so each config contributes exactly one metric line. ``emit_final=False``
    (batch-sweep alternates) measures without printing — the caller emits
    only if the alternate beats the primary, because the driver takes the
    LAST line and a slower alternate must never shadow a faster primary.

    ``deadline`` (time.monotonic value) is the row's wall budget: the
    timed loops stop early when it passes and the rate is computed over
    the steps actually completed (protocol records the cut), so a suite
    row that runs long yields a shorter valid measurement instead of
    eating the rows behind it. Compile+warmup is never interrupted — by
    the time the deadline can fire the expensive part is already paid. If
    the deadline passes before ANY timed step completes, TimeoutError."""
    import jax

    from distributeddeeplearning_tpu import data as datalib
    from distributeddeeplearning_tpu.config import (
        AllReduceConfig, DataConfig, ParallelConfig, TrainConfig,
        resolve_mlm_max_predictions)
    from distributeddeeplearning_tpu.models import model_spec
    from distributeddeeplearning_tpu.observability import telemetry
    from distributeddeeplearning_tpu.train import loop

    # Configure telemetry BEFORE build: the per-bucket collective spans are
    # recorded at trace time, i.e. during the first train_step compile.
    tele = None
    if getattr(args, "trace_dir", None):
        tele = telemetry.configure(trace_dir=args.trace_dir,
                                   process_index=jax.process_index(),
                                   process_name="bench")

    # Pipeline rows: the measured bubble comes from the trace-time
    # pipeline_tick instants (models/pipeline.py), so a buffer-only
    # registry captures them without adding clock reads to the timed
    # windows — the metric keeps its untraced protocol (no _tele drift).
    # A --trace-dir run reuses its own registry instead.
    pp = getattr(args, "pp", 1) or 1
    pipe_tele = tele
    if pp > 1 and pipe_tele is None:
        pipe_tele = telemetry.configure(enabled=True,
                                        process_index=jax.process_index(),
                                        process_name="bench")

    n_dev = jax.device_count()
    spec = model_spec(args.model)
    tokens = spec.input_kind == "tokens"
    mlm_pred = resolve_mlm_max_predictions(
        args.mlm_max_predictions, args.seq_len, spec.objective)
    data = (DataConfig(synthetic=True, dataset="mlm", seq_len=args.seq_len,
                       mlm_max_predictions=mlm_pred)
            if tokens else DataConfig(synthetic=True))
    ar_kw = {}
    if getattr(args, "allreduce_bucket_mb", None) is not None:
        ar_kw["bucket_mb"] = args.allreduce_bucket_mb
    if getattr(args, "allreduce_dtype", None):
        ar_kw["dtype"] = args.allreduce_dtype
    # Precision-policy A/B arms (ISSUE 20): --precision selects an explicit
    # policy (the compute dtype follows the policy); bare --dtype covers
    # legacy-knob runs. Default stays the bf16 protocol of record.
    prec_kw = {}
    dtype = getattr(args, "dtype", None) or "bfloat16"
    prec = getattr(args, "precision", None)
    if prec:
        from distributeddeeplearning_tpu.config import PrecisionPolicy
        pol = (PrecisionPolicy.mixed() if prec == "mixed"
               else PrecisionPolicy.fp32())
        prec_kw["precision"] = pol
        dtype = pol.compute_dtype
    cfg = TrainConfig(
        model=args.model,
        global_batch_size=args.batch_size * n_dev,
        dtype=dtype,
        **prec_kw,
        log_every=10**9,  # silent; bench prints only metric lines on stdout
        attention_impl=args.attention_impl,
        remat=args.remat,
        fused_bn=args.fused_bn,
        fused_block=args.fused_block,
        parallel=ParallelConfig(data=max(1, n_dev // pp), pipeline=pp),
        data=data,
        allreduce=AllReduceConfig(**ar_kw),
        optimizer_sharding=(getattr(args, "optimizer_sharding", None)
                            or "none"),
        overlap_collectives=getattr(args, "overlap_collectives", True),
        pipeline_schedule=(getattr(args, "pipeline_schedule", None)
                           or "gpipe"),
        pipeline_virtual_stages=(getattr(args, "pipeline_virtual_stages", 1)
                                 or 1))
    if pp > 1 and n_dev % pp:
        raise ValueError(f"pipeline stages {pp} must divide the device "
                         f"count {n_dev}")

    quick_w = (args.warmup_steps if args.warmup_steps is not None
               else args.quick_warmup)
    quick_n = args.quick_steps
    total = quick_w + quick_n + args.steps
    _note(f"building {args.model} batch={cfg.global_batch_size} on "
          f"{n_dev} device(s)")
    t_row0 = time.perf_counter()
    mesh, model, batch_shd, state, train_step, sched, rng = loop.build(
        cfg, total)
    source = datalib.make_source(cfg, spec.input_kind, batch_shd,
                                 objective=spec.objective)
    t_compile = time.perf_counter()
    i = 0
    metrics = None
    compile_time_s = time_to_first_step_s = None
    for _ in range(quick_w):
        t_step0 = time.perf_counter() if i == 0 else None
        state, metrics = train_step(state, source.batch(i), rng)
        if t_step0 is not None:
            # First dispatch blocks the host for trace+compile (or the AOT
            # load); waiting for its outputs closes the cold-start window.
            compile_time_s = time.perf_counter() - t_step0
            jax.block_until_ready(metrics)
            time_to_first_step_s = time.perf_counter() - t_row0
        i += 1
    # Dispatch is asynchronous: wait for the last warmup step's outputs so
    # the timed window opens with the device idle.
    jax.block_until_ready(metrics)
    _note(f"compile+warmup({quick_w}) done in "
          f"{time.perf_counter() - t_compile:.1f}s; quick window starts")
    # Per-device memory annotation for every metric line this row emits:
    # peak HBM where the allocator reports it, plus params/grads/opt-state
    # resident bytes (shard-aware) and their sum — the numbers the ZeRO
    # ladder rows compare (replicated -> zero1 -> zero2 -> zero3 must fall
    # monotonically).
    mem = {}
    try:
        stats = loop._device_memory_stats(state, train_step)
        for key in ("peak_bytes_in_use", "bytes_in_use",
                    "params_bytes_per_device", "grads_bytes_per_device",
                    "opt_state_bytes_per_device",
                    "ema_params_bytes_per_device",
                    "resident_bytes_per_device"):
            if key in stats:
                mem[key] = int(stats[key])
    except Exception:
        pass  # annotation only — never costs a measurement
    # Pipeline A/B annotation: measured bubble (idle / total stage-ticks
    # over the trace-time tick instants; null on an AOT cache hit that
    # skipped tracing) next to the schedule table's analytic value — the
    # pair the gpipe-vs-1f1b acceptance check reads (docs/pipeline.md).
    pipe = {}
    if pp > 1 and pipe_tele is not None:
        bub = telemetry.pipeline_bubble_fraction(pipe_tele.snapshot())
        pipe_rec = {"stages": pp, "schedule": cfg.pipeline_schedule,
                    "virtual_stages": cfg.pipeline_virtual_stages,
                    "bubble_fraction": None if bub is None
                    else round(bub, 4)}
        try:
            from distributeddeeplearning_tpu.models import pipeline as plib
            ticks = [e for e in pipe_tele.snapshot()
                     if e.get("name") == "pipeline_tick"]
            mm = int(ticks[0]["args"]["microbatches"]) if ticks else 0
            if mm:
                pipe_rec["microbatches"] = mm
                pipe_rec["analytic_bubble_fraction"] = round(
                    plib.build_schedule(
                        cfg.pipeline_schedule, num_stages=pp,
                        num_microbatches=mm,
                        virtual_stages=cfg.pipeline_virtual_stages,
                    ).analytic_bubble_fraction(), 4)
        except Exception:
            pass  # annotation only
        pipe["pipeline"] = pipe_rec
    def timed_window(n_steps: int):
        """Dispatch up to n_steps; returns (steps_done, elapsed).

        Without a deadline: one barrier at the end (steps pipeline freely).
        With a deadline: steps are dispatched in chunks of 5 with a barrier
        + clock check between chunks — async dispatch would otherwise queue
        the whole window in milliseconds and make the deadline
        unenforceable. The extra barriers drain the dispatch queue once per
        chunk, the price of a row that can be cut on budget."""
        nonlocal state, metrics, i
        t0 = time.perf_counter()
        done = 0
        chunk = n_steps if deadline is None else 5
        while done < n_steps:
            for _ in range(min(chunk, n_steps - done)):
                if tele is None:
                    state, metrics = train_step(state, source.batch(i), rng)
                else:
                    # Traced protocol (metric name carries _tele): two extra
                    # monotonic reads per step split data_wait from dispatch.
                    ta = telemetry.now_s()
                    batch = source.batch(i)
                    tb = telemetry.now_s()
                    state, metrics = train_step(state, batch, rng)
                    tc = telemetry.now_s()
                    tele.record_span("data_wait", ta, tb, step=i)
                    tele.record_span("dispatch", tb, tc, step=i)
                i += 1
                done += 1
            if tele is None:
                jax.block_until_ready(metrics)
            else:
                with tele.span("fetch_barrier", step=i - 1):
                    jax.block_until_ready(metrics)
            if deadline is not None and time.monotonic() >= deadline:
                break
        return done, time.perf_counter() - t0

    # Cold-start annotations (docs/compile_cache.md): every record carries
    # the row's compile cost and whether the AOT executable cache served it.
    cold = {}
    # The perf/aot.py config fingerprint ties the number to the compiled
    # program it measured — two records with different fingerprints are
    # different experiments however similar the CLI looked.
    try:
        from distributeddeeplearning_tpu.perf import aot as aotlib
        cold["config_fingerprint"] = aotlib.config_fingerprint(
            cfg, total_steps=total)
    except Exception:
        pass  # annotation only
    try:
        # Policy + ramp provenance on every line (ISSUE 20): an fp32 and a
        # mixed arm (or a ramped and unramped run) must never be conflated.
        from distributeddeeplearning_tpu.config import resolve_precision
        from distributeddeeplearning_tpu.train import optim as optimlib
        cold["precision"] = resolve_precision(cfg).describe()
        cold["batch_ramp"] = optimlib.ramp_describe(cfg)
    except Exception:
        pass  # annotation only
    if compile_time_s is not None:
        cold["compile_time_s"] = round(compile_time_s, 2)
        cold["time_to_first_step_s"] = round(time_to_first_step_s, 2)
        aot = getattr(train_step, "aot", None)
        if aot is not None and aot.enabled:
            cold["aot_source"] = aot.sources.get("dp_train_step", "n/a")

    def row_extra() -> dict:
        """Per-line annotations: memory + cold-start, plus (traced rows)
        the phase breakdown aggregated from the buffered spans so far."""
        if tele is None:
            return {**mem, **cold, **pipe}
        return {**mem, **cold, **pipe,
                "phases": telemetry.phase_totals(tele.snapshot())}

    # Protocol marker: chunked barriers are measurement-protocol drift vs
    # the barrier-free round-2/3 windows (one pipeline drain per 5 steps
    # instead of one per window) — the emitted numbers must say so, or
    # they'd overwrite prior last-good entries as silently incomparable.
    mark = "" if deadline is None else " chunked"
    q_done, q_elapsed = timed_window(quick_n)
    q_rate = (cfg.global_batch_size * q_done / q_elapsed / n_dev
              if q_done else 0.0)
    if emit_quick and q_done:
        _emit_metric(args, q_rate,
                     protocol=f"quick w{quick_w}+{q_done} "
                              f"b{args.batch_size}{mark}", extra=row_extra())
    # Full-protocol window: everything so far (quick_w + quick_n >= the
    # classic 10) counts as warmup; time a fresh window of args.steps.
    if deadline is None or time.monotonic() < deadline:
        done, elapsed = timed_window(args.steps)
    else:
        done = 0
    if done:
        rate = cfg.global_batch_size * done / elapsed / n_dev
        cut = "" if done == args.steps else " cut"
        if emit_final:
            _emit_metric(args, rate,
                         protocol=f"w{quick_w + q_done}+{done} "
                                  f"b{args.batch_size}{mark}{cut}",
                         extra=row_extra())
        if tele is not None and tele.export():
            _note(f"telemetry trace written to "
                  f"{telemetry.trace_path(args.trace_dir, tele.process_index)}")
        return rate
    if q_done:
        # Deadline landed inside the quick window: the quick measurement
        # is the row's result (still post-compile, >= 1 timed step).
        if emit_final:
            _emit_metric(args, q_rate,
                         protocol=f"quick w{quick_w}+{q_done} "
                                  f"b{args.batch_size}{mark} cut",
                         extra=row_extra())
        if tele is not None and tele.export():
            _note(f"telemetry trace written to "
                  f"{telemetry.trace_path(args.trace_dir, tele.process_index)}")
        return q_rate
    raise TimeoutError(
        f"row deadline passed before any timed step (warmup {quick_w})")


def _sweep_batches(args) -> list[int]:
    """Alternate per-chip batches to try after the primary measurement."""
    if args.sweep == "none":
        return []
    if args.sweep == "auto":
        # Headline protocol only: the sweep exists to catch the session-
        # dependent 256/512 sweet-spot flip without inflating every run.
        if args.model == "resnet50" and args.batch_size == 512:
            return [256]
        return []
    return [int(b) for b in args.sweep.split(",") if int(b) != args.batch_size]


def _child(args) -> int:
    import jax

    if args.platform:
        os.environ["JAX_PLATFORMS"] = args.platform
        jax.config.update("jax_platforms", args.platform)
    from distributeddeeplearning_tpu.parallel import mesh as meshlib
    from distributeddeeplearning_tpu.perf import compile_cache
    cache_dir = compile_cache.activate(not args.no_compile_cache)
    if cache_dir:
        _note(f"compile cache at {cache_dir}")

    t0 = time.perf_counter()
    _note("initializing backend")
    # A measurement needs the chip: without --platform the devices must be
    # TPUs (parallel/mesh.backend_devices raises otherwise).
    devices = meshlib.backend_devices(args.platform or "tpu")
    _note(f"backend up: {len(devices)} x {devices[0].platform} in "
          f"{time.perf_counter() - t0:.1f}s")

    if not args.suite:
        best = _child_measure(args)
        best_batch = args.batch_size
        # Batch sweep: the throughput sweet spot moved between the sessions
        # BASELINE.md records (b256 1341 < b512 2325 one day, b256 2497 >
        # b512 2366 another). Measure the alternates and emit only a
        # STRICTLY better number — last parseable line wins, so a slower
        # alternate stays silent.
        for alt in _sweep_batches(args):
            row = copy.copy(args)
            row.batch_size = alt
            try:
                rate = _child_measure(row, emit_quick=False,
                                      emit_final=False)
            except Exception as e:  # an OOM alternate must not kill the run
                _note(f"sweep b{alt} failed: {type(e).__name__}: {e}")
                continue
            _note(f"sweep b{alt}: {rate:.1f}/chip (best {best:.1f})")
            if rate > best:
                best, best_batch = rate, alt
                _emit_metric(row, rate,
                             protocol=f"w{row.quick_warmup + row.quick_steps}"
                                      f"+{row.steps} b{alt} sweep")
        # Conv-epilogue fusion alternate: measured at the winning batch,
        # emitted ONLY if strictly faster — so the headline run captures a
        # fusion win the moment there is one, and stays silent otherwise.
        # Restricted to the headline protocol like the batch sweep.
        if (args.model == "resnet50" and args.batch_size == 512
                and not args.fused_block and args.sweep == "auto"):
            row = copy.copy(args)
            row.batch_size = best_batch
            row.fused_block = True
            try:
                rate = _child_measure(row, emit_quick=False,
                                      emit_final=False)
            except Exception as e:
                _note(f"fused-block alternate failed: "
                      f"{type(e).__name__}: {e}")
            else:
                _note(f"fused-block b{best_batch}: {rate:.1f}/chip "
                      f"(best {best:.1f})")
                if rate > best:
                    best = rate
                    _emit_metric(
                        row, rate,
                        protocol=f"w{row.quick_warmup + row.quick_steps}"
                                 f"+{row.steps} b{best_batch} sweep")
        return 0
    wanted = (set(args.suite_models.split(","))
              if args.suite_models else None)
    wanted_rows = (set(args.suite_rows.split(","))
                   if args.suite_rows else None)
    # Suite budget discipline: rows run in SUITE's
    # value-per-minute order against one deadline anchored at backend-up.
    # A row is ADMITTED only if 60% of its est_s fits in the remaining
    # budget (a partially-measured row still emits, so starting with most
    # of a row's budget available beats skipping it); a row that runs long
    # is CUT by its own deadline (min(est_s * 2, suite deadline)) instead
    # of eating the rows behind it. Skips are visible on stderr.
    suite_deadline = (time.monotonic() + args.suite_budget
                      if args.suite_budget > 0 else None)
    for row_name, model, overrides, est_s in SUITE:
        if wanted is not None and model not in wanted:
            continue
        if wanted_rows is not None and row_name not in wanted_rows:
            continue
        row = copy.copy(args)
        row.model = model
        row.attention_impl, row.remat, row.fused_bn = None, False, False
        row.fused_block = False
        row.allreduce_bucket_mb = row.allreduce_dtype = None
        row.optimizer_sharding = None
        row.overlap_collectives = True
        row.pp, row.pipeline_schedule = 1, "gpipe"
        row.pipeline_virtual_stages = 1
        row.dtype = row.precision = None
        for k, v in overrides.items():
            setattr(row, k, v)
        row_deadline = None
        if suite_deadline is not None:
            remaining = suite_deadline - time.monotonic()
            if remaining < 0.6 * est_s:
                _note(f"suite row {model} b{row.batch_size}"
                      f"{_protocol_suffix(row)} SKIPPED on budget "
                      f"(remaining {remaining:.0f}s < 0.6*est {est_s}s)")
                continue
            row_deadline = min(suite_deadline,
                               time.monotonic() + 2.0 * est_s)
        try:
            _child_measure(row, emit_quick=False, deadline=row_deadline)
        except Exception as e:  # one OOM must not sink the rest of the suite
            from distributeddeeplearning_tpu.observability import perf_report
            metric, unit = _metric_name_unit(row)
            print(json.dumps(perf_report.annotate({
                "metric": metric, "value": None, "unit": unit,
                "vs_baseline": None,
                "protocol": _protocol_suffix(row).strip() or None,
                "error": f"{type(e).__name__}: {e}"[:600],
            }, provenance="error")), flush=True)
    return 0


def _emit_error(args, msg: str, attempts: list | None = None) -> None:
    from distributeddeeplearning_tpu.observability import perf_report
    metric, unit = _metric_name_unit(args)
    rec = {
        "metric": metric,
        "value": None,
        "unit": unit,
        "vs_baseline": None,
        "error": msg[-800:],
    }
    # with_backend=False: this runs in the PARENT, which must never
    # initialize jax — a parent that holds the chip starves its child.
    perf_report.annotate(rec, provenance="error", attempts=attempts,
                         with_backend=False)
    print(json.dumps(rec), flush=True)


def _last_summary(stdout: str):
    """Last ``{"summary": ...}`` line a train.py child printed, or None.
    Under ``launch.py --max-restarts`` the crashed attempt prints no
    summary, so the last one belongs to the attempt that finished."""
    for line in reversed((stdout or "").splitlines()):
        if '"summary"' not in line:
            continue
        try:
            return json.loads(line)["summary"]
        except (ValueError, KeyError, TypeError):
            continue
    return None


def _run_chaos(args) -> int:
    """Chaos recovery benchmark (CPU, no chip needed): run the same tiny
    synthetic job twice — once clean, once killed by fault injection at
    step F under ``launch.py --max-restarts 1`` — and report the wall-clock
    overhead of surviving one fault (relaunch + backend re-init +
    re-compile + checkpoint restore + replayed steps). Deterministic on
    purpose: ``crash@F`` is attempt-scoped (robustness/faults.py), so the
    restarted attempt runs fault-free to completion.

    All runs share one fresh compile cache (perf/compile_cache.py): the
    clean run cold-compiles and populates it, so the faulted run's restart
    attempt recovers *warm* — measuring the recovery path users actually
    hit when the launcher exports the cache to every attempt. Pass
    ``--chaos-cold`` to additionally rerun the faulted job with the cache
    disabled and report the cold-recovery overhead next to the warm one."""
    import shutil
    import tempfile

    from distributeddeeplearning_tpu.observability import perf_report

    base = os.path.dirname(os.path.abspath(__file__))
    steps, fail_at, every = args.chaos_steps, args.chaos_fail_at, 2
    metric = "chaos_recovery_overhead"
    if not 0 < fail_at < steps:
        # with_backend=False here and below: the chaos harness is the
        # PARENT — it spawns launch.py children and never initializes jax.
        print(json.dumps(perf_report.annotate({
            "metric": metric, "value": None, "unit": "s per fault",
            "error": f"--chaos-fail-at must be in (0, {steps})"},
            provenance="error", with_backend=False)), flush=True)
        return 1
    root = tempfile.mkdtemp(prefix="ddl_chaos_")
    cache = os.path.join(root, "cache")
    os.makedirs(cache, exist_ok=True)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # A fresh temp cache root on purpose: this bench measures cold against
    # warm recovery, so it must start from an empty cache it owns.
    env["JAX_COMPILATION_CACHE_DIR"] = cache

    def train_cmd(ckpt_dir: str, extra: tuple = ()) -> list[str]:
        return [sys.executable, os.path.join(base, "train.py"),
                "--backend", "cpu", "--synthetic",
                "--model", "resnet18_thin", "--image-size", "32",
                "--batch-size", "8", "--dtype", "float32",
                "--steps", str(steps), "--checkpoint-every", str(every),
                "--log-every", "1000", "--checkpoint-dir", ckpt_dir,
                *extra]

    def fail(stage: str, proc) -> int:
        tail = (proc.stderr or "")[-600:]
        print(json.dumps(perf_report.annotate({
            "metric": metric, "value": None, "unit": "s per fault",
            "error": f"{stage} run failed rc={proc.returncode}: {tail}"},
            provenance="error", with_backend=False)), flush=True)
        return 1

    def faulted_run(tag: str, *extra: str):
        launch_cmd = [sys.executable, os.path.join(base, "launch.py"),
                      "--num-processes", "1", "--max-restarts", "1",
                      "--backoff", "0.2", "--",
                      *train_cmd(os.path.join(root, tag),
                                 ("--fault-plan", f"crash@{fail_at}",
                                  *extra))]
        t = time.monotonic()
        proc = subprocess.run(launch_cmd, env=env, capture_output=True,
                              text=True, timeout=420)
        return time.monotonic() - t, proc

    try:
        t0 = time.monotonic()
        populate = subprocess.run(
            train_cmd(os.path.join(root, "populate")), env=env,
            capture_output=True, text=True, timeout=420)
        w_populate = time.monotonic() - t0
        if populate.returncode != 0:
            return fail("populate", populate)

        # The warm BASELINE must itself run warm: comparing a warm faulted
        # run against the cold populate run would subtract the populate
        # run's compile time and report a (nonsensical) negative overhead.
        t0 = time.monotonic()
        clean = subprocess.run(
            train_cmd(os.path.join(root, "clean")), env=env,
            capture_output=True, text=True, timeout=420)
        w_clean = time.monotonic() - t0
        if clean.returncode != 0:
            return fail("clean", clean)

        w_faulted, faulted = faulted_run("faulted")
        if faulted.returncode != 0 or "restart 1/1" not in faulted.stderr:
            return fail("faulted", faulted)

        # Checkpoint cadence fixes the resume point analytically: the loop
        # saves at step F before the injector kills it only when F is on
        # cadence, so the restart replays F - floor(F/every)*every steps.
        resumed_from = (fail_at // every) * every
        rec = {
            "metric": metric,
            "value": round(w_faulted - w_clean, 2),
            "unit": "s per fault",
            "vs_baseline": None,
            "steps_lost": fail_at - resumed_from,
            "restarts": 1,
            "clean_s": round(w_clean, 1),
            "clean_cold_s": round(w_populate, 1),
            "faulted_s": round(w_faulted, 1),
            "cache": "warm",
            "protocol": (f"cpu resnet18_thin b8 {steps} steps, "
                         f"crash@{fail_at}, ckpt every {every}, shared "
                         f"compile cache (a populate run cold-compiles it, "
                         f"then clean baseline, faulted run, and restart "
                         f"all recover warm); overhead = relaunch + "
                         f"re-init + cached compile + restore + "
                         f"{fail_at - resumed_from} replayed step(s)"),
        }
        # The restarted attempt's own cold-start telemetry (train/loop.py
        # stamps both into the run summary the child prints on stdout).
        summary = _last_summary(faulted.stdout)
        if summary:
            for k in ("compile_time_s", "time_to_first_step_s"):
                if summary.get(k) is not None:
                    rec[f"recovery_{k}"] = summary[k]

        if getattr(args, "chaos_cold", False):
            w_cold, cold = faulted_run("faulted_cold", "--no-compile-cache")
            if cold.returncode != 0 or "restart 1/1" not in cold.stderr:
                return fail("faulted_cold", cold)
            rec["faulted_cold_s"] = round(w_cold, 1)
            # Cold-vs-cold: the cache-off faulted run's attempt 0 compiles
            # cold too, so its baseline is the cold populate run.
            rec["overhead_cold_s"] = round(w_cold - w_populate, 2)
            rec["recovery_compile_saved_s"] = round(w_cold - w_faulted, 2)
            cold_summary = _last_summary(cold.stdout)
            if cold_summary:
                for k in ("compile_time_s", "time_to_first_step_s"):
                    if cold_summary.get(k) is not None:
                        rec[f"recovery_cold_{k}"] = cold_summary[k]
        perf_report.annotate(rec, provenance="fresh", with_backend=False)
        print(json.dumps(rec), flush=True)
        return 0
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _run_elastic_chaos(args) -> int:
    """Elastic soak benchmark (CPU, no chip needed): a 2-host x 2-device
    dp4 transformer job under ``launch.py --elastic`` loses host 1 to a
    ``host_lost`` fault (SIGKILL + heartbeat suppressed), auto-re-forms at
    dp2 from the last good checkpoint, then grows back to dp4 when the
    survivor announces a ``host_rejoin`` — all with the global batch fixed,
    so the trajectory matches an uninterrupted run to the last float32 ulp
    (tests/test_elastic_resume.py proves that part; this benchmark measures
    the OUTAGE). The record's value is ``reconfiguration_time_s`` — fault
    detection to first post-resume step, both ends on the shared local
    CLOCK_MONOTONIC — as stamped into the final attempt's run summary by
    train/loop.py."""
    import shutil
    import tempfile

    from distributeddeeplearning_tpu import hostmesh
    from distributeddeeplearning_tpu.observability import perf_report

    base = os.path.dirname(os.path.abspath(__file__))
    metric = "reconfiguration_time_s"
    steps, lose_at, rejoin_at = 12, 4, 8
    root = tempfile.mkdtemp(prefix="ddl_elastic_")
    cache = os.path.join(root, "cache")
    os.makedirs(cache, exist_ok=True)
    env = dict(os.environ)
    env.update(hostmesh.virtual_host_env(2))  # 2 fake devices per host
    env["JAX_COMPILATION_CACHE_DIR"] = cache  # fresh root: cold by design

    def fail(stage: str, proc=None, detail: str = "") -> int:
        tail = detail or (getattr(proc, "stderr", "") or "")[-600:]
        rc = getattr(proc, "returncode", None)
        print(json.dumps(perf_report.annotate({
            "metric": metric, "value": None, "unit": "s",
            "error": f"{stage} failed rc={rc}: {tail}"},
            provenance="error", with_backend=False)), flush=True)
        return 1

    cmd = [sys.executable, os.path.join(base, "launch.py"),
           "--num-processes", "2", "--elastic",
           "--max-restarts", "2", "--backoff", "0.2",
           "--heartbeat-dir", os.path.join(root, "hb"),
           # Attempt 0: host 1 dies at dp4 -> shrink to dp2. Attempt 1:
           # the survivor (original host 0) announces a rejoin -> graceful
           # stop, grow back to dp4. Attempt 2 runs fault-free to the end.
           "--child-fault-plan", f"1:host_lost@{lose_at}",
           "--child-fault-plan", f"0:host_rejoin@{rejoin_at}:a1",
           "--",
           sys.executable, os.path.join(base, "train.py"),
           "--backend", "cpu", "--synthetic", "--model", "bert_tiny",
           "--seq-len", "32", "--batch-size", "8", "--dtype", "float32",
           "--dp", "4", "--steps", str(steps),
           "--checkpoint-every", "2", "--log-every", "1000",
           "--checkpoint-dir", os.path.join(root, "ckpt")]
    try:
        t0 = time.monotonic()
        try:
            proc = subprocess.run(cmd, env=env, capture_output=True,
                                  text=True, timeout=900)
        except subprocess.TimeoutExpired as e:
            return fail("soak", detail=f"timeout after {e.timeout}s")
        wall = time.monotonic() - t0
        if proc.returncode != 0:
            return fail("soak", proc)
        if "elastic re-formation (host_lost)" not in proc.stderr:
            return fail("soak", proc, detail="no host_lost re-formation in "
                        "launcher output")
        summary = _last_summary(proc.stdout)
        if not summary or summary.get(metric) is None:
            return fail("soak", proc,
                        detail="final summary carries no "
                        f"{metric} (elastic event not delivered?)")
        event = summary.get("elastic_event") or {}
        grew = "elastic re-formation (host_rejoin)" in proc.stderr
        rec = {
            "metric": metric,
            "value": round(float(summary[metric]), 2),
            "unit": "s per re-formation",
            "vs_baseline": None,
            "trigger": event.get("trigger"),
            "degree_before": event.get("degree_before"),
            "degree_after": event.get("degree_after"),
            "reformations": proc.stderr.count("# launcher: elastic event:"),
            "grew_back": grew,
            # Rendezvous-path observability: the outage's detect -> drain ->
            # restore -> compile -> first-step split and the membership
            # epoch the final attempt resumed under (train/loop.py).
            "phases": summary.get("reconfiguration_phases"),
            "membership_epoch": event.get("epoch"),
            "final_step": summary.get("final_step"),
            "total_s": round(wall, 1),
            "protocol": (f"cpu bert_tiny b8 seq32 {steps} steps, 2 hosts x "
                         f"2 devices, host_lost@{lose_at} shrinks dp4->dp2, "
                         f"host_rejoin@{rejoin_at} grows dp2->dp4, global "
                         f"batch fixed; value = launcher fault detection -> "
                         f"first post-resume step of the last re-formation "
                         f"(shared CLOCK_MONOTONIC)"),
        }
        perf_report.annotate(rec, provenance="fresh")
        print(json.dumps(rec), flush=True)
        return 0
    finally:
        shutil.rmtree(root, ignore_errors=True)


def _parse_record(line: str):
    """A parseable bench record (measurement or per-config error), or None."""
    if not line.startswith("{"):
        return None
    try:
        rec = json.loads(line)
    except ValueError:
        return None
    return rec if isinstance(rec, dict) and "metric" in rec else None


def _run_attempt(child_cmd, timeout: float, *,
                 relay_errors: bool) -> tuple[int, int, str, object]:
    """Run one child, RELAYING metric lines to stdout as they appear.

    Returns (measurements_relayed, error_records_relayed, stderr_tail, rc).
    The relay is the point:
    once a line is printed it survives any outer kill. ``relay_errors``
    (suite mode) also passes through per-config error records so a failed
    row is visible, not silently absent; default mode keeps them back
    because the driver takes the LAST parseable line and an error record
    must never shadow a real measurement."""
    proc = subprocess.Popen(child_cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
    relayed = [0, 0]  # [measurements, error records]
    err_lines: list[str] = []

    def _pump_out():
        for line in proc.stdout:
            line = line.strip()
            rec = _parse_record(line)
            if rec is None:
                continue
            if rec.get("value") is not None:
                print(line, flush=True)
                relayed[0] += 1
            elif relay_errors:
                print(line, flush=True)
                relayed[1] += 1

    def _pump_err():
        for line in proc.stderr:
            err_lines.append(line.rstrip())
            del err_lines[:-40]

    threads = [threading.Thread(target=_pump_out, daemon=True),
               threading.Thread(target=_pump_err, daemon=True)]
    for t in threads:
        t.start()
    start = time.monotonic()
    rc: object = None
    while True:
        try:
            rc = proc.wait(timeout=1)
            break
        except subprocess.TimeoutExpired:
            pass
        if time.monotonic() - start >= timeout:
            proc.kill()
            proc.wait()
            rc = f"timeout {int(timeout)}s"
            break
    for t in threads:
        t.join(timeout=5)
    return relayed[0], relayed[1], "\n".join(err_lines), rc


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50")
    # 512/chip was the sweet spot of BASELINE.md's 2026-07-29 sweep (2325
    # img/s/chip vs 1341 at 256 and 1978 at 1024, before PRs 1-20 and on
    # another JAX; not measured on the current code).
    p.add_argument("--batch-size", type=int, default=512)
    p.add_argument("--seq-len", type=int, default=512,
                   help="sequence length for token (BERT/GPT) models")
    p.add_argument("--mlm-max-predictions", type=int, default=-1,
                   help="gather-mode MLM head width; -1 = auto "
                        "(round(0.15*seq_len), the canonical BERT recipe), "
                        "0 = dense full-sequence logits")
    p.add_argument("--attention-impl", default=None,
                   choices=[None, "dense", "flash", "ring", "zigzag"],
                   help="attention implementation for token models")
    p.add_argument("--remat", action="store_true",
                   help="rematerialize transformer layers in backward")
    p.add_argument("--fused-bn", action="store_true",
                   help="Pallas fused BN(+residual)+ReLU kernels (CNNs)")
    p.add_argument("--fused-block", action="store_true",
                   help="conv-epilogue fusion: 1x1 convs as Pallas "
                        "matmul+BN (resnet50/101/152)")
    p.add_argument("--allreduce-bucket-mb", type=float, default=None,
                   help="gradient tensor-fusion bucket size in MB "
                        "(parallel/collectives.py); 0 = per-leaf reduction "
                        "(the unfused A/B reference, emitted under its own "
                        "_perleaf_ar metric name); unset = config default "
                        "(fused, 4 MB)")
    p.add_argument("--allreduce-dtype", default=None,
                   choices=[None, "float32", "bfloat16"],
                   help="gradient all-reduce payload dtype (bfloat16 = "
                        "compressed wire payload, fp32 restored after)")
    p.add_argument("--optimizer-sharding", default=None,
                   choices=[None, "none", "zero1", "zero2", "zero3"],
                   help="ZeRO sharding ladder (parallel/zero.py): zero1 = "
                        "sharded optimizer state, zero2 = + grads stay "
                        "reduce-scattered per bucket, zero3 = + params "
                        "1/N-chunked, all-gathered per bucket; each stage "
                        "emitted under its own _<stage> metric name; unset "
                        "= replicated optimizer")
    p.add_argument("--no-overlap-collectives", dest="overlap_collectives",
                   action="store_false", default=True,
                   help="serialize the zero2/zero3 reduce-scatters after "
                        "backward instead of issuing them per fusion "
                        "bucket as cotangents are produced (A/B for the "
                        "overlap win; marked no-overlap in the protocol)")
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline stages (models/pipeline.py); must divide "
                        "the device count, remaining devices become the "
                        "data axis; the model must be a *_pp registry "
                        "variant with matching pipeline_stages")
    p.add_argument("--pipeline-schedule", default="gpipe",
                   choices=["gpipe", "1f1b"],
                   help="pipeline schedule: gpipe = fill/drain, 1f1b = "
                        "interleaved one-forward-one-backward over "
                        "--pipeline-virtual-stages chunks per stage; each "
                        "(stages, schedule, V) tuple reports under its own "
                        "metric name and records carry the measured "
                        "pipeline_bubble_fraction (docs/pipeline.md)")
    p.add_argument("--pipeline-virtual-stages", type=int, default=1,
                   help="virtual chunks per stage for --pipeline-schedule "
                        "1f1b (V>1 shrinks the bubble to "
                        "(P-1)/(M*V+P-1)); must divide layers-per-stage")
    p.add_argument("--dtype", default=None,
                   choices=[None, "float32", "bfloat16"],
                   help="compute dtype via the legacy knob (unset = the "
                        "bfloat16 protocol of record); subsumed by "
                        "--precision when that is set")
    p.add_argument("--precision", default=None,
                   choices=[None, "fp32", "mixed"],
                   help="explicit precision policy (config.PrecisionPolicy) "
                        "for the large-batch %%-of-peak A/B: 'fp32' = "
                        "everything float32 scored against the fp32 roof, "
                        "'mixed' = bf16 compute + fp32 master weights + "
                        "dynamic loss scaling scored against the bf16 roof; "
                        "each arm emits under its own _<precision> metric "
                        "name (docs/mixed_precision.md)")
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--quick-steps", type=int, default=8,
                   help="timed steps in the progressive quick window")
    p.add_argument("--quick-warmup", type=int, default=3,
                   help="warmup steps before the quick window")
    p.add_argument("--warmup-steps", type=int, default=None,
                   help="compat alias for --quick-warmup (pre-progressive "
                        "protocol name)")
    p.add_argument("--sweep", default="auto",
                   help="alternate per-chip batch sizes to try after the "
                        "primary measurement (comma list, 'none', or "
                        "'auto' = 256 for the resnet50 b512 headline); "
                        "an alternate line is emitted only if faster")
    p.add_argument("--suite-models", default=None,
                   help="with --suite: only measure rows whose model is "
                        "in this comma list (re-run a single row)")
    p.add_argument("--suite-rows", default=None,
                   help="with --suite: only measure rows with these NAMES "
                        "(comma list, see SUITE; runs in suite order) — "
                        "unlike --suite-models this selects EXACT rows, "
                        "e.g. one of the bert_base protocol variants; "
                        "names stay valid when rows are inserted or "
                        "reordered")
    p.add_argument("--suite", action="store_true",
                   help="measure every acceptance config, one line each")
    p.add_argument("--suite-budget", type=int, default=-1,
                   help="wall budget (s) for the suite rows themselves, "
                        "anchored after backend init; rows that don't fit "
                        "are skipped with a stderr note and a row that "
                        "runs long is cut at 2x its estimate. -1 = derive "
                        "from --budget minus an init margin; 0 = no "
                        "budget (measure every row to completion)")
    p.add_argument("--platform", default=None,
                   help="force a JAX platform (e.g. cpu) for smoke runs")
    p.add_argument("--trace-dir", default=None,
                   help="write a Chrome-trace JSON (phase spans + per-bucket "
                        "collective spans) for the timed windows under this "
                        "directory, and attach a per-phase breakdown to the "
                        "metric record; traced rows report under a _tele "
                        "metric name because tracing reads the clock inside "
                        "the timed loop (protocol drift by design — it is "
                        "how the overhead A/B measures itself)")
    p.add_argument("--attempt-timeout", type=int, default=480,
                   help="hard wall-clock limit per measurement attempt (s); "
                        "a hanging child must leave the parent time to "
                        "print the error record before any outer driver "
                        "timeout")
    p.add_argument("--attempts", type=int, default=3)
    p.add_argument("--budget", type=int, default=1200,
                   help="total wall-clock budget across all attempts (s); "
                        "guarantees the error record is printed before any "
                        "outer driver timeout can strike")
    p.add_argument("--chaos", action="store_true",
                   help="CPU recovery-overhead benchmark: time a clean tiny "
                        "run vs the same run crashed at --chaos-fail-at and "
                        "auto-restarted by launch.py; emits one "
                        "chaos_recovery_overhead record (no chip needed)")
    p.add_argument("--chaos-steps", type=int, default=8,
                   help="total steps of each --chaos run")
    p.add_argument("--chaos-fail-at", type=int, default=5,
                   help="step after which the faulted --chaos run crashes")
    p.add_argument("--chaos-cold", action="store_true",
                   help="--chaos: also run the faulted job with the compile "
                        "cache disabled and report the cold-cache recovery "
                        "overhead next to the warm one (roughly doubles the "
                        "chaos runtime)")
    p.add_argument("--chaos-elastic", action="store_true",
                   help="CPU elastic soak benchmark: a 2-host dp4 "
                        "transformer job loses a host (host_lost fault), "
                        "auto-re-forms at dp2, grows back to dp4 on rejoin, "
                        "and reports the measured reconfiguration_time_s "
                        "(fault detection -> first post-resume step) as one "
                        "provenance-stamped record (no chip needed)")
    p.add_argument("--no-compile-cache", action="store_true",
                   help="run without the persistent compile cache + AOT "
                        "step executables (docs/compile_cache.md; it lives "
                        "at $JAX_COMPILATION_CACHE_DIR, else "
                        "<repo>/.cache/jax_compile)")
    p.add_argument("--run-child", action="store_true", help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.chaos:
        return _run_chaos(args)
    if args.chaos_elastic:
        return _run_elastic_chaos(args)

    if args.allreduce_bucket_mb is not None and args.allreduce_bucket_mb < 0:
        p.error(f"--allreduce-bucket-mb must be >= 0 "
                f"(got {args.allreduce_bucket_mb}); 0 selects per-leaf "
                f"reduction")
    # Same up-front rejects as train.py / models/pipeline.build_schedule:
    # a malformed schedule must die at parse time, not after backend init.
    if args.pp < 1:
        p.error(f"--pp must be >= 1 (got {args.pp})")
    if args.pipeline_virtual_stages < 1:
        p.error(f"--pipeline-virtual-stages must be >= 1 "
                f"(got {args.pipeline_virtual_stages})")
    if args.pipeline_virtual_stages > 1 and args.pipeline_schedule != "1f1b":
        p.error("--pipeline-virtual-stages > 1 requires "
                "--pipeline-schedule 1f1b (gpipe has no virtual chunks)")
    try:  # fail a malformed --sweep at parse time, not after the primary
        _sweep_batches(args)
    except ValueError:
        p.error(f"--sweep {args.sweep!r}: expected a comma list of ints, "
                f"'auto', or 'none'")
    if args.suite and args.sweep not in ("auto", "none"):
        p.error("--sweep is a headline-run option; suite rows pin their "
                "measured sweet-spot batches (see SUITE)")
    if args.suite_models:
        known = {m for _n, m, _o, _e in SUITE}
        asked = {s.strip() for s in args.suite_models.split(",") if s.strip()}
        if not asked or asked - known:
            p.error(f"--suite-models: unknown model(s) "
                    f"{sorted(asked - known) or args.suite_models!r}; "
                    f"suite rows: {sorted(known)}")
        args.suite_models = ",".join(sorted(asked))
    if args.suite_rows:
        if args.suite_models:
            p.error("--suite-rows and --suite-models are mutually "
                    "exclusive (rows select exact entries)")
        row_names = [n for n, _m, _o, _e in SUITE]
        asked = [s.strip() for s in args.suite_rows.split(",") if s.strip()]
        resolved, unknown = [], []
        for s in asked:
            if s in row_names:
                resolved.append(s)
            elif s.isdigit() and int(s) < len(row_names):
                # Deprecated alias: positional indices predate named rows
                # and silently select the wrong row when the suite is
                # reordered — accept them for old drivers, but say so.
                print(f"# bench: --suite-rows index {s} is deprecated, "
                      f"resolving to row {row_names[int(s)]!r}; indices "
                      f"break when suite rows are inserted or reordered",
                      file=sys.stderr, flush=True)
                resolved.append(row_names[int(s)])
            else:
                unknown.append(s)
        if not asked or unknown:
            p.error(f"--suite-rows: unknown row name(s) "
                    f"{unknown or args.suite_rows!r}; suite rows: "
                    f"{row_names}")
        args.suite_rows = ",".join(dict.fromkeys(resolved))  # dedupe, ordered

    if args.run_child:
        return _child(args)

    child_cmd = [sys.executable, os.path.abspath(__file__), "--run-child",
                 "--model", args.model,
                 "--batch-size", str(args.batch_size),
                 "--seq-len", str(args.seq_len),
                 "--steps", str(args.steps),
                 "--quick-steps", str(args.quick_steps),
                 "--quick-warmup", str(args.warmup_steps
                                       if args.warmup_steps is not None
                                       else args.quick_warmup),
                 "--mlm-max-predictions", str(args.mlm_max_predictions)]
    child_cmd += ["--sweep", args.sweep]
    if args.platform:
        child_cmd += ["--platform", args.platform]
    if args.attention_impl:
        child_cmd += ["--attention-impl", args.attention_impl]
    if args.remat:
        child_cmd += ["--remat"]
    if args.fused_bn:
        child_cmd += ["--fused-bn"]
    if args.fused_block:
        child_cmd += ["--fused-block"]
    if args.allreduce_bucket_mb is not None:
        child_cmd += ["--allreduce-bucket-mb", str(args.allreduce_bucket_mb)]
    if args.allreduce_dtype:
        child_cmd += ["--allreduce-dtype", args.allreduce_dtype]
    if args.optimizer_sharding:
        child_cmd += ["--optimizer-sharding", args.optimizer_sharding]
    if not args.overlap_collectives:
        child_cmd += ["--no-overlap-collectives"]
    if args.dtype:
        child_cmd += ["--dtype", args.dtype]
    if args.precision:
        child_cmd += ["--precision", args.precision]
    if args.pp > 1:
        child_cmd += ["--pp", str(args.pp)]
    if args.pipeline_schedule != "gpipe":
        child_cmd += ["--pipeline-schedule", args.pipeline_schedule]
    if args.pipeline_virtual_stages != 1:
        child_cmd += ["--pipeline-virtual-stages",
                      str(args.pipeline_virtual_stages)]
    if args.trace_dir:
        child_cmd += ["--trace-dir", args.trace_dir]
    if args.no_compile_cache:
        child_cmd += ["--no-compile-cache"]
    if args.suite:
        child_cmd += ["--suite"]
        if args.suite_models:
            child_cmd += ["--suite-models", args.suite_models]
        if args.suite_rows:
            child_cmd += ["--suite-rows", args.suite_rows]
        args.attempt_timeout = max(args.attempt_timeout, args.budget)

    last_err = "no attempt ran"
    attempt_log: list = []  # retry history for the error record's schema
    deadline = time.monotonic() + args.budget
    for attempt in range(args.attempts):
        if attempt:
            time.sleep(RETRY_BACKOFF_SEC[min(attempt - 1,
                                             len(RETRY_BACKOFF_SEC) - 1)])
        remaining = deadline - time.monotonic()
        if remaining < 30:
            last_err += "; budget exhausted"
            attempt_log.append({"attempt": attempt + 1,
                                "rc": "skipped: budget exhausted"})
            break
        # Children stamp their fresh records with the attempt that produced
        # them (observability/perf_report.py).
        os.environ["DDL_BENCH_ATTEMPT"] = str(attempt + 1)
        cmd = list(child_cmd)
        if args.suite:
            # The child's row budget excludes backend init (its clock
            # starts after jax.devices() returns) but must leave the
            # parent room to relay the last row before --budget ends —
            # derived from the budget REMAINING at this attempt, so a
            # retry's gating matches the time it actually has (a first
            # derivation reused verbatim would admit rows the parent's
            # deadline then kills mid-row). Floor of 60s: a derived
            # budget must never collapse to 0, which means "no gating".
            suite_budget = (args.suite_budget if args.suite_budget >= 0
                            else max(60, int(remaining) - 120))
            cmd += ["--suite-budget", str(suite_budget)]
        n_good, n_err, err_tail, rc = _run_attempt(
            cmd, timeout=min(args.attempt_timeout, remaining),
            relay_errors=args.suite)
        if n_good and rc == 0 and not n_err:
            return 0
        if n_good or n_err:
            # Measurements already on stdout stay valid, but the run is not
            # a success: the child died or was cut (rc) or a suite row
            # failed. No error record — it would become the last line and
            # shadow real data — just the exit code and a note.
            print(f"# bench: incomplete (child rc={rc}, {n_good} "
                  f"measurement(s), {n_err} failed row(s)); lines above "
                  f"are valid, the rest unmeasured",
                  file=sys.stderr, flush=True)
            return 1
        last_err = f"attempt {attempt + 1}: rc={rc}: {err_tail[-600:]}"
        attempt_log.append({"attempt": attempt + 1, "rc": str(rc),
                            "relayed_lines": 0})

    _emit_error(args, last_err, attempts=attempt_log)
    return 1


if __name__ == "__main__":
    sys.exit(main())
