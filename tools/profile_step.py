#!/usr/bin/env python
"""Op-level device-time profile of a train step (the BASELINE.md method).

Runs a few steps of any config under ``jax.profiler.trace`` with a perfetto
JSON trace, then aggregates on-device slice durations by the part of the
program each operation belongs to: the step's own anatomy
(``train_step.anatomy()``, analysis/anatomy.py — phase and part read from
the scope and kernel names the program compiled in), joined to the trace on
the instruction name. It needs a live chip to say anything about TPU.

    python tools/profile_step.py --model resnet50 --batch-size 256 \
        [--fused-bn] [--steps 6] [--top 25]

Prints one JSON line: total device ms/step and a per-part + per-op-top-N
breakdown (ms/step, averaged over the traced steps).
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import re
import sys
import tempfile
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run_and_trace(args, log_dir: str) -> dict:
    """Trace ``args.steps`` steps into ``log_dir``; returns the step's
    anatomy table (``{instruction name: op_name}``)."""
    import jax

    from distributeddeeplearning_tpu import data as datalib
    from distributeddeeplearning_tpu.config import (
        DataConfig, ParallelConfig, TrainConfig, resolve_mlm_max_predictions)
    from distributeddeeplearning_tpu.models import model_spec
    from distributeddeeplearning_tpu.train import loop

    n_dev = jax.device_count()
    spec = model_spec(args.model)
    tokens = spec.input_kind == "tokens"
    mlm = resolve_mlm_max_predictions(-1, args.seq_len, spec.objective)
    data = (DataConfig(synthetic=True, dataset="mlm", seq_len=args.seq_len,
                       mlm_max_predictions=mlm)
            if tokens else DataConfig(synthetic=True))
    cfg = TrainConfig(
        model=args.model, global_batch_size=args.batch_size * n_dev,
        dtype="bfloat16", log_every=10**9, fused_bn=args.fused_bn,
        fused_block=args.fused_block,
        attention_impl=args.attention_impl, remat=args.remat,
        parallel=ParallelConfig(data=n_dev), data=data)
    mesh, model, batch_shd, state, train_step, sched, rng = loop.build(
        cfg, args.warmup + args.steps)
    source = datalib.make_source(cfg, spec.input_kind, batch_shd,
                                 objective=spec.objective)
    i = 0
    metrics = None
    for _ in range(args.warmup):
        state, metrics = train_step(state, source.batch(i), rng)
        i += 1
    jax.device_get(metrics)
    with jax.profiler.trace(log_dir, create_perfetto_trace=True):
        for _ in range(args.steps):
            state, metrics = train_step(state, source.batch(i), rng)
            i += 1
        jax.device_get(metrics)
    return train_step.anatomy()


def summarize(log_dir: str, steps: int, top: int, table: dict):
    """Device ms/step of the newest perfetto trace under ``log_dir``: total,
    by ``phase/part`` of the step's anatomy ``table``, and the top ops. An
    operation the table does not hold is booked under ``-/unattributed``."""
    from distributeddeeplearning_tpu.analysis import anatomy

    paths = glob.glob(os.path.join(
        log_dir, "**", "*perfetto_trace.json.gz"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no perfetto trace under {log_dir}")
    with gzip.open(sorted(paths)[-1], "rt") as f:
        trace = json.load(f)
    events = trace["traceEvents"] if isinstance(trace, dict) else trace
    # Keep complete slices from device tracks (TPU/device PIDs). Perfetto
    # process names live in metadata events; device tracks are named like
    # "/device:TPU:0" / "TPU:0" / "Device N".
    pid_names = {}
    tid_names = {}
    for ev in events:
        if ev.get("ph") != "M":
            continue
        if ev.get("name") == "process_name":
            pid_names[ev.get("pid")] = ev.get("args", {}).get("name", "")
        elif ev.get("name") == "thread_name":
            tid_names[(ev.get("pid"), ev.get("tid"))] = (
                ev.get("args", {}).get("name", ""))
    device_pids = {pid for pid, name in pid_names.items()
                   if re.search(r"tpu|device|xla:#", name, re.I)
                   and not re.search(r"python|host", name, re.I)}
    # The device process carries several stacked tracks (XLA Modules, Steps,
    # XLA Ops, TraceMe); only the "XLA Ops" line holds leaf op slices —
    # summing all lines would double-count every nesting level.
    op_keys = {key for key, name in tid_names.items()
               if key[0] in device_pids and "op" in name.lower()}
    per_op = collections.Counter()
    for ev in events:
        if ev.get("ph") != "X" or (ev.get("pid"), ev.get("tid")) not in op_keys:
            continue
        per_op[ev.get("name", "?")] += ev.get("dur", 0)  # microseconds
    if not per_op:  # fall back: no recognized op track
        for ev in events:
            if ev.get("ph") == "X":
                per_op[ev.get("name", "?")] += ev.get("dur", 0)
    parts = collections.Counter({
        "/".join(key): us
        for key, us in anatomy.by_part(per_op, table).items()})
    total_ms = sum(per_op.values()) / 1000 / steps
    return {
        "device_ms_per_step": round(total_ms, 2),
        "by_part_ms": {k: round(v / 1000 / steps, 2)
                       for k, v in parts.most_common()},
        "top_ops_ms": {name: round(us / 1000 / steps, 2)
                       for name, us in per_op.most_common(top)},
        "device_tracks": sorted(pid_names[p] for p in device_pids),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50")
    p.add_argument("--batch-size", type=int, default=256)
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--attention-impl", default=None)
    p.add_argument("--remat", action="store_true")
    p.add_argument("--fused-bn", action="store_true")
    p.add_argument("--fused-block", action="store_true")
    p.add_argument("--warmup", type=int, default=4)
    p.add_argument("--steps", type=int, default=6)
    p.add_argument("--top", type=int, default=25)
    p.add_argument("--keep-trace", default=None,
                   help="directory to keep the raw trace in (default: tmp)")
    args = p.parse_args(argv)

    log_dir = args.keep_trace or tempfile.mkdtemp(prefix="ddl_profile_")
    t0 = time.time()
    table = run_and_trace(args, log_dir)
    out = summarize(log_dir, args.steps, args.top, table)
    out["model"] = args.model
    out["batch_per_chip"] = args.batch_size
    out["fused_bn"] = args.fused_bn
    out["fused_block"] = args.fused_block
    # Analytic-MFU cross-check against DEVICE-BUSY time (not wall):
    # by_part_ms should roughly partition this much useful work.
    try:
        from distributeddeeplearning_tpu.config import (
            resolve_mlm_max_predictions)
        from distributeddeeplearning_tpu.models import flops as flopslib
        from distributeddeeplearning_tpu.models import model_spec
        spec = model_spec(args.model)
        mlm = (resolve_mlm_max_predictions(-1, args.seq_len,
                                           spec.objective)
               if spec.input_kind == "tokens" else 0)
        per_ex = flopslib.train_flops_per_example(
            args.model, seq_len=args.seq_len, mlm_positions=mlm)
        if per_ex and len(out.get("device_tracks", [])) == 1:
            busy_s = out["device_ms_per_step"] / 1e3
            tflops = args.batch_size * per_ex / busy_s / 1e12
            out["busy_tflops_per_sec"] = round(tflops, 2)
            import jax
            peak = flopslib.bf16_peak_flops(
                jax.devices()[0].device_kind)
            if peak:
                out["busy_mfu_pct"] = round(
                    100.0 * tflops * 1e12 / peak, 1)
    except Exception:
        pass
    out["wall_s"] = round(time.time() - t0, 1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
