#!/usr/bin/env python
"""Environment diagnosis: one command that answers "why doesn't it run?".

    python tools/doctor.py [--probe-timeout 45]

Checks, each printed as one JSON line (never raises, never hangs):
accelerator reachability (a subprocess probe with a hard timeout, so this
process never holds the chip), virtual CPU mesh, library versions,
native toolchain + in-tree loader build, data-loader auto-resolution,
XLA compile-cache state, and the last recorded benchmark measurement.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def emit(check: str, **kw) -> None:
    print(json.dumps({"check": check, **kw}), flush=True)


def check_accelerator(timeout: int) -> None:
    t0 = time.time()
    try:
        r = subprocess.run(
            [sys.executable, "-c",
             "import jax; d=jax.devices(); "
             "print(d[0].platform, len(d))"],
            capture_output=True, text=True, timeout=timeout)
        out = r.stdout.strip().splitlines()
        if r.returncode == 0 and out:
            platform, n = out[-1].split()
            emit("accelerator", ok=True, platform=platform, devices=int(n),
                 init_s=round(time.time() - t0, 1))
        else:
            emit("accelerator", ok=False,
                 error=(r.stderr.strip().splitlines() or ["no output"])[-1])
    except subprocess.TimeoutExpired:
        emit("accelerator", ok=False,
             error=f"backend init exceeded {timeout}s (is another process "
                   f"holding the chip?); CPU paths still work "
                   f"(JAX_PLATFORMS=cpu)")


def check_cpu_mesh() -> None:
    try:
        r = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(len(jax.devices()))"],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
        n = int(r.stdout.strip().splitlines()[-1])
        emit("virtual_cpu_mesh", ok=n == 8, devices=n)
    except Exception as e:
        emit("virtual_cpu_mesh", ok=False, error=str(e)[:200])


def check_kernels() -> None:
    """Interpret-mode smoke of every Pallas kernel family on tiny shapes —
    an import error or interpret regression in any of them should show up
    in one doctor run, not at bench time on the chip."""
    code = """
import os
os.environ['JAX_PLATFORMS'] = 'cpu'
import jax, jax.numpy as jnp
from distributeddeeplearning_tpu.ops.flash_attention import flash_attention
from distributeddeeplearning_tpu.ops.fused_linear_bn import linear_stats
from distributeddeeplearning_tpu.ops.embedding import embedding_lookup
q = jax.random.normal(jax.random.key(0), (1, 16, 2, 8))
flash_attention(q, q, q)
x = jax.random.normal(jax.random.key(1), (32, 8))
linear_stats(x, jax.random.normal(jax.random.key(2), (8, 16)))
embedding_lookup(x, jnp.array([[0, 3]]))
print('OK')
"""
    try:
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=300)
        emit("pallas_kernels_interpret",
             ok=r.stdout.strip().endswith("OK"),
             **({} if r.returncode == 0 else
                {"error": r.stderr[-300:]}))
    except Exception as e:
        emit("pallas_kernels_interpret", ok=False, error=str(e)[:200])


def check_versions() -> None:
    import importlib.metadata as md
    vers = {}
    for pkg in ("jax", "jaxlib", "libtpu", "flax", "optax",
                "orbax-checkpoint", "grain", "tensorflow", "torch",
                "transformers"):
        try:
            vers[pkg] = md.version(pkg)
        except md.PackageNotFoundError:
            vers[pkg] = None
    emit("versions", ok=all(vers[p] for p in ("jax", "flax", "optax")),
         **{k.replace("-", "_"): v for k, v in vers.items()})


def check_native() -> None:
    tools = {t: bool(shutil.which(t)) for t in ("g++", "make", "cmake")}
    lib = os.path.join(REPO, "distributeddeeplearning_tpu", "data",
                       "_native", "libddl_loader.so")
    built = os.path.exists(lib)
    if not built and tools["make"]:  # the loader builds on demand
        try:
            r = subprocess.run(
                ["make", "-C", os.path.join(REPO, "csrc"), "lib"],
                capture_output=True, text=True, timeout=300)
            built = r.returncode == 0 and os.path.exists(lib)
        except (subprocess.TimeoutExpired, OSError):
            built = False  # report, never raise: doctor must finish
    emit("native_toolchain", ok=tools["g++"] and tools["make"] and built,
         **tools, loader_built=built)


def check_loader() -> None:
    import tempfile
    try:
        from distributeddeeplearning_tpu.config import DataConfig, TrainConfig
        from distributeddeeplearning_tpu.data import resolve_loader
        with tempfile.TemporaryDirectory() as d:
            os.makedirs(os.path.join(d, "train", "class0"))
            cfg = TrainConfig(data=DataConfig(synthetic=False, data_dir=d,
                                              loader="auto"))
            emit("data_loader", ok=True,
                 auto_resolves_to=resolve_loader(cfg, "image"))
    except Exception as e:
        emit("data_loader", ok=False, error=str(e)[:200])


def check_caches(prune_days: float = 0.0) -> None:
    """Compile-cache state via the shared policy module
    (distributeddeeplearning_tpu/perf/compile_cache.py): location
    ($JAX_COMPILATION_CACHE_DIR, else the repo default), entry count / size
    split into XLA entries vs AOT step executables, and the last run's
    hit/miss counters from the stats sidecar. ``--prune N`` evicts entries
    older than N days first."""
    from distributeddeeplearning_tpu.perf import compile_cache
    cache = compile_cache.cache_dir()
    pruned = None
    if prune_days > 0:
        removed, kept = compile_cache.prune(cache, max_age_days=prune_days)
        pruned = {"removed": removed, "kept": kept,
                  "max_age_days": prune_days}
    info = compile_cache.summarize(cache)
    stats = compile_cache.read_stats(cache)
    fields = {
        "compile_cache_dir": info["dir"],
        "compile_cache_entries": info["entries"],
        "compile_cache_aot_entries": info["aot_entries"],
        "compile_cache_mb": round(info["total_bytes"] / 1e6, 1),
    }
    if isinstance(stats, dict):
        fields["last_run_stats"] = {
            k: stats[k] for k in ("aot_hits", "aot_misses", "aot_saves",
                                  "aot_failures", "sources",
                                  "updated_at")
            if k in stats}
    if pruned is not None:
        fields["pruned"] = pruned
    emit("caches", ok=True, **fields)


def check_sharding() -> None:
    """Optimizer-sharding state of the LAST run (loop.py drops
    .cache/last_run_sharding.json on process 0): active ZeRO stage,
    whether the overlapped backward/collective schedule was in effect and
    the measured overlap fraction — so "which
    sharding did that run actually use?" is answerable from doctor output
    without re-reading run logs. ok=True always: an absent sidecar just
    means no sharded run has happened yet."""
    from distributeddeeplearning_tpu.observability import sidecars
    side = sidecars.read("last_run_sharding")
    if side is not None:
        emit("optimizer_sharding", ok=True,
             **{k: side.get(k) for k in (
                 "optimizer_sharding", "overlap_collectives", "overlap",
                 "overlap_fraction", "dp", "model")})
    else:
        emit("optimizer_sharding", ok=True, last_run=None,
             note="no sharding sidecar; written by the first train run")


def check_pipeline() -> None:
    """Pipeline-schedule state of the LAST run (the same
    .cache/last_run_sharding.json sidecar carries a ``pipeline`` block
    for pipelined configs): stage count, schedule (gpipe / 1f1b),
    virtual stages, and the measured bubble fraction — null on an AOT
    warm boot, where nothing re-traced so nothing was observed (see
    docs/pipeline.md). ok=True always: no block just means the last run
    was not pipelined."""
    from distributeddeeplearning_tpu.observability import sidecars
    side = sidecars.read("last_run_sharding")
    pipe = side.get("pipeline") if isinstance(side, dict) else None
    if isinstance(pipe, dict):
        emit("pipeline", ok=True,
             **{k: pipe.get(k) for k in (
                 "stages", "schedule", "virtual_stages",
                 "bubble_fraction")})
    else:
        emit("pipeline", ok=True, last_run=None,
             note="no pipeline block in the sharding sidecar; written by "
                  "the first pipelined (--pp > 1) train run")


def check_precision() -> None:
    """Precision policy of the LAST run (the same
    .cache/last_run_sharding.json sidecar carries ``precision`` /
    ``precision_explicit`` / ``batch_ramp``): the resolved
    compute/param/reduce-dtype triple with any dynamic loss scale
    (e.g. ``bf16/f32/bf16+dls32768``), whether it came from an explicit
    PrecisionPolicy or the legacy --dtype flag, and the batch-ramp
    schedule if one ran — so "did that run actually train mixed?" is
    answerable from doctor output (ISSUE 20). ok=True always: an absent
    sidecar just means no run has happened yet."""
    from distributeddeeplearning_tpu.observability import sidecars
    side = sidecars.read("last_run_sharding")
    if isinstance(side, dict) and side.get("precision") is not None:
        emit("precision", ok=True,
             **{k: side.get(k) for k in (
                 "precision", "precision_explicit", "batch_ramp",
                 "model")})
    else:
        emit("precision", ok=True, last_run=None,
             note="no precision field in the sharding sidecar; written "
                  "by the first train run after the PrecisionPolicy "
                  "change")


def check_elastic() -> None:
    """Last elastic re-formation (loop.py drops
    .cache/last_elastic_event.json on process 0 when a run resumes under a
    launch.py --elastic membership event): trigger (host_lost / hung /
    host_rejoin / host_join / host_drain), degree before/after, the
    membership epoch it re-formed into, the measured reconfiguration
    seconds with its detect->drain->restore->compile->first-step phase
    split, and the resume step — so "what did the last re-formation
    cost, and where did the time go?" is answerable from doctor output.
    ok=True always: an absent sidecar just means no elastic
    re-formation has happened yet."""
    from distributeddeeplearning_tpu.observability import sidecars
    side = sidecars.read("last_elastic_event")
    if side is not None:
        emit("elastic", ok=True,
             **{k: side.get(k) for k in (
                 "trigger", "degree_before", "degree_after", "epoch",
                 "reconfiguration_time_s", "phases", "resume_step")})
    else:
        emit("elastic", ok=True, last_event=None,
             note="no elastic sidecar; written when a launch.py --elastic "
                  "run re-forms")


def check_flight() -> None:
    """Last incident from the flight record (observability/flight.py):
    the most recent fault / anomaly / attributed child exit / stale
    heartbeat on record, in one human line — so "what killed the last
    run?" is answerable from doctor output before anyone opens
    tools/postmortem.py. ok=True always: an absent or incident-free
    record is a healthy state, not a failure."""
    try:
        from distributeddeeplearning_tpu.observability import flight
        directory = flight.default_dir()
        incident = flight.last_incident(directory)
        if incident is None:
            emit("flight_record", ok=True, last_incident=None,
                 flight_dir=directory,
                 note="no incident on record; record with --flight-dir "
                      "(train.py / launch.py)")
        else:
            emit("flight_record", ok=True, flight_dir=directory,
                 last_incident=flight.describe(incident),
                 run=incident.get("run"), kind=incident.get("ev"),
                 step=incident.get("step"))
    except Exception as e:
        emit("flight_record", ok=True, error=str(e)[:200])


def check_ddl_lint() -> None:
    """Static distributed-correctness state (tools/ddl_lint.py): the two
    jax-free AST passes run LIVE (they are fast), plus the recorded
    last_ddl_lint sidecar for the tracing pass's verdict and schedule
    fingerprints. ok=False only on live findings or a recorded failing
    run — an absent sidecar just means ddl_lint has not run yet."""
    try:
        from distributeddeeplearning_tpu.analysis import (donation, lints,
                                                          repo_root)
        from distributeddeeplearning_tpu.observability import sidecars
        roots = [os.path.join(repo_root(), r)
                 for r in ("distributeddeeplearning_tpu", "tools",
                           "train.py", "bench.py", "generate.py",
                           "launch.py")]
        live = lints.analyze_paths(roots) + donation.analyze_paths(roots)
        side = sidecars.read("last_ddl_lint")
        age = sidecars.age_s(side)
        recorded_ok = side.get("ok") if side else None
        emit("ddl_lint", ok=not live and recorded_ok is not False,
             live_findings=len(live),
             live_detail=[f"{f.get('file')}:{f.get('line')} {f['rule']}"
                          for f in live[:5]],
             last_run_ok=recorded_ok,
             last_run_age_s=round(age, 1) if age is not None else None,
             schedules=(side or {}).get("collective_schedules"),
             note=(None if side else "no last_ddl_lint sidecar; run "
                   "python tools/ddl_lint.py"))
    except Exception as e:
        emit("ddl_lint", ok=True, error=str(e)[:200])


def check_serve() -> None:
    """Last continuous-batching serve bench (tools/bench_serve.py drops
    the last_serve sidecar): tokens/sec/chip, speedup over the sequential
    generate() baseline, TTFT p50/p99 and AOT executable sources — so
    "what did serving last measure?" is answerable from doctor output.
    ok=True always: an absent sidecar just means the bench has not run."""
    try:
        from distributeddeeplearning_tpu.observability import sidecars
        side = sidecars.read("last_serve")
        if side is None:
            emit("serve", ok=True, last_bench=None,
                 note="no last_serve sidecar; run python tools/"
                      "bench_serve.py")
            return
        rec = side.get("record") or {}
        cont = rec.get("continuous") or {}
        chaos = rec.get("chaos") or {}
        age = sidecars.age_s(side)
        # Serve health proper: shed / deadline-miss / retry counts from
        # the last bench window (nonzero on a fault-free run means the
        # SLO config or pool sizing is wrong), plus the chaos arm's
        # recovery story when bench_serve ran with --chaos.
        extra = {}
        if chaos:
            extra = {
                "chaos_recovery_overhead_frac":
                    chaos.get("recovery_overhead_frac"),
                "chaos_redispatched": chaos.get("redispatched"),
                "chaos_restarts": chaos.get("restarts"),
                "chaos_token_identity":
                    chaos.get("token_identity_checked"),
                "chaos_leak_check_ok": chaos.get("leak_check_ok"),
            }
        # Fast-path health: prefix reuse and speculative acceptance from
        # the last bench window. A hit rate of 0 under a shared-prefix
        # trace, or acceptance far below the drafter's usual, is a fast
        # path that is configured but not paying for itself.
        if cont.get("prefix_hit_rate") is not None:
            extra["prefix_hit_rate"] = cont.get("prefix_hit_rate")
            extra["prefix_tokens_reused"] = cont.get("prefix_tokens_reused")
            extra["prefix_evictions"] = cont.get("prefix_evictions")
            extra["cow_copies"] = cont.get("cow_copies")
        if cont.get("spec_rounds"):
            extra["spec_rounds"] = cont.get("spec_rounds")
            extra["spec_acceptance_rate"] = cont.get("spec_acceptance_rate")
        if rec.get("speedup_at_slo") is not None:
            extra["speedup_at_slo"] = rec.get("speedup_at_slo")
            extra["slo_p99_ttft_s"] = rec.get("slo_p99_ttft_s")
        emit("serve", ok=True,
             tokens_per_sec_per_chip=rec.get("value"),
             speedup_vs_sequential=rec.get("speedup_vs_sequential"),
             ttft_s=cont.get("ttft_s"),
             preemptions=cont.get("preemptions"),
             sheds=cont.get("sheds"),
             deadline_misses=cont.get("deadline_misses"),
             retries=cont.get("retries"),
             model=rec.get("model"), provenance=rec.get("provenance"),
             aot_sources=(rec.get("aot") or {}).get("sources"),
             age_s=round(age, 1) if age is not None else None, **extra)
    except Exception as e:
        emit("serve", ok=True, error=str(e)[:200])


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--probe-timeout", type=int, default=45)
    p.add_argument("--prune", type=float, default=0.0, metavar="DAYS",
                   help="evict compile-cache entries older than DAYS "
                        "before reporting (0 = report only)")
    args = p.parse_args(argv)
    check_accelerator(args.probe_timeout)
    check_cpu_mesh()
    check_kernels()
    check_versions()
    check_native()
    check_loader()
    check_caches(prune_days=args.prune)
    check_sharding()
    check_pipeline()
    check_precision()
    check_elastic()
    check_flight()
    check_ddl_lint()
    check_serve()
    return 0


if __name__ == "__main__":
    sys.exit(main())
