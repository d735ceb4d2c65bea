"""CPU-proxy perf regression gate: perf bugs fail tier-1, not chip time.

Chip time is budgeted per PR, so a perf regression that waits for a chip
run to be noticed waits too long. A timing on the CPU is never a speed of
the system; what this gate catches is the host-visible class of
regression — slower compiled step on a fixed workload, a phase whose share
of the step exploded (data pipeline stall, accidental sync, pathological
retrace) — on CPU, deterministically, inside the tier-1 test budget.

How it works:

- :class:`ProxyRunner` builds ONE tiny fixed-shape training program
  (``resnet18_thin``, 32 px, batch 8, seed 0, float32, single device —
  deliberately the chaos benchmark's workload) through the real
  ``train/loop.build`` path, then measures per-step wall time and the
  telemetry phase breakdown (``data_wait`` / ``dispatch`` /
  ``fetch_barrier`` — the same phase names the production loop records).
- Wall time is normalized by :func:`calibrate` — a fixed numpy matmul
  workload timed in the same process — so the checked-in baseline
  (``perf_baselines.json``) transfers across machine speeds: the gate
  compares ``step_wall / calib_unit`` ratios, not absolute seconds.
- :func:`compare` fails the build when the normalized step time exceeds
  ``baseline x step_hi`` or any phase's share of the step grew by more
  than ``share_abs`` — both tolerances live IN the baseline file, so
  recalibration and tolerance changes are one reviewed diff.
- ``inject_sleep_s`` plants a sleep inside the traced ``data_wait`` phase;
  the self-test in tests/test_perf_gate.py proves the gate flips on it
  (a gate that cannot fail is decoration, not a gate).

``tools/perf_gate.py`` is the CLI (check / --recalibrate); the tier-1
test (``@pytest.mark.perf_gate``, audited by tools/marker_audit.py) is
the enforcement point. Results land in ``.cache/perf_gate_last.json`` so
``tools/doctor.py`` can report gate status without rerunning pytest.
"""

from __future__ import annotations

import json
import os
import statistics
import time
from typing import Any, Optional

from distributeddeeplearning_tpu.observability import (perf_report,
                                                       sidecars, telemetry)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BASELINE_PATH = os.path.join(_REPO_ROOT, "perf_baselines.json")
LAST_RESULT_PATH = sidecars.path_for("perf_gate_last")

SCHEMA_VERSION = 1

# The fixed proxy workload. Any change here invalidates perf_baselines.json
# — bump via ``python tools/perf_gate.py --recalibrate`` in the same PR.
WORKLOAD = {
    "model": "resnet18_thin",
    "image_size": 32,
    "batch": 8,
    "dtype": "float32",
    "seed": 0,
    "steps": 10,
    "warmup": 3,
}
# Named gate workloads. "default" is the headline proxy above (top level
# of perf_baselines.json); the rest live under the file's "extras" key and
# gate specific schedules. ``zero2_overlap`` drives the overlapped ZeRO-2
# path on a dp=2 CPU mesh — the custom_vjp bucket boundaries, per-bucket
# reduce-scatter, and chunked update all sit inside its timed step, so a
# retrace or added sync in the sharded schedule fails tier-1 here instead
# of waiting for chip time. Fewer steps than the default: the sharded
# step is slower per step and the gate needs a median, not a mean.
# The serve-engine decode proxy (kind="serve_decode" routes construction
# to :class:`ServeProxyRunner`): a tiny Engine with every slot held live,
# so each timed step is one compiled decode advance plus the engine's
# host bookkeeping — the per-token serving cost continuous batching pays.
# A regression here (retrace in the decode program, accidental pool copy,
# host loop bloat) fails tier-1 instead of waiting for chip time.
SERVE_WORKLOAD = {
    "kind": "serve_decode",
    "model": "gpt_tiny",
    "vocab_size": 256,
    "dtype": "float32",
    "max_slots": 4,
    "page_size": 4,
    "num_pages": 32,
    "max_pages_per_slot": 8,
    "prefill_buckets": [8],
    "seed": 0,
    "steps": 10,
    "warmup": 3,
}
# The prefix-hit admission proxy (kind="serve_prefix_prefill"): a tiny
# Engine with the radix prefix cache ON, its tree primed with one shared
# head; each timed step is one admission whose prompt hits that prefix —
# radix walk, shared-page mapping, suffix block prefill, retire. The
# serve fast path's headline win lives in this path, so a regression
# here (retrace in the block-prefill program, host-side tree bloat, a
# COW copy that stopped being in-place) fails tier-1 on CPU.
SERVE_PREFIX_WORKLOAD = {
    "kind": "serve_prefix_prefill",
    "model": "gpt_tiny",
    "vocab_size": 256,
    "dtype": "float32",
    "max_slots": 4,
    "page_size": 4,
    "num_pages": 64,
    "max_pages_per_slot": 8,
    "prefill_buckets": [8, 32],
    "shared_prefix_len": 16,
    "tail_len": 2,
    "prefix_cache": True,
    "seed": 0,
    "steps": 10,
    "warmup": 3,
}
# The interleaved-pipeline proxy: bert_tiny_pp4 (4 layers, 2 stages,
# layers_per_stage=2) under the 1F1B schedule with V=2 virtual chunks on
# a pipeline=2 CPU sub-mesh — every steady-state 1F1B tick, both
# activation-shift forms (inject + circular wrap), the per-tick chunk
# selection, and the canonical->interleaved param re-layout all sit
# inside its timed step. A retrace in the tick loop, a chunk gather that
# stopped being a static slice, or an accidental sync between ticks
# fails tier-1 here instead of waiting for chip time.
PIPELINE_WORKLOAD = {
    "model": "bert_tiny_pp4",
    "seq_len": 16,
    "vocab_size": 256,
    "batch": 8,
    "dtype": "float32",
    "seed": 0,
    "steps": 6,
    "warmup": 2,
    "pp": 2,
    "pipeline_schedule": "1f1b",
    "pipeline_virtual_stages": 2,
}
# The large-batch mixed-precision proxy (ISSUE 20): the default model at
# 2x the default batch under the explicit mixed policy (bf16 compute +
# fp32 master weights + dynamic loss scaling) with LARS — the large-batch
# recipe's compiled step, including every op the policy adds (loss
# scale/unscale, the overflow reduction, the skip-select on params and
# opt state, the scale automaton). A retrace, added sync, or host stall
# in the mixed path fails tier-1 here instead of waiting for chip time.
LARGEBATCH_WORKLOAD = dict(WORKLOAD, batch=16, steps=6, dtype="bfloat16",
                           precision="mixed", optimizer="lars")
WORKLOADS = {
    "default": WORKLOAD,
    "zero2_overlap": dict(WORKLOAD, steps=6, dp=2,
                          optimizer_sharding="zero2"),
    "largebatch_bf16": LARGEBATCH_WORKLOAD,
    "pipeline_1f1b": PIPELINE_WORKLOAD,
    "serve_decode": SERVE_WORKLOAD,
    "serve_prefix_prefill": SERVE_PREFIX_WORKLOAD,
}
# LR-schedule horizon compiled into the step program; fixed so every
# measure() pass (and the AOT cache) shares one executable.
_TOTAL_STEPS = 64

DEFAULT_TOLERANCE = {
    # Normalized step time may grow to this multiple of baseline before
    # the gate fails. Generous: machine-speed variance is mostly divided
    # out by the calibration unit, but XLA-version jitter on a tiny
    # program is real; an injected regression worth catching (extra sync,
    # pipeline stall) shows up as 5-100x on a ~10 ms step.
    "step_hi": 3.0,
    # A phase's share of summed span time may grow this much (absolute)
    # before the gate fails — catches mix shifts (data_wait ballooning)
    # even when total step time hides inside step_hi.
    "share_abs": 0.25,
}


def calibrate(reps: int = 24, size: int = 192, best_of: int = 3) -> float:
    """Machine-speed unit: seconds for a fixed numpy matmul workload,
    best-of-``best_of`` (load spikes inflate single samples). The SAME
    unit divides both the baseline and the current measurement, so the
    checked-in ratio transfers across boxes of different speeds."""
    import numpy as np

    a = np.arange(size * size, dtype=np.float32).reshape(size, size) / size
    best = float("inf")
    for _ in range(best_of):
        t0 = time.perf_counter()
        b = a
        for _ in range(reps):
            b = b @ a
            b *= 1.0 / max(float(b[0, 0]), 1.0)
        best = min(best, time.perf_counter() - t0)
    return best


class ProxyRunner:
    """Builds the fixed proxy program once; each :meth:`measure` pass
    reuses the compiled step, so the self-test's injected-slowdown
    remeasure costs steps, not a recompile."""

    def __init__(self, workload: Optional[dict] = None):
        self.workload = dict(WORKLOAD, **(workload or {}))
        from distributeddeeplearning_tpu import data as datalib
        from distributeddeeplearning_tpu.config import (
            DataConfig, OptimizerConfig, ParallelConfig, PrecisionPolicy,
            TrainConfig)
        from distributeddeeplearning_tpu.models import model_spec
        from distributeddeeplearning_tpu.train import loop

        w = self.workload
        spec = model_spec(w["model"])
        # Optional workload keys: ``dp``/``pp`` widen the CPU mesh (need
        # --xla_force_host_platform_device_count >= dp*pp, as
        # tests/conftest.py and tools/perf_gate.py both force),
        # ``optimizer_sharding`` selects a ZeRO stage (the zero2_overlap
        # workload), ``pipeline_schedule``/``pipeline_virtual_stages``
        # pick the pipeline schedule (the pipeline_1f1b workload). Token
        # models get a synthetic token stream sized by ``seq_len``/
        # ``vocab_size`` instead of the image pipeline.
        if spec.input_kind == "tokens":
            data = DataConfig(
                synthetic=True, seq_len=w.get("seq_len", 16),
                vocab_size=w.get("vocab_size", 256))
        else:
            data = DataConfig(synthetic=True, image_size=w["image_size"],
                              num_classes=10)
        # Optional policy/optimizer keys (the largebatch_bf16 workload):
        # "precision" arms an explicit PrecisionPolicy, "optimizer" swaps
        # the update rule (LARS for the large-batch recipe).
        extra_kw: dict = {}
        if w.get("precision") == "mixed":
            extra_kw["precision"] = PrecisionPolicy.mixed()
        elif w.get("precision") == "fp32":
            extra_kw["precision"] = PrecisionPolicy.fp32()
        if w.get("optimizer"):
            extra_kw["optimizer"] = OptimizerConfig(
                name=w["optimizer"], schedule="constant")
        self.config = TrainConfig(
            model=w["model"], backend="cpu",
            global_batch_size=w["batch"], dtype=w["dtype"],
            seed=w["seed"], log_every=10**9, **extra_kw,
            optimizer_sharding=w.get("optimizer_sharding", "none"),
            pipeline_schedule=w.get("pipeline_schedule", "gpipe"),
            pipeline_virtual_stages=w.get("pipeline_virtual_stages", 1),
            data=data,
            parallel=ParallelConfig(data=w.get("dp", 1),
                                    pipeline=w.get("pp", 1)))
        (self.mesh, self.model, batch_shd, self.state, self.train_step,
         _sched, self.rng) = loop.build(self.config, _TOTAL_STEPS)
        self.source = datalib.make_source(self.config, spec.input_kind,
                                          batch_shd,
                                          objective=spec.objective)
        self._jax = __import__("jax")

    def measure(self, *, steps: Optional[int] = None,
                warmup: Optional[int] = None,
                inject_sleep_s: float = 0.0) -> dict:
        """One measurement pass: per-step wall times (median over the
        timed steps) + phase breakdown, normalized by a fresh calibration
        unit. ``inject_sleep_s`` sleeps inside the traced ``data_wait``
        phase each timed step — the deliberate slowdown the gate's
        self-test must catch."""
        jax = self._jax
        steps = self.workload["steps"] if steps is None else steps
        warmup = self.workload["warmup"] if warmup is None else warmup
        state, rng = self.state, self.rng
        metrics = None
        i = 0
        for _ in range(warmup):  # compile + cache warmup, never timed
            state, metrics = self.train_step(state, self.source.batch(i),
                                             rng)
            i += 1
        if metrics is not None:
            jax.device_get(metrics)
        # Fresh telemetry per pass: warmup (compile) spans must not
        # pollute the phase mix the gate compares.
        tele = telemetry.Telemetry(enabled=True)
        per_step: list[float] = []
        for _ in range(steps):
            t0 = telemetry.now_s()
            with tele.span("data_wait", step=i):
                batch = self.source.batch(i)
                if inject_sleep_s > 0:
                    time.sleep(inject_sleep_s)
            t1 = telemetry.now_s()
            state, metrics = self.train_step(state, batch, rng)
            t2 = telemetry.now_s()
            tele.record_span("dispatch", t1, t2, step=i)
            # Per-step fetch: a true execution barrier, so each wall
            # sample covers exactly one step's device work (the
            # production loop pipelines; the gate wants determinism).
            with tele.span("fetch_barrier", step=i):
                jax.device_get(metrics)
            per_step.append(telemetry.now_s() - t0)
            i += 1
        self.state = state  # reuse across passes; shapes never change
        phases = telemetry.phase_totals(tele.snapshot())
        span_total = sum(p["total_ms"] for p in phases.values()) or 1.0
        calib = calibrate()
        step_s = statistics.median(per_step)
        return {
            "schema_version": SCHEMA_VERSION,
            "workload": dict(self.workload,
                             **({"steps": steps, "warmup": warmup})),
            "step_time_ms": round(step_s * 1e3, 3),
            "calib_unit_ms": round(calib * 1e3, 3),
            "normalized_step": round(step_s / calib, 4),
            "phase_share": {name: round(p["total_ms"] / span_total, 4)
                            for name, p in phases.items()},
            "phases": phases,
            "injected_sleep_s": inject_sleep_s,
        }


class ServeProxyRunner:
    """Serve-engine proxies for serve/engine.py. Builds ONE tiny Engine
    (compile-cache off — the gate times the build in front of it, never a
    deserialized one); what a timed step is depends on the workload kind:

    - ``serve_decode``: every slot held live by a long request — each
      timed ``Engine.step()`` is one static-shape decode advance, the
      per-token serving cost continuous batching pays.
    - ``serve_prefix_prefill``: the radix tree primed with a shared head
      — each timed step is one admission that HITS the prefix cache
      (tree walk + shared-page mapping + suffix block prefill + retire),
      the admission cost the fast path is supposed to have shrunk.

    Same result schema as :class:`ProxyRunner`, so :func:`compare` and the
    baseline file work unchanged."""

    def __init__(self, workload: Optional[dict] = None):
        self.workload = dict(SERVE_WORKLOAD, **(workload or {}))
        from distributeddeeplearning_tpu.serve.engine import (Engine,
                                                              ServeConfig)

        w = self.workload
        self.config = ServeConfig(
            model=w["model"], vocab_size=w["vocab_size"], dtype=w["dtype"],
            max_slots=w["max_slots"], page_size=w["page_size"],
            num_pages=w["num_pages"],
            max_pages_per_slot=w["max_pages_per_slot"],
            prefill_buckets=tuple(w["prefill_buckets"]), seed=w["seed"],
            prefix_cache=bool(w.get("prefix_cache", False)),
            compile_cache=False)
        self.engine = Engine(self.config)
        self.engine.warmup()

    def _timed_steps(self, steps, tele, inject_sleep_s):
        """Time ``steps`` decode advances; the caller has filled every
        slot so each one is a pure static-shape decode step."""
        eng = self.engine
        per_step: list[float] = []
        for k in range(steps):
            t0 = telemetry.now_s()
            with tele.span("host_stall", step=k):
                if inject_sleep_s > 0:
                    time.sleep(inject_sleep_s)
            with tele.span("decode_step", step=k):
                eng.step()  # np.asarray on the emitted tokens is the sync
            per_step.append(telemetry.now_s() - t0)
        return per_step

    def measure(self, *, steps: Optional[int] = None,
                warmup: Optional[int] = None,
                inject_sleep_s: float = 0.0) -> dict:
        w = self.workload
        steps = w["steps"] if steps is None else steps
        warmup = w["warmup"] if warmup is None else warmup
        eng = self.engine
        if not eng.idle:  # leftovers from a previous pass
            eng.run_until_idle()
        if w.get("kind") == "serve_prefix_prefill":
            head_len = int(w["shared_prefix_len"])
            tail_len = int(w["tail_len"])
            head = [1 + (i % (w["vocab_size"] - 2))
                    for i in range(head_len)]
            # Prime the radix tree (one full prefill), then queue one
            # max_new=1 request per step: each admits, hits the shared
            # head, block-prefills only the tail, and retires in-step.
            eng.submit(head + [2] * tail_len, max_new_tokens=1)
            eng.run_until_idle()
            hits_before = eng.prefix_hits

            def one_admit(k: int) -> None:
                # Submit-then-step so each step admits exactly ONE
                # prefix-hit request (and retires it: max_new=1).
                tail = [2 + ((k + j) % (w["vocab_size"] - 3))
                        for j in range(tail_len)]
                eng.submit(head + tail, max_new_tokens=1)
                eng.step()

            for k in range(warmup):
                one_admit(k)
            tele = telemetry.Telemetry(enabled=True)
            per_step = []
            for k in range(steps):
                tail = [2 + ((warmup + k + j) % (w["vocab_size"] - 3))
                        for j in range(tail_len)]
                eng.submit(head + tail, max_new_tokens=1)
                t0 = telemetry.now_s()
                with tele.span("host_stall", step=k):
                    if inject_sleep_s > 0:
                        time.sleep(inject_sleep_s)
                with tele.span("prefix_admit", step=k):
                    eng.step()
                per_step.append(telemetry.now_s() - t0)
            eng.run_until_idle()
            if eng.prefix_hits - hits_before < warmup + steps:
                raise RuntimeError(
                    f"serve_prefix_prefill proxy mis-primed: only "
                    f"{eng.prefix_hits - hits_before} prefix hits for "
                    f"{warmup + steps} admissions — the gate would be "
                    f"timing cold prefills, not the fast path")
        else:
            # One request per slot, sized to outlive warmup + timed steps
            # (admission prefill emits token 1; each step emits one more).
            prompt_len = min(4, max(self.config.prefill_buckets))
            max_new = warmup + steps + 1
            if prompt_len + max_new > self.config.slot_capacity:
                raise ValueError(
                    f"serve_decode workload needs {prompt_len + max_new} "
                    f"tokens/slot but slot capacity is "
                    f"{self.config.slot_capacity}; shrink steps or grow "
                    f"pages")
            for s in range(self.config.max_slots):
                eng.submit([1 + s] * prompt_len, max_new_tokens=max_new)
            for _ in range(warmup):
                eng.step()
            assert eng.num_live == self.config.max_slots
            tele = telemetry.Telemetry(enabled=True)
            per_step = self._timed_steps(steps, tele, inject_sleep_s)
            eng.run_until_idle()
        phases = telemetry.phase_totals(tele.snapshot())
        span_total = sum(p["total_ms"] for p in phases.values()) or 1.0
        calib = calibrate()
        step_s = statistics.median(per_step)
        return {
            "schema_version": SCHEMA_VERSION,
            "workload": dict(self.workload,
                             **({"steps": steps, "warmup": warmup})),
            "step_time_ms": round(step_s * 1e3, 3),
            "calib_unit_ms": round(calib * 1e3, 3),
            "normalized_step": round(step_s / calib, 4),
            "phase_share": {name: round(p["total_ms"] / span_total, 4)
                            for name, p in phases.items()},
            "phases": phases,
            "injected_sleep_s": inject_sleep_s,
        }


def runner_for(workload: str = "default"):
    """The right proxy runner for a named gate workload: training loop by
    default, the serve engine for kind="serve_decode" /
    "serve_prefix_prefill" entries."""
    if workload == "default":
        return ProxyRunner()
    w = WORKLOADS[workload]
    if w.get("kind") in ("serve_decode", "serve_prefix_prefill"):
        return ServeProxyRunner(w)
    return ProxyRunner(w)


def measure(runner: Optional[ProxyRunner] = None, **kw) -> dict:
    return (runner or ProxyRunner()).measure(**kw)


def load_baseline(path: Optional[str] = None,
                  name: str = "default") -> Optional[dict]:
    """Baseline for a named gate workload: the file's top level for
    "default", the matching ``extras`` entry otherwise (None = not yet
    recalibrated for that workload)."""
    try:
        with open(path or BASELINE_PATH) as fh:
            obj = json.load(fh)
    except (OSError, ValueError):
        return None
    if not isinstance(obj, dict):
        return None
    if name == "default":
        return obj
    extra = (obj.get("extras") or {}).get(name)
    return extra if isinstance(extra, dict) else None


def compare(baseline: Optional[dict], current: dict,
            tolerance: Optional[dict] = None) -> list[str]:
    """Violations of ``current`` against ``baseline`` (empty = gate
    passes). Tolerances come from the baseline file unless overridden —
    loosening the gate is a reviewed diff, not a test-local constant."""
    if not baseline:
        return ["no baseline: run `python tools/perf_gate.py "
                "--recalibrate [--workload NAME]` and commit "
                "perf_baselines.json"]
    tol = dict(DEFAULT_TOLERANCE, **(baseline.get("tolerance") or {}),
               **(tolerance or {}))
    out = []
    base_norm = float(baseline.get("normalized_step") or 0.0)
    cur_norm = float(current.get("normalized_step") or 0.0)
    base_ms = float(baseline.get("step_time_ms") or 0.0)
    cur_ms = float(current.get("step_time_ms") or 0.0)
    # Fail only when BOTH views regress past the band: the normalized
    # ratio forgives a slower machine (calibration divides speed out) and
    # the raw ratio forgives a loaded one (contention inflates the
    # calibration unit too) — a real regression (injected sleep, added
    # sync) inflates both by the same large factor.
    if base_norm > 0 and base_ms > 0:
        ratio = min(cur_norm / base_norm, cur_ms / base_ms)
        if ratio > float(tol["step_hi"]):
            out.append(
                f"step-time regression: {ratio:.1f}x baseline > "
                f"{tol['step_hi']:g}x tolerance (normalized "
                f"{cur_norm:.2f} vs {base_norm:.2f}; raw {cur_ms:g} ms "
                f"vs {base_ms:g} ms)")
    base_share = baseline.get("phase_share") or {}
    for phase, share in (current.get("phase_share") or {}).items():
        grew = float(share) - float(base_share.get(phase, 0.0))
        if grew > float(tol["share_abs"]):
            out.append(
                f"phase-mix regression: {phase!r} share "
                f"{float(share):.0%} grew {grew:+.0%} over baseline "
                f"{float(base_share.get(phase, 0.0)):.0%} "
                f"(> {float(tol['share_abs']):.0%} tolerance)")
    return out


def _write_sidecar(result: dict) -> None:
    # Atomic + enveloped via sidecars.write (never raises): the sidecar
    # is for doctor.py; losing it costs no gate run.
    sidecars.write(LAST_RESULT_PATH, result)


def check(baseline_path: Optional[str] = None,
          runner: Optional[ProxyRunner] = None,
          inject_sleep_s: float = 0.0,
          write_sidecar: bool = True,
          workload: str = "default") -> dict:
    """Measure the named proxy workload and gate it against its checked-in
    baseline. Returns ``{ok, violations, current, baseline}``; the default
    workload also drops the result into ``.cache/perf_gate_last.json`` for
    tools/doctor.py (extras never overwrite the headline sidecar)."""
    baseline = load_baseline(baseline_path, name=workload)
    if runner is None:
        runner = runner_for(workload)
    current = measure(runner, inject_sleep_s=inject_sleep_s)
    violations = compare(baseline, current)
    result: dict[str, Any] = {
        "ok": not violations,
        "violations": violations,
        "workload_name": workload,
        "current": current,
        "baseline_normalized_step": (baseline or {}).get("normalized_step"),
        "baseline_recorded": (baseline or {}).get("recorded"),
        "checked_at": time.strftime("%Y-%m-%d %H:%M:%S"),
    }
    rev = perf_report.git_rev()
    if rev:
        result["git_rev"] = rev
    if write_sidecar and inject_sleep_s == 0 and workload == "default":
        # Never persist a deliberately-slowed self-test pass as "the
        # last gate result" — doctor would report a phantom regression.
        _write_sidecar(result)
    return result


def recalibrate(path: Optional[str] = None,
                runner: Optional[ProxyRunner] = None,
                passes: int = 3,
                workload: str = "default") -> dict:
    """Measure ``passes`` times, keep the fastest pass (baseline = the
    machine's honest capability, not its worst moment), and write the
    baseline file. Recalibrating "default" rewrites the top level but
    PRESERVES any ``extras`` entries; recalibrating a named extra rewrites
    only its entry under ``extras``. Returns the baseline entry written."""
    r = runner or runner_for(workload)
    best = None
    for _ in range(max(passes, 1)):
        cur = r.measure()
        if best is None or cur["normalized_step"] < best["normalized_step"]:
            best = cur
    entry = {
        "schema_version": SCHEMA_VERSION,
        "workload": best["workload"],
        "step_time_ms": best["step_time_ms"],
        "calib_unit_ms": best["calib_unit_ms"],
        "normalized_step": best["normalized_step"],
        "phase_share": best["phase_share"],
        "tolerance": dict(DEFAULT_TOLERANCE),
        "recorded": {
            "recorded_at": time.strftime("%Y-%m-%d %H:%M:%S"),
            "git_rev": perf_report.git_rev(),
            "backend": perf_report.backend_identity(),
        },
    }
    out = path or BASELINE_PATH
    existing = None
    try:
        with open(out) as fh:
            existing = json.load(fh)
    except (OSError, ValueError):
        pass
    if not isinstance(existing, dict):
        existing = None
    if workload == "default":
        baseline = dict(entry)
        if existing and isinstance(existing.get("extras"), dict):
            baseline["extras"] = existing["extras"]
    else:
        if existing is None:
            raise ValueError(
                f"cannot recalibrate extra workload {workload!r} into a "
                f"missing/invalid baseline file {out!r}: recalibrate the "
                f"default workload first")
        baseline = existing
        baseline.setdefault("extras", {})[workload] = entry
    tmp = f"{out}.tmp.{os.getpid()}"
    with open(tmp, "w") as fh:
        json.dump(baseline, fh, indent=2, sort_keys=True)
        fh.write("\n")
    os.replace(tmp, out)
    return entry


def status(baseline_path: Optional[str] = None) -> dict:
    """Gate status WITHOUT running the proxy — what doctor.py prints:
    baseline presence/age + the last recorded check result."""
    baseline = load_baseline(baseline_path)
    out: dict[str, Any] = {"baseline_present": baseline is not None}
    if baseline:
        out["baseline_normalized_step"] = baseline.get("normalized_step")
        out["baseline_recorded"] = baseline.get("recorded", {})
        out["tolerance"] = baseline.get("tolerance", {})
        out["extra_baselines"] = sorted((baseline.get("extras") or {}))
    last = sidecars.read(LAST_RESULT_PATH)
    out["last_check"] = ({
        k: last.get(k) for k in ("ok", "violations", "checked_at",
                                 "git_rev")}
        if last is not None else None)
    return out
