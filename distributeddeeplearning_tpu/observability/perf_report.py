"""One self-describing record schema for every perf number this repo emits.

A number without its evidence cannot be trusted a week later: which devices
answered, which build, which compiled program, after how many attempts.
Every measurement surface (``bench.py`` metric lines, ``train/loop.py`` run
summaries, ``tools/summarize_trace.py`` analyses) emits into the schema
defined here:

- ``provenance`` — exactly one of :data:`PROVENANCE_STATES`:

  * ``fresh`` — measured by THIS invocation on the backend it names;
  * ``error`` — no measurement; the record explains why.

  There is no state for a cached number: a measurement path that could not
  measure says so and fails; it never replays an earlier value.

- ``backend`` — platform/device_kind/device+process counts the number was
  measured on (a v5e row and a CPU smoke row must never be conflated);
- ``attempts`` — the retry history that produced (or failed to produce)
  the number;
- ``git_rev`` + ``config_fingerprint`` (perf/aot.py) — which build and
  which compiled-program-shaping config the number belongs to;
- roofline accounting via ``models/flops.py`` — ``pct_of_peak`` makes
  numbers comparable across meshes the way the large-batch ResNet
  literature reports them (PAPERS.md: arXiv:1711.04325): analytic
  train FLOPs/example x rate / the chip's peak at the compute dtype. Off
  TPU the peak fields are absent; a TPU kind without a published peak in
  the table is an error.

Provenance stamping is annotation, never measurement: a missing git dir
must not cost a throughput number.
"""

from __future__ import annotations

import json
import os
from typing import Any, Optional

SCHEMA_VERSION = 1

PROVENANCE_STATES = ("fresh", "error")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def git_rev(repo_root: Optional[str] = None) -> Optional[str]:
    """Short commit hash of HEAD, read straight from ``.git`` (no
    subprocess — this runs inside bench children where every fork counts).
    None when the tree is not a git checkout or HEAD is unreadable."""
    root = repo_root or _REPO_ROOT
    git = os.path.join(root, ".git")
    try:
        with open(os.path.join(git, "HEAD")) as fh:
            head = fh.read().strip()
        if not head.startswith("ref:"):
            return head[:12] or None  # detached HEAD: the hash itself
        ref = head.split(None, 1)[1]
        ref_path = os.path.join(git, *ref.split("/"))
        if os.path.exists(ref_path):
            with open(ref_path) as fh:
                return fh.read().strip()[:12] or None
        with open(os.path.join(git, "packed-refs")) as fh:
            for line in fh:
                parts = line.split()
                if len(parts) == 2 and parts[1] == ref:
                    return parts[0][:12]
    except OSError:
        pass
    return None


def backend_identity() -> Optional[dict]:
    """Which hardware answered: platform, device_kind, device/process
    counts. Guarded — returns None wherever jax (or the backend) is
    unavailable, because identity annotation must never initialize or
    crash a backend on its own."""
    try:
        import jax
        dev = jax.devices()[0]
        return {
            "platform": dev.platform,
            "device_kind": getattr(dev, "device_kind", "?"),
            "device_count": jax.device_count(),
            "process_count": jax.process_count(),
        }
    except Exception:
        return None


def roofline(value: Optional[float], model: str, *,
             seq_len: Optional[int] = None, mlm_positions: int = 0,
             device_kind: Optional[str] = None,
             compute_dtype: str = "bfloat16") -> dict:
    """Roofline fields for a rate of ``value`` examples/sec/chip:
    ``tflops_per_sec`` (analytic model FLOPs actually sustained) and
    ``pct_of_peak`` (vs the chip's spec peak AT ``compute_dtype`` — the
    %-of-peak axis the large-batch ResNet papers compare on; an fp32 arm
    scores against the fp32 roof, a mixed arm against bf16, so the two
    arms measure distance from their own speed of light). An unknown
    model omits every field and a device that is not a TPU omits the peak
    fields; a TPU whose ``device_kind`` has no peak in models/flops.py
    raises."""
    out: dict = {}
    if value is None:
        return out
    from distributeddeeplearning_tpu.models import flops as flopslib
    per_ex = flopslib.train_flops_per_example(
        model, seq_len=seq_len, mlm_positions=mlm_positions)
    if per_ex is None:
        return out
    out["tflops_per_sec"] = round(value * per_ex / 1e12, 2)
    peak = (flopslib.peak_flops(device_kind, compute_dtype)
            if device_kind else None)
    if peak:
        out["pct_of_peak"] = round(100.0 * value * per_ex / peak, 1)
        out["peak_tflops"] = round(peak / 1e12, 0)
        out["peak_dtype"] = compute_dtype
        if compute_dtype == "bfloat16":
            # Back-compat alias: pre-policy records carried the bf16 roof
            # under this name.
            out["bf16_peak_tflops"] = out["peak_tflops"]
    return out


def annotate(rec: dict, *, provenance: str,
             config: Any = None, total_steps: Optional[int] = None,
             attempts: Optional[list] = None,
             with_backend: bool = True) -> dict:
    """Stamp a record with the schema's provenance block (in place, and
    returned). ``config`` (a TrainConfig) adds the perf/aot.py
    config_fingerprint so the number is tied to the compiled program it
    measured. ``with_backend=False`` for pure-host analyses (trace
    summaries) that must not touch jax."""
    if provenance not in PROVENANCE_STATES:
        raise ValueError(f"provenance {provenance!r} not in "
                         f"{PROVENANCE_STATES}")
    rec["schema_version"] = SCHEMA_VERSION
    rec["provenance"] = provenance
    rev = git_rev()
    if rev:
        rec["git_rev"] = rev
    if with_backend:
        backend = backend_identity()
        if backend:
            rec["backend"] = backend
    if attempts is not None:
        rec["attempts"] = list(attempts)
    if config is not None:
        try:
            from distributeddeeplearning_tpu.perf import aot as aotlib
            rec["config_fingerprint"] = aotlib.config_fingerprint(
                config, total_steps=total_steps)
        except Exception:
            pass  # fingerprint is annotation; its absence is visible anyway
        try:
            # Precision-policy + batch-ramp provenance: every config-tied
            # perf record names the policy and ramp it ran under, so an
            # fp32 and a mixed arm (or a ramped and an unramped run) can
            # never be conflated.
            from distributeddeeplearning_tpu.config import resolve_precision
            from distributeddeeplearning_tpu.train import optim as optimlib
            rec.setdefault("precision",
                           resolve_precision(config).describe())
            rec.setdefault("batch_ramp", optimlib.ramp_describe(config))
        except Exception:
            pass  # annotation only, like the fingerprint
    if provenance != "error":
        schedules = lint_schedules()
        if schedules:
            rec.setdefault("collective_schedules", schedules)
    return rec


# Schedule fingerprints older than this describe some other build, not
# the one being measured.
LINT_SCHEDULES_MAX_AGE_S = 24 * 3600.0


def lint_schedules() -> Optional[dict]:
    """Collective-schedule fingerprints from the last ddl_lint run
    (tools/ddl_lint.py's ``last_ddl_lint`` sidecar) — attached to perf
    records so a throughput number names the collective schedule it was
    measured under. ``None`` when absent, stale, or unreadable (pure
    annotation, never a failure)."""
    try:
        from distributeddeeplearning_tpu.observability import sidecars
        side = sidecars.read("last_ddl_lint")
        age = sidecars.age_s(side)
        schedules = (side or {}).get("collective_schedules")
        if (isinstance(schedules, dict) and schedules
                and age is not None and age < LINT_SCHEDULES_MAX_AGE_S):
            return dict(schedules)
    except Exception:  # noqa: BLE001 — annotation only
        pass
    return None


def validate(rec: dict) -> list[str]:
    """Schema problems in a record (empty list = conforming). The rules
    tests pin so no surface can quietly drift:

    - provenance present and one of :data:`PROVENANCE_STATES`;
    - ``fresh`` requires a real value;
    - ``error`` requires a null value (an error that reports a value is a
      mislabeled measurement) and an ``error`` message.
    """
    problems = []
    prov = rec.get("provenance")
    if prov not in PROVENANCE_STATES:
        problems.append(f"provenance {prov!r} not in {PROVENANCE_STATES}")
        return problems
    if prov == "fresh":
        # Bench records carry an explicit ``value`` (null on failure);
        # run summaries measure through other keys and omit it entirely.
        if "value" in rec and rec["value"] is None:
            problems.append("fresh record with null value")
    else:
        if rec.get("value") is not None:
            problems.append("error record carrying a value")
        if not rec.get("error"):
            problems.append("error record without an error message")
    return problems


def dumps(rec: dict) -> str:
    return json.dumps(rec)
