"""One read/write helper for the ``.cache/*.json`` run sidecars.

Before this module each writer hand-rolled the same twelve lines —
repo-root discovery, tmp-file + ``os.replace``, bare ``except`` — and
each reader open-coded its own ``json.load``. Three sidecars had already
drifted into three slightly different shapes. Every sidecar now goes
through ``write()`` / ``read()``:

- atomic publish (tmp + ``os.replace``), best-effort: a full disk or
  read-only checkout never kills a run, ``write`` just returns ``None``;
- one envelope: the payload is stored flat, plus ``schema`` (bumped on
  incompatible layout changes) and ``written_at`` (unix seconds) so
  readers like ``tools/doctor.py`` can age-stamp what they report;
- one location: ``<repo>/.cache/<name>.json`` for named sidecars, or an
  explicit path for sidecars that live elsewhere (compile-cache stats
  live inside the cache dir they describe).

Known sidecar names (the registry is deliberately just a tuple — the
point is a shared shape, not a gatekeeper):

    last_run_sharding      train/loop.py — sharding/overlap of the last run
    last_elastic_event     train/loop.py — last elastic re-formation
    last_bench             bench.py — per-metric last good measurements
    last_ddl_lint          tools/ddl_lint.py — last analyzer run + schedule
                           fingerprints
    schedule_fingerprints  analysis/collectives.py — config-fp -> schedule-fp
                           pairing registry for the AOT cache cross-check
    last_serve             tools/bench_serve.py — last continuous-batching
                           serve bench record (doctor.py serve report)

Pure stdlib; safe to import from jax-free tools.
"""
from __future__ import annotations

import json
import os
import time
from typing import Any, Optional

SCHEMA_VERSION = 1

KNOWN = ("last_run_sharding", "last_elastic_event", "last_bench",
         "last_ddl_lint", "schedule_fingerprints", "last_serve")


def cache_dir() -> str:
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(repo, ".cache")


def path_for(name_or_path: str) -> str:
    """A bare name maps to ``<repo>/.cache/<name>.json``; anything with a
    path separator or a ``.json`` suffix is used as-is."""
    if os.sep in name_or_path or name_or_path.endswith(".json"):
        return name_or_path
    return os.path.join(cache_dir(), f"{name_or_path}.json")


def write(name_or_path: str, payload: dict[str, Any]) -> Optional[str]:
    """Atomically publish ``payload`` (+ envelope). Returns the path, or
    ``None`` on any failure. Never raises."""
    path = path_for(name_or_path)
    try:
        record = dict(payload)
        record.setdefault("schema", SCHEMA_VERSION)
        record.setdefault("written_at", time.time())
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(record, fh, indent=2, sort_keys=True)
        os.replace(tmp, path)
        return path
    except Exception:  # noqa: BLE001 — sidecars are best-effort telemetry
        return None


def read(name_or_path: str) -> Optional[dict[str, Any]]:
    """Load a sidecar; absent or malformed yields ``None`` (a missing
    sidecar is a note, never a failure)."""
    try:
        with open(path_for(name_or_path), encoding="utf-8") as fh:
            obj = json.load(fh)
        return obj if isinstance(obj, dict) else None
    except Exception:  # noqa: BLE001
        return None


def age_s(record: Optional[dict[str, Any]],
          now: Optional[float] = None) -> Optional[float]:
    """Seconds since the sidecar was written, when the envelope (or a
    legacy ``updated_at``) carries a timestamp."""
    if not record:
        return None
    stamp = record.get("written_at", record.get("updated_at"))
    if not isinstance(stamp, (int, float)):
        return None
    return (time.time() if now is None else now) - float(stamp)
