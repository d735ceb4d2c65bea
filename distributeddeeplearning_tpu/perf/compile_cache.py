"""Shared persistent compile-cache policy for every entry point.

The cache is placed from outside, by one rule kept in this module and nowhere
else:

- ``JAX_COMPILATION_CACHE_DIR`` set  -> JAX's persistent compilation cache
  and the AOT executables (``<dir>/aot/``, perf/aot.py) live there, and the
  code sets no other directory and never rewrites the variable;
- not set -> ``<repo>/.cache/jax_compile``.

Either way the path is fixed for a given checkout and environment — never a
temporary name, pid or time, since the path is part of the cache key. Child
processes (launcher spawns, restart attempts, serve replicas) inherit the
variable or compute the same default, so nothing is exported. A run may turn
the cache off (``TrainConfig.compile_cache=False`` / ``--no-compile-cache``);
nothing else about it is configurable.

This module stays importable without jax (launch.py runs on hosts before jax
is initialized); jax is imported inside ``activate()`` only.

Hit/miss counters for the AOT executable layer (perf/aot.py) are persisted
to ``<cache_dir>/ddl_cache_stats.json`` so ``tools/doctor.py`` can report
the last run's cache behaviour after the fact.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Optional

from distributeddeeplearning_tpu.observability import telemetry

ENV_CACHE = "JAX_COMPILATION_CACHE_DIR"
STATS_FILE = "ddl_cache_stats.json"
AOT_SUBDIR = "aot"


def default_dir() -> str:
    """``<repo>/.cache/jax_compile`` — where the cache lives when the
    environment does not place it."""
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(repo, ".cache", "jax_compile")


def cache_dir(enabled: bool = True) -> Optional[str]:
    """The cache directory: ``$JAX_COMPILATION_CACHE_DIR`` if set, else the
    repo default; None when this run turned the cache off."""
    if not enabled:
        return None
    return os.environ.get(ENV_CACHE) or default_dir()


def activate(enabled: bool = True) -> Optional[str]:
    """Point JAX's persistent compilation cache at :func:`cache_dir` (or
    switch it off for this process) before the first compile. Returns the
    active directory, or None when disabled. Every entry point calls this
    first, so it also starts recording each program JAX builds into the
    phase log (``telemetry.watch_compiles``), cache on or off."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    telemetry.watch_compiles()
    path = cache_dir(enabled)
    if (jax.config.jax_enable_compilation_cache != (path is not None)
            or (path and jax.config.jax_compilation_cache_dir != path)):
        # JAX latches "is the cache used, and where" at its first compile;
        # a process that changes either (tests, a bench arm) must unlatch.
        cc.reset_cache()
    jax.config.update("jax_enable_compilation_cache", path is not None)
    if path is None:
        return None
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    # jax gates the persistent cache behind a minimum compile time / entry
    # size meant for interactive use; a training step is always worth
    # caching, and the CPU test path must exercise the same machinery the
    # TPU path uses.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


# ---------------------------------------------------------------------------
# Introspection for tools/doctor.py and run summaries.
# ---------------------------------------------------------------------------

def summarize(path: Optional[str] = None) -> dict[str, Any]:
    """Entry count / total size for a cache directory (0s when absent)."""
    path = cache_dir() if path is None else path
    out: dict[str, Any] = {"dir": path, "entries": 0, "aot_entries": 0,
                           "total_bytes": 0}
    if not path or not os.path.isdir(path):
        return out
    for root, _dirs, files in os.walk(path):
        for name in files:
            if name == STATS_FILE:
                continue
            full = os.path.join(root, name)
            try:
                out["total_bytes"] += os.path.getsize(full)
            except OSError:
                continue
            if os.path.basename(root) != AOT_SUBDIR:
                out["entries"] += 1
            elif name.endswith(".aotx"):  # not its .anatomy.json beside it
                out["aot_entries"] += 1
    return out


def _stats_path(path: str) -> str:
    return os.path.join(path, STATS_FILE)


def write_stats(path: Optional[str], stats: dict[str, Any]) -> None:
    """Persist last-run counters (best-effort; last writer wins)."""
    if not path:
        return
    try:
        payload = dict(stats)
        payload["updated_at"] = time.time()
        payload["pid"] = os.getpid()
        tmp = _stats_path(path) + f".tmp.{os.getpid()}"
        with open(tmp, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
        os.replace(tmp, _stats_path(path))
    except Exception:  # noqa: BLE001
        pass


def read_stats(path: Optional[str] = None) -> Optional[dict[str, Any]]:
    path = cache_dir() if path is None else path
    try:
        with open(_stats_path(path)) as fh:
            return json.load(fh)
    except Exception:  # noqa: BLE001
        return None


def prune(path: Optional[str] = None, *,
          max_age_days: float = 30.0) -> tuple[int, int]:
    """Delete cache entries older than ``max_age_days`` (by mtime).

    Returns ``(removed, kept)``. Safe on a live cache: jax re-creates
    entries on miss, and the AOT layer treats a vanished file as a miss.
    """
    path = cache_dir() if path is None else path
    removed = kept = 0
    if not os.path.isdir(path):
        return removed, kept
    cutoff = time.time() - max_age_days * 86400.0
    for root, _dirs, files in os.walk(path):
        for name in files:
            if name == STATS_FILE:
                continue
            full = os.path.join(root, name)
            try:
                if os.path.getmtime(full) < cutoff:
                    os.remove(full)
                    removed += 1
                else:
                    kept += 1
            except OSError:
                continue
    return removed, kept
