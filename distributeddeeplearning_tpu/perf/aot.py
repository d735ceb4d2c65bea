"""Ahead-of-time step executables keyed by a stable config fingerprint.

A restart attempt (launch.run_with_restarts) and a re-launch of the same
config pay the largest fixed cost of the run again: tracing + XLA-compiling
the train step. This module removes that cost end to end:

- ``config_fingerprint`` hashes exactly the parts of a ``TrainConfig`` that
  reach the compiled program (model, topology, parallel axes, dtypes,
  optimizer/schedule inputs, jax/jaxlib versions, a digest of this
  package's source) and *excludes* volatile
  host-side knobs (trace dirs, checkpoint paths, log cadence, fault plans).
  The one program-affecting piece of fault injection — compiled-in NaN-grad
  injection and the bad-step guard — re-enters the hash via the *resolved*
  plan for this restart attempt, so a recovery attempt whose injected fault
  has expired fingerprints identically to a clean run and can reuse its
  executable.
- ``StepExecutableCache`` stores ``jax.experimental.serialize_executable``
  payloads under ``<compile_cache>/aot/<key>.aotx``; a warm restart
  deserializes the executable and skips tracing entirely. Any mismatch
  (format, jax version, unreadable payload) is a silent miss that falls
  back to a cold ``lower().compile()`` — never a failure.

A cache hit loads byte-identical XLA output for the same program, so
numerics are unchanged (the zero1<->replicated and chaos-soak bitwise pins
hold with the cache hot or cold).

Beside each entry ``save`` writes ``<key>.anatomy.json``, the executable's
``{instruction name: op_name}`` table (analysis/anatomy.py), and the module
remembers which entry each step name last resolved to, so that
:func:`anatomy` can read the table after the step itself has been freed (the
benchmark's metric readers run then). Written on the cold path only; a warm
load touches nothing of it.

The serve engine rides the same ``StepExecutableCache`` under its own
``serve/engine.py serve_fingerprint`` (a full-``ServeConfig`` hash, so
fast-path fields — ``prefix_cache``, ``spec_draft_model``, ``spec_k`` —
extend the key automatically): prefill buckets, decode, and the fast
path's block-prefill / page-clone / draft / verify programs all warm-boot
from it.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import os
import pickle
import sys
import time
from typing import Any, Optional

from distributeddeeplearning_tpu.analysis import anatomy as anatomy_lib
from distributeddeeplearning_tpu.observability import telemetry
from distributeddeeplearning_tpu.perf import compile_cache

FORMAT_VERSION = 1

# step name -> path of the entry it last resolved to (hit or save) in this
# process; what :func:`anatomy` reads from.
_RESOLVED: dict[str, str] = {}

# TrainConfig fields that never reach the compiled step program: paths,
# cadences, watchdog thresholds, and host-side fault orchestration. The
# nan-grad/guard portion of fault handling DOES reach the program and is
# re-added as _fault_program below from the plan resolved for this attempt.
VOLATILE_FIELDS = frozenset({
    "log_every", "eval_every_epochs",
    "checkpoint_dir", "checkpoint_every_steps", "resume",
    "profile_steps", "profile_dir",
    "trace_dir", "trace_steps", "trace_max_events",
    "straggler_threshold", "bad_step_limit",
    "fault_plan", "fail_at_step",
    "compile_cache",
})

# Same for DataConfig: host-pipeline knobs that leave batch shapes alone.
VOLATILE_DATA_FIELDS = frozenset({
    "data_dir", "loader", "shuffle_buffer", "prefetch_depth",
    "loader_timeout_s", "loader_retries",
})


@functools.cache
def source_digest() -> str:
    """Digest of the package's ``.py`` files (relative path and bytes),
    read once per process. The config says what program was asked for; the
    source says what program that is. Without it a checkout whose step code
    changed would load the executable its parent saved under the same
    config, wherever the cache outlives a checkout."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    digest = hashlib.sha256()
    for folder, subdirs, files in os.walk(root):
        subdirs.sort()
        for name in sorted(f for f in files if f.endswith(".py")):
            path = os.path.join(folder, name)
            digest.update(os.path.relpath(path, root).encode() + b"\0")
            with open(path, "rb") as fh:
                digest.update(fh.read() + b"\0")
    return digest.hexdigest()[:16]


def versions() -> dict[str, str]:
    """What, besides a config, decides which program gets compiled. Part
    of every fingerprint (train and serve) and checked again at load."""
    import jax
    import jaxlib
    # The RNG lowering is part of the compiled program: an executable built
    # under legacy threefry replays legacy bits forever, so a flag flip
    # (set in the package __init__) must miss the cache, not poison it.
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "threefry_partitionable":
                str(bool(jax.config.jax_threefry_partitionable)),
            "source": source_digest()}


def config_fingerprint(config, *, total_steps: Optional[int] = None,
                       extra: Any = None) -> str:
    """Stable hash of everything about ``config`` that shapes the compiled
    step program. Equal configs -> equal keys; volatile fields (trace dirs,
    checkpoint paths, host-side fault plans, cadences) never perturb it;
    a jax/jaxlib upgrade or an edit of this package's source always does.

    ``total_steps`` must be passed when known: the LR schedule bakes it
    into the update computation (train/optim.py), so two runs differing
    only in horizon compile different programs.
    """
    d = dataclasses.asdict(config)
    for field in VOLATILE_FIELDS:
        d.pop(field, None)
    if isinstance(d.get("data"), dict):
        for field in VOLATILE_DATA_FIELDS:
            d["data"].pop(field, None)
    # Resolved per-attempt fault program: nan-grad injection steps and the
    # bad-step guard are compiled into the step (train/steps._guard_config).
    from distributeddeeplearning_tpu.robustness import faults
    nan_steps = faults.resolve(config).nan_grad_steps()
    d["_fault_program"] = {
        "nan_steps": sorted(nan_steps),
        "guard": bool(nan_steps) or bool(getattr(config, "bad_step_guard",
                                                 False)),
    }
    d["_total_steps"] = total_steps
    d["_versions"] = versions()
    if extra is not None:
        d["_extra"] = extra
    blob = json.dumps(d, sort_keys=True, default=repr)
    return hashlib.sha256(blob.encode()).hexdigest()[:32]


def runtime_tag(devices) -> str:
    """Device component of executable keys: an executable compiled for one
    platform/chip/device set never deserializes onto another (a replica on
    device 2 must not be handed the entry built for device 0). ``devices``
    are the program's, in assignment order."""
    import jax
    dev = devices[0]
    return (f"{dev.platform}:{getattr(dev, 'device_kind', '?')}:"
            f"{','.join(str(d.id) for d in devices)}x{jax.process_count()}")


def _aval_signature(args) -> list:
    """Tree structure + per-leaf (shape, dtype) of the call arguments."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return [str(treedef),
            [(tuple(getattr(x, "shape", ())),
              str(getattr(x, "dtype", type(x).__name__))) for x in leaves]]


def donation_signature(compiled_exec) -> Optional[str]:
    """The executable's ``input_output_alias`` header from its HLO text —
    the compiled encoding of which inputs were donated. ``None`` when the
    text or header is unavailable (older jax, partial dumps): the caller
    treats that as "cannot check", never as a mismatch."""
    try:
        text = compiled_exec.as_text()
        marker = "input_output_alias="
        start = text.index(marker) + len(marker)
        brace = text.index("{", start)
        depth = 0
        for i in range(brace, min(len(text), brace + 100_000)):
            if text[i] == "{":
                depth += 1
            elif text[i] == "}":
                depth -= 1
                if depth == 0:
                    return "".join(text[brace:i + 1].split())
        return None
    except Exception:  # noqa: BLE001 — absence of evidence, not mismatch
        return None


def compile_lowered(lowered):
    """``lowered.compile()`` for a program that becomes an entry here, with
    JAX's persistent cache keyed on metadata for this one compile.

    By default JAX leaves ``op_name`` and source lines out of its key, so a
    tree that changed nothing but scope names gets back the executable an
    older tree compiled, old names inside (seen on the CPU: ``lossA`` ->
    ``lossB`` came back as ``lossA``) — and ``save`` would write that as
    this step's anatomy. Only an entry's own compile pays for the stricter
    key (metadata holds file paths and the caller's frames, so another
    checkout or entry script misses); every other program keeps JAX's
    default and is shared. The flag is process-wide for the moment of the
    compile: a compile on another thread meanwhile only keys more strictly.
    """
    import jax
    flag = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        return lowered.compile()
    finally:
        jax.config.update(flag, was)


def _anatomy_path(entry_path: str) -> str:
    return entry_path[:-len(".aotx")] + ".anatomy.json"


def anatomy(step_name: str) -> Optional[dict[str, str]]:
    """``{instruction name: op_name}`` of the executable that ``step_name``
    (``"gspmd_train_step"``, ``"dp_train_step"``, ...) resolved to in this
    process, read from the file ``save`` wrote beside the entry. None where
    no such step was resolved through a cache, or the file is gone."""
    path = _RESOLVED.get(step_name)
    if path is None:
        return None
    try:
        with open(_anatomy_path(path)) as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return None


class StepExecutableCache:
    """Fingerprint-keyed store of serialized step executables.

    One instance per run (train/loop.build creates it); disabled entirely
    when the compile cache is off (``cache_dir=None``). ``devices`` are the
    ones the run's programs are compiled for, in assignment order (the
    mesh's, or the serve replica's one device): they are part of every key,
    and a saved executable is loaded back onto exactly these —
    ``deserialize_and_load`` would otherwise spread a one-device program
    over every local device and fail at its first dispatch. A broken entry
    is a miss and a failed save is a warning; an entry that loads is
    trusted, so a dispatch failure after it propagates.
    """

    def __init__(self, cache_dir: Optional[str], fingerprint: str,
                 devices):
        self.cache_dir = cache_dir
        self.devices = list(devices)
        self.dir = (os.path.join(cache_dir, compile_cache.AOT_SUBDIR)
                    if cache_dir else None)
        self.fingerprint = fingerprint
        self.hits = 0
        self.misses = 0
        self.failures = 0
        self.saves = 0
        self.sources: dict[str, str] = {}  # step name -> aot_hit | compiled

    @classmethod
    def for_config(cls, config, devices, *,
                   total_steps: Optional[int] = None) -> "StepExecutableCache":
        return cls(compile_cache.cache_dir(config.compile_cache),
                   config_fingerprint(config, total_steps=total_steps),
                   devices)

    @property
    def enabled(self) -> bool:
        return self.dir is not None

    def key(self, name: str, args) -> str:
        blob = json.dumps(
            [self.fingerprint, name, runtime_tag(self.devices),
             _aval_signature(args)],
            sort_keys=True, default=repr)
        return hashlib.sha256(blob.encode()).hexdigest()[:32]

    def _path(self, key: str) -> str:
        return os.path.join(self.dir, f"{key}.aotx")

    def load(self, name: str, key: str):
        """Deserialize the cached executable for ``key`` onto this run's
        devices; None on miss or on ANY mismatch (format, jax version,
        corrupt payload) — the caller cold-compiles and overwrites the
        entry."""
        if self.dir is None:
            return None
        path = self._path(key)
        if not os.path.exists(path):
            self.misses += 1
            self.sources[name] = "compiled"
            return None
        try:
            with telemetry.phase("aot_load", program=name):
                with open(path, "rb") as fh:
                    payload = pickle.load(fh)
                if payload.get("format") != FORMAT_VERSION:
                    raise ValueError(f"format {payload.get('format')!r}")
                if payload.get("versions") != versions():
                    raise ValueError(
                        f"built under jax {payload.get('versions')}, "
                        f"running {versions()}")
                from jax.experimental import serialize_executable
                fn = serialize_executable.deserialize_and_load(
                    payload["executable"], payload["in_tree"],
                    payload["out_tree"],
                    execution_devices=self.devices)
                # Donation backstop (the PR 5 bug class, cheap runtime form
                # of analysis/donation.py): the deserialized executable must
                # donate exactly the inputs it donated when saved. A drifted
                # donation set means a dispatch through this hit could
                # donate buffers the caller still aliases — delete +
                # recompile cold.
                saved_donation = payload.get("donation")
                live_donation = donation_signature(fn)
                if (saved_donation is not None and live_donation is not None
                        and saved_donation != live_donation):
                    raise ValueError(
                        f"donation set drifted: saved "
                        f"input_output_alias {saved_donation} != "
                        f"deserialized {live_donation}")
        except Exception as exc:  # noqa: BLE001 - any mismatch = cold path
            self.failures += 1
            self.misses += 1
            self.sources[name] = "compiled"
            print(f"[aot] cached executable for {name} unusable "
                  f"({type(exc).__name__}: {exc}); recompiling cold",
                  file=sys.stderr)
            try:
                os.remove(path)
            except OSError:
                pass
            return None
        self.hits += 1
        self.sources[name] = "aot_hit"
        _RESOLVED[name] = path
        return fn

    def save(self, name: str, key: str, compiled_exec) -> bool:
        """Serialize ``compiled_exec`` under ``key`` (atomic write; every
        process writes identical bytes, last rename wins)."""
        if self.dir is None:
            return False
        try:
            with telemetry.phase("aot_save", program=name):
                from jax.experimental import serialize_executable
                executable, in_tree, out_tree = serialize_executable.serialize(
                    compiled_exec)
                blob = pickle.dumps({
                    "format": FORMAT_VERSION,
                    "versions": versions(),
                    "runtime": runtime_tag(self.devices),
                    "name": name,
                    "fingerprint": self.fingerprint,
                    "executable": executable,
                    "in_tree": in_tree,
                    "out_tree": out_tree,
                    "donation": donation_signature(compiled_exec),
                    "saved_at": time.time(),
                })
                table = anatomy_lib.table(compiled_exec.as_text())
                os.makedirs(self.dir, exist_ok=True)
                path = self._path(key)
                for target, mode, data in (
                        (_anatomy_path(path), "w", json.dumps(table)),
                        (path, "wb", blob)):
                    tmp = f"{target}.tmp.{os.getpid()}"
                    with open(tmp, mode) as fh:
                        fh.write(data)
                    os.replace(tmp, target)
            _RESOLVED[name] = path
        except Exception as exc:  # noqa: BLE001 - saving is optional
            print(f"[aot] could not serialize {name} "
                  f"({type(exc).__name__}: {exc}); run continues uncached",
                  file=sys.stderr)
            return False
        self.saves += 1
        return True

    def stats(self) -> dict[str, Any]:
        return {"aot_hits": self.hits, "aot_misses": self.misses,
                "aot_failures": self.failures, "aot_saves": self.saves,
                "fingerprint": self.fingerprint,
                "sources": dict(self.sources)}

    def flush_stats(self) -> None:
        """Persist counters next to the cache for tools/doctor.py."""
        compile_cache.write_stats(self.cache_dir, self.stats())
