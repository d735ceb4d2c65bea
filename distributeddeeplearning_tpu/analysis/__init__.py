"""ddl-lint: static distributed-correctness analysis (docs/static_analysis.md).

Every distributed-correctness bug this repo has shipped was found the hard
way at runtime: PR 5's donated-over orbax-restored arrays corrupted the
heap steps after a warm resume, PR 9's async cadence saves serialized
zero-copy views the next step had already donated over, and a collective
schedule that differs across ranks is the classic SPMD hang (Horovod,
PAPERS.md: arXiv 1802.05799). This package is the compile/lint-time layer
that catches those bug classes before a chip ever runs them:

- :mod:`.collectives` — extract the ordered collective-op schedule from a
  jaxpr or lowered-HLO text, fingerprint it canonically, and verify
  schedule identity across simulated ranks/configs, deterministic bucket
  ordering against ``parallel/collectives.py``'s planner, and the
  AOT-cache pairing (a ``perf/aot.py`` config fingerprint may never map
  to two different schedules).
- :mod:`.donation` — AST taint analysis encoding the invariant PRs 5 and
  9 each rediscovered at runtime: a restored / orbax-aliased / snapshot-
  shared array must pass through ``checkpoint.device_copy`` before it can
  reach a donated argument of a compiled step.
- :mod:`.lints` — repo-invariant AST lints: fsync-before-fire event
  emitters, ``.cache/*.json`` writes routed through
  ``observability/sidecars.py``, telemetry spans actually entered,
  provenance stamps on perf-record writes, and axis-name consistency
  between ``parallel/mesh.py`` and collective call sites.

Beside the passes, :mod:`.anatomy` reads a compiled step's HLO text the
other way round: not to check it, but to say which part of the program
(flash kernel, head, loss, optimizer, ...) each instruction belongs to, from
the scope and kernel names the program put there. It feeds the benchmark's
``device_ms.*`` metrics and ``tools/profile_step.py``; it is no pass and
reports no findings.

All passes share one finding shape (:func:`finding`) and run through the
``tools/ddl_lint.py`` CLI, which gates tier-1 via ``@pytest.mark.lint``
tests. Everything here is *analysis*: passes report, they never mutate,
and every reader is tolerant — truncated HLO, unknown custom-call
collectives, and jax-version drift degrade to a reported note, never a
crash (the ``observability/flight.py`` tolerant-reader rule).
"""
from __future__ import annotations

import os
from typing import Any, Iterator, Optional

PASSES = ("collectives", "donation", "lints")


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def finding(pass_name: str, rule: str, message: str, *,
            file: Optional[str] = None,
            line: Optional[int] = None) -> dict[str, Any]:
    """The one finding shape every pass emits (and the baseline keys on).

    ``file`` is stored repo-relative when it lives under the repo, so
    baselines and JSON output are stable across checkouts.
    """
    if file:
        root = repo_root()
        absfile = os.path.abspath(file)
        if absfile.startswith(root + os.sep):
            file = os.path.relpath(absfile, root)
    return {"pass": pass_name, "rule": rule, "message": message,
            "file": file, "line": line}


def suppression_matches(finding_rec: dict, suppression: dict) -> bool:
    """A baseline entry suppresses a finding when every key it carries
    matches (``rule`` and/or ``file``; ``file`` matches on suffix so a
    bare basename works). Line numbers are deliberately NOT part of the
    key — they drift with every edit."""
    rule = suppression.get("rule")
    if rule and rule != finding_rec.get("rule"):
        return False
    file = suppression.get("file")
    if file:
        have = finding_rec.get("file") or ""
        if not (have == file or have.endswith(os.sep + file)
                or have.endswith("/" + file)):
            return False
    return bool(rule or file)


def iter_py_files(roots, *, exclude_parts=("tests", "__pycache__",
                                           ".cache")) -> Iterator[str]:
    """Yield .py files under ``roots`` (files yielded as-is), skipping
    test trees and caches — the passes lint the shipping code; the test
    corpus seeds its violations in temp files on purpose."""
    for root in roots:
        if os.path.isfile(root):
            if root.endswith(".py"):
                yield root
            continue
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = [d for d in dirnames if d not in exclude_parts]
            for name in sorted(filenames):
                if name.endswith(".py"):
                    yield os.path.join(dirpath, name)
