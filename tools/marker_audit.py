#!/usr/bin/env python
"""Gate the tier-1 wall budget: fail if an unmarked test runs too long.

Tier-1 (``pytest -m 'not slow'``) has an 870 s budget on the 1-vCPU test
box; a single unmarked ~60 s+ test silently eats 7% of it and the budget
erodes one PR at a time. The audit closes that loop:

1. tests/conftest.py records every test's call duration each run and, when
   ``MARKER_AUDIT_JSON=<path>`` is set, dumps the records there.
2. This script reads the dump and exits 1 listing every test that exceeded
   the threshold without ``@pytest.mark.slow`` — chain it after pytest::

       MARKER_AUDIT_JSON=/tmp/durations.json pytest tests/ -m 'not slow'
       python tools/marker_audit.py /tmp/durations.json

The threshold (default 60 s) is deliberately far above any healthy tier-1
test here (slowest observed ~35 s) and far below the budget, so it only
trips on genuine misclassification, not machine jitter. Tests already
marked slow are never violations regardless of duration.
"""

from __future__ import annotations

import json
import os
import sys

DEFAULT_THRESHOLD_S = 60.0
BUDGET_NOTE = "tier-1 budget 870s; mark tests >60s @pytest.mark.slow"


def find_violations(records, threshold_s: float = DEFAULT_THRESHOLD_S):
    """Records exceeding ``threshold_s`` without the slow marker.

    ``records``: iterables of dicts with ``nodeid``, ``duration`` (seconds,
    call phase only — setup/teardown cost is fixture-shared and not the
    test author's marker decision), ``slow`` (bool). Malformed entries are
    skipped rather than crashing the gate; sorted slowest-first.
    """
    out = []
    for rec in records:
        try:
            if rec["slow"] or float(rec["duration"]) <= threshold_s:
                continue
        except (KeyError, TypeError, ValueError):
            continue
        out.append(rec)
    return sorted(out, key=lambda r: -float(r["duration"]))


def audit_elastic(records) -> list[str]:
    """Problems with elastic-resume coverage in this run.

    The cross-degree resume path (tests marked ``elastic``) has two
    silent-disarm failure modes: the marked tests vanish from the
    selection, or every one of them is also marked ``slow`` and
    tier-1's ``-m 'not slow'`` filters elastic coverage out entirely (the
    soak is legitimately slow — but a FAST variant must survive in
    tier-1; tests/test_elastic_resume.py keeps one).

    The rendezvous extension adds two coverage requirements: the
    topology-aware survivor-selection unit grid must run in EVERY
    selection (it is fast — losing it silently un-pins the deterministic
    shrink choice), and when the selection includes slow tests at all,
    the cross-axis soak (ZeRO stage + pipeline degree changing mid-run)
    must be among them."""
    problems = []
    elastic = [r for r in records if r.get("elastic")]
    if not elastic:
        problems.append(
            "no elastic-marked test ran — the cross-degree resume path is "
            "untested in this run (tests/test_elastic_resume.py missing, "
            "renamed, or deselected?)")
    elif all(r.get("slow") for r in elastic):
        problems.append(
            "every elastic-marked test is also marked slow — tier-1 runs "
            "-m 'not slow', so the cross-degree resume path is silently "
            "untested in tier-1 (keep a fast elastic variant unmarked)")
    if not any("survivor" in (r.get("nodeid") or "") for r in elastic):
        problems.append(
            "no elastic-marked survivor-selection test ran — the "
            "topology-aware shrink (hostmesh.select_survivors: "
            "deterministic, ring-contiguous) is un-pinned in this run "
            "(tests/test_rendezvous.py missing, renamed, or deselected?)")
    if (any(r.get("slow") for r in records)
            and not any("cross_axis" in (r.get("nodeid") or "")
                        for r in elastic)):
        problems.append(
            "slow tests ran but no elastic-marked cross_axis soak did — "
            "re-formation across the ZeRO-stage + pipeline-degree axes is "
            "untested in this slow run (tests/test_elastic_resume.py "
            "cross_axis soak missing, renamed, or deselected?)")
    return problems


def audit_flight(records) -> list[str]:
    """Problems with flight-recorder / post-mortem coverage in this run.

    The crash-surviving flight record (tests marked ``flight``) has the
    same silent-disarm failure modes: the marked tests vanish from the
    selection, or every one of them is also marked ``slow`` and tier-1's
    ``-m 'not slow'`` stops proving that a SIGKILL leaves a complete,
    parseable record with an attributable post-mortem."""
    problems = []
    flight = [r for r in records if r.get("flight")]
    if not flight:
        problems.append(
            "no flight-marked test ran — the crash-surviving flight "
            "record is untested in this run (tests/test_flight.py "
            "missing, renamed, or deselected?)")
    elif all(r.get("slow") for r in flight):
        problems.append(
            "every flight-marked test is also marked slow — tier-1 runs "
            "-m 'not slow', so the flight record / post-mortem path is "
            "silently untested in tier-1 (keep a fast flight variant "
            "unmarked)")
    return problems


def audit_lint(records) -> list[str]:
    """Problems with ddl-lint gate coverage in this run.

    The static-analysis gate (tests marked ``lint``) has the same
    silent-disarm failure modes: the marked tests vanish from the
    selection, every one is also marked ``slow`` and tier-1's
    ``-m 'not slow'`` filters the gate out, or the marker itself was
    dropped from pytest.ini and pytest's strict-marker path stops
    recognizing it."""
    problems = []
    lint = [r for r in records if r.get("lint")]
    if not lint:
        problems.append(
            "no lint-marked test ran — the ddl-lint static-analysis gate "
            "is untested in this run (tests/test_ddl_lint.py missing, "
            "renamed, or deselected?)")
    elif all(r.get("slow") for r in lint):
        problems.append(
            "every lint-marked test is also marked slow — tier-1 runs "
            "-m 'not slow', so the static-analysis gate is silently "
            "disarmed in tier-1 (lint tests are fast; never mark them "
            "slow)")
    ini = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "pytest.ini")
    try:
        with open(ini, encoding="utf-8") as f:
            registered = any(line.strip().startswith("lint:")
                             for line in f)
    except OSError:
        registered = False
    if not registered:
        problems.append(
            "the 'lint' marker is not registered in pytest.ini — "
            "register it under [pytest] markers or the gate tests "
            "become warnings instead of a gate")
    return problems


def audit_serve(records) -> list[str]:
    """Problems with serve-engine coverage in this run.

    The continuous-batching engine (tests marked ``serve``) has the same
    silent-disarm failure modes: the marked tests vanish from the
    selection, or every one is also marked ``slow`` and tier-1's
    ``-m 'not slow'`` stops pinning engine token-identity against
    sequential generate(). The fast-path identity tests
    (tests/test_serve_fastpath.py: prefix cache + speculative decoding
    vs sequential generate()) must be present, or the COW/spec paths
    regress to "configured but unproven"."""
    problems = []
    serve = [r for r in records if r.get("serve")]
    if not serve:
        problems.append(
            "no serve-marked test ran — the continuous-batching engine is "
            "untested in this run (tests/test_serve.py missing, renamed, "
            "or deselected?)")
    elif all(r.get("slow") for r in serve):
        problems.append(
            "every serve-marked test is also marked slow — tier-1 runs "
            "-m 'not slow', so engine token-identity is silently unpinned "
            "in tier-1 (keep a fast serve variant unmarked)")
    if serve and not any("fastpath" in (r.get("nodeid") or "")
                         for r in serve):
        problems.append(
            "no serve-marked fast-path test ran — prefix-cache / "
            "speculative-decoding token identity is unpinned "
            "(tests/test_serve_fastpath.py missing, renamed, or "
            "deselected?)")
    return problems


def audit_serve_chaos(records) -> list[str]:
    """Problems with serve-chaos coverage in this run.

    The fault-tolerant serving path (tests marked BOTH ``serve`` and
    ``chaos``: replica SIGKILL mid-stream through the supervised launch
    path, token-identical recovery, page-leak check) has the same
    silent-disarm failure modes: the combo-marked soak vanishes from the
    selection, or every instance is also marked ``slow`` and tier-1's
    ``-m 'not slow'`` stops proving recovery is token-identical."""
    problems = []
    soak = [r for r in records if r.get("serve") and r.get("chaos")]
    if not soak:
        problems.append(
            "no serve+chaos-marked test ran — token-identical recovery "
            "from a replica killed mid-stream is unproven in this run "
            "(tests/test_serve.py chaos soak missing, renamed, or "
            "deselected?)")
    elif all(r.get("slow") for r in soak):
        problems.append(
            "every serve+chaos-marked test is also marked slow — tier-1 "
            "runs -m 'not slow', so token-identical recovery is silently "
            "unproven in tier-1 (keep a fast serve-chaos soak unmarked)")
    return problems


def audit_pipeline(records) -> list[str]:
    """Problems with pipeline-schedule coverage in this run.

    The pipeline parity pins (tests marked ``pipeline``: 1f1b-vs-gpipe
    final-params identity, ZeRO-2 composition, cross-schedule resume)
    have the same silent-disarm failure modes: the marked tests vanish
    from the selection, or every one is also marked ``slow`` and tier-1's
    ``-m 'not slow'`` stops pinning schedule equivalence."""
    problems = []
    pipe = [r for r in records if r.get("pipeline")]
    if not pipe:
        problems.append(
            "no pipeline-marked test ran — the pipeline schedules are "
            "untested in this run (tests/test_pipeline.py missing, "
            "renamed, or deselected?)")
    elif all(r.get("slow") for r in pipe):
        problems.append(
            "every pipeline-marked test is also marked slow — tier-1 runs "
            "-m 'not slow', so schedule equivalence is silently unpinned "
            "in tier-1 (keep a fast pipeline variant unmarked)")
    return problems


def audit_largebatch(records) -> list[str]:
    """Problems with large-batch / mixed-precision coverage in this run.

    The large-batch recipe (ISSUE 20: mixed-precision PrecisionPolicy,
    dynamic loss scaling, batch ramp): the loss-scale skip path and the
    ramp-boundary resume pin must have run, or the recipe regresses to
    "configured but unproven"."""
    problems = []
    if not any("loss_scale" in (r.get("nodeid") or "") for r in records):
        problems.append(
            "no loss-scale test ran — the overflow->skip->halve->recover "
            "automaton is unpinned in this run "
            "(tests/test_mixed_precision.py missing, renamed, or "
            "deselected?)")
    if not any("ramp" in (r.get("nodeid") or "") for r in records):
        problems.append(
            "no batch-ramp test ran — ramp-boundary resume identity is "
            "unpinned in this run (tests/test_mixed_precision.py ramp "
            "tests missing, renamed, or deselected?)")
    return problems


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        print(f"usage: marker_audit.py <durations.json> [threshold_s="
              f"{DEFAULT_THRESHOLD_S:g}] [--expect-elastic] "
              f"[--expect-flight] [--expect-lint] [--expect-serve] "
              f"[--expect-serve-chaos] [--expect-pipeline] "
              f"[--expect-largebatch]")
        return 0 if argv else 2
    expect_elastic = "--expect-elastic" in argv
    expect_flight = "--expect-flight" in argv
    expect_lint = "--expect-lint" in argv
    expect_serve = "--expect-serve" in argv
    expect_serve_chaos = "--expect-serve-chaos" in argv
    expect_pipeline = "--expect-pipeline" in argv
    expect_largebatch = "--expect-largebatch" in argv
    argv = [a for a in argv
            if a not in ("--expect-elastic", "--expect-flight",
                         "--expect-lint", "--expect-serve",
                         "--expect-serve-chaos", "--expect-pipeline",
                         "--expect-largebatch")]
    threshold = float(argv[1]) if len(argv) > 1 else DEFAULT_THRESHOLD_S
    try:
        with open(argv[0]) as f:
            records = json.load(f)
    except (OSError, ValueError) as e:
        print(f"marker-audit: cannot read {argv[0]}: {e}", file=sys.stderr)
        return 2
    violations = find_violations(records, threshold)
    # Every coverage audit is opt-in: its problems are presence checks,
    # meaningless on partial runs (pytest tests/test_flops.py).
    problems = []
    if expect_elastic:
        problems += audit_elastic(records)
    if expect_flight:
        problems += audit_flight(records)
    if expect_lint:
        problems += audit_lint(records)
    if expect_serve:
        problems += audit_serve(records)
    if expect_serve_chaos:
        problems += audit_serve_chaos(records)
    if expect_pipeline:
        problems += audit_pipeline(records)
    if expect_largebatch:
        problems += audit_largebatch(records)
    if not violations and not problems:
        print(f"marker-audit: OK — {len(records)} tests, none over "
              f"{threshold:g}s unmarked")
        return 0
    if violations:
        print(f"marker-audit: {len(violations)} test(s) over {threshold:g}s "
              f"without @pytest.mark.slow ({BUDGET_NOTE}):")
        for rec in violations:
            print(f"  {rec['duration']:7.1f}s  {rec['nodeid']}")
    for p in problems:
        print(f"marker-audit: {p}")
    return 1


if __name__ == "__main__":
    sys.exit(main())
