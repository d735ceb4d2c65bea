"""Test env: 8 fake CPU devices, no TPU (SURVEY.md §4 "Distributed-without-
a-cluster"). Set before jax is imported; subprocesses the tests spawn
inherit it.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 fake CPU devices, got {len(devs)}"
    return devs


# --- slow-marker audit (tools/marker_audit.py) -----------------------------
# The tier-1 budget (870 s, ROADMAP) only holds if every long test carries
# @pytest.mark.slow. Each run records (nodeid, call duration, slow?) and
# prints offenders in the terminal summary; MARKER_AUDIT_JSON=<path> dumps
# the records for tools/marker_audit.py to gate on in CI.

_audit_records = []


def pytest_runtest_logreport(report):
    if report.when != "call":
        return
    _audit_records.append({
        "nodeid": report.nodeid,
        "duration": report.duration,
        "slow": "slow" in report.keywords,
        # perf_gate rides along so tools/marker_audit.py can verify the
        # CPU-proxy gate actually ran in this tier-1 pass (a gate that
        # silently fell out of the selection is no gate).
        "perf_gate": "perf_gate" in report.keywords,
        # elastic likewise: tools/marker_audit.py --expect-elastic verifies
        # a fast cross-degree resume test survived in tier-1.
        "elastic": "elastic" in report.keywords,
        # flight likewise: tools/marker_audit.py --expect-flight verifies
        # the crash-surviving flight record is exercised in tier-1.
        "flight": "flight" in report.keywords,
        # lint likewise: tools/marker_audit.py --expect-lint verifies the
        # ddl-lint static-analysis gate actually ran in this tier-1 pass.
        "lint": "lint" in report.keywords,
        # serve likewise: tools/marker_audit.py --expect-serve verifies the
        # engine token-identity pin survived in tier-1.
        "serve": "serve" in report.keywords,
        # chaos likewise: --expect-serve-chaos verifies a serve+chaos soak
        # (replica killed mid-stream, token-identical recovery) survived.
        "chaos": "chaos" in report.keywords,
        # pipeline likewise: --expect-pipeline verifies the schedule
        # parity pins and the pipeline_1f1b perf-gate workload survived.
        "pipeline": "pipeline" in report.keywords,
    })


def pytest_terminal_summary(terminalreporter):
    import json
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from tools.marker_audit import BUDGET_NOTE, find_violations

    out = os.environ.get("MARKER_AUDIT_JSON")
    if out:
        with open(out, "w") as f:
            json.dump(_audit_records, f)
    for rec in find_violations(_audit_records):
        terminalreporter.write_line(
            f"MARKER-AUDIT: {rec['nodeid']} took {rec['duration']:.1f}s "
            f"without @pytest.mark.slow ({BUDGET_NOTE})", yellow=True)
