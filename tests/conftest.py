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
# JAX reads this one as it is imported; the tests' own is set below.
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)

import atexit  # noqa: E402
import itertools  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 fake CPU devices, got {len(devs)}"
    return devs


# No test shares a persistent compile cache with another test, another worker
# or another run. ``loop.run``, ``Engine`` and ``compile_cache.activate()``
# switch JAX's persistent cache on for the whole process with a minimum
# compile time of zero, at ``$JAX_COMPILATION_CACHE_DIR`` or else the repo's
# ``.cache/jax_compile``; from then on every eager op is written there and
# read from there, and on XLA:CPU an executable read back beside a loaded AOT
# entry of the same kernel names fails at its first call (``NOT_FOUND:
# Function ... not found``). Whether a test met that depended on which tests
# had run before it in its process, on what the other xdist workers were
# writing, and on what an earlier run had left in the repo. So the variable is
# set after jax is imported (JAX itself does not start caching) and names a
# directory under a root made for this process; child processes inherit it.
_cache_root = tempfile.mkdtemp(prefix="ddl_test_compile_cache_")
atexit.register(shutil.rmtree, _cache_root, ignore_errors=True)
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_cache_root, "between")
_cache_dirs = itertools.count()

# What activate() changes in the process, read once while this file is
# imported: before any test or fixture has run.
_CACHE_CONFIG = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
                 "jax_persistent_cache_min_compile_time_secs",
                 "jax_persistent_cache_min_entry_size_bytes")
_CACHE_ENV = ("JAX_COMPILATION_CACHE_DIR", "DDL_COMPILE_CACHE")
_cache_config_at_start = {k: getattr(jax.config, k) for k in _CACHE_CONFIG}
_cache_env_at_start = {k: os.environ.get(k) for k in _CACHE_ENV}


@pytest.fixture(autouse=True)
def _compile_cache_of_its_own():
    """Every test starts with an empty cache directory of its own, which
    nothing else reads or writes, and leaves the cache's configuration and
    environment as the process began: a test meets the same cache alone, in
    its file run whole, and under any number of workers. The directories go
    when the process exits (an object one test built may write to its
    directory from a later test)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        _cache_root, str(next(_cache_dirs)))
    yield
    from jax.experimental.compilation_cache import compilation_cache as cc

    for k, v in _cache_env_at_start.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    if any(getattr(jax.config, k) != v
           for k, v in _cache_config_at_start.items()):
        cc.reset_cache()  # JAX latches "used, and where" at a compile
        for k, v in _cache_config_at_start.items():
            jax.config.update(k, v)


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
        # elastic rides along so tools/marker_audit.py --expect-elastic can
        # verify a fast cross-degree resume test survived in tier-1.
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
        # parity pins survived.
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
