"""Marker audit (tools/marker_audit.py): the tier-1 budget gate itself."""

import json
import subprocess
import sys

from tools.marker_audit import DEFAULT_THRESHOLD_S, find_violations


def _rec(nodeid, duration, slow=False):
    return {"nodeid": nodeid, "duration": duration, "slow": slow}


def test_fast_and_marked_tests_pass():
    records = [
        _rec("tests/test_a.py::fast", 0.5),
        _rec("tests/test_a.py::near_limit", DEFAULT_THRESHOLD_S),  # <=, not <
        _rec("tests/test_b.py::marked_slow", 300.0, slow=True),
    ]
    assert find_violations(records) == []


def test_unmarked_slow_test_flagged_slowest_first():
    records = [
        _rec("tests/test_a.py::bad", 75.0),
        _rec("tests/test_a.py::worse", 120.0),
        _rec("tests/test_a.py::ok", 1.0),
    ]
    got = find_violations(records)
    assert [r["nodeid"] for r in got] == ["tests/test_a.py::worse",
                                          "tests/test_a.py::bad"]


def test_custom_threshold_and_malformed_records_skipped():
    records = [
        _rec("tests/test_a.py::t", 10.0),
        {"nodeid": "tests/test_a.py::no_duration", "slow": False},
        {"duration": "not-a-number", "slow": False, "nodeid": "x"},
    ]
    assert find_violations(records, threshold_s=5.0) == [records[0]]
    assert find_violations(records) == []


def test_cli_exit_codes(tmp_path):
    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps([_rec("t::fast", 1.0)]))
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps([_rec("t::unmarked", 200.0)]))
    cmd = [sys.executable, "tools/marker_audit.py"]
    assert subprocess.run(cmd + [str(ok)]).returncode == 0
    proc = subprocess.run(cmd + [str(bad)], capture_output=True, text=True)
    assert proc.returncode == 1
    assert "t::unmarked" in proc.stdout
    assert subprocess.run(cmd + [str(tmp_path / "missing.json")],
                          capture_output=True).returncode == 2
    # threshold override: 200s is fine under a 600s threshold
    assert subprocess.run(cmd + [str(bad), "600"]).returncode == 0


# --- elastic coverage audit (ISSUE 9 satellite) -----------------------------

from tools.marker_audit import audit_elastic  # noqa: E402


def test_audit_elastic_clean_run():
    records = [_rec("t::fast", 1.0),
               {**_rec("t::fast_cross_degree", 20.0), "elastic": True},
               {**_rec("t::test_survivor_selection_grid", 1.0),
                "elastic": True},
               {**_rec("t::test_cross_axis_soak", 300.0, slow=True),
                "elastic": True}]
    assert audit_elastic(records) == []


def test_audit_elastic_flags_no_coverage():
    problems = audit_elastic([_rec("t::fast", 1.0)])
    assert len(problems) == 2
    assert "no elastic-marked test ran" in problems[0]
    assert "survivor-selection" in problems[1]


def test_audit_elastic_flags_all_slow():
    """The soak is legitimately slow, but if EVERY elastic test is slow the
    cross-degree resume path silently leaves tier-1 (-m 'not slow')."""
    records = [{**_rec("t::test_cross_axis_soak", 300.0, slow=True),
                "elastic": True},
               {**_rec("t::test_survivor_selection_grid", 300.0, slow=True),
                "elastic": True}]
    problems = audit_elastic(records)
    assert len(problems) == 1
    assert "every elastic-marked test is also marked slow" in problems[0]


def test_audit_elastic_requires_survivor_grid():
    """Rendezvous extension: the topology-aware shrink's deterministic
    survivor choice must stay pinned in EVERY selection."""
    records = [{**_rec("t::fast_cross_degree", 20.0), "elastic": True}]
    problems = audit_elastic(records)
    assert len(problems) == 1
    assert "survivor-selection" in problems[0]


def test_audit_elastic_requires_cross_axis_when_slow_runs():
    """When the selection includes slow tests at all, the cross-axis soak
    (ZeRO stage + pipeline degree changing mid-run) must be among them."""
    base = [{**_rec("t::fast_cross_degree", 20.0), "elastic": True},
            {**_rec("t::test_survivor_selection_grid", 1.0),
             "elastic": True}]
    # Fast-only selection: the soak is legitimately absent.
    assert audit_elastic(base) == []
    slow_run = base + [_rec("t::unrelated_soak", 200.0, slow=True)]
    problems = audit_elastic(slow_run)
    assert len(problems) == 1
    assert "cross_axis" in problems[0]


def test_cli_expect_elastic_flag(tmp_path):
    cmd = [sys.executable, "tools/marker_audit.py"]
    no_elastic = tmp_path / "no_elastic.json"
    no_elastic.write_text(json.dumps([_rec("t::fast", 1.0)]))
    # Entirely opt-in: partial runs stay quiet...
    assert subprocess.run(cmd + [str(no_elastic)]).returncode == 0
    # ...the tier-1 chain opts in and fails loudly.
    proc = subprocess.run(cmd + [str(no_elastic), "--expect-elastic"],
                          capture_output=True, text=True)
    assert proc.returncode == 1
    assert "no elastic-marked test ran" in proc.stdout
    # With the coverage present the opt-in run is clean.
    full = tmp_path / "full.json"
    full.write_text(json.dumps(
        [{**_rec("t::fast_cross_degree", 20.0), "elastic": True},
         {**_rec("t::test_survivor_selection_grid", 1.0),
          "elastic": True}]))
    assert subprocess.run(
        cmd + [str(full), "--expect-elastic"]).returncode == 0


# --- large-batch recipe audit (ISSUE 20 satellite) --------------------------

from tools.marker_audit import audit_largebatch  # noqa: E402


def test_audit_largebatch_clean_run():
    records = [
        _rec("t::test_loss_scale_overflow_skips_and_halves", 3.0),
        _rec("t::test_ramp_boundary_resume_bitwise", 8.0),
    ]
    assert audit_largebatch(records) == []


def test_audit_largebatch_flags_all_missing():
    problems = audit_largebatch([_rec("t::fast", 1.0)])
    assert len(problems) == 2
    assert any("loss-scale" in p for p in problems)
    assert any("batch-ramp" in p for p in problems)


def test_cli_expect_largebatch_flag(tmp_path):
    cmd = [sys.executable, "tools/marker_audit.py"]
    partial = tmp_path / "partial.json"
    partial.write_text(json.dumps([_rec("t::fast", 1.0)]))
    # Opt-in: partial runs stay quiet...
    assert subprocess.run(cmd + [str(partial)]).returncode == 0
    # ...the tier-1 chain opts in and fails loudly.
    proc = subprocess.run(cmd + [str(partial), "--expect-largebatch"],
                          capture_output=True, text=True)
    assert proc.returncode == 1
    assert "no loss-scale test ran" in proc.stdout
    full = tmp_path / "full.json"
    full.write_text(json.dumps(
        [_rec("t::test_loss_scale_overflow", 2.0),
         _rec("t::test_ramp_boundary_resume", 2.0)]))
    assert subprocess.run(
        cmd + [str(full), "--expect-largebatch"]).returncode == 0
