"""The six readers of the program's phase log (`benchmark/setup_phases.py`):
each against a seeded log and a window of runner spans, what each returns
where the program keeps no log, and one traced tiny rehearsal that reports
all six."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

sys.path.insert(0, tiny.REPO)

from benchmark import harness  # noqa: E402
from distributeddeeplearning_tpu.observability import telemetry  # noqa: E402

READERS = ("setup_build_s", "setup_trace_lower_s", "setup_compile_s",
           "setup_executable_load_s", "setup_programs_compiled",
           "window_programs_compiled")


class Spans:
    def __init__(self, spans):
        self.spans = spans


def rec(name, start, end, **args):
    return telemetry.Phase(name, start, end, args)


# The window opens at 100 and closes at 110; build holds the init program.
LOG = [
    rec("build", 10.0, 40.0),
    rec("trace", 11.0, 12.0, fun="init_fn"),
    rec("lower", 12.0, 12.5, fun="jit_init_fn"),
    rec("xla_compile", 12.5, 20.0, fun="jit_init_fn"),
    rec("aot_save", 20.0, 21.0, program="gspmd_init"),
    rec("aot_load", 41.0, 43.0, program="gspmd_train_step"),
    rec("trace", 44.0, 44.25, fun="<lambda>"),
    rec("trace", 44.1, 44.2, fun="inner"),          # traced inside <lambda>
    rec("cache_load", 45.0, 45.5, fun="jit__lambda_"),
    rec("compile", 46.0, 90.0, program="gspmd_train_step"),
    rec("xla_compile", 50.0, 89.0, fun="jit_step_fn"),
    rec("xla_compile", 99.0, 101.0, fun="jit_late"),   # ends in the window
    rec("cache_load", 105.0, 106.0, fun="jit__lambda_"),
    rec("xla_compile", 111.0, 112.0, fun="jit_after"),  # after the window
]
WANT = {"setup_build_s": 30.0, "setup_trace_lower_s": 1.75,
        "setup_compile_s": 47.5, "setup_executable_load_s": 2.5,
        "setup_programs_compiled": 2, "window_programs_compiled": 2}


@pytest.fixture
def ctx(monkeypatch):
    monkeypatch.setattr(telemetry, "phases", lambda: list(LOG))
    monkeypatch.setattr(telemetry, "watching_compiles", lambda: True)
    return {"spans": Spans({"batch": [(100.0, 100.1), (104.0, 104.1)],
                            "dispatch": [(100.1, 100.2)],
                            "block": [(109.0, 110.0)]})}


def read(name, ctx):
    return harness.load_module("metrics", name).read(ctx)


@pytest.mark.parametrize("name", READERS)
def test_each_reader_against_a_seeded_log(ctx, name):
    assert read(name, ctx) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", READERS)
def test_no_log_no_spans_or_a_dropped_record_read_none(ctx, monkeypatch,
                                                       name):
    assert read(name, {"spans": Spans({})}) is None
    assert read(name, {}) is None
    monkeypatch.setattr(telemetry, "phases", lambda: None)
    assert read(name, ctx) is None
    monkeypatch.delattr(telemetry, "phases")   # a tree without the log
    assert read(name, ctx) is None


def test_unwatched_compiles_leave_only_the_builds_phase(ctx, monkeypatch):
    monkeypatch.setattr(telemetry, "watching_compiles", lambda: False)
    got = {n: read(n, ctx) for n in READERS}
    assert got == {n: (30.0 if n == "setup_build_s" else None)
                   for n in READERS}


def test_the_entries_are_the_readers():
    spec = harness.read_json("BENCHMARK.json")
    entries = {m["name"]: m for m in spec["per_layer"]
               if m["name"] in READERS}
    assert set(entries) == set(READERS)
    for name, m in entries.items():
        assert "workloads" not in m
        assert m["better"] == "lower"
        if name == "window_programs_compiled":
            assert (m["layer"], m["moves"]) == ("loop",
                                                "train_examples_per_s")
        else:
            assert (m["layer"], m["moves"]) == ("set-up", "setup_s")
        assert m["source"] == ("program_counter" if "programs" in name
                               else "program_span")


def test_a_traced_rehearsal_reports_all_six(tmp_path):
    """On the empty cache of a fresh checkout set-up compiles its programs;
    the window compiles none."""
    checkout = tiny.make_checkout(str(tmp_path))
    rc, out, err = tiny.run_cell(
        checkout, "--workload", "gpt_tiny.train_b4_s64", "--seed",
        str(2 ** 31 + 7), "--seconds", "2", "--trace", "1", "--rehearsal")
    assert rc == 0, err[-3000:]
    metrics = json.loads(out.strip().splitlines()[-1])["metrics"]
    assert set(READERS) <= set(metrics)
    got = {n: metrics[n]["value"] for n in READERS}
    assert got["setup_programs_compiled"] > 0
    assert got["window_programs_compiled"] == 0
    assert got["setup_build_s"] > 0 and got["setup_trace_lower_s"] > 0
    assert got["setup_compile_s"] > 0
    assert metrics["setup_programs_compiled"]["unit"] == "programs"
