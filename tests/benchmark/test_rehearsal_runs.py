"""One tiny-size CPU rehearsal of each runner: the result line's keys, the
traced run's extra keys, and that a run without a TPU fails unless told it is
a rehearsal, as does a tree that holds only the benchmark."""

import json
import os
import shutil
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny.make_checkout(str(tmp_path_factory.mktemp("bench")))


def last_line(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def check_line(line: dict, metrics: set):
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared" and line["compared"]
    for entry in line["compared"].values():
        assert set(entry) == {"value", "limit"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == metrics
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert line["device"]["platform"] == "cpu"
    assert {"kind", "count", "memory_peak_bytes"} <= set(line["device"])


CELLS = {
    "gpt_tiny.train_b4_s64": {"train_examples_per_s", "setup_s"},
    "resnet_tiny.train_b8_i32": {"train_examples_per_s", "setup_s"},
    "gpt_tiny.serve_tiny": {"serve_tokens_per_s", "ttft_p95_ms",
                            "itl_p95_ms", "setup_s"},
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_rehearsal_prints_the_result_line(checkout, cell):
    rc, out, err = tiny.run_cell(checkout, "--workload", cell, "--seed",
                                 str(2 ** 31 + 11), "--seconds", "2",
                                 "--trace", "0", "--rehearsal")
    assert rc == 0, err[-3000:]
    check_line(last_line(out), CELLS[cell])
    tail = err.strip().splitlines()
    assert tail[-1] == "correct: True"
    assert tail[-2].startswith("compared ") and " limit " in tail[-2]


HELD = os.path.join(os.path.dirname(os.path.abspath(__file__)), "held.py")


@pytest.mark.parametrize("cell", ["gpt_tiny.train_b4_s64",
                                  "resnet_tiny.train_b8_i32"])
def test_set_up_holds_no_second_tree_of_the_parameters_size(checkout, cell):
    """While the checked steps run the runner holds the step's state, the
    batch and scalars: the seed's weights are not kept beside the state
    (they are made again when the change is taken) and the first gradient is
    never a tree. Of the memory a cell reports, the step's own is all."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run([sys.executable, HELD, cell, "7"], cwd=checkout,
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    calls = last_line(proc.stdout)
    assert len(calls) == 3                       # train_check.STEPS
    for held in calls:
        beside = held["live"] - held["state"] - held["batch"]
        assert 0 <= beside < held["largest_leaf"] < held["params"]


TRACED = {
    # spans and counters exist on a CPU; a share of a TPU's peak or of its
    # trace does not, and its reader returns nothing rather than 0
    "gpt_tiny.train_b4_s64": (
        {"dispatch_ms.train", "dispatch_count.train"},
        {"step_mfu.train", "flash_attention_roofline",
         "device_idle_share.train", "decode_step_ms", "setup_s"}),
    "gpt_tiny.serve_tiny": (
        {"decode_step_ms"},
        {"step_mfu.serve", "decode_roofline", "device_idle_share.serve",
         "dispatch_ms.train", "setup_s"}),
}


@pytest.mark.parametrize("cell", sorted(TRACED))
def test_traced_rehearsal_reports_what_a_cpu_can(checkout, cell):
    rc, out, err = tiny.run_cell(
        checkout, "--workload", cell, "--seed", "5", "--seconds", "5",
        "--trace", "1", "--rehearsal")
    assert rc == 0, err[-3000:]
    line = last_line(out)
    present, absent = TRACED[cell]
    assert present <= set(line["metrics"])
    assert not absent & set(line["metrics"])
    assert line["correct"] is True
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


def test_sweep_prints_one_line_per_rate(checkout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/sweep.py", "--workload",
         "gpt_tiny.serve_tiny", "--rates", "10,40", "--seconds", "1",
         "--rehearsal"], cwd=checkout, env=env, capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    recs = [json.loads(line) for line in proc.stdout.strip().splitlines()]
    assert [r["rate_rps"] for r in recs] == [10.0, 40.0]
    for r in recs:
        assert r["attempted"] > 0 and r["failed"] == 0
        assert r["tokens_per_s"] > 0 and r["backlog_at_close"] >= 0
    assert recs[1]["attempted"] > 2 * recs[0]["attempted"]


def test_without_a_tpu_the_run_fails_and_prints_no_result(checkout):
    rc, out, err = tiny.run_cell(checkout, "--workload",
                                 "gpt_tiny.train_b4_s64", "--seed", "1",
                                 "--seconds", "1", "--trace", "0")
    assert rc != 0 and out.strip() == ""
    assert "no" in err and "TPU" in err


def test_an_unknown_workload_fails(checkout):
    rc, out, _ = tiny.run_cell(checkout, "--workload", "no.such_cell",
                               "--seed", "1", "--seconds", "1", "--trace",
                               "0", "--rehearsal")
    assert rc != 0 and out.strip() == ""


def test_a_tree_with_only_the_benchmark_fails(tmp_path):
    for rel in ("benchmark", os.path.join("tests", "benchmark")):
        shutil.copytree(os.path.join(tiny.REPO, rel),
                        os.path.join(str(tmp_path), rel),
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(tiny.REPO, "BENCHMARK.json"), str(tmp_path))
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as fh:
        cell = json.load(fh)["workloads"][0]["name"]
    rc, out, err = tiny.run_cell(str(tmp_path), "--workload", cell, "--seed",
                                 "1", "--seconds", "1", "--trace", "0",
                                 "--rehearsal")
    assert rc != 0 and out.strip() == "", err[-2000:]
