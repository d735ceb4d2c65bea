"""`correct` has to come out false when the timed path is broken underneath
it, and the lower-precision control has to fail the comparison. At a tiny size
on the CPU; the chip's readings at the cells' own sizes are in PERF.md."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

PLANT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "plant.py")


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny.make_checkout(str(tmp_path_factory.mktemp("faults")))


def plant(checkout, workload, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, PLANT, workload, fault, "7", "2"], cwd=checkout,
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,fault,caught_by", [
    ("gpt_tiny.train_b4_s64", "unchanged_state", "change_gap"),
    ("gpt_tiny.train_b4_s64", "half_batch", "grad_gap"),
    ("resnet_tiny.train_b8_i32", "unchanged_state", "change_gap"),
    ("resnet_tiny.train_b8_i32", "half_batch", "grad_gap"),
    ("gpt_tiny.serve_tiny", "altered_token", "logit_gap"),
])
def test_a_planted_fault_is_not_correct(checkout, workload, fault, caught_by):
    line = plant(checkout, workload, fault)
    assert line["correct"] is False
    got = line["compared"][caught_by]
    assert got["value"] > got["limit"]


def test_the_lower_precision_control_fails_the_training_comparison(checkout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/calibrate.py", "--workload",
         "gpt_tiny.train_b4_s64", "--seeds", "7", "--control", "7",
         "--rehearsal"], cwd=checkout, env=env, capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]  # 1: a wrong verdict
    recs = {r["kind"]: r for r in map(json.loads,
                                      proc.stdout.strip().splitlines())}
    program, control = recs["program"], recs["control:fp8"]
    assert program["correct"] is True and control["correct"] is False
    limits = control["limits"]
    assert limits == tiny.TRAIN_TINY["limits"]
    assert any(control[k] > limits[k] for k in limits)
    assert control["grad_gap"] > 3 * program["grad_gap"]
