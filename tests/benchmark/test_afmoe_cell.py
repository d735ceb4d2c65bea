"""The trinity_mini cell rehearsed at a tiny size on the CPU (the program's
`afmoe_tiny` preset under the tiny training traffic, added as new files plus
entries): the result line, the traced run's per-layer metrics that a CPU can
give, the planted faults and the lower-precision control."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402
import tiny_afmoe  # noqa: E402

PLANT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "plant.py")
NEW_METRICS = {"moe_routing_device_ms", "moe_experts_device_ms",
               "attention_window_device_ms", "attention_full_device_ms",
               "flash_window_roofline", "flash_full_roofline",
               "moe_experts_roofline", "blocks_other_device_ms",
               "head_loss_device_ms"}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_afmoe.make_checkout(str(tmp_path_factory.mktemp("afmoe")))


def last_line(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def check_the_cells_metrics(spec):
    tiny.check_cell_metrics(spec, tiny_afmoe.TRINITY_CELL, NEW_METRICS)


def test_the_new_metrics_are_the_cells_and_only_the_cells():
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as fh:
        check_the_cells_metrics(json.load(fh))


def test_rehearsal_prints_the_result_line(checkout):
    rc, out, err = tiny.run_cell(
        checkout, "--workload", tiny_afmoe.CELL, "--seed",
        str(2 ** 31 + 11), "--seconds", "2", "--trace", "0", "--rehearsal")
    assert rc == 0, err[-3000:]
    line = last_line(out)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"train_examples_per_s", "setup_s"}
    assert set(line["compared"]) == {"loss_gap", "grad_gap", "change_gap"}
    for entry in line["compared"].values():
        assert entry["value"] < 0.1 * entry["limit"]   # float32 policy


def test_traced_rehearsal_reports_what_a_cpu_can(checkout):
    """Spans exist on a CPU; a device trace and a TPU's peaks do not, and the
    seven new readers then return nothing rather than raise or report 0."""
    rc, out, err = tiny.run_cell(
        checkout, "--workload", tiny_afmoe.CELL, "--seed", "5", "--seconds",
        "4", "--trace", "1", "--rehearsal")
    assert rc == 0, err[-3000:]
    line = last_line(out)
    assert line["correct"] is True
    assert "dispatch_ms.train" in line["metrics"]
    assert not NEW_METRICS & set(line["metrics"])
    assert "setup_s" not in line["metrics"]


def plant(checkout, fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, PLANT, tiny_afmoe.CELL, fault, "7", "2"],
        cwd=checkout, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return last_line(proc.stdout)


@pytest.mark.parametrize("fault,caught_by", [
    ("unchanged_state", "change_gap"), ("half_batch", "grad_gap")])
def test_a_planted_fault_is_not_correct(checkout, fault, caught_by):
    line = plant(checkout, fault)
    assert line["correct"] is False
    got = line["compared"][caught_by]
    assert got["value"] > got["limit"]


@pytest.mark.parametrize("workload", [tiny_afmoe.CELL,
                                      "gpt_tiny.train_b4_s64"])
def test_half_a_sequence_left_out_is_not_correct(checkout, workload):
    """The fault for cells whose batch is one sequence (`half_batch` has no
    row to leave out there), through its own command line, as the chip
    reading is made."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/faults_one_sequence.py", "--workload",
         workload, "--seeds", "7", "--rehearsal"],
        cwd=checkout, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]  # 1: it came out correct
    rec = last_line(proc.stdout)
    assert rec["kind"] == "fault:half_sequence" and rec["correct"] is False
    assert rec["grad_gap"] > 3 * rec["limits"]["grad_gap"]


def test_the_lower_precision_control_fails_the_comparison(checkout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "benchmark/calibrate.py", "--workload",
         tiny_afmoe.CELL, "--seeds", "7", "--control", "7", "--rehearsal"],
        cwd=checkout, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]  # 1: a wrong verdict
    recs = {r["kind"]: r for r in map(json.loads,
                                      proc.stdout.strip().splitlines())}
    program, control = recs["program"], recs["control:fp8"]
    assert program["correct"] is True and control["correct"] is False
    limits = control["limits"]
    assert any(control[k] > limits[k] for k in limits)
    assert control["grad_gap"] > 3 * program["grad_gap"]


def test_the_readers_read_a_recorded_step(monkeypatch):
    """The six device-time readers and the three rooflines over a made-up
    trace of one step, by the program's own rule (`analysis/anatomy.py`):
    times book under the new parts, and each share is least over measured."""
    sys.path.insert(0, tiny.REPO)
    from benchmark import harness
    from distributeddeeplearning_tpu.perf import aot

    step = "jit(step_fn)/grads/"
    table = {
        "fusion.1": step + "jvp(AfmoeLM)/layer1/attention/attn_window/"
                           "flash_fwd/pallas_call",
        "fusion.2": step + "transpose(jvp(AfmoeLM))/layer4/attention/"
                           "attn_full/flash_dkv/pallas_call",
        "fusion.3": step + "jvp(AfmoeLM)/layer2/moe/moe_dispatch/sort",
        "ragged-dot-none.4": step + "transpose(jvp(AfmoeLM))/layer2/moe/"
                                    "moe_combine/mul/moe_experts",
        "fusion.5": step + "jvp(AfmoeLM)/layer2/moe/mlp/dot_general",
        "fusion.6": step + "jvp(AfmoeLM)/head/dot_general",
        "fusion.7": step + "transpose(jvp(loss))/mul",
        "fusion.8": step + "jvp(AfmoeLM)/layer1/attention/q_proj/"
                           "dot_general",
    }
    monkeypatch.setattr(
        aot, "anatomy",
        lambda name: table if name == "gspmd_train_step" else None)
    per_op = {"%fusion.1 = bf16[] fusion()": 0.040,
              "%fusion.2 = bf16[] fusion()": 0.030,
              "%fusion.3 = s32[] fusion()": 0.004,
              "%ragged-dot-none.4 = bf16[] custom-call()": 0.010,
              "%fusion.5 = bf16[] fusion()": 0.016,
              "%fusion.6 = f32[] fusion()": 0.006,
              "%fusion.7 = f32[] fusion()": 0.002,
              "%fusion.8 = bf16[] fusion()": 0.012}
    with open(os.path.join(tiny.REPO, "benchmark", "configs",
                           "trinity_mini.json")) as fh:
        cfg = json.load(fh)
    ctx = {"trace": {"per_op": per_op, "per_module": {"step": 0.12},
                     "busy_s": 0.12, "window_s": 0.12, "chips": 1},
           "chips": 1, "traffic": {"batch": 1, "seq_len": 8192},
           "traced_units": 2, "config": cfg,
           "peaks": harness.peaks_for("TPU v5 lite")}

    def read(name):
        return harness.load_module("metrics", name).read(ctx)

    assert read("attention_window_device_ms") == pytest.approx(20.0)
    assert read("attention_full_device_ms") == pytest.approx(15.0)
    assert read("moe_routing_device_ms") == pytest.approx(2.0)
    assert read("moe_experts_device_ms") == pytest.approx(5.0)
    # the shared expert (scope mlp) and a projection; the head and the loss
    assert read("blocks_other_device_ms") == pytest.approx(8.0 + 6.0)
    assert read("head_loss_device_ms") == pytest.approx(3.0 + 1.0)
    # four window layers at 3.663 ms least, one full at 8.373, four expert
    # layers at 1.570
    assert read("flash_window_roofline") == pytest.approx(
        100 * 4 * 3.66297 / 20.0, rel=1e-4)
    assert read("flash_full_roofline") == pytest.approx(
        100 * 8.37294 / 15.0, rel=1e-4)
    assert read("moe_experts_roofline") == pytest.approx(
        100 * 4 * 1.56973 / 5.0, rel=1e-4)
    # a configuration without these layers, or a run without a trace, gives
    # nothing to read
    ctx["config"] = {"n_layer": 12}
    assert read("flash_window_roofline") is None
    assert read("moe_experts_roofline") is None
    ctx["trace"] = None
    ctx.pop("anatomy_ms")
    assert read("moe_routing_device_ms") is None
