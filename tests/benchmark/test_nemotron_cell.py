"""The nemotron3_nano cell rehearsed at a tiny size on the CPU (the program's
`nemotron3_nano_tiny` preset under the tiny training traffic): one run
through the training runner shared by its cases, the result line, the
step's counters and the parts its compiled step names, the planted fault
and the lower-precision control; the counts against a hand count; the
limits against the chip's readings; the cell's readers on a made-up
reduction; the configuration file against the published config.json and
the cut."""

import argparse
import json
import math
import os
import sys
import time

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402
import tiny_nemotron  # noqa: E402

sys.path.insert(0, tiny.REPO)
from benchmark import harness  # noqa: E402

counts = harness.load_module("counts", "nemotron_h")
NEW_METRICS = {"mamba_device_ms", "ssd_roofline"}
# The parts this step shares with the other expert cells' are read under the
# names they have there: one name a part.
SHARED_METRICS = {"moe_routing_device_ms", "moe_experts_device_ms",
                  "blocks_other_device_ms", "head_loss_device_ms",
                  "attention_full_device_ms", "flash_full_roofline"}
CELL_METRICS = NEW_METRICS | SHARED_METRICS
# The published config.json's numbers this configuration keeps as they are
# (every width, the router's width and its experts a token).
PUBLISHED = {
    "hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
    "n_groups": 8, "ssm_state_size": 128, "conv_kernel": 4, "expand": 2,
    "chunk_size": 128, "num_attention_heads": 32, "num_key_value_heads": 2,
    "head_dim": 128, "intermediate_size": 1856, "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_shared_experts": 1,
    "num_experts_per_tok": 6, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "layer_norm_epsilon": 1e-05,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 0.0001,
    "use_conv_bias": True, "mlp_hidden_act": "relu2",
    "tie_word_embeddings": False, "max_position_embeddings": 262144}


def nemotron():
    with open(os.path.join(tiny.REPO, "benchmark", "configs",
                           "nemotron3_nano.json")) as fh:
        return json.load(fh)


def test_the_new_metrics_are_the_cells_and_only_the_cells():
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    tiny.check_cell_metrics(spec, tiny_nemotron.NEMOTRON_CELL, CELL_METRICS)
    for m in spec["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [tiny_nemotron.NEMOTRON_CELL]


@pytest.fixture(scope="module")
def rehearsed():
    """One run of the tiny cell through the training runner, in this
    process, as `benchmark/run.py --rehearsal` runs it: its result line,
    the metrics of every step the program took and the compiled step; then,
    in the same test's compile cache (the program and the float32
    reference are compiled once), the readings of the planted
    `half_sequence` fault as `faults_one_sequence.py` takes them and of the
    lower-precision control as `calibrate.py --control` takes them, both
    against the run's own float32 reference readings."""
    from benchmark import faults_one_sequence, train_check
    from benchmark.references import lowprec
    from benchmark.runners import train as runner

    cell = tiny_nemotron.tiny_cell()
    seed = 2 ** 31 + 17
    args = argparse.Namespace(seed=seed, seconds=1.0, trace=0,
                              rehearsal=True)
    devices = harness.devices_for(cell, True)
    limits = cell["traffic_file"]["limits"]
    steps = {}

    def record(step):
        steps["step"], steps["metrics"] = step, []

        def recorded(*a):
            state, metrics = step(*a)
            steps["metrics"].append(metrics)
            return state, metrics
        return recorded

    # the float32 reference's readings `runner.check` takes are kept: the
    # fault and the control are compared with the same seed's
    kept = []

    def reference_readings(*a, **kw):
        kept.append(real(*a, **kw))
        return kept[-1]

    real = train_check.reference_readings
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(train_check, "reference_readings", reference_readings)
        line = runner.run(cell, args, devices, time.time(),
                          wrap_step=record)
    (want,) = kept
    # the fault's program readings and the control's 8-bit reference, each
    # against the float32 reference (`runner.check`'s comparison)
    prep = runner.prepare(cell, args, devices,
                          faults_one_sequence.half_sequence)
    ref, sz, tr = prep["ref"], prep["sz"], prep["traffic"]
    opt, key, rng = prep["opt"], prep["seed_key"], prep["rng"]
    got = real(ref, sz, tr, opt, key, rng, quant=lowprec.fp8)
    fault, control = (train_check.compare(readings, want)
                      for readings in (prep["program"], got))
    (fault_ok, _), (control_ok, _) = (train_check.verdict(numbers, limits)
                                      for numbers in (fault, control))
    return {"line": line, "metrics": jax.device_get(steps["metrics"]),
            "step": steps["step"], "limits": limits,
            "fault": (fault_ok, fault), "control": (control_ok, control)}


def test_the_rehearsal_is_correct(rehearsed):
    """The program's three steps agree with the reference's far inside the
    cell's limits (float32 both sides), and the window's steps all count."""
    line, metrics = rehearsed["line"], rehearsed["metrics"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and len(metrics) == line["attempted"] + 3
    assert set(line["metrics"]) == {"train_examples_per_s", "setup_s"}
    assert set(line["compared"]) == {"loss_gap", "grad_gap", "change_gap"}
    for entry in line["compared"].values():
        assert entry["value"] < 0.1 * entry["limit"]   # float32 policy


def test_the_step_carries_the_mixers_counter(rehearsed):
    """Two mixers' least summed decay of a chunk of 16, one number a step:
    head 4 of 4 at A = -4, steps of 0.1 at most as the reference draws
    them; and the experts' counters."""
    for m in rehearsed["metrics"]:
        assert -16 * 4 * 2.0 < m["ssd_min_chunk_log_decay"] < 0.0
        assert m["moe_dropped"] == 0.0
        assert 0 < m["moe_tokens_here"] <= 4 * 64 * 2   # T x k


def test_the_compiled_step_names_the_new_part(rehearsed):
    from distributeddeeplearning_tpu.analysis import anatomy

    table = rehearsed["step"].anatomy()
    parts = {anatomy.part_of(op_name) for op_name in table.values()}
    for part in ("mixer_mamba", "attention_full", "moe_routing",
                 "moe_experts", "attention_other", "mlp"):
        assert ("forward", part) in parts and ("backward", part) in parts
    # a mixer's projections are the block's products, outside its scope
    assert any("in_proj" in op_name and "mamba" not in op_name
               and anatomy.part_of(op_name)[1] == "attention_other"
               for op_name in table.values())


def test_half_a_sequence_left_out_is_not_correct(rehearsed):
    ok, fault = rehearsed["fault"]
    assert ok is False
    assert fault["grad_gap"] > 3 * rehearsed["limits"]["grad_gap"]


def test_the_lower_precision_control_fails_the_comparison(rehearsed):
    """The reference with every product's operands in 8-bit floating point
    is not correct against the float32 reference, by a limit the program's
    own run stays far inside."""
    ok, control = rehearsed["control"]
    limits = rehearsed["limits"]
    assert ok is False and any(control[k] > limits[k] for k in limits)
    program = {k: v["value"] for k, v in rehearsed["line"]["compared"].items()}
    assert control["grad_gap"] > 3 * program["grad_gap"]


# --------------------------------------------------------------------------
# the counts, by hand
# --------------------------------------------------------------------------

HAND = {"hidden_size": 4, "hybrid_override_pattern": "MEM*E",
        "mamba_num_heads": 2, "mamba_head_dim": 3, "n_groups": 1,
        "ssm_state_size": 2, "conv_kernel": 4, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 3, "moe_intermediate_size": 5,
        "moe_shared_expert_intermediate_size": 6, "n_routed_experts": 2,
        "num_experts_per_tok": 4, "share": {"router_width": 8},
        "vocab_size": 7}


def test_a_tiny_model_by_hand():
    """hidden 4; a mixer of 2 heads of 3 (6 wide), one group of B / C of 2,
    so xBC is 10 wide; attention 2 heads of 3 on one K/V head; an expert 5
    wide, the shared one 6, a router 8 wide of which 2 experts are held, 4
    a token; 3 positions, vocab 7."""
    mamba = 4 * (6 + 10 + 2) + 6 * 4 + 4 * 10
    gqa = 2 * 4 * 6 + 2 * 4 * 3
    held = 4 * 2 / 8
    experts = 4 * 8 + 2 * 4 * 6 + held * 2 * 4 * 5
    assert counts.mamba_macs_per_token(HAND) == mamba == 136
    assert counts.attention_macs_per_token(HAND) == gqa == 72
    assert counts.expected_held_experts_per_token(HAND) == 1.0
    assert counts.experts_macs_per_token(HAND) == experts == 120
    assert counts.linear_macs_per_token(HAND) == 2 * mamba + gqa + 2 * experts
    per_pair = 2 * 2 * 3 * 2
    recurrence = 4 * 3 * 2 * 2             # a token: 4 a state entry
    assert counts.attention_ops_per_pair(HAND) == per_pair
    assert counts.recurrence_ops_per_token(HAND) == recurrence
    forward = (2 * 3 * (2 * mamba + gqa + 2 * experts) + 2 * 2 * 4 * 7
               + per_pair * 6 + 2 * recurrence * 3)
    assert counts.forward_ops_per_example(HAND, 3) == forward
    assert counts.train_ops_per_example(HAND, {"seq_len": 3}) == 3 * forward


def test_least_seconds_say_which_bound_holds():
    """The recurrence is bound by bytes: three passes over x and y (4096
    wide) and B, C (1024 each) in bf16 and the float32 dt (64): 0.51 GB a
    mixer, 0.62 ms; its operations (4 x 64 x 128 x 64 a token, three passes)
    would take 0.26 ms."""
    cfg, peaks = nemotron(), harness.peaks_for("TPU v5 lite")
    s = 8192
    a_pass = s * ((2 * 4096 + 2048) * 2 + 4 * 64)
    assert counts.ssd_least_seconds(cfg, s, peaks) == \
        pytest.approx(3 * a_pass / 819e9)
    ops = 3 * 4 * 64 * 128 * 64 * s
    assert ops / 197e12 < 3 * a_pass / 819e9
    assert counts.ssd_least_seconds(cfg, s, peaks) == pytest.approx(
        0.6222e-3, rel=1e-3)


def test_the_mixers_projections_are_7_6_tflop_a_step():
    """The mixers' projections are 7.6 TFLOP a step before recompute."""
    cfg = nemotron()
    s = 8192
    assert counts.expected_held_experts_per_token(cfg) == 0.375
    mixers = 3 * 2 * s * 4 * counts.mamba_macs_per_token(cfg)
    assert mixers == pytest.approx(7.6e12, rel=0.01)
    assert counts.train_ops_per_example(cfg, {"seq_len": s}) == \
        pytest.approx(3 * counts.forward_ops_per_example(cfg, s))


# --------------------------------------------------------------------------
# the configuration file
# --------------------------------------------------------------------------

def test_the_configuration_is_the_published_one_cut_to_a_share():
    cfg = nemotron()
    assert cfg["source"].endswith("nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
                                  "/blob/main/config.json")
    assert {k: cfg[k] for k in PUBLISHED} == PUBLISHED
    assert cfg["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131072,
        "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"}
    assert sorted(cfg["reduced"]) == sorted([
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"])
    assert (cfg["num_hidden_layers"], cfg["hybrid_override_pattern"],
            cfg["n_routed_experts"], cfg["vocab_size"]) == \
        (9, "MEMEM*EME", 8, 16384)
    assert cfg["published"]["hybrid_override_pattern"][:9] == "MEMEM*EME"
    assert cfg["share"] == {"chips_per_layer": 16, "first_expert": 0,
                            "router_width": 128, "first_vocab_row": 0}
    assert cfg["layer_types"] == [
        {"M": "mamba", "E": "moe", "*": "full_attention"}[k]
        for k in cfg["hybrid_override_pattern"]]
    assert cfg["train"]["model"] == "nemotron3_nano_ep16"
    ref = harness.load_module("references", "nemotron_h")
    assert ref.param_count(ref.sizes(cfg)) == 666_962_944
    sizes = ref._shapes(ref.sizes(cfg))
    assert sum(math.prod(s) for s in sizes.values()) < 666_962_944


def test_the_limits_sit_between_the_chip_readings():
    """The traffic's limits against the readings its `limits_why` gives
    (v5e, the cell's own size): above the largest sound run, below the
    fp8 control's least `grad_gap` and below half a sequence left out."""
    with open(os.path.join(tiny.REPO, "benchmark", "traffic",
                           "train_b1_s8192_ep16.json")) as fh:
        traffic = json.load(fh)
    limits = traffic["limits"]
    assert set(traffic["limits_why"]) == set(limits) == {
        "loss_gap", "grad_gap", "change_gap"}
    assert 1.05e-4 < limits["loss_gap"] < 6.8e-4
    assert 0.00495 < limits["grad_gap"] < 0.0102
    assert 0.0030 < limits["change_gap"] < 0.21


# --------------------------------------------------------------------------
# the readers
# --------------------------------------------------------------------------

def test_the_readers_read_a_recorded_step(monkeypatch):
    """The device-time readers and the two rooflines over a made-up trace
    of one step, by the program's own rule (`analysis/anatomy.py`): times
    book under the new part, and each share is least over measured."""
    from distributeddeeplearning_tpu.analysis import anatomy  # noqa: F401
    from distributeddeeplearning_tpu.perf import aot

    step = "jit(step_fn)/grads/"
    mixer = "layer2/mixer/"
    table = {
        "fusion.1": step + "jvp(NemotronHLM)/" + mixer + "mamba/exp",
        "fusion.2": step + "transpose(jvp(NemotronHLM))/" + mixer
                    + "mamba/dot_general",
        "fusion.3": step + "jvp(NemotronHLM)/layer5/mixer/attn_full/"
                           "flash_fwd/pallas_call",
        "fusion.4": step + "jvp(NemotronHLM)/layer1/moe/moe_dispatch/sort",
        "ragged-dot-none.5": step + "transpose(jvp(NemotronHLM))/layer1/"
                                    "moe/moe_combine/mul/moe_experts",
        "fusion.6": step + "jvp(NemotronHLM)/layer1/moe/mlp/dot_general",
        "fusion.7": step + "jvp(NemotronHLM)/head/dot_general",
        "fusion.8": step + "transpose(jvp(loss))/mul",
        "fusion.9": step + "jvp(NemotronHLM)/" + mixer
                    + "in_proj/dot_general",
    }
    monkeypatch.setattr(
        aot, "anatomy",
        lambda name: table if name == "gspmd_train_step" else None)
    per_op = {"%fusion.1 = f32[] fusion()": 0.030,
              "%fusion.2 = f32[] fusion()": 0.050,
              "%fusion.3 = bf16[] fusion()": 0.060,
              "%fusion.4 = s32[] fusion()": 0.004,
              "%ragged-dot-none.5 = bf16[] custom-call()": 0.010,
              "%fusion.6 = bf16[] fusion()": 0.016,
              "%fusion.7 = f32[] fusion()": 0.006,
              "%fusion.8 = f32[] fusion()": 0.002,
              "%fusion.9 = bf16[] fusion()": 0.012}
    ctx = {"trace": {"per_op": per_op, "per_module": {"step": 0.19},
                     "busy_s": 0.19, "window_s": 0.19, "chips": 1},
           "chips": 1, "traffic": {"batch": 1, "seq_len": 8192},
           "traced_units": 2, "config": nemotron(),
           "peaks": harness.peaks_for("TPU v5 lite")}

    def read(name):
        return harness.load_module("metrics", name).read(ctx)

    assert read("mamba_device_ms") == pytest.approx(40.0)
    assert read("attention_full_device_ms") == pytest.approx(30.0)
    assert read("moe_routing_device_ms") == pytest.approx(2.0)
    assert read("moe_experts_device_ms") == pytest.approx(5.0)
    # the shared expert (scope mlp) and a mixer's projection; the head and
    # the loss
    assert read("blocks_other_device_ms") == pytest.approx(8.0 + 6.0)
    assert read("head_loss_device_ms") == pytest.approx(3.0 + 1.0)
    # four mixers at 0.6222 ms least; one attention layer
    assert read("ssd_roofline") == pytest.approx(100 * 4 * 0.6222 / 40.0,
                                                 rel=1e-3)
    flash = harness.load_module("counts", "flash_attention_gqa")
    least = flash.least_seconds(nemotron(), 8192, None, ctx["peaks"])
    assert read("flash_full_roofline") == pytest.approx(
        100 * least * 1e3 / 30.0)
    # a configuration without mixers, or a run without a trace, gives
    # nothing to read
    ctx["config"] = {"n_layer": 12}
    assert read("ssd_roofline") is None
    ctx["trace"] = None
    ctx.pop("anatomy_ms")
    for name in CELL_METRICS:
        assert read(name) is None
