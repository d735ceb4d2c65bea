"""The kimi_linear cell rehearsed at a tiny size on the CPU (the program's
`kimi_linear_tiny` preset under the tiny training traffic, added as new files
plus entries): the result line, the traced run's per-layer metrics that a CPU
can give, the planted fault and the lower-precision control; the counts
against a hand count; the cell's eight readers on a made-up reduction; the
configuration file against what the issue states."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402
import tiny_kimi  # noqa: E402

sys.path.insert(0, tiny.REPO)
from benchmark import harness  # noqa: E402

counts = harness.load_module("counts", "kimi_linear")
NEW_METRICS = {"kda_device_ms", "kda_roofline", "mla_device_ms",
               "flash_mla_roofline"}
# The parts this step shares with the trinity cell's are read under the
# names they have there: one name a part.
SHARED_METRICS = {"moe_routing_device_ms", "moe_experts_device_ms",
                  "blocks_other_device_ms", "head_loss_device_ms"}
CELL_METRICS = NEW_METRICS | SHARED_METRICS


def kimi():
    with open(os.path.join(tiny.REPO, "benchmark", "configs",
                           "kimi_linear.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_kimi.make_checkout(str(tmp_path_factory.mktemp("kimi")))


def last_line(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def check_the_cells_metrics(spec):
    tiny.check_cell_metrics(spec, tiny_kimi.KIMI_CELL, CELL_METRICS)


def test_the_new_metrics_are_the_cells_and_only_the_cells():
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as fh:
        check_the_cells_metrics(json.load(fh))


def test_rehearsal_prints_the_result_line(checkout):
    rc, out, err = tiny.run_cell(
        checkout, "--workload", tiny_kimi.CELL, "--seed",
        str(2 ** 31 + 17), "--seconds", "2", "--trace", "0", "--rehearsal")
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
    cell's part readers then return nothing rather than raise or report 0."""
    rc, out, err = tiny.run_cell(
        checkout, "--workload", tiny_kimi.CELL, "--seed", "5", "--seconds",
        "4", "--trace", "1", "--rehearsal")
    assert rc == 0, err[-3000:]
    line = last_line(out)
    assert line["correct"] is True
    assert "dispatch_ms.train" in line["metrics"]
    assert not CELL_METRICS & set(line["metrics"])
    assert "setup_s" not in line["metrics"]


def test_half_a_sequence_left_out_is_not_correct(checkout):
    proc = subprocess.run(
        [sys.executable, "benchmark/faults_one_sequence.py", "--workload",
         tiny_kimi.CELL, "--seeds", "7", "--rehearsal"],
        cwd=checkout, env=_cpu_env(), capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]  # 1: it came out correct
    rec = last_line(proc.stdout)
    assert rec["kind"] == "fault:half_sequence" and rec["correct"] is False
    assert rec["grad_gap"] > 3 * rec["limits"]["grad_gap"]


def test_the_lower_precision_control_fails_the_comparison(checkout):
    proc = subprocess.run(
        [sys.executable, "benchmark/calibrate.py", "--workload",
         tiny_kimi.CELL, "--seeds", "7", "--control", "7", "--rehearsal"],
        cwd=checkout, env=_cpu_env(), capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]  # 1: a wrong verdict
    recs = {r["kind"]: r for r in map(json.loads,
                                      proc.stdout.strip().splitlines())}
    program, control = recs["program"], recs["control:fp8"]
    assert program["correct"] is True and control["correct"] is False
    limits = control["limits"]
    assert any(control[k] > limits[k] for k in limits)
    assert control["grad_gap"] > 3 * program["grad_gap"]


# --------------------------------------------------------------------------
# the counts, by hand
# --------------------------------------------------------------------------

HAND = {"hidden_size": 4, "intermediate_size": 5, "moe_intermediate_size": 2,
        "num_experts": 2, "num_experts_per_token": 4, "num_shared_experts": 1,
        "share": {"router_width": 8}, "num_hidden_layers": 3,
        "first_k_dense_replace": 1, "vocab_size": 7,
        "num_attention_heads": 2, "kv_lora_rank": 3, "qk_nope_head_dim": 2,
        "qk_rope_head_dim": 1, "v_head_dim": 2,
        "linear_attn_config": {"kda_layers": [1, 3], "full_attn_layers": [2],
                               "num_heads": 2, "head_dim": 3,
                               "short_conv_kernel_size": 4}}


def test_a_tiny_model_by_hand():
    """hidden 4; KDA: 2 heads of 3 (6 wide); latent: 2 heads, nope 2, rope 1,
    values 2, latent 3; dense width 5, expert width 2, a router 8 wide of
    which 2 experts are held, 4 a token; KDA with the dense FFN, latent and
    KDA with experts; 3 positions, vocab 7."""
    kda = (4 * 4 * 6              # q, k, v, o
           + 2 * (4 * 3 + 3 * 6)  # the two low-rank gates
           + 4 * 2                # beta
           + 3 * 4 * 6)           # three convolutions of 4 taps
    mla = 4 * 2 * 3 + 4 * (3 + 1) + 3 * 2 * (2 + 2) + 2 * 2 * 4
    dense = 3 * 4 * 5
    held = 4 * 2 / 8
    moe = 4 * 8 + 3 * 4 * 2 + held * 3 * 4 * 2
    assert counts.kda_macs_per_token(HAND) == kda == 236
    assert counts.mla_macs_per_token(HAND) == mla == 80
    assert counts.expected_held_experts_per_token(HAND) == 1.0
    assert counts.linear_macs_per_token(HAND) == 2 * kda + mla + dense + 2 * moe
    pairs = 6                                  # 3 positions, causal
    per_pair = 2 * 2 * (2 + 1 + 2)             # QK^T at 3, PV at 2, 2 heads
    recurrence = 7 * 3 * 3 * 2                 # a token: 7 a state entry
    assert counts.mla_ops_per_pair(HAND) == per_pair
    assert counts.recurrence_ops_per_token(HAND) == recurrence
    forward = (2 * 3 * (2 * kda + mla + dense + 2 * moe) + 2 * 2 * 4 * 7
               + per_pair * pairs + 2 * recurrence * 3)
    assert counts.forward_ops_per_example(HAND, 3) == forward
    assert counts.train_ops_per_example(HAND, {"seq_len": 3}) == 3 * forward


def test_the_cells_step_is_what_the_issue_counts():
    """ISSUE 31: ≈ 19 TFLOP a step: 16.5 of products, 2.06 of latent
    attention's pairs, ≈ 0.4 of the recurrence."""
    cfg = kimi()
    s = 8192
    assert counts.expected_held_experts_per_token(cfg) == 0.25
    assert counts.kda_macs_per_token(cfg) == 39_510_016
    assert counts.mla_macs_per_token(cfg) == 29_114_368
    products = 3 * (2 * s * counts.linear_macs_per_token(cfg)
                    + 2 * (s - 1) * 2304 * 20480)
    assert products == pytest.approx(16.5e12, rel=0.01)
    pairs = 3 * counts.mla_ops_per_pair(cfg) * counts.causal_pairs(s)
    assert counts.mla_ops_per_pair(cfg) == 2 * (192 + 128) * 32
    assert pairs == pytest.approx(2.06e12, rel=0.005)
    recurrence = 3 * 4 * counts.recurrence_ops_per_token(cfg) * s
    assert recurrence == pytest.approx(0.36e12, rel=0.01)
    assert counts.train_ops_per_example(cfg, {"seq_len": s}) == \
        pytest.approx(products + pairs + recurrence)


def test_least_seconds_say_which_bound_holds():
    """KDA's recurrence is bound by bytes (three passes over q, k, v, o in
    bf16 and the float32 gates: 1.21 GB a layer, 1.48 ms), latent attention
    by operations (2.06 TFLOP, 10.5 ms)."""
    cfg, peaks = kimi(), harness.peaks_for("TPU v5 lite")
    s = 8192
    a_pass = s * 32 * (4 * 128 * 2 + 128 * 4 + 4)
    assert counts.kda_least_seconds(cfg, s, peaks) == \
        pytest.approx(3 * a_pass / 819e9)
    assert 3 * counts.recurrence_ops_per_token(cfg) * s / 197e12 < \
        3 * a_pass / 819e9
    ops = 3 * 2 * (192 + 128) * 32 * (s * (s + 1) // 2)
    assert counts.mla_least_seconds(cfg, s, peaks) == \
        pytest.approx(ops / 197e12)


# --------------------------------------------------------------------------
# the configuration file
# --------------------------------------------------------------------------

def test_the_configuration_is_the_published_one_cut_as_the_issue_says():
    cfg = kimi()
    assert cfg["source"].endswith(
        "moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")
    published = {"hidden_size": 2304, "intermediate_size": 9216,
                 "moe_intermediate_size": 1024, "kv_lora_rank": 512,
                 "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                 "v_head_dim": 128, "num_attention_heads": 32,
                 "num_experts_per_token": 8, "first_k_dense_replace": 1,
                 "routed_scaling_factor": 2.446, "num_shared_experts": 1}
    assert {k: cfg[k] for k in published} == published
    lin = cfg["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert lin["kda_layers"] == [1, 2, 3, 5]
    assert lin["full_attn_layers"] == [4]
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 8, 20480)
    assert cfg["published"]["num_experts"] == 256
    assert cfg["published"]["vocab_size"] == 163840
    assert cfg["published"]["num_hidden_layers"] == 27
    assert sorted(cfg["reduced"]) == sorted([
        "num_hidden_layers", "linear_attn_config", "num_experts",
        "vocab_size"])
    assert cfg["share"] == {"chips_per_layer": 32, "first_expert": 0,
                            "router_width": 256, "first_vocab_row": 0}
    assert cfg["train"]["model"] == "kimi_linear_ep32"
    ref = harness.load_module("references", "kimi_linear")
    sizes = ref._shapes(ref.sizes(cfg))
    extra = 5 * 2 * 2304 + 2304 + 4 * (32 + 4096 + 4096 + 128) + 512
    total = sum(__import__("math").prod(s) for s in sizes.values()) + extra
    assert total == 602_449_792          # ISSUE 31: 602.5M


# --------------------------------------------------------------------------
# the readers
# --------------------------------------------------------------------------

def test_the_readers_read_a_recorded_step(monkeypatch):
    """The six device-time readers and the two rooflines over a made-up
    trace of one step, by the program's own rule (`analysis/anatomy.py`):
    times book under the new parts, the loops' own time is left out, and
    each share is least over measured."""
    from distributeddeeplearning_tpu.analysis import anatomy
    from distributeddeeplearning_tpu.perf import aot

    step = "jit(step_fn)/grads/"
    kda = "layer2/attention/attn_kda/"
    table = {
        "fusion.1": step + "jvp(KimiLinearLM)/" + kda + "while/body/exp",
        "fusion.2": step + "transpose(jvp(KimiLinearLM))/" + kda
                    + "while/body/checkpoint/dot_general",
        "while.1": anatomy.SPANS_ITS_BRANCH + step + "jvp(KimiLinearLM)/"
                   + kda + "while",
        "fusion.3": step + "jvp(KimiLinearLM)/layer3/attention/attn_mla/"
                           "flash_fwd/pallas_call",
        "fusion.4": step + "jvp(KimiLinearLM)/layer2/moe/moe_dispatch/sort",
        "ragged-dot-none.5": step + "transpose(jvp(KimiLinearLM))/layer2/"
                                    "moe/moe_combine/mul/moe_experts",
        "fusion.6": step + "jvp(KimiLinearLM)/layer2/moe/mlp/dot_general",
        "fusion.7": step + "jvp(KimiLinearLM)/head/dot_general",
        "fusion.8": step + "transpose(jvp(loss))/mul",
        "fusion.9": step + "jvp(KimiLinearLM)/layer3/attention/kv_b_proj/"
                           "dot_general",
    }
    monkeypatch.setattr(
        aot, "anatomy",
        lambda name: table if name == "gspmd_train_step" else None)
    per_op = {"%fusion.1 = f32[] fusion()": 0.050,
              "%fusion.2 = f32[] fusion()": 0.070,
              "%while.1 = () while()": 0.125,
              "%fusion.3 = bf16[] fusion()": 0.060,
              "%fusion.4 = s32[] fusion()": 0.004,
              "%ragged-dot-none.5 = bf16[] custom-call()": 0.010,
              "%fusion.6 = bf16[] fusion()": 0.016,
              "%fusion.7 = f32[] fusion()": 0.006,
              "%fusion.8 = f32[] fusion()": 0.002,
              "%fusion.9 = bf16[] fusion()": 0.012}
    ctx = {"trace": {"per_op": per_op, "per_module": {"step": 0.23},
                     "busy_s": 0.23, "window_s": 0.23, "chips": 1},
           "chips": 1, "traffic": {"batch": 1, "seq_len": 8192},
           "traced_units": 2, "config": kimi(),
           "peaks": harness.peaks_for("TPU v5 lite")}

    def read(name):
        return harness.load_module("metrics", name).read(ctx)

    assert read("kda_device_ms") == pytest.approx(60.0)   # not the while's
    assert read("mla_device_ms") == pytest.approx(30.0)
    assert read("moe_routing_device_ms") == pytest.approx(2.0)
    assert read("moe_experts_device_ms") == pytest.approx(5.0)
    # the shared expert (scope mlp) and a projection; the head and the loss
    assert read("blocks_other_device_ms") == pytest.approx(8.0 + 6.0)
    assert read("head_loss_device_ms") == pytest.approx(3.0 + 1.0)
    # four KDA layers at 1.479 ms least, one latent layer at 10.466
    assert read("kda_roofline") == pytest.approx(100 * 4 * 1.47876 / 60.0,
                                                 rel=1e-4)
    assert read("flash_mla_roofline") == pytest.approx(
        100 * 10.46617 / 30.0, rel=1e-4)
    # a configuration without these layers, or a run without a trace, gives
    # nothing to read
    ctx["config"] = {"n_layer": 12}
    assert read("kda_roofline") is None
    assert read("flash_mla_roofline") is None
    ctx["trace"] = None
    ctx.pop("anatomy_ms")
    assert read("kda_device_ms") is None
    for name in SHARED_METRICS:
        assert read(name) is None
