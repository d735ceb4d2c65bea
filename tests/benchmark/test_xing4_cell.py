"""The xing4 cell rehearsed at a tiny size on the CPU (the program's
`xing4_tiny` preset under the tiny training traffic, added as new files plus
entries): the result line, the traced run's per-layer metrics that a CPU can
give, the planted faults and the lower-precision control; the counts against a
hand count; the cell's readers on a made-up reduction; the configuration file
against what the issue and the catalog state."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402
import tiny_xing4  # noqa: E402

sys.path.insert(0, tiny.REPO)
from benchmark import harness  # noqa: E402

counts = harness.load_module("counts", "xing4")
PLANT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "plant.py")
NEW_METRICS = {"mhc_device_ms", "mhc_roofline", "flash_mla_rope_roofline"}
# The parts this step shares with the trinity and kimi cells' are read under
# the names they have there: one name a part.
SHARED_METRICS = {"moe_routing_device_ms", "moe_experts_device_ms",
                  "blocks_other_device_ms", "head_loss_device_ms",
                  "mla_device_ms"}
CELL_METRICS = NEW_METRICS | SHARED_METRICS


def xing():
    with open(os.path.join(tiny.REPO, "benchmark", "configs",
                           "xing4.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return tiny_xing4.make_checkout(str(tmp_path_factory.mktemp("xing4")))


def last_line(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def _cpu_env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def test_the_new_metrics_are_the_cells_and_only_the_cells():
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    tiny.check_cell_metrics(spec, tiny_xing4.XING_CELL, CELL_METRICS)
    by_name = {m["name"]: m for m in spec["per_layer"]}
    for name in NEW_METRICS:
        assert by_name[name]["workloads"] == [tiny_xing4.XING_CELL]
    assert by_name["mhc_device_ms"]["layer"] == "model step"
    assert by_name["mhc_roofline"]["layer"] == "kernels"
    cell = [w for w in spec["workloads"]
            if w["name"] == tiny_xing4.XING_CELL][0]
    assert (cell["config"], cell["traffic"]) == ("xing4",
                                                 "train_b1_s4096_ep8")
    with open(os.path.join(tiny.REPO, "benchmark", "traffic",
                           "train_b1_s4096_ep8.json")) as fh:
        traffic = json.load(fh)
    assert (traffic["runner"], traffic["batch"], traffic["seq_len"]) == \
        ("train", 1, 4096)
    # three limits, each with its reason; the loss's is held against half a
    # sequence left out, not against the fp8 control (PERF.md section 2)
    assert set(traffic["limits"]) == {"loss_gap", "grad_gap", "change_gap"}
    assert set(traffic["limits_why"]) == set(traffic["limits"])
    # between the largest sound reading and the fault's, with room each way
    assert 3 * 2.01e-4 < traffic["limits"]["loss_gap"] < 2.65e-3 / 3


def test_traced_rehearsal_reports_what_a_cpu_can(checkout):
    """One run, traced: the result line with the comparison's three numbers
    (float32 policy: far inside the tiny limits), and of the per-layer
    metrics what a CPU can give. Spans exist on a CPU; a device trace and a
    TPU's peaks do not, and the cell's part readers then return nothing
    rather than raise or report 0. (The untraced line's two end-to-end
    metrics are `tests/benchmark/test_rehearsal_runs.py`'s to hold, for
    every runner.)"""
    rc, out, err = tiny.run_cell(
        checkout, "--workload", tiny_xing4.CELL, "--seed",
        str(2 ** 31 + 17), "--seconds", "3", "--trace", "1", "--rehearsal")
    assert rc == 0, err[-3000:]
    line = last_line(out)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["compared"]) == {"loss_gap", "grad_gap", "change_gap"}
    for entry in line["compared"].values():
        assert entry["value"] < 0.1 * entry["limit"]   # float32 policy
    assert "dispatch_ms.train" in line["metrics"]
    assert not CELL_METRICS & set(line["metrics"])
    assert "setup_s" not in line["metrics"]


def test_a_state_left_unchanged_is_not_correct(checkout):
    proc = subprocess.run(
        [sys.executable, PLANT, tiny_xing4.CELL, "unchanged_state", "7", "2"],
        cwd=checkout, env=_cpu_env(), capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = last_line(proc.stdout)
    assert line["correct"] is False
    got = line["compared"]["change_gap"]
    assert got["value"] == pytest.approx(1.0) and got["value"] > got["limit"]


def test_half_a_sequence_left_out_is_not_correct(checkout):
    proc = subprocess.run(
        [sys.executable, "benchmark/faults_one_sequence.py", "--workload",
         tiny_xing4.CELL, "--seeds", "7", "--rehearsal"],
        cwd=checkout, env=_cpu_env(), capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]  # 1: it came out correct
    rec = last_line(proc.stdout)
    assert rec["kind"] == "fault:half_sequence" and rec["correct"] is False
    assert rec["grad_gap"] > 3 * rec["limits"]["grad_gap"]


def test_the_lower_precision_control_fails_the_comparison(checkout):
    proc = subprocess.run(
        [sys.executable, "benchmark/calibrate.py", "--workload",
         tiny_xing4.CELL, "--seeds", "7", "--control", "7", "--rehearsal"],
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
        "n_routed_experts": 2, "num_experts_per_tok": 4,
        "n_shared_experts": 1, "share": {"router_width": 8},
        "num_hidden_layers": 3, "first_k_dense_replace": 1, "vocab_size": 7,
        "num_attention_heads": 2, "q_lora_rank": 3, "kv_lora_rank": 3,
        "qk_nope_head_dim": 2, "qk_rope_head_dim": 1, "v_head_dim": 2,
        "hc_mult": 2}


def test_a_tiny_model_by_hand():
    """hidden 4, 2 streams; latent attention: 2 heads, nope 2, rope 1, values
    2, query bottleneck 3, latent 3; dense width 5, expert width 2, a router 8
    wide of which 2 experts are held, 4 a token; one dense layer, two of
    experts; 3 positions, vocab 7."""
    mla = (4 * 3 + 3 * 2 * 3        # the query: down, up to 2 heads of 3
           + 4 * (3 + 1)            # keys and values: down, with the rope key
           + 3 * 2 * (2 + 2)        # up to 2 heads of (nope | values)
           + 2 * 2 * 4)             # o
    mhc = (2 * 4) * (2 * 2 + 2 * 2) + (2 + 4 + 2) * 4   # Phi, then the mixes
    dense = 3 * 4 * 5
    held = 4 * 2 / 8
    moe = 4 * 8 + 3 * 4 * 2 + held * 3 * 4 * 2
    assert counts.mla_macs_per_token(HAND) == mla == 86
    assert counts.mhc_macs_per_token(HAND) == mhc == 96
    assert counts.expected_held_experts_per_token(HAND) == 1.0
    assert counts.linear_macs_per_token(HAND) == \
        3 * (mla + 2 * mhc) + dense + 2 * moe
    pairs = 6                                  # 3 positions, causal
    per_pair = 2 * 2 * (2 + 1 + 2)             # QK^T at 3, PV at 2, 2 heads
    assert counts.mla_ops_per_pair(HAND) == per_pair
    forward = (2 * 3 * (3 * (mla + 2 * mhc) + dense + 2 * moe)
               + 2 * 2 * 4 * 7 + 3 * per_pair * pairs)
    assert counts.forward_ops_per_example(HAND, 3) == forward
    assert counts.train_ops_per_example(HAND, {"seq_len": 3}) == 3 * forward
    assert counts.mhc_bytes_per_token(HAND) == (3 * 2 + 2) * 4 * 2


def test_the_cells_step_is_what_the_issue_counts():
    """ISSUE 34: ≈ 11.7 TFLOP an example at S = 4096: 0.86 TFLOP of
    attention's pairs forward, the rest products, times three."""
    cfg = xing()
    s = 4096
    assert counts.expected_held_experts_per_token(cfg) == 0.5
    assert counts.mla_macs_per_token(cfg) == 28_409_856
    assert counts.mhc_macs_per_token(cfg) == 14336 * 24 + 24 * 3584
    # ISSUE 34's 370.3M multiply-accumulates a token forward: the blocks'
    # and the head's (it left the mixes' 0.86M out)
    with_head = counts.linear_macs_per_token(cfg) + 3584 * 16384
    assert with_head - 10 * 24 * 3584 == pytest.approx(370.3e6, rel=5e-4)
    pairs = 5 * counts.mla_ops_per_pair(cfg) * counts.causal_pairs(s)
    assert counts.mla_ops_per_pair(cfg) == 2 * (192 + 128) * 32
    assert pairs == pytest.approx(0.86e12, rel=0.005)
    products = (2 * s * counts.linear_macs_per_token(cfg)
                + 2 * (s - 1) * 3584 * 16384)
    assert counts.train_ops_per_example(cfg, {"seq_len": s}) == \
        pytest.approx(3 * (products + pairs))
    assert 3 * (products + pairs) == pytest.approx(11.7e12, rel=0.005)


def test_least_seconds_say_which_bound_holds():
    """A hyper-connection is bound by bytes (three passes' worth of the
    streams read twice and written once and the sub-layer's input and
    result, in bf16: 1.23 GB, 1.51 ms), latent attention by operations (0.52
    TFLOP a layer, 2.62 ms)."""
    cfg, peaks = xing(), harness.peaks_for("TPU v5 lite")
    s = 4096
    moved = 3 * s * (3 * 4 + 2) * 3584 * 2
    assert counts.mhc_least_seconds(cfg, s, peaks) == \
        pytest.approx(moved / 819e9)
    assert moved == pytest.approx(1.233e9, rel=1e-3)
    assert 3 * 2 * counts.mhc_macs_per_token(cfg) * s / 197e12 < \
        0.1 * moved / 819e9
    ops = 3 * 2 * (192 + 128) * 32 * (s * (s + 1) // 2)
    assert counts.mla_least_seconds(cfg, s, peaks) == \
        pytest.approx(ops / 197e12)


# --------------------------------------------------------------------------
# the configuration file
# --------------------------------------------------------------------------

REDUCED = {"num_hidden_layers": (40, 5), "first_k_dense_replace": (2, 1),
           "n_routed_experts": (64, 8), "vocab_size": (131072, 16384),
           "num_nextn_predict_layers": (1, 0)}


def test_the_configuration_is_the_published_one_cut_as_the_issue_says():
    cfg = xing()
    assert cfg["source"].endswith(
        "XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json")
    published = {"hidden_size": 3584, "intermediate_size": 9216,
                 "moe_intermediate_size": 1024, "q_lora_rank": 768,
                 "kv_lora_rank": 512, "qk_nope_head_dim": 128,
                 "qk_rope_head_dim": 64, "v_head_dim": 128,
                 "num_attention_heads": 32, "num_experts_per_tok": 4,
                 "routed_scaling_factor": 2, "n_shared_experts": 1,
                 "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
                 "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
                 "rms_norm_eps": 1e-6, "rope_theta": 10000}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    assert sorted(cfg["reduced"]) == sorted(REDUCED)
    for key, (was, now) in REDUCED.items():
        assert cfg[key] == now
        stated = cfg["published"][key]
        assert stated == was or str(stated).startswith(f"{was}:")
    assert cfg["share"] == {"chips_per_layer": 8, "first_expert": 0,
                            "router_width": 64, "first_vocab_row": 0}
    assert "8 chips share each layer" in cfg["deployment"]
    for assumed in ("hyper_connections", "hc_alpha_init",
                    "hc_res_diagonal_init", "hc_static_terms",
                    "latent_attention", "rope_pairing", "load_balance_coeff",
                    "selection_bias", "optimizer", "weights"):
        assert assumed in cfg["assumed"]
    assert cfg["train"]["model"] == "xing4_ep8"
    assert cfg["train"]["precision"] == "mixed"
    assert cfg["train"]["attention_impl"] == "flash"
    assert cfg["train"]["optimizer"]["learning_rate"] == 1e-5
    ref = harness.load_module("references", "xing4")
    assert ref.param_count(ref.sizes(cfg)) == 759_489_550   # ISSUE 34


def test_the_configuration_keeps_every_number_of_the_catalog_row():
    """Where the catalog beside the `model-configs` guide is installed: every
    top-level number of the row's `config` is in the file under the same key,
    or the key is in `reduced`; nested groups are copied whole."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as fh:
        row = [r for r in map(json.loads, fh)
               if r["name"] == "Xing4.0-29B-A4B"][0]
    cfg = xing()
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value
        else:
            assert cfg[key] == value, key
    widths = {"hidden_size", "intermediate_size", "moe_intermediate_size",
              "num_experts_per_tok", "hc_mult"}
    assert not [k for k in cfg["reduced"]
                if k in widths or k.endswith(("_dim", "_rank"))]


# --------------------------------------------------------------------------
# the readers
# --------------------------------------------------------------------------

def test_the_readers_read_a_recorded_step(monkeypatch):
    """The six device-time readers and the two rooflines over a made-up
    trace of one step, by the program's own rule (`analysis/anatomy.py`):
    times book under the new part, the Sinkhorn loops' own time is left out,
    and each share is least over measured."""
    from distributeddeeplearning_tpu.analysis import anatomy
    from distributeddeeplearning_tpu.perf import aot

    step = "jit(step_fn)/grads/"
    fwd, bwd = step + "jvp(Xing4LM)/", step + "transpose(jvp(Xing4LM))/"
    table = {
        "fusion.1": fwd + "layer2/attn_hc/mhc/bsk,km->mbs/dot_general",
        "fusion.2": bwd + "layer2/mhc/reduce_sum",
        "fusion.3": fwd + "layer2/ffn_hc/mhc/while/body/div",
        "while.1": anatomy.SPANS_ITS_BRANCH + fwd + "layer2/ffn_hc/mhc/while",
        "fusion.4": fwd + "mhc/concatenate",
        "fusion.5": fwd + "layer3/attention/attn_mla/flash_fwd/pallas_call",
        "fusion.6": fwd + "layer2/moe/moe_dispatch/sort",
        "ragged-dot-none.7": bwd + "layer2/moe/moe_combine/mul/moe_experts",
        "fusion.8": fwd + "layer2/moe/mlp/dot_general",
        "fusion.9": fwd + "head/dot_general",
        "fusion.10": step + "transpose(jvp(loss))/mul",
        "fusion.11": fwd + "layer3/attention/q_b_proj/dot_general",
        "fusion.12": fwd + "layer3/input_layernorm/mul",
    }
    monkeypatch.setattr(
        aot, "anatomy",
        lambda name: table if name == "gspmd_train_step" else None)
    per_op = {"%fusion.1 = f32[] fusion()": 0.020,
              "%fusion.2 = f32[] fusion()": 0.050,
              "%fusion.3 = f32[] fusion()": 0.006,
              "%while.1 = () while()": 0.007,
              "%fusion.4 = bf16[] fusion()": 0.004,
              "%fusion.5 = bf16[] fusion()": 0.040,
              "%fusion.6 = s32[] fusion()": 0.004,
              "%ragged-dot-none.7 = bf16[] custom-call()": 0.010,
              "%fusion.8 = bf16[] fusion()": 0.016,
              "%fusion.9 = f32[] fusion()": 0.006,
              "%fusion.10 = f32[] fusion()": 0.002,
              "%fusion.11 = bf16[] fusion()": 0.012,
              "%fusion.12 = bf16[] fusion()": 0.002}
    ctx = {"trace": {"per_op": per_op, "per_module": {"step": 0.172},
                     "busy_s": 0.172, "window_s": 0.172, "chips": 1},
           "chips": 1, "traffic": {"batch": 1, "seq_len": 4096},
           "traced_units": 2, "config": xing(),
           "peaks": harness.peaks_for("TPU v5 lite")}

    def read(name):
        return harness.load_module("metrics", name).read(ctx)

    assert read("mhc_device_ms") == pytest.approx(40.0)   # not the while's
    assert read("mla_device_ms") == pytest.approx(20.0)
    assert read("moe_routing_device_ms") == pytest.approx(2.0)
    assert read("moe_experts_device_ms") == pytest.approx(5.0)
    # the shared expert (scope mlp), a projection and a norm; head and loss
    assert read("blocks_other_device_ms") == pytest.approx(8.0 + 6.0 + 1.0)
    assert read("head_loss_device_ms") == pytest.approx(3.0 + 1.0)
    # ten hyper-connections at 1.506 ms least, five layers at 2.617
    assert read("mhc_roofline") == pytest.approx(100 * 10 * 1.50565 / 40.0,
                                                 rel=1e-4)
    assert read("flash_mla_rope_roofline") == pytest.approx(
        100 * 5 * 2.61686 / 20.0, rel=1e-4)
    # kimi's share is keyed by its own configuration and reads nothing here
    assert read("flash_mla_roofline") is None
    # a configuration without hyper-connections, a program whose rule has no
    # such part, or a run without a trace gives nothing to read
    ctx["config"] = {"n_layer": 12}
    assert read("mhc_roofline") is None
    assert read("flash_mla_rope_roofline") is None
    ctx["config"] = xing()
    monkeypatch.setitem(anatomy._MODEL_SCOPES, "mhc", "attention_other")
    ctx.pop("anatomy_ms")
    assert read("mhc_device_ms") is None and read("mhc_roofline") is None
    ctx["trace"] = None
    ctx.pop("anatomy_ms")
    assert read("mhc_device_ms") is None
    for name in SHARED_METRICS:
        assert read(name) is None
