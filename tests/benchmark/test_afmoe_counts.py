"""`counts/afmoe.py` and `counts/flash_attention_gqa.py` against hand counts,
and the trinity_mini configuration file against what the issue states."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402

sys.path.insert(0, tiny.REPO)
from benchmark import harness  # noqa: E402

counts = harness.load_module("counts", "afmoe")
gqa = harness.load_module("counts", "flash_attention_gqa")


def trinity():
    with open(os.path.join(tiny.REPO, "benchmark", "configs",
                           "trinity_mini.json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("s,window,want", [
    # by hand: rows 0..w-1 see 1..w keys, the other s-w rows w each
    (4, 2, 1 + 2 + 2 + 2), (5, 5, 15), (3, 10, 6), (6, 1, 6),
    (8192, 2048, 14_681_088)])
def test_window_pairs(s, window, want):
    assert counts.window_pairs(s, window) == want
    brute = sum(1 for i in range(min(s, 64)) for j in range(min(s, 64))
                if 0 <= i - j < window)
    if s <= 64:
        assert brute == want


def test_causal_pairs():
    assert counts.causal_pairs(3) == 6
    assert counts.causal_pairs(8192) == 33_558_528


def test_a_tiny_model_by_hand():
    """hidden 4, 2 heads of 3 on 1 K/V head, dense width 5, expert width 2,
    a router 8 wide of which 2 experts are held, 4 a token; one dense layer
    (sliding) and one expert layer (full); window 2, 3 positions, vocab 7."""
    cfg = {"hidden_size": 4, "head_dim": 3, "num_attention_heads": 2,
           "num_key_value_heads": 1, "intermediate_size": 5,
           "moe_intermediate_size": 2, "num_experts": 2,
           "num_experts_per_tok": 4, "num_shared_experts": 1,
           "share": {"router_width": 8}, "num_hidden_layers": 2,
           "num_dense_layers": 1, "sliding_window": 2, "vocab_size": 7,
           "layer_types": ["sliding_attention", "full_attention"]}
    attention = 3 * 4 * 6 + 2 * 4 * 3          # q, gate, o; k, v: 96 MACs
    dense = 3 * 4 * 5                          # 60
    held = 4 * 2 / 8                           # one expert a token
    moe = 4 * 8 + 3 * 4 * 2 + held * 3 * 4 * 2  # router, shared, routed: 80
    assert counts.expected_held_experts_per_token(cfg) == 1.0
    assert counts.linear_macs_per_token(cfg) == 2 * attention + dense + moe
    pairs = (1 + 2 + 2) + 6                    # window 2, then causal
    assert counts.attention_pairs(cfg, 3) == pairs
    head = 2 * 2 * 4 * 7                       # the 2 positions with a target
    forward = 2 * 3 * (2 * 96 + 60 + 80) + head + 2 * 2 * 3 * 2 * pairs
    assert counts.forward_ops_per_example(cfg, 3) == forward
    assert counts.train_ops_per_example(cfg, {"seq_len": 3}) == 3 * forward
    # the routed experts of one layer: 3 products, forward and two backward
    assert counts.experts_train_ops(cfg, 3) == 3 * 3 * 2 * (3 * 1.0) * 4 * 2
    assert counts.experts_train_bytes(cfg, 3) == 3 * 2 * (
        2 * 3 * 4 * 2 + 3 * (2 * 4 + 2 * 2))
    # flash over grouped heads: six products a pair and Q head
    assert gqa.train_ops(cfg, 3, 2) == 6 * 2 * 5 * 3 * 2
    assert gqa.train_ops(cfg, 3) == 6 * 2 * 6 * 3 * 2
    assert gqa.train_bytes(cfg, 3) == 6 * (2 + 1) * 3 * 3 * 2


def test_the_cell_as_the_issue_counts_it():
    cfg = trinity()
    ops = counts.train_ops_per_example(cfg, {"seq_len": 8192})
    assert abs(ops - 18.1e12) / 18.1e12 < 5e-3
    per_pair = 2 * 2 * cfg["head_dim"] * cfg["num_attention_heads"]
    assert per_pair == 16384
    peaks = harness.peaks_for("TPU v5 lite")
    window = gqa.least_seconds(cfg, 8192, cfg["sliding_window"], peaks)
    full = gqa.least_seconds(cfg, 8192, None, peaks)
    assert window == pytest.approx(3.66e-3, rel=2e-3)   # compute-bound
    assert full == pytest.approx(8.37e-3, rel=2e-3)
    assert gqa.train_bytes(cfg, 8192) / peaks["hbm_bytes_per_s"] < window
    # each held expert sees S * 8 / 128 = 512 rows a step in expectation
    assert 8192 * counts.expected_held_experts_per_token(cfg) / 16 == 512


def test_the_configuration_file_is_the_published_one_cut_as_stated():
    cfg = trinity()
    catalog = {"head_dim": 128, "hidden_size": 2048,
               "intermediate_size": 6144, "moe_intermediate_size": 1024,
               "num_attention_heads": 32, "num_key_value_heads": 4,
               "num_experts_per_tok": 8, "num_shared_experts": 1,
               "sliding_window": 2048, "route_scale": 2.826,
               "rope_theta": 10000, "rms_norm_eps": 1e-05,
               "load_balance_coeff": 0.001, "score_func": "sigmoid",
               "max_position_embeddings": 131072}
    for key, value in catalog.items():
        assert cfg[key] == value, key
        assert key not in cfg["reduced"]
    assert cfg["published"] == {
        "num_hidden_layers": 32, "num_dense_layers": 2,
        "layer_types": cfg["published"]["layer_types"], "num_experts": 128,
        "vocab_size": 200192}
    assert sorted(cfg["reduced"]) == sorted(cfg["published"])
    # the guide's floors: a whole period and four expert layers, at least 8
    # experts, at least an eighth of the vocabulary
    kinds = cfg["layer_types"][cfg["num_dense_layers"]:]
    assert kinds == ["sliding_attention"] * 3 + ["full_attention"]
    assert cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["published"]["vocab_size"]
    assert cfg["share"]["chips_per_layer"] * cfg["num_experts"] == \
        cfg["share"]["router_width"] == 128
    ref = harness.load_module("references", "afmoe")
    sz = ref.sizes(cfg)
    n = sum(ref._size(s) for s in ref._shapes(sz).values()) + sum(
        ref._norm_scales(sz).values())
    assert abs(n - 705.5e6) / 705.5e6 < 1e-3
    assert 0.25 < 16 * n / 17179869184 < 0.8   # 16 B a parameter: 66 %
