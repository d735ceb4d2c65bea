"""The tiny Xing4.0 cell of the CPU tests, added to `tiny.make_checkout`'s
throw-away copy as new files plus entries: the program's `xing4_tiny` preset
(every mechanism of the family at small widths, as a share: experts 2-5 of a
router 8 wide, four residual streams) under the tiny training traffic,
reporting the per-layer metrics the xing4 cell reports."""

from __future__ import annotations

import json
import os

import tiny

XING_TINY = {
    "source": "tests only: the program's xing4_tiny preset",
    "model_type": "xing4_0", "num_hidden_layers": 2,
    "first_k_dense_replace": 1, "hidden_size": 64,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "num_attention_heads": 2, "q_lora_rank": 32, "kv_lora_rank": 24,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32, "type": "yarn"},
    "rms_norm_eps": 1e-06, "intermediate_size": 96,
    "moe_intermediate_size": 32, "n_shared_experts": 1,
    "n_routed_experts": 4, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2, "scoring_func": "sigmoid", "n_group": 1,
    "topk_group": 1, "num_nextn_predict_layers": 0, "vocab_size": 512,
    "share": {"chips_per_layer": 2, "first_expert": 2, "router_width": 8},
    "assumed": {"load_balance_coeff": 0.001, "hc_alpha_init": 0.01,
                "hc_res_diagonal_init": 4.0},
    "reference": "xing4", "counts": "xing4",
    "train": {"model": "xing4_tiny", "precision": "fp32",
              "attention_impl": "flash",
              "optimizer": {"name": "adamw", "learning_rate": 3e-4,
                            "beta1": 0.9, "beta2": 0.95, "eps": 1e-8,
                            "weight_decay": 0.1}},
}
CELL = "xing_tiny.train_b4_s64"
XING_CELL = "xing4.train_b1_s4096_ep8"


def add_cell(checkout: str) -> str:
    """Add the tiny Xing4.0 cell to a checkout `tiny.make_checkout` made."""
    tiny.add(checkout, "benchmark/configs/xing_tiny.json",
             json.dumps(XING_TINY))
    spec_path = os.path.join(checkout, "BENCHMARK.json")
    with open(spec_path) as fh:
        spec = json.load(fh)
    spec["configs"].append({
        "name": "xing_tiny", "source": XING_TINY["source"],
        "file": "benchmark/configs/xing_tiny.json", "reduced": [],
        "why": "tests only"})
    spec["workloads"].append({
        "name": CELL, "config": "xing_tiny", "traffic": "train_b4_s64",
        "chips": 1, "why": "tests only"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if XING_CELL in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(spec_path, "w") as fh:
        json.dump(spec, fh)
    return CELL


def make_checkout(dst: str) -> str:
    tiny.make_checkout(dst)
    add_cell(dst)
    return dst
