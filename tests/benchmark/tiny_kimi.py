"""The tiny Kimi Linear cell of the CPU tests, added to `tiny.make_checkout`'s
throw-away copy as new files plus entries: the program's `kimi_linear_tiny`
preset (every mechanism of the family at small widths, as a share: experts
2-5 of a router 8 wide) under the tiny training traffic, reporting the
per-layer metrics the kimi_linear cell reports."""

from __future__ import annotations

import json
import os

import tiny

KIMI_TINY = {
    "source": "tests only: the program's kimi_linear_tiny preset",
    "model_type": "kimi_linear", "num_hidden_layers": 4,
    "first_k_dense_replace": 1, "hidden_size": 64,
    "linear_attn_config": {"kda_layers": [1, 2, 4], "full_attn_layers": [3],
                           "num_heads": 2, "head_dim": 16,
                           "short_conv_kernel_size": 4},
    "num_attention_heads": 2, "kv_lora_rank": 24, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "mla_use_nope": True, "rms_norm_eps": 1e-05, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_shared_experts": 1, "num_experts": 4,
    "num_experts_per_token": 2, "moe_renormalize": True,
    "routed_scaling_factor": 2.446, "vocab_size": 512,
    "share": {"chips_per_layer": 2, "first_expert": 2, "router_width": 8},
    "assumed": {"load_balance_coeff": 0.001},
    "reference": "kimi_linear", "counts": "kimi_linear",
    "train": {"model": "kimi_linear_tiny", "precision": "fp32",
              "attention_impl": "flash",
              "optimizer": {"name": "adamw", "learning_rate": 3e-4,
                            "beta1": 0.9, "beta2": 0.95, "eps": 1e-8,
                            "weight_decay": 0.1}},
}
CELL = "kimi_tiny.train_b4_s64"
KIMI_CELL = "kimi_linear.train_b1_s8192_ep32"


def add_cell(checkout: str) -> str:
    """Add the tiny Kimi Linear cell to a checkout `tiny.make_checkout`
    made."""
    tiny.add(checkout, "benchmark/configs/kimi_tiny.json",
             json.dumps(KIMI_TINY))
    spec_path = os.path.join(checkout, "BENCHMARK.json")
    with open(spec_path) as fh:
        spec = json.load(fh)
    spec["configs"].append({
        "name": "kimi_tiny", "source": KIMI_TINY["source"],
        "file": "benchmark/configs/kimi_tiny.json", "reduced": [],
        "why": "tests only"})
    spec["workloads"].append({
        "name": CELL, "config": "kimi_tiny", "traffic": "train_b4_s64",
        "chips": 1, "why": "tests only"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if KIMI_CELL in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(spec_path, "w") as fh:
        json.dump(spec, fh)
    return CELL


def make_checkout(dst: str) -> str:
    tiny.make_checkout(dst)
    add_cell(dst)
    return dst
