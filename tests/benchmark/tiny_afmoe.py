"""The tiny AFMoE cell of the CPU tests, added to `tiny.make_checkout`'s
throw-away copy as new files plus entries: the program's `afmoe_tiny` preset
(every mechanism of the family at small widths, as a share: experts 2-5 of a
router 8 wide) under the tiny training traffic, reporting the per-layer
metrics the trinity_mini cell reports."""

from __future__ import annotations

import json
import os

import tiny

S, F = "sliding_attention", "full_attention"
AFMOE_TINY = {
    "source": "tests only: the program's afmoe_tiny preset",
    "model_type": "afmoe", "layer_types": [S, S, F], "num_hidden_layers": 3,
    "num_dense_layers": 1, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 16,
    "rope_theta": 10000, "rms_norm_eps": 1e-05, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_shared_experts": 1, "num_experts": 4,
    "num_experts_per_tok": 2, "route_norm": True, "route_scale": 2.826,
    "score_func": "sigmoid", "load_balance_coeff": 0.001,
    "mup_enabled": True, "vocab_size": 512,
    "share": {"chips_per_layer": 2, "first_expert": 2, "router_width": 8},
    "reference": "afmoe", "counts": "afmoe",
    "train": {"model": "afmoe_tiny", "precision": "fp32",
              "attention_impl": "flash",
              "optimizer": {"name": "adamw", "learning_rate": 3e-4,
                            "beta1": 0.9, "beta2": 0.95, "eps": 1e-8,
                            "weight_decay": 0.1}},
}
CELL = "afmoe_tiny.train_b4_s64"
TRINITY_CELL = "trinity_mini.train_b1_s8192"


def add_cell(checkout: str) -> str:
    """Add the tiny AFMoE cell to a checkout `tiny.make_checkout` made."""
    tiny.add(checkout, "benchmark/configs/afmoe_tiny.json",
             json.dumps(AFMOE_TINY))
    spec_path = os.path.join(checkout, "BENCHMARK.json")
    with open(spec_path) as fh:
        spec = json.load(fh)
    spec["configs"].append({
        "name": "afmoe_tiny", "source": AFMOE_TINY["source"],
        "file": "benchmark/configs/afmoe_tiny.json", "reduced": [],
        "why": "tests only"})
    spec["workloads"].append({
        "name": CELL, "config": "afmoe_tiny", "traffic": "train_b4_s64",
        "chips": 1, "why": "tests only"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if TRINITY_CELL in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(spec_path, "w") as fh:
        json.dump(spec, fh)
    return CELL


def make_checkout(dst: str) -> str:
    tiny.make_checkout(dst)
    add_cell(dst)
    return dst
