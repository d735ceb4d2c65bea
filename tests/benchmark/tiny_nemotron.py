"""The tiny Nemotron-H cell of the CPU tests: the program's
`nemotron3_nano_tiny` preset (every mechanism of the family at small widths,
as a share: experts 2-5 of a router 8 wide) under the tiny training traffic,
reporting the per-layer metrics the nemotron3_nano cell reports. It is run
in the test's own process, so it is a cell dictionary as
`benchmark/harness.py::load_cell` makes one, not files in a checkout."""

from __future__ import annotations

import json
import os

import tiny

NEMOTRON_TINY = {
    "source": "tests only: the program's nemotron3_nano_tiny preset",
    "model_type": "nemotron_h", "num_hidden_layers": 4,
    "hybrid_override_pattern": "MEM*", "hidden_size": 64,
    "layer_norm_epsilon": 1e-05, "mamba_num_heads": 4, "mamba_head_dim": 8,
    "n_groups": 2, "ssm_state_size": 8, "conv_kernel": 4, "chunk_size": 16,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "layer_types": ["mamba", "moe", "mamba", "full_attention"],
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48,
    "n_routed_experts": 4, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 0.0001, "vocab_size": 512,
    "share": {"chips_per_layer": 2, "first_expert": 2, "router_width": 8},
    "assumed": {"load_balance_coeff": 0.001},
    "reference": "nemotron_h", "counts": "nemotron_h",
    "train": {"model": "nemotron3_nano_tiny", "precision": "fp32",
              "attention_impl": "flash",
              "optimizer": {"name": "adamw", "learning_rate": 3e-4,
                            "beta1": 0.9, "beta2": 0.95, "eps": 1e-8,
                            "weight_decay": 0.1}},
}
CELL = "nemotron_tiny.train_b4_s64"
NEMOTRON_CELL = "nemotron3_nano.train_b1_s8192_ep16"


def tiny_cell() -> dict:
    """The tiny cell as `harness.load_cell` gives a cell: the entry, its
    configuration and traffic, and the benchmark's own `BENCHMARK.json` with
    the cell added to the lists that name the nemotron3_nano cell."""
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as fh:
        spec = json.load(fh)
    entry = {"name": CELL, "config": "nemotron_tiny",
             "traffic": "train_b4_s64", "chips": 1, "why": "tests only"}
    spec["workloads"].append(entry)
    for m in spec["end_to_end"] + spec["per_layer"]:
        if NEMOTRON_CELL in m.get("workloads", []):
            m["workloads"].append(CELL)
    return dict(entry, config_file=NEMOTRON_TINY,
                traffic_file=tiny.TRAIN_TINY, spec=spec)
