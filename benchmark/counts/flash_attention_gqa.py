"""What causal flash attention over grouped K/V heads, with or without a
window, requires of the chip for one layer and one example, forward and
backward together (the three kernels: forward, dQ, dK/dV).

Operations: forward QK^T and PV; backward dV, dP, dQ, dK: six products over
the pairs the mask allows (`counts/afmoe.py`: the causal triangle, or its band
under a window), 2 * head_dim operations a pair and Q head. The scores a
backward kernel recomputes are not required work. Bytes, at the activations'
width: Q, O, dO, dQ at the Q heads (forward reads Q and writes O; backward
reads Q, O, dO and writes dQ: six tensors) and K, V, dK, dV at the K/V heads
(read twice, written once: six tensors). The softmax statistics are left
out: under 1 % of that.
"""

from __future__ import annotations

from benchmark import harness


def pairs(seq_len: int, window) -> int:
    afmoe = harness.load_module("counts", "afmoe")
    return (afmoe.causal_pairs(seq_len) if window is None
            else afmoe.window_pairs(seq_len, window))


def train_ops(config: dict, seq_len: int, window=None) -> float:
    return (6 * 2 * pairs(seq_len, window) * config["head_dim"]
            * config["num_attention_heads"])


def train_bytes(config: dict, seq_len: int, width: int = 2) -> float:
    return (6 * (config["num_attention_heads"]
                 + config["num_key_value_heads"])
            * seq_len * config["head_dim"] * width)


def least_seconds(config: dict, seq_len: int, window, peaks: dict) -> float:
    """The chip's least time for one layer and example: the larger of
    operations over its peak and bytes over its bandwidth."""
    return max(train_ops(config, seq_len, window) / peaks["bf16_flops_per_s"],
               train_bytes(config, seq_len) / peaks["hbm_bytes_per_s"])
