"""What causal flash attention requires of the chip for one layer and one
example, forward and backward together (the three kernels: forward, dQ,
dK/dV): operations and the least bytes that must cross HBM.

Operations: forward QK^T and PV; backward dV, dP, dQ, dK: six products over
the S(S+1)/2 causal pairs, 2 * head_dim operations a pair and head. The
scores a backward kernel recomputes are not required work. Bytes, at the
activations' width: forward reads Q, K, V and writes O; backward reads Q, K,
V, O, dO and writes dQ, dK, dV: twelve (S, hidden) tensors. The softmax
statistics, (S, heads) float32, are left out: under 1 % of that.
"""

from __future__ import annotations


def train_ops(config: dict, seq_len: int) -> float:
    pairs = seq_len * (seq_len + 1) / 2
    return 6 * 2 * pairs * config["n_embd"]


def train_bytes(config: dict, seq_len: int, width: int = 2) -> float:
    return 12 * seq_len * config["n_embd"] * width
