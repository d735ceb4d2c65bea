"""Operations a decoder-only transformer's training step requires.

Copied from `distributeddeeplearning_tpu/models/flops.py`
(`_transformer_fwd_flops`, `train_flops_per_example`) and corrected for a
causal model: the original counts both attention products over the full S x S
square; a causal forward needs only the S(S+1)/2 pairs on and below the
diagonal, and the loss only the S-1 positions that have a next token. A
multiply-accumulate is 2 operations; backward is twice forward (two products
per forward product, attention included: dV, dP, dQ, dK for QK^T and PV);
what a kernel recomputes is not counted, nor are the optimizer's O(parameters)
operations.
"""

from __future__ import annotations


def forward_ops_per_example(config: dict, seq_len: int) -> float:
    s, d = seq_len, config["n_embd"]
    f = config.get("n_inner") or 4 * d
    per_layer = (4 * 2 * s * d * d          # query, key, value, output
                 + 2 * 2 * s * d * f        # the two MLP products
                 + attention_forward_ops(config, s))
    head = 2 * (s - 1) * d * config["vocab_size"]
    return config["n_layer"] * per_layer + head


def attention_forward_ops(config: dict, seq_len: int) -> float:
    """QK^T and PV of one layer, one example, all heads, causal."""
    pairs = seq_len * (seq_len + 1) / 2
    return 2 * 2 * pairs * config["n_embd"]


def train_ops_per_example(config: dict, traffic: dict) -> float:
    return 3.0 * forward_ops_per_example(config, traffic["seq_len"])
