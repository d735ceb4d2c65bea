"""Operations one chip's share of an AFMoE decoder's training step requires.

A multiply-accumulate is 2 operations; backward is twice forward; what is
recomputed (remat, a kernel's scores) is not counted, nor are the optimizer's
O(parameters) operations, the norms, the softmax or the router's top-k.

Linear products, of what a token uses here: attention's five projections (q,
gate and o at heads x head_dim, k and v at kv_heads x head_dim) in every
layer; the dense feed-forward's three in the leading layers; in an expert
layer the router (its whole width), the shared expert's three and the routed
experts' three at the EXPECTED number of held experts a token:
`num_experts_per_tok * num_experts / share.router_width` (8 x 16 / 128 = 1 for
the benchmark's share), since the train runner keeps no counter of the rows
that landed. The head, over the vocabulary's slice, at the S - 1 positions
that have a target.

Attention: QK^T and PV over the pairs the mask allows: S(S+1)/2 on a full
layer, and on a sliding layer the pairs with query - key < window (14 681 088
and 33 558 528 at S = 8192, window 2048), 2 * 2 * head_dim * heads operations
a pair (16 384).
"""

from __future__ import annotations


def causal_pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def window_pairs(seq_len: int, window: int) -> int:
    """Pairs with 0 <= query - key < window."""
    w = min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def expected_held_experts_per_token(config: dict) -> float:
    router = config.get("share", {}).get("router_width",
                                         config["num_experts"])
    return config["num_experts_per_tok"] * config["num_experts"] / router


def linear_macs_per_token(config: dict) -> float:
    """Multiply-accumulates of the blocks' matrix products, one token."""
    d, hd = config["hidden_size"], config["head_dim"]
    q = config["num_attention_heads"] * hd
    kv = config["num_key_value_heads"] * hd
    attention = 3 * d * q + 2 * d * kv
    dense = 3 * d * config["intermediate_size"]
    expert = 3 * d * config["moe_intermediate_size"]
    router = d * config.get("share", {}).get("router_width",
                                             config["num_experts"])
    moe = (router + config["num_shared_experts"] * expert
           + expected_held_experts_per_token(config) * expert)
    layers = config["num_hidden_layers"]
    n_dense = config["num_dense_layers"]
    return layers * attention + n_dense * dense + (layers - n_dense) * moe


def attention_pairs(config: dict, seq_len: int) -> int:
    """Allowed (query, key) pairs summed over the layers."""
    return sum(window_pairs(seq_len, config["sliding_window"])
               if kind == "sliding_attention" else causal_pairs(seq_len)
               for kind in config["layer_types"])


def forward_ops_per_example(config: dict, seq_len: int) -> float:
    s = seq_len
    per_pair = 2 * 2 * config["head_dim"] * config["num_attention_heads"]
    head = 2 * (s - 1) * config["hidden_size"] * config["vocab_size"]
    return (2 * s * linear_macs_per_token(config) + head
            + per_pair * attention_pairs(config, s))


def train_ops_per_example(config: dict, traffic: dict) -> float:
    return 3.0 * forward_ops_per_example(config, traffic["seq_len"])


def experts_train_ops(config: dict, seq_len: int) -> float:
    """The routed experts' three products of ONE layer and example, forward
    and the two backward products of each, over the expected rows."""
    rows = seq_len * expected_held_experts_per_token(config)
    return 3 * 3 * 2 * rows * (config["hidden_size"]
                               * config["moe_intermediate_size"])


def experts_train_bytes(config: dict, seq_len: int, width: int = 2) -> float:
    """Least bytes of the same: the held experts' kernels once a pass
    (forward, backward to the rows, backward to the kernels), and the rows
    in and out of each pass (hidden-wide in and out, expert-wide twice)."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    rows = seq_len * expected_held_experts_per_token(config)
    kernels = config["num_experts"] * 3 * d * f
    return 3 * width * (kernels + rows * (2 * d + 2 * f))
