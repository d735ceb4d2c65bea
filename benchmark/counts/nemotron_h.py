"""Operations one chip's share of a Nemotron-H decoder's training step
requires, and the least time the chip could take for its state-space
recurrence.

A multiply-accumulate is 2 operations; backward is twice forward; what is
recomputed (remat, a kernel's scores, a chunked form's extra products) is
not counted, nor are the optimizer's O(parameters) operations, the norms,
the softmax or the router's top-k.

Linear products, of what a token uses here. A Mamba-2 mixer: the input
projection to z, xBC and dt (heads x head_dim, heads x head_dim + 2 x groups
x state, heads), the output projection back, and the convolution's 4 taps a
channel of xBC. An attention layer: q and o at heads x head_dim, k and v at
kv_heads x head_dim. An expert layer: the router (its whole width), the
shared expert's two products (relu2: up and down) and the routed experts'
two at the EXPECTED number of held experts a token,
`num_experts_per_tok * n_routed_experts / share.router_width` (6 x 8 / 128 =
0.375 for the benchmark's share), since the train runner keeps no counter of
the rows that landed. The head, over the vocabulary's slice, at the S - 1
positions that have a target.

Attention: QK^T and PV over the S(S+1)/2 causal pairs, 2 x 2 x head_dim x
heads operations a pair.

The recurrence (what a mixer requires token by token, whatever form computes
it), a token and head, on a head_dim x state state: the rank-one write
(dt x) B^T and the read h C, 2 operations an entry each: 4 x head_dim x
state.
"""

from __future__ import annotations

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def causal_pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def _layers(config: dict, kind: str) -> int:
    return config["hybrid_override_pattern"].count(kind)


def expected_held_experts_per_token(config: dict) -> float:
    router = config.get("share", {}).get("router_width",
                                         config["n_routed_experts"])
    return (config["num_experts_per_tok"] * config["n_routed_experts"]
            / router)


def mamba_macs_per_token(config: dict) -> int:
    d = config["hidden_size"]
    h, inner = config["mamba_num_heads"], (config["mamba_num_heads"]
                                           * config["mamba_head_dim"])
    width = inner + 2 * config["n_groups"] * config["ssm_state_size"]
    return d * (inner + width + h) + inner * d + config["conv_kernel"] * width


def attention_macs_per_token(config: dict) -> int:
    d, hd = config["hidden_size"], config["head_dim"]
    return (2 * d * config["num_attention_heads"] * hd
            + 2 * d * config["num_key_value_heads"] * hd)


def experts_macs_per_token(config: dict) -> float:
    d = config["hidden_size"]
    router = d * config.get("share", {}).get("router_width",
                                             config["n_routed_experts"])
    shared = 2 * d * config["moe_shared_expert_intermediate_size"]
    expert = 2 * d * config["moe_intermediate_size"]
    return (router + shared
            + expected_held_experts_per_token(config) * expert)


def linear_macs_per_token(config: dict) -> float:
    """Multiply-accumulates of the layers' matrix products, one token."""
    return (_layers(config, MAMBA) * mamba_macs_per_token(config)
            + _layers(config, ATTENTION) * attention_macs_per_token(config)
            + _layers(config, EXPERTS) * experts_macs_per_token(config))


def attention_ops_per_pair(config: dict) -> int:
    return 2 * 2 * config["head_dim"] * config["num_attention_heads"]


def recurrence_ops_per_token(config: dict) -> int:
    """One mixer, all heads."""
    return (4 * config["mamba_head_dim"] * config["ssm_state_size"]
            * config["mamba_num_heads"])


def forward_ops_per_example(config: dict, seq_len: int) -> float:
    s = seq_len
    head = 2 * (s - 1) * config["hidden_size"] * config["vocab_size"]
    return (2 * s * linear_macs_per_token(config) + head
            + _layers(config, ATTENTION) * attention_ops_per_pair(config)
            * causal_pairs(s)
            + _layers(config, MAMBA) * recurrence_ops_per_token(config) * s)


def train_ops_per_example(config: dict, traffic: dict) -> float:
    return 3.0 * forward_ops_per_example(config, traffic["seq_len"])


def ssd_least_seconds(config: dict, seq_len: int, peaks: dict,
                      width: int = 2) -> float:
    """The chip's least time for ONE mixer's recurrence and one example,
    forward and backward: the larger of the recurrence's operations (three
    passes' worth: backward is twice forward) over the bf16 peak, and three
    passes over x, B, C and y at the activations' width with the float32
    dt, over the HBM bandwidth."""
    h, p = config["mamba_num_heads"], config["mamba_head_dim"]
    bc = 2 * config["n_groups"] * config["ssm_state_size"]
    ops = 3 * recurrence_ops_per_token(config) * seq_len
    a_pass = seq_len * ((2 * h * p + bc) * width + 4 * h)
    return max(ops / peaks["bf16_flops_per_s"],
               3 * a_pass / peaks["hbm_bytes_per_s"])
