"""Operations one chip's share of a Kimi Linear decoder's training step
requires, and the least time the chip could take for its two attention kinds.

A multiply-accumulate is 2 operations; backward is twice forward; what is
recomputed (remat, a kernel's scores, a chunk's intermediates) is not
counted, nor are the optimizer's O(parameters) operations, the norms, the
softmax or the router's top-k.

Linear products, of what a token uses here. A KDA layer: q, k, v and o at
heads x head_dim (4096), the two low-rank gates (hidden -> head_dim -> 4096
each), beta (hidden -> heads) and the three convolutions' 4 taps a channel. A
latent layer: q at heads x (qk_nope + qk_rope), the down-projection to
kv_lora_rank + qk_rope, the up-projection to heads x (qk_nope + v_head), and
o. The dense feed-forward's three in the leading layers; in an expert layer
the router (its whole width), the shared expert's three and the routed
experts' three at the EXPECTED number of held experts a token,
`num_experts_per_token * num_experts / share.router_width` (8 x 8 / 256 =
0.25 for the benchmark's share), since the train runner keeps no counter of
the rows that landed. The head, over the vocabulary's slice, at the S - 1
positions that have a target.

Latent attention: QK^T at qk_nope + qk_rope and PV at v_head over the
S(S+1)/2 causal pairs, 2 x (192 + 128) x 32 operations a pair.

The recurrence (what KDA requires token by token, whatever form computes
it), a token and head, on a head_dim x head_dim state: the decay (1
operation an entry), the read S'^T k, the rank-one write and the query S^T q
(2 each): 7 x head_dim^2.
"""

from __future__ import annotations


def causal_pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def expected_held_experts_per_token(config: dict) -> float:
    router = config.get("share", {}).get("router_width",
                                         config["num_experts"])
    return config["num_experts_per_token"] * config["num_experts"] / router


def _kinds(config: dict) -> tuple:
    """(KDA layers, latent layers)"""
    lin = config["linear_attn_config"]
    return len(lin["kda_layers"]), len(lin["full_attn_layers"])


def kda_macs_per_token(config: dict) -> int:
    lin = config["linear_attn_config"]
    d, h, hd = config["hidden_size"], lin["num_heads"], lin["head_dim"]
    wide = h * hd
    return (4 * d * wide + 2 * (d * hd + hd * wide) + d * h
            + 3 * lin["short_conv_kernel_size"] * wide)


def mla_macs_per_token(config: dict) -> int:
    d, h = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, rank = config["v_head_dim"], config["kv_lora_rank"]
    return (d * h * (nope + rope) + d * (rank + rope)
            + rank * h * (nope + dv) + h * dv * d)


def linear_macs_per_token(config: dict) -> float:
    """Multiply-accumulates of the blocks' matrix products, one token."""
    d = config["hidden_size"]
    dense = 3 * d * config["intermediate_size"]
    expert = 3 * d * config["moe_intermediate_size"]
    router = d * config.get("share", {}).get("router_width",
                                             config["num_experts"])
    moe = (router + config["num_shared_experts"] * expert
           + expected_held_experts_per_token(config) * expert)
    n_kda, n_mla = _kinds(config)
    n_dense = config["first_k_dense_replace"]
    return (n_kda * kda_macs_per_token(config)
            + n_mla * mla_macs_per_token(config) + n_dense * dense
            + (config["num_hidden_layers"] - n_dense) * moe)


def mla_ops_per_pair(config: dict) -> int:
    return 2 * config["num_attention_heads"] * (
        config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
        + config["v_head_dim"])


def recurrence_ops_per_token(config: dict) -> int:
    """One KDA layer, all heads."""
    lin = config["linear_attn_config"]
    return 7 * lin["head_dim"] ** 2 * lin["num_heads"]


def forward_ops_per_example(config: dict, seq_len: int) -> float:
    s = seq_len
    n_kda, n_mla = _kinds(config)
    head = 2 * (s - 1) * config["hidden_size"] * config["vocab_size"]
    return (2 * s * linear_macs_per_token(config) + head
            + n_mla * mla_ops_per_pair(config) * causal_pairs(s)
            + n_kda * recurrence_ops_per_token(config) * s)


def train_ops_per_example(config: dict, traffic: dict) -> float:
    return 3.0 * forward_ops_per_example(config, traffic["seq_len"])


def kda_least_seconds(config: dict, seq_len: int, peaks: dict,
                      width: int = 2) -> float:
    """The chip's least time for ONE KDA layer's recurrence and one example,
    forward and backward: the larger of the recurrence's operations (three
    passes' worth: backward is twice forward) over the bf16 peak, and three
    passes over q, k, v and o at the activations' width with the float32
    gates g and beta, over the HBM bandwidth."""
    lin = config["linear_attn_config"]
    h, hd = lin["num_heads"], lin["head_dim"]
    ops = 3 * recurrence_ops_per_token(config) * seq_len
    a_pass = seq_len * h * (4 * hd * width + hd * 4 + 4)
    return max(ops / peaks["bf16_flops_per_s"],
               3 * a_pass / peaks["hbm_bytes_per_s"])


def mla_least_seconds(config: dict, seq_len: int, peaks: dict,
                      width: int = 2) -> float:
    """The same for ONE latent layer's attention kernels: the causal pairs'
    operations, forward and backward, and three passes over q and k at
    qk_nope + qk_rope and v and o at v_head."""
    h = config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    ops = 3 * mla_ops_per_pair(config) * causal_pairs(seq_len)
    a_pass = seq_len * h * (2 * qk + 2 * config["v_head_dim"]) * width
    return max(ops / peaks["bf16_flops_per_s"],
               3 * a_pass / peaks["hbm_bytes_per_s"])
