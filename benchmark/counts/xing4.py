"""Operations one chip's share of a Xing4.0 decoder's training step requires,
and the least time the chip could take for its latent attention and for one
hyper-connection.

A multiply-accumulate is 2 operations; backward is twice forward; what is
recomputed (remat, a kernel's scores) is not counted, nor are the optimizer's
O(parameters) operations, the norms, the softmax, the Sinkhorn iterations or
the router's top-k.

Linear products, of what a token uses here. A layer's latent attention: the
query's bottleneck (hidden -> q_lora_rank -> heads x (qk_nope + qk_rope)), the
down-projection to kv_lora_rank + qk_rope, the up-projection to heads x
(qk_nope + v_head), and o. Its two hyper-connections: the product with Phi
(hc_mult x hidden -> hc_mult^2 + 2 hc_mult) and the three mixes (H_pre X:
hc_mult x hidden; H_res X: hc_mult^2 x hidden; H_post^T y: hc_mult x hidden).
The dense feed-forward's three in the leading layers; in an expert layer the
router (its whole width), the shared expert's three and the routed experts'
three at the EXPECTED number of held experts a token, `num_experts_per_tok *
n_routed_experts / share.router_width` (4 x 8 / 64 = 0.5 for the benchmark's
share), since the train runner keeps no counter of the rows that landed. The
head, over the vocabulary's slice, at the S - 1 positions that have a target.

Latent attention: QK^T at qk_nope + qk_rope and PV at v_head over the
S(S+1)/2 causal pairs, 2 x (192 + 128) x 32 operations a pair: the kernels,
the head sizes and the keys that name them are the Kimi Linear file's, and so
are the functions that count them (`counts/kimi_linear.py`).
"""

from __future__ import annotations

from benchmark import harness

_kimi = harness.load_module("counts", "kimi_linear")
causal_pairs = _kimi.causal_pairs
mla_ops_per_pair = _kimi.mla_ops_per_pair
# the chip's least time for ONE layer's attention kernels and one example,
# forward and backward: the causal pairs' operations against the bf16 peak
# (which binds), or three passes over q, k, v and o against the bandwidth
mla_least_seconds = _kimi.mla_least_seconds


def expected_held_experts_per_token(config: dict) -> float:
    router = config.get("share", {}).get("router_width",
                                         config["n_routed_experts"])
    return (config["num_experts_per_tok"] * config["n_routed_experts"]
            / router)


def mla_macs_per_token(config: dict) -> int:
    d, h = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    dv, rank = config["v_head_dim"], config["kv_lora_rank"]
    q_rank = config["q_lora_rank"]
    return (d * q_rank + q_rank * h * (nope + rope) + d * (rank + rope)
            + rank * h * (nope + dv) + h * dv * d)


def mhc_macs_per_token(config: dict) -> int:
    """One hyper-connection: the product with Phi and the three mixes."""
    n, d = config["hc_mult"], config["hidden_size"]
    return n * d * (n * n + 2 * n) + (n + n * n + n) * d


def linear_macs_per_token(config: dict) -> float:
    """Multiply-accumulates of the blocks' matrix products and mixes, one
    token."""
    d = config["hidden_size"]
    dense = 3 * d * config["intermediate_size"]
    expert = 3 * d * config["moe_intermediate_size"]
    router = d * config.get("share", {}).get("router_width",
                                             config["n_routed_experts"])
    moe = (router + config["n_shared_experts"] * expert
           + expected_held_experts_per_token(config) * expert)
    layers, n_dense = (config["num_hidden_layers"],
                       config["first_k_dense_replace"])
    return (layers * (mla_macs_per_token(config)
                      + 2 * mhc_macs_per_token(config))
            + n_dense * dense + (layers - n_dense) * moe)


def forward_ops_per_example(config: dict, seq_len: int) -> float:
    s = seq_len
    head = 2 * (s - 1) * config["hidden_size"] * config["vocab_size"]
    return (2 * s * linear_macs_per_token(config) + head
            + config["num_hidden_layers"] * mla_ops_per_pair(config)
            * causal_pairs(s))


def train_ops_per_example(config: dict, traffic: dict) -> float:
    return 3.0 * forward_ops_per_example(config, traffic["seq_len"])


def mhc_bytes_per_token(config: dict, width: int = 2) -> int:
    """What ONE hyper-connection must move a token and pass: the streams
    read once for the coefficients and the sub-layer's input, read again and
    written once for the result (3 x hc_mult x hidden), with the sub-layer's
    input written and its result read (hidden each), at the activations'
    width. The coefficients themselves (hc_mult^2 + 2 hc_mult numbers a
    token) are not counted."""
    n, d = config["hc_mult"], config["hidden_size"]
    return (3 * n + 2) * d * width


def mhc_least_seconds(config: dict, seq_len: int, peaks: dict,
                      width: int = 2) -> float:
    """The chip's least time for ONE hyper-connection round a sub-layer and
    one example: three passes' worth (forward, and backward at twice
    forward; what remat computes again is not required) of
    `mhc_bytes_per_token` over the HBM bandwidth, or of the product with Phi
    and the mixes over the bf16 peak, whichever is larger (the bytes, by a
    factor of some twenty)."""
    ops = 3 * 2 * mhc_macs_per_token(config) * seq_len
    moved = 3 * mhc_bytes_per_token(config, width) * seq_len
    return max(ops / peaks["bf16_flops_per_s"],
               moved / peaks["hbm_bytes_per_s"])
