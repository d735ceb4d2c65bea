"""Xing4.0 decoder (XingChen-AGI's Xing4.0-29B-A4B, ``model_type: xing4_0``)
in Flax.

A decoder-only causal LM whose residual path is FOUR streams
(manifold-constrained hyper-connections, models/hyper_connections.py): each
sub-layer reads a learned, input-dependent mix of the streams, writes its
result back to all four with learned weights, and mixes the streams among
themselves by a 4 x 4 matrix made doubly stochastic, a token, by 20 Sinkhorn
iterations. A block is two such rounds: latent attention with its input
RMSNorm, then the feed-forward with its. The embedding is copied to the
streams, and after the last block they are summed, normalised and read by an
untied head.

Attention is latent attention WITH positions: queries come up from a 768-wide
normalised bottleneck, keys and values from a 512-wide normalised latent;
queries and keys are 192 wide (128 + 64), values 128; the 64 channels are
rotated (on the keys' side one 64-channel key shared by all heads), with YaRN
frequencies (models/llama.py::yarn_frequencies: factor 64 from 4096
positions) and a softmax scale of ``192^-1/2 * mscale^2``, mscale = 0.1 ln 64
+ 1. Frequencies and scale reach the shared code as arguments
(``apply_rope(freqs=)``, ``multihead_attention(scale=)``): the kernels scale
the float32 scores, nothing is folded into bfloat16 queries. The feed-forward
is a dense SwiGLU in the leading layers and, after them, routed experts with
one shared expert (models/moe.py::RoutedExperts as it stands: sigmoid scores,
top-4 of 64 with a selection bias, normalised gates times 2, no token
dropped). docs/xing4.md has the equations and what is assumed beyond
``config.json``; the multi-token-prediction module is not built.

One configuration class builds the published model (``xing4_29b``: 40 layers,
64 experts, 131072 tokens: 29.5B parameters, for shape tests) and one chip's
share of it (``xing4_ep8``: 8 chips share each layer, so this chip holds
experts 0-7 of 64 and rows 0-16383 of the vocabulary, and the first five
layers; every width stays as published).

Scopes for analysis/anatomy.py: ``embed``, ``head``, ``mlp`` (dense FFN and
shared expert), ``mhc`` round everything the hyper-connections add, ``attn_mla``
round the attention kernels, and RoutedExperts' own four.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributeddeeplearning_tpu.models import hyper_connections as mhc
from distributeddeeplearning_tpu.models.llama import (apply_rope,
                                                      yarn_frequencies,
                                                      yarn_mscale)
from distributeddeeplearning_tpu.models.moe import ROUTED_OUT, RoutedExperts
from distributeddeeplearning_tpu.ops.attention import multihead_attention
from distributeddeeplearning_tpu.ops.embedding import embedding_lookup
from distributeddeeplearning_tpu.ops.flash_attention import (FLASH_LSE,
                                                             FLASH_OUT)

Dtype = Any


@dataclasses.dataclass(frozen=True)
class Xing4Config:
    """Xing4.0-29B-A4B's published sizes by default (its ``config.json``)."""

    vocab_size: int = 131072
    hidden_size: int = 3584
    num_layers: int = 40
    hc_mult: int = 4                     # residual streams
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_clamp: tuple = (-30.0, 30.0)      # mhc_h_res_clamp_min / _max
    num_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    rope_factor: float = 64.0            # rope_scaling (yarn)
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    intermediate_size: int = 9216        # the dense FFN of the leading layers
    moe_intermediate_size: int = 1024    # an expert's, and the shared one's
    num_dense_layers: int = 2            # first_k_dense_replace
    num_experts: int = 64                # the router's width
    experts_held: tuple = (0, 64)        # (first, count) held by this chip
    experts_per_token: int = 4
    num_shared_experts: int = 1
    route_norm: bool = True              # norm_topk_prob
    route_scale: float = 2.0             # routed_scaling_factor
    load_balance_coeff: float = 0.001    # the selection bias's step
    rms_eps: float = 1e-6
    attention_impl: str = "dense"        # dense | flash
    remat: bool = False                  # recompute each block in backward

    @property
    def softmax_scale(self) -> float:
        """``(qk_nope + qk_rope)^-1/2 * mscale^2``: YaRN's temperature over
        all dimensions, on both sides of the product."""
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m


def _dense(features, logical_axes, name, dtype):
    return nn.Dense(
        features, dtype=dtype, param_dtype=jnp.float32, use_bias=False,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.normal(0.02), logical_axes),
        name=name)


def _rms_norm(cfg: Xing4Config, dtype, name: str):
    return nn.RMSNorm(epsilon=cfg.rms_eps, dtype=dtype,
                      param_dtype=jnp.float32, name=name)


class XingMlaAttention(nn.Module):
    cfg: Xing4Config
    dtype: Dtype

    @nn.compact
    def __call__(self, x, pad_mask):
        cfg = self.cfg
        b, s, _ = x.shape
        h, rank = cfg.num_heads, cfg.kv_lora_rank
        nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        c_q = _rms_norm(cfg, self.dtype, "q_a_norm")(
            _dense(cfg.q_lora_rank, ("embed", None), "q_a_proj",
                   self.dtype)(x))
        q = _dense(h * (nope + rope), (None, "heads"), "q_b_proj",
                   self.dtype)(c_q).reshape(b, s, h, nope + rope)
        c = _dense(rank + rope, ("embed", None), "kv_a_proj", self.dtype)(x)
        c_kv = _rms_norm(cfg, self.dtype, "kv_a_norm")(c[..., :rank])
        kv = _dense(h * (nope + dv), (None, "heads"), "kv_b_proj",
                    self.dtype)(c_kv).reshape(b, s, h, nope + dv)
        # cos and sin would be times mscale(factor, mscale) / mscale(factor,
        # mscale_all_dim), which is 1 for the published pair (1, 1) and is
        # refused otherwise rather than left out in silence
        if cfg.rope_mscale != cfg.rope_mscale_all_dim:
            raise ValueError("rope_mscale != rope_mscale_all_dim: the "
                             "rotation's own factor is not built")
        freqs, _, _ = yarn_frequencies(
            rope, theta=cfg.rope_theta, factor=cfg.rope_factor,
            original_max_position=cfg.rope_original_max,
            beta_fast=cfg.rope_beta_fast, beta_slow=cfg.rope_beta_slow)
        q = jnp.concatenate(
            [q[..., :nope], apply_rope(q[..., nope:], freqs=freqs)], -1)
        k_pe = apply_rope(c[:, :, None, rank:], freqs=freqs)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (b, s, h, rope))], -1)
        v = kv[..., nope:]
        with jax.named_scope("attn_mla"):
            out = multihead_attention(q, k, v, pad_mask,
                                      impl=cfg.attention_impl, causal=True,
                                      dtype=self.dtype,
                                      scale=cfg.softmax_scale)
        return _dense(cfg.hidden_size, ("heads", "embed"), "o_proj",
                      self.dtype)(out)


class Xing4Block(nn.Module):
    """Two hyper-connection rounds on (B, S, n*C) streams: attention, then
    the feed-forward."""

    cfg: Xing4Config
    index: int
    dtype: Dtype

    def _hyper_connection(self, name: str):
        cfg = self.cfg
        return mhc.HyperConnection(
            streams=cfg.hc_mult, sinkhorn_iters=cfg.hc_sinkhorn_iters,
            eps=cfg.hc_eps, clamp=cfg.hc_clamp, rms_eps=cfg.rms_eps,
            name=name)

    @nn.compact
    def __call__(self, x, pad_mask, *, train: bool):
        cfg = self.cfg
        h, coef, x = self._hyper_connection("attn_hc")(x)
        h = _rms_norm(cfg, self.dtype, "input_layernorm")(h)
        x = mhc.write(x, XingMlaAttention(cfg, self.dtype, name="attention")(
            h, pad_mask), coef)
        h, coef, x = self._hyper_connection("ffn_hc")(x)
        h = _rms_norm(cfg, self.dtype, "post_attention_layernorm")(h)
        if self.index < cfg.num_dense_layers:
            with jax.named_scope("mlp"):
                gate = _dense(cfg.intermediate_size, ("embed", "mlp"),
                              "gate_proj", self.dtype)(h)
                up = _dense(cfg.intermediate_size, ("embed", "mlp"),
                            "up_proj", self.dtype)(h)
                h = _dense(cfg.hidden_size, ("mlp", "embed"), "down_proj",
                           self.dtype)(nn.silu(gate) * up)
        else:
            h = RoutedExperts(
                hidden_size=cfg.hidden_size,
                expert_width=cfg.moe_intermediate_size,
                num_experts=cfg.num_experts,
                experts_per_token=cfg.experts_per_token,
                experts_held=cfg.experts_held, score_func="sigmoid",
                route_norm=cfg.route_norm, route_scale=cfg.route_scale,
                shared_width=(cfg.num_shared_experts
                              * cfg.moe_intermediate_size),
                bias_update_rate=cfg.load_balance_coeff, dtype=self.dtype,
                name="moe")(h, train=train)
        return mhc.write(x, h, coef)


# the streams as one array: batch, sequence, and the n streams' channels
# side by side (models/hyper_connections.py says why); not sharded over
# ``embed``'s mesh axis, whose shards would each hold parts of one stream
STREAMS_AXES = ("batch", "seq", None)


class Xing4LM(nn.Module):
    """Decoder-only LM; returns (B, S, vocab) float32 logits."""

    cfg: Xing4Config
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, *, train: bool = True):
        cfg = self.cfg
        b, s = input_ids.shape
        pad_mask = (jnp.ones((b, s), jnp.bool_) if attention_mask is None
                    else attention_mask.astype(jnp.bool_))
        embed = self.param(
            "embed_tokens",
            nn.with_logical_partitioning(nn.initializers.normal(0.02),
                                         ("vocab", "embed")),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        with jax.named_scope("embed"):
            x = embedding_lookup(embed, input_ids).astype(self.dtype)
        x = nn.with_logical_constraint(mhc.spread(x, cfg.hc_mult),
                                       STREAMS_AXES)
        for i in range(cfg.num_layers):
            block = Xing4Block(cfg, i, self.dtype, name=f"layer{i}")
            if cfg.remat:
                # the boundary carries the streams, (B, S, n*C): four times
                # what a one-stream model keeps a block. Inside, a block
                # keeps what models/kimi_linear.py's keeps: the routed
                # experts' result and the flash forward kernel's with its
                # log-sum-exp, so neither runs twice
                x = nn.remat(
                    lambda mdl, h, m: mdl(h, m, train=train),
                    policy=jax.checkpoint_policies.save_only_these_names(
                        ROUTED_OUT, FLASH_OUT, FLASH_LSE))(block, x, pad_mask)
            else:
                x = block(x, pad_mask, train=train)
            x = nn.with_logical_constraint(x, STREAMS_AXES)
        x = _rms_norm(cfg, self.dtype, "final_layernorm")(
            mhc.collect(x, cfg.hc_mult))
        with jax.named_scope("head"):
            logits = _dense(cfg.vocab_size, ("embed", "vocab"), "lm_head",
                            self.dtype)(x)
            return logits.astype(jnp.float32)


def xing4_29b(vocab_size: int = 131072, dtype: Dtype = jnp.bfloat16,
              seq_len: Optional[int] = None, **overrides: Any) -> Xing4LM:
    """Xing4.0-29B-A4B as published, without its multi-token-prediction
    module: 40 layers (two dense, then 38 of 64 experts). No chip here holds
    it; shape tests do."""
    del seq_len  # rotary positions: any sequence length
    return Xing4LM(Xing4Config(vocab_size=vocab_size, **overrides),
                   dtype=dtype)


def xing4_ep8(vocab_size: int = 16384, dtype: Dtype = jnp.bfloat16,
              seq_len: Optional[int] = None, **overrides: Any) -> Xing4LM:
    """One chip's share of Xing4.0-29B-A4B when 8 chips share each layer:
    experts 0-7 of 64, vocabulary rows 0-16383, and the first five layers
    kept (one leading dense layer, the two published counted once, and four
    expert layers; the others would lie on further chips). Every width is the
    published one. Blocks are recomputed in the backward pass: float32
    masters, gradients and Adam's moments of 759M parameters leave a 16 GB
    chip little else."""
    del seq_len
    return Xing4LM(Xing4Config(
        vocab_size=vocab_size,
        **{"num_layers": 5, "num_dense_layers": 1, "experts_held": (0, 8),
           "remat": True, **overrides}), dtype=dtype)


def xing4_tiny(vocab_size: int = 512, dtype: Dtype = jnp.float32,
               seq_len: Optional[int] = None, **overrides: Any) -> Xing4LM:
    """Test-sized: every mechanism of the family at small widths, as a share
    (experts 2-5 of 8)."""
    del seq_len
    return Xing4LM(Xing4Config(
        vocab_size=vocab_size,
        **{"hidden_size": 64, "num_layers": 2, "num_dense_layers": 1,
           "num_heads": 2, "q_lora_rank": 32, "kv_lora_rank": 24,
           "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
           "rope_original_max": 32, "intermediate_size": 96,
           "moe_intermediate_size": 32, "num_experts": 8,
           "experts_held": (2, 4), "experts_per_token": 2, **overrides}),
        dtype=dtype)
