"""Kimi Linear decoder (Moonshot's Kimi-Linear-48B-A3B, ``model_type:
kimi_linear``) in Flax.

A decoder-only causal LM whose blocks differ by layer in the KIND of
attention: three layers in four are Kimi Delta Attention (``kda``: no
softmax, a 128 x 128 state a head carried along the sequence, decayed
channel by channel and corrected by a delta rule: ops/kda.py), with a short
causal convolution and a SiLU on each of q, k and v, L2-normalised q and k,
low-rank gates and a gated RMSNorm on the result; the fourth is latent
attention (``mla``): causal softmax attention whose keys and values come up
from a 512-wide normalised latent, queries and keys 192 wide (128 + 64, the
64 shared by all heads) and values 128 wide, with no positions at all: order
comes from the KDA layers. The feed-forward is a dense SwiGLU in the leading
layer and, after it, routed experts with one shared expert
(models/moe.py::RoutedExperts as it stands: sigmoid scores, top-8 of 256 with
a selection bias, normalised and scaled gates, no token dropped). A block is
pre-norm with two RMSNorms: ``h = x + Attn(N1(x))``, ``y = h + FFN(N2(h))``.
The embedding is unscaled and the head untied. docs/kimi_linear.md has the
equations and what is taken from the family's published modelling code and
report and not from ``config.json``.

One configuration class builds the published model (``kimi_linear_48b``: 27
layers, 256 experts, 163840 tokens: 48B parameters, for shape tests) and one
chip's share of it (``kimi_linear_ep32``: 32 chips share each layer, so this
chip holds experts 0-7 of 256 and rows 0-20479 of the vocabulary, and the
first five layers; every width stays as published).

Scopes for analysis/anatomy.py: ``embed``, ``head``, ``mlp`` (dense FFN and
shared expert), ``attn_kda`` round the convolutions, the gates, the operator
and the gated norm, ``attn_mla`` round the attention kernels, and
RoutedExperts' own four.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributeddeeplearning_tpu.models.llama import Held
from distributeddeeplearning_tpu.models.moe import ROUTED_OUT, RoutedExperts
from distributeddeeplearning_tpu.ops import kda as kda_ops
from distributeddeeplearning_tpu.ops import kda_stages
from distributeddeeplearning_tpu.ops.attention import multihead_attention
from distributeddeeplearning_tpu.ops.embedding import embedding_lookup
from distributeddeeplearning_tpu.ops.flash_attention import (FLASH_LSE,
                                                             FLASH_OUT)

Dtype = Any

KDA, MLA = "kda", "mla"


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    """Kimi-Linear-48B-A3B's published sizes by default (its
    ``config.json``)."""

    vocab_size: int = 163840
    hidden_size: int = 2304
    layer_kinds: tuple = (KDA, KDA, KDA, MLA) * 6 + (KDA, KDA, MLA)
    kda_heads: int = 32
    kda_head_dim: int = 128
    conv_size: int = 4
    num_heads: int = 32                  # latent attention's
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64           # carries no rotation (mla_use_nope)
    v_head_dim: int = 128
    intermediate_size: int = 9216        # the dense FFN of the leading layer
    moe_intermediate_size: int = 1024    # an expert's, and the shared one's
    num_dense_layers: int = 1            # first_k_dense_replace
    num_experts: int = 256               # the router's width
    experts_held: tuple = (0, 256)       # (first, count) held by this chip
    experts_per_token: int = 8
    num_shared_experts: int = 1
    route_norm: bool = True              # moe_renormalize
    route_scale: float = 2.446           # routed_scaling_factor
    load_balance_coeff: float = 0.001    # the selection bias's step
    rms_eps: float = 1e-5
    attention_impl: str = "dense"        # latent attention: dense | flash
    remat: bool = False                  # recompute each block in backward

    @property
    def num_layers(self) -> int:
        return len(self.layer_kinds)


def _dense(features, logical_axes, name, dtype, use_bias=False):
    return nn.Dense(
        features, dtype=dtype, param_dtype=jnp.float32, use_bias=use_bias,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.normal(0.02), logical_axes),
        name=name)


def _rms_norm(cfg: KimiLinearConfig, dtype, name: str):
    return nn.RMSNorm(epsilon=cfg.rms_eps, dtype=dtype,
                      param_dtype=jnp.float32, name=name)


def _a_log_init(key, shape, dtype):
    """log U(1, 16) a head: decay rates spread over a factor of 16."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype):
    """The inverse softplus of a step drawn log-uniform in [1e-3, 0.1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class KdaAttention(nn.Module):
    cfg: KimiLinearConfig
    dtype: Dtype

    @nn.compact
    def __call__(self, x, pad_mask):
        cfg = self.cfg
        h, d = cfg.kda_heads, cfg.kda_head_dim
        f32 = jnp.float32
        # a padded token reads as the zeros before the sequence do: it puts
        # nothing into a convolution, and with g = 0, beta = 0 (the
        # operator's own rule for a short last chunk) it neither decays the
        # state nor writes to it, so padding on the left or at the end
        # leaves the real tokens' results and the last state as they were
        x = x * pad_mask[..., None].astype(x.dtype)
        proj = {n: _dense(h * d, ("embed", "heads"), n + "_proj",
                          self.dtype)(x) for n in ("q", "k", "v")}
        f = _dense(h * d, (None, "heads"), "f_b_proj", self.dtype)(
            _dense(d, ("embed", None), "f_a_proj", self.dtype)(x))
        beta = _dense(h, ("embed", None), "b_proj", self.dtype)(x)
        gate = _dense(h * d, (None, "heads"), "g_b_proj", self.dtype,
                      use_bias=True)(
            _dense(d, ("embed", None), "g_a_proj", self.dtype)(x))
        a_log = self.param("A_log", _a_log_init, (h,), f32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (h * d,), f32)
        # depthwise causal convolutions along the sequence, no bias: (taps,
        # channels), ``y_t[c] = sum_j w[j, c] x_{t-K+1+j}[c]``
        taps = [Held("kernel", (cfg.conv_size, h * d),
                     nn.initializers.normal(0.02), (None, "heads"),
                     name=n + "_conv")() for n in ("q", "k", "v")]
        o_scale = Held("scale", (d,), nn.initializers.ones, name="o_norm")()
        with jax.named_scope("attn_kda"):
            # conv, SiLU, the L2 norms and the gate in one pass, written as
            # the operands the chunked operator scans over; the gated norm
            # in another, back to (B, S, H*D) (ops/kda_stages.py)
            q, k, v, g = kda_stages.kda_in(
                (proj["q"], proj["k"], proj["v"], f), taps, a_log, dt_bias,
                pad_mask)
            beta = kda_ops.lay_out(
                nn.sigmoid(beta.astype(f32)) * pad_mask[..., None])
            self.sow(kda_ops.KDA_METRICS, "min_chunk_log_decay",
                     jax.lax.stop_gradient(kda_ops.min_chunk_log_decay(g)))
            o = kda_ops.kda_groups(q, k, v, g, beta)
            o = kda_stages.kda_out(o, gate, o_scale, eps=cfg.rms_eps)
        return _dense(cfg.hidden_size, ("heads", "embed"), "o_proj",
                      self.dtype)(o)


class MlaAttention(nn.Module):
    cfg: KimiLinearConfig
    dtype: Dtype

    @nn.compact
    def __call__(self, x, pad_mask):
        cfg = self.cfg
        b, s, _ = x.shape
        h, rank = cfg.num_heads, cfg.kv_lora_rank
        nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        q = _dense(h * (nope + rope), ("embed", "heads"), "q_proj",
                   self.dtype)(x).reshape(b, s, h, nope + rope)
        c = _dense(rank + rope, ("embed", None), "kv_a_proj", self.dtype)(x)
        c_kv = _rms_norm(cfg, self.dtype, "kv_a_norm")(c[..., :rank])
        kv = _dense(h * (nope + dv), (None, "heads"), "kv_b_proj",
                    self.dtype)(c_kv).reshape(b, s, h, nope + dv)
        k_pe = jnp.broadcast_to(c[:, :, None, rank:], (b, s, h, rope))
        k = jnp.concatenate([kv[..., :nope], k_pe], -1)
        v = kv[..., nope:]
        with jax.named_scope("attn_mla"):
            out = multihead_attention(q, k, v, pad_mask,
                                      impl=cfg.attention_impl, causal=True,
                                      dtype=self.dtype)
        return _dense(cfg.hidden_size, ("heads", "embed"), "o_proj",
                      self.dtype)(out)


class KimiLinearBlock(nn.Module):
    cfg: KimiLinearConfig
    index: int
    dtype: Dtype

    @nn.compact
    def __call__(self, x, pad_mask, *, train: bool):
        cfg = self.cfg
        attention = (KdaAttention if cfg.layer_kinds[self.index] == KDA
                     else MlaAttention)
        h = _rms_norm(cfg, self.dtype, "input_layernorm")(x)
        x = x + attention(cfg, self.dtype, name="attention")(h, pad_mask)
        h = _rms_norm(cfg, self.dtype, "post_attention_layernorm")(x)
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
        return x + h


class KimiLinearLM(nn.Module):
    """Decoder-only LM; returns (B, S, vocab) float32 logits."""

    cfg: KimiLinearConfig
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
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        for i in range(cfg.num_layers):
            block = KimiLinearBlock(cfg, i, self.dtype, name=f"layer{i}")
            if cfg.remat:
                # a block keeps what models/afmoe.py's keeps, the routed
                # experts' result and the flash forward kernel's with its
                # log-sum-exp, and the chunked recurrence's result with the
                # state that enters each chunk (67 + 268 MB a KDA layer):
                # its backward kernel remakes each chunk from those
                # (ops/kda.py), so the recomputed forward runs no forward
                # kernel of the recurrence (docs/kimi_linear.md)
                x = nn.remat(
                    lambda mdl, h, m: mdl(h, m, train=train),
                    policy=jax.checkpoint_policies.save_only_these_names(
                        ROUTED_OUT, FLASH_OUT, FLASH_LSE, kda_ops.KDA_OUT,
                        kda_ops.KDA_STATES))(block, x, pad_mask)
            else:
                x = block(x, pad_mask, train=train)
            x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        x = _rms_norm(cfg, self.dtype, "final_layernorm")(x)
        with jax.named_scope("head"):
            logits = _dense(cfg.vocab_size, ("embed", "vocab"), "lm_head",
                            self.dtype)(x)
            return logits.astype(jnp.float32)


def kimi_linear_48b(vocab_size: int = 163840, dtype: Dtype = jnp.bfloat16,
                    seq_len: Optional[int] = None,
                    **overrides: Any) -> KimiLinearLM:
    """Kimi-Linear-48B-A3B as published: 27 layers (20 KDA, 7 latent; one
    dense, then 26 of 256 experts). No chip here holds it; shape tests do."""
    del seq_len  # no positions: any sequence length
    return KimiLinearLM(KimiLinearConfig(vocab_size=vocab_size, **overrides),
                        dtype=dtype)


def kimi_linear_ep32(vocab_size: int = 20480, dtype: Dtype = jnp.bfloat16,
                     seq_len: Optional[int] = None,
                     **overrides: Any) -> KimiLinearLM:
    """One chip's share of Kimi-Linear-48B-A3B when 32 chips share each
    layer: experts 0-7 of 256, vocabulary rows 0-20479, and the first five
    layers as published (KDA with the dense FFN, then one whole period: KDA,
    KDA, latent, KDA; the others would lie on further chips). Every width is
    the published one. Blocks are recomputed in the backward pass: float32
    masters, gradients and Adam's moments of 602M parameters leave a 16 GB
    chip little else."""
    del seq_len
    return KimiLinearLM(KimiLinearConfig(
        vocab_size=vocab_size,
        **{"layer_kinds": (KDA, KDA, KDA, MLA, KDA), "experts_held": (0, 8),
           "remat": True, **overrides}), dtype=dtype)


def kimi_linear_tiny(vocab_size: int = 512, dtype: Dtype = jnp.float32,
                     seq_len: Optional[int] = None,
                     **overrides: Any) -> KimiLinearLM:
    """Test-sized: every mechanism of the family at small widths, as a share
    (experts 2-5 of 8)."""
    del seq_len
    return KimiLinearLM(KimiLinearConfig(
        vocab_size=vocab_size,
        **{"hidden_size": 64, "layer_kinds": (KDA, KDA, MLA, KDA),
           "kda_heads": 2, "kda_head_dim": 16, "num_heads": 2,
           "kv_lora_rank": 24, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
           "v_head_dim": 16, "intermediate_size": 96,
           "moe_intermediate_size": 32, "num_experts": 8,
           "experts_held": (2, 4), "experts_per_token": 2, **overrides}),
        dtype=dtype)
