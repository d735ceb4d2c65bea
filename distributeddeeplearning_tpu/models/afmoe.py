"""AFMoE decoder (Arcee Trinity family, ``model_type: afmoe``) in Flax.

A decoder-only causal LM whose blocks differ by layer: attention is either
``sliding_attention`` (causal, cut to a window, rotary positions) or
``full_attention`` (causal, no positions at all), over grouped K/V heads with
an RMSNorm on each query and key head and a sigmoid output gate; the
feed-forward is a dense SwiGLU in the leading layers and, after them, routed
experts with one shared expert (models/moe.py::RoutedExperts: sigmoid scores,
top-k with a selection bias, normalised and scaled gates, no token dropped).
Every block has four RMSNorms, two round each half: ``x + N2(Attn(N1(x)))``,
``h + N4(FFN(N3(h)))``. The embedding is scaled by sqrt(hidden) and the head
is untied. docs/afmoe.md has the equations and what is taken from the
family's published modelling code and not from ``config.json``.

One configuration class builds the published model (``trinity_mini``: 32
layers, all 128 experts, 200192 tokens: 26B parameters, for shape tests) and
one chip's share of it (``trinity_mini_ep8``: eight chips share each layer, so
this chip holds experts 0-15 of 128 and rows 0-25023 of the vocabulary, and
five of the layers: ``experts_held``, ``vocab_size`` and ``layer_types`` say
so; every width stays as published). A sliced vocabulary is a smaller
vocabulary: ids, logits and loss are over the slice.

Scopes for analysis/anatomy.py: ``embed``, ``head``, ``mlp`` (dense FFN and
shared expert), ``attn_window`` / ``attn_full`` round the attention kernels,
and RoutedExperts' ``moe_router`` / ``moe_dispatch`` / ``moe_experts`` /
``moe_combine``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributeddeeplearning_tpu.models.llama import apply_rope
from distributeddeeplearning_tpu.models.moe import ROUTED_OUT, RoutedExperts
from distributeddeeplearning_tpu.ops.attention import multihead_attention
from distributeddeeplearning_tpu.ops.embedding import embedding_lookup
from distributeddeeplearning_tpu.ops.flash_attention import (FLASH_LSE,
                                                             FLASH_OUT)

Dtype = Any

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    """Trinity-Mini's published sizes by default (its ``config.json``)."""

    vocab_size: int = 200192
    hidden_size: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 6144        # the dense FFN of the leading layers
    moe_intermediate_size: int = 1024    # an expert's, and the shared one's
    num_dense_layers: int = 2
    layer_types: tuple = (SLIDING, SLIDING, SLIDING, FULL) * 8
    sliding_window: int = 2048
    num_experts: int = 128               # the router's width
    experts_held: tuple = (0, 128)       # (first, count) held by this chip
    experts_per_token: int = 8
    num_shared_experts: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.826
    load_balance_coeff: float = 0.001    # the selection bias's step
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    mup_enabled: bool = True             # embedding times sqrt(hidden)
    attention_impl: str = "dense"        # dense | flash
    remat: bool = False                  # recompute each block in backward

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)


def _dense(features, logical_axes, name, dtype):
    return nn.Dense(
        features, dtype=dtype, param_dtype=jnp.float32, use_bias=False,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.normal(0.02), logical_axes),
        name=name)


def _rms_norm(cfg: AfmoeConfig, dtype, name: str):
    return nn.RMSNorm(epsilon=cfg.rms_eps, dtype=dtype,
                      param_dtype=jnp.float32, name=name)


class AfmoeAttention(nn.Module):
    cfg: AfmoeConfig
    kind: str
    dtype: Dtype

    @nn.compact
    def __call__(self, x, pad_mask):
        cfg = self.cfg
        b, s, _ = x.shape
        h, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = _dense(h * d, ("embed", "heads"), "q_proj", self.dtype)(x)
        k = _dense(hkv * d, ("embed", "heads"), "k_proj", self.dtype)(x)
        v = _dense(hkv * d, ("embed", "heads"), "v_proj", self.dtype)(x)
        gate = _dense(h * d, ("embed", "heads"), "gate_proj", self.dtype)(x)
        q = _rms_norm(cfg, self.dtype, "q_norm")(q.reshape(b, s, h, d))
        k = _rms_norm(cfg, self.dtype, "k_norm")(k.reshape(b, s, hkv, d))
        v = v.reshape(b, s, hkv, d)
        sliding = self.kind == SLIDING
        if sliding:  # full layers carry no positions
            q = apply_rope(q, theta=cfg.rope_theta)
            k = apply_rope(k, theta=cfg.rope_theta)
        with jax.named_scope("attn_window" if sliding else "attn_full"):
            out = multihead_attention(
                q, k, v, pad_mask, impl=cfg.attention_impl, causal=True,
                dtype=self.dtype,
                window=cfg.sliding_window if sliding else None)
        out = out * nn.sigmoid(gate)
        return _dense(cfg.hidden_size, ("heads", "embed"), "o_proj",
                      self.dtype)(out)


class AfmoeBlock(nn.Module):
    cfg: AfmoeConfig
    index: int
    dtype: Dtype

    @nn.compact
    def __call__(self, x, pad_mask, *, train: bool):
        cfg = self.cfg
        h = _rms_norm(cfg, self.dtype, "input_layernorm")(x)
        h = AfmoeAttention(cfg, cfg.layer_types[self.index], self.dtype,
                           name="attention")(h, pad_mask)
        x = x + _rms_norm(cfg, self.dtype, "post_attention_layernorm")(h)
        h = _rms_norm(cfg, self.dtype, "pre_mlp_layernorm")(x)
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
                experts_held=cfg.experts_held, score_func=cfg.score_func,
                route_norm=cfg.route_norm, route_scale=cfg.route_scale,
                shared_width=(cfg.num_shared_experts
                              * cfg.moe_intermediate_size),
                bias_update_rate=cfg.load_balance_coeff, dtype=self.dtype,
                name="moe")(h, train=train)
        return x + _rms_norm(cfg, self.dtype, "post_mlp_layernorm")(h)


class AfmoeLM(nn.Module):
    """Decoder-only LM; returns (B, S, vocab) float32 logits."""

    cfg: AfmoeConfig
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
            x = embedding_lookup(embed, input_ids)
            if cfg.mup_enabled:
                x = x * (cfg.hidden_size ** 0.5)
            x = x.astype(self.dtype)
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        for i in range(cfg.num_layers):
            block = AfmoeBlock(cfg, i, self.dtype, name=f"layer{i}")
            if cfg.remat:
                # a block keeps the two things whose recomputation is a
                # kernel pass over the whole sequence: the routed experts'
                # result (their backward rule runs their forward pass
                # itself, moe.py) and the flash forward kernel's result with
                # its log-sum-exp, which is all the backward kernels ask of
                # a second forward. q, k, v are remade: more bytes than
                # both, for three projections' time (docs/afmoe.md)
                x = nn.remat(
                    lambda mdl, h, m: mdl(h, m, train=train),
                    policy=jax.checkpoint_policies.save_only_these_names(
                        ROUTED_OUT, FLASH_OUT, FLASH_LSE))(block, x, pad_mask)
            else:
                x = block(x, pad_mask, train=train)
            x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        x = _rms_norm(cfg, self.dtype, "final_layernorm")(x)
        with jax.named_scope("head"):
            logits = _dense(cfg.vocab_size, ("embed", "vocab"), "lm_head",
                            self.dtype)(x)
            return logits.astype(jnp.float32)


def trinity_mini(vocab_size: int = 200192, dtype: Dtype = jnp.bfloat16,
                 seq_len: Optional[int] = None, **overrides: Any) -> AfmoeLM:
    """Trinity-Mini as published: 32 layers (2 dense, then 30 of 128
    experts), 26B parameters. No chip here holds it; shape tests do."""
    del seq_len  # rotary positions: any sequence length
    return AfmoeLM(AfmoeConfig(vocab_size=vocab_size, **overrides),
                   dtype=dtype)


def trinity_mini_ep8(vocab_size: int = 25024, dtype: Dtype = jnp.bfloat16,
                     seq_len: Optional[int] = None,
                     **overrides: Any) -> AfmoeLM:
    """One chip's share of Trinity-Mini when eight chips share each layer:
    experts 0-15 of 128, vocabulary rows 0-25023, and five layers (one
    leading dense layer, then one whole period: sliding, sliding, sliding,
    full; the others would lie on further chips). Every width is the
    published one. Blocks are recomputed in the backward pass: float32
    masters, gradients and Adam's moments of 705M parameters leave a 16 GB
    chip little else."""
    del seq_len
    return AfmoeLM(AfmoeConfig(
        vocab_size=vocab_size,
        **{"layer_types": (SLIDING, SLIDING, SLIDING, SLIDING, FULL),
           "num_dense_layers": 1, "experts_held": (0, 16), "remat": True,
           **overrides}), dtype=dtype)


def tiny_afmoe(vocab_size: int = 512, dtype: Dtype = jnp.float32,
               seq_len: Optional[int] = None, **overrides: Any) -> AfmoeLM:
    """Test-sized: every mechanism of the family at small widths, as a share
    (experts 2-5 of 8)."""
    del seq_len
    return AfmoeLM(AfmoeConfig(
        vocab_size=vocab_size,
        **{"hidden_size": 64, "num_heads": 4, "num_kv_heads": 2,
           "head_dim": 16, "intermediate_size": 96,
           "moe_intermediate_size": 32, "num_dense_layers": 1,
           "layer_types": (SLIDING, SLIDING, FULL), "sliding_window": 16,
           "num_experts": 8, "experts_held": (2, 4), "experts_per_token": 2,
           **overrides}), dtype=dtype)
