"""Nemotron-H hybrid decoder (NVIDIA's Nemotron 3 Nano 30B-A3B, ``model_type:
nemotron_h``) in Flax.

A decoder-only causal LM whose layers differ by KIND, one letter of
``hybrid_override_pattern`` a layer, and each is one mixer or one FFN, never
both: ``x <- x + f(RMSNorm(x))``.

- ``M``, a Mamba-2 mixer: ``[z | xBC | dt] = u W_in``; ``xBC`` through a
  causal depthwise convolution of 4 taps with a bias and a SiLU, then split
  into x (heads x head_dim), B and C (groups x state); ``dt = softplus(dt +
  dt_bias)`` a head; the state-space recurrence with a scalar decay a head
  (ops/ssd.py: ``A = -exp(A_log)``, a ``(head_dim, state)`` state a head, B
  and C shared by a group's heads, and the ``D`` skip); then ``y * silu(z)``
  RMS-normalised over groups of ``heads x head_dim / groups`` channels, times
  a scale, and ``W_out``.
- ``E``, routed experts with a shared expert, each a squared ReLU,
  ``down(relu(up u)^2)`` (models/moe.py::RoutedExperts with ``activation``
  "relu2": sigmoid scores over all experts, top-k with a selection bias that
  chooses and does not weigh, normalised and scaled gates, no token dropped).
- ``*``, grouped-query causal softmax attention with no positions at all.

The embedding is unscaled and the head untied. docs/nemotron_h.md has the
equations and what is taken from the family's published modelling code and
not from ``config.json``.

One configuration class builds the published model
(``nemotron3_nano_30b``: 52 layers, 128 experts, 131072 tokens: 31.6B
parameters, for shape tests) and one chip's share of it
(``nemotron3_nano_ep16``: 16 chips share each layer, so this chip holds
experts 0-7 of 128 and rows 0-16383 of the vocabulary, and the first nine
layers; every width stays as published).

Scopes for analysis/anatomy.py: ``embed``, ``head``, ``mlp`` (the shared
expert), ``mamba`` round a mixer's convolution, step sizes, recurrence, skip
and gated norm (its two projections stay outside, as the block's products),
``attn_full`` round the attention kernels, and RoutedExperts' own four.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributeddeeplearning_tpu.models.afmoe import (KEPT_BY_A_BLOCK,
                                                      _dense, _rms_norm)
from distributeddeeplearning_tpu.models.kimi_linear import _dt_bias_init
from distributeddeeplearning_tpu.models.llama import Held
from distributeddeeplearning_tpu.models.moe import RoutedExperts
from distributeddeeplearning_tpu.ops import ssd
from distributeddeeplearning_tpu.ops.attention import multihead_attention
from distributeddeeplearning_tpu.ops.embedding import embedding_lookup

Dtype = Any

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """Nemotron 3 Nano 30B-A3B's published sizes by default (its
    ``config.json``)."""

    vocab_size: int = 131072
    hidden_size: int = 2688
    pattern: str = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8                    # of B and C
    state_size: int = 128
    conv_size: int = 4
    chunk_size: int = 128                # the recurrence's chunked form
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    moe_intermediate_size: int = 1856    # an expert's
    shared_intermediate_size: int = 3712
    num_experts: int = 128               # the router's width
    experts_held: tuple = (0, 128)       # (first, count) held by this chip
    experts_per_token: int = 6
    route_norm: bool = True              # norm_topk_prob
    route_scale: float = 2.5             # routed_scaling_factor
    load_balance_coeff: float = 0.001    # the selection bias's step
    rms_eps: float = 1e-5
    attention_impl: str = "dense"        # dense | flash
    remat: bool = False                  # recompute each block in backward

    @property
    def num_layers(self) -> int:
        return len(self.pattern)


def _a_log_init(key, shape, dtype):
    """log(1), ..., log(heads): a head's decay rate A = -(its number)."""
    del key
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=dtype))


class _ConvTaps(nn.Module):
    """A depthwise convolution's taps ``(size, channels)`` and its bias."""

    size: int
    channels: int

    @nn.compact
    def __call__(self):
        taps = self.param("kernel", nn.with_logical_partitioning(
            nn.initializers.normal(0.02), (None, "heads")),
            (self.size, self.channels), jnp.float32)
        bias = self.param("bias", nn.with_logical_partitioning(
            nn.initializers.zeros, ("heads",)), (self.channels,),
            jnp.float32)
        return taps, bias


def causal_conv(x, taps, bias):
    """Depthwise causal convolution along the sequence in float32: x (B, S,
    C), taps (K, C), ``y_t = b + sum_j w[j] x_{t-K+1+j}``, zeros before the
    first token."""
    s, k = x.shape[1], taps.shape[0]
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    return bias + sum(taps[j] * xp[:, j:j + s] for j in range(k))


def gated_group_norm(y, z, scale, groups: int, eps: float):
    """``RMSNorm(y * silu(z))`` over ``groups`` equal groups of the channels,
    times ``scale``, in float32."""
    f32 = jnp.float32
    g = y.astype(f32) * nn.silu(z.astype(f32))
    shape = g.shape
    g = g.reshape(shape[:-1] + (groups, shape[-1] // groups))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    return g.reshape(shape) * scale


class Mamba2Mixer(nn.Module):
    cfg: NemotronHConfig
    dtype: Dtype

    @nn.compact
    def __call__(self, x, pad_mask):
        cfg = self.cfg
        b, s, _ = x.shape
        h, p = cfg.mamba_heads, cfg.mamba_head_dim
        g, n = cfg.n_groups, cfg.state_size
        inner, f32 = h * p, jnp.float32
        width = inner + 2 * g * n           # the convolution's: x, B, C
        mask = pad_mask[..., None]
        zxbcdt = _dense(inner + width + h, ("embed", "heads"), "in_proj",
                        self.dtype)(x)
        taps, conv_bias = _ConvTaps(cfg.conv_size, width, name="conv1d")()
        a_log = self.param("A_log", _a_log_init, (h,), f32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (h,), f32)
        d_skip = self.param("D", nn.initializers.ones, (h,), f32)
        scale = Held("scale", (inner,), nn.initializers.ones, name="norm")()
        with jax.named_scope("mamba"):
            z, xbc, dt = jnp.split(zxbcdt, [inner, inner + width], axis=-1)
            # a padded token writes nothing and decays nothing (dt = 0), and
            # puts nothing into a convolution
            xbc = xbc * mask.astype(xbc.dtype)
            xbc = (nn.silu(causal_conv(xbc, taps, conv_bias))
                   * mask).astype(self.dtype)
            xs, bs, cs = jnp.split(xbc, [inner, inner + g * n], axis=-1)
            dt = nn.softplus(dt.astype(f32) + dt_bias) * mask
            a = -jnp.exp(a_log)
            self.sow(ssd.SSD_METRICS, "min_chunk_log_decay",
                     jax.lax.stop_gradient(ssd.min_chunk_log_decay(
                         dt, a, cfg.chunk_size)))
            y = ssd.ssd_chunked(xs.reshape(b, s, h, p), dt, a,
                                bs.reshape(b, s, g, n),
                                cs.reshape(b, s, g, n), d_skip,
                                chunk=cfg.chunk_size)
            y = gated_group_norm(y.reshape(b, s, inner), z, scale, g,
                                 cfg.rms_eps).astype(self.dtype)
        return _dense(cfg.hidden_size, ("heads", "embed"), "out_proj",
                      self.dtype)(y)


class GqaAttention(nn.Module):
    cfg: NemotronHConfig
    dtype: Dtype

    @nn.compact
    def __call__(self, x, pad_mask):
        cfg = self.cfg
        b, s, _ = x.shape
        h, hkv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = _dense(h * d, ("embed", "heads"), "q_proj", self.dtype)(x)
        k = _dense(hkv * d, ("embed", "heads"), "k_proj", self.dtype)(x)
        v = _dense(hkv * d, ("embed", "heads"), "v_proj", self.dtype)(x)
        with jax.named_scope("attn_full"):   # no positions
            out = multihead_attention(
                q.reshape(b, s, h, d), k.reshape(b, s, hkv, d),
                v.reshape(b, s, hkv, d), pad_mask, impl=cfg.attention_impl,
                causal=True, dtype=self.dtype)
        return _dense(cfg.hidden_size, ("heads", "embed"), "o_proj",
                      self.dtype)(out)


class NemotronHBlock(nn.Module):
    cfg: NemotronHConfig
    index: int
    dtype: Dtype

    @nn.compact
    def __call__(self, x, pad_mask, *, train: bool):
        cfg = self.cfg
        kind = cfg.pattern[self.index]
        u = _rms_norm(cfg, self.dtype, "input_layernorm")(x)
        if kind == MAMBA:
            f = Mamba2Mixer(cfg, self.dtype, name="mixer")(u, pad_mask)
        elif kind == ATTENTION:
            f = GqaAttention(cfg, self.dtype, name="mixer")(u, pad_mask)
        elif kind == EXPERTS:
            f = RoutedExperts(
                hidden_size=cfg.hidden_size,
                expert_width=cfg.moe_intermediate_size,
                num_experts=cfg.num_experts,
                experts_per_token=cfg.experts_per_token,
                experts_held=cfg.experts_held, score_func="sigmoid",
                route_norm=cfg.route_norm, route_scale=cfg.route_scale,
                shared_width=cfg.shared_intermediate_size,
                bias_update_rate=cfg.load_balance_coeff,
                activation="relu2", dtype=self.dtype, name="moe")(
                    u, train=train)
        else:
            raise ValueError(f"layer {self.index}: unknown kind {kind!r} in "
                             f"pattern {cfg.pattern!r}")
        return x + f


class NemotronHLM(nn.Module):
    """Decoder-only LM; returns (B, S, vocab) float32 logits."""

    cfg: NemotronHConfig
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
            block = NemotronHBlock(cfg, i, self.dtype, name=f"layer{i}")
            if cfg.remat:
                # a mixer keeps nothing: its recurrence is XLA's products,
                # remade from the block's input
                x = nn.remat(
                    lambda mdl, h, m: mdl(h, m, train=train),
                    policy=jax.checkpoint_policies.save_only_these_names(
                        *KEPT_BY_A_BLOCK))(block, x, pad_mask)
            else:
                x = block(x, pad_mask, train=train)
            x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        x = _rms_norm(cfg, self.dtype, "final_layernorm")(x)
        with jax.named_scope("head"):
            logits = _dense(cfg.vocab_size, ("embed", "vocab"), "lm_head",
                            self.dtype)(x)
            return logits.astype(jnp.float32)


def nemotron3_nano_30b(vocab_size: int = 131072,
                       dtype: Dtype = jnp.bfloat16,
                       seq_len: Optional[int] = None,
                       **overrides: Any) -> NemotronHLM:
    """Nemotron 3 Nano 30B-A3B as published: 52 layers (23 Mamba-2, 23 of
    128 experts, 6 attention). No chip here holds it; shape tests do."""
    del seq_len  # no positions: any sequence length
    return NemotronHLM(NemotronHConfig(vocab_size=vocab_size, **overrides),
                       dtype=dtype)


def nemotron3_nano_ep16(vocab_size: int = 16384,
                        dtype: Dtype = jnp.bfloat16,
                        seq_len: Optional[int] = None,
                        **overrides: Any) -> NemotronHLM:
    """One chip's share of Nemotron 3 Nano 30B-A3B when 16 chips share each
    layer: experts 0-7 of 128, vocabulary rows 0-16383, and the first nine
    layers as published, ``MEMEM*EME`` (4 Mamba-2, 4 expert, 1 attention:
    the published 23 : 23 : 6 to a layer; the other 43 would lie on further
    chips). Every width is the published one. Blocks are recomputed in the
    backward pass: float32 masters, gradients and Adam's moments of 667M
    parameters leave a 16 GB chip little else."""
    del seq_len
    return NemotronHLM(NemotronHConfig(
        vocab_size=vocab_size,
        **{"pattern": "MEMEM*EME", "experts_held": (0, 8), "remat": True,
           **overrides}), dtype=dtype)


def nemotron3_nano_tiny(vocab_size: int = 512, dtype: Dtype = jnp.float32,
                        seq_len: Optional[int] = None,
                        **overrides: Any) -> NemotronHLM:
    """Test-sized: every mechanism of the family at small widths, as a share
    (experts 2-5 of 8), with chunks of 16 so that a short sequence spans
    several."""
    del seq_len
    return NemotronHLM(NemotronHConfig(
        vocab_size=vocab_size,
        **{"hidden_size": 64, "pattern": "MEM*", "mamba_heads": 4,
           "mamba_head_dim": 8, "n_groups": 2, "state_size": 8,
           "chunk_size": 16, "num_heads": 4, "num_kv_heads": 2,
           "head_dim": 16, "moe_intermediate_size": 32,
           "shared_intermediate_size": 48, "num_experts": 8,
           "experts_held": (2, 4), "experts_per_token": 2, **overrides}),
        dtype=dtype)
