"""GPT-2-style decoder-only causal LM in Flax, TPU-first.

Beyond the reference's CNN+BERT scope: the causal counterpart to
models/bert.py, sharing the same logical-axis sharding rules (tp via
``heads``/``mlp``/``vocab``, sp activations, fsdp ``embed``) and the same
train loop — one more family behind the one trainer. Pre-LN residual
blocks, learned positions, gelu MLP, weight-tied LM head; the parameter
layout matches the public GPT-2 124M checkpoint's shapes (param count
asserted in tests).

Attention: dense causal by default; ``attention_impl='flash'`` uses the
Pallas kernel with ``causal=True`` (ops/flash_attention.py), which skips
above-diagonal blocks — the long-context training path.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributeddeeplearning_tpu.ops.embedding import embedding_lookup

Dtype = Any


@dataclasses.dataclass(frozen=True)
class GptConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_position: int = 1024
    dropout_rate: float = 0.1
    layer_norm_eps: float = 1e-5
    attention_impl: str = "dense"   # dense | flash (causal Pallas kernel) |
                                    # ring (causal ring over the `seq` axis) |
                                    # zigzag (load-balanced causal ring)
    remat: bool = False
    # Pipeline over the `pipeline` mesh axis (models/pipeline.py);
    # num_layers must divide evenly into stages. Schedule "gpipe" or
    # interleaved "1f1b" with pipeline_virtual_stages chunks per stage.
    pipeline_stages: int = 1
    pipeline_microbatches: int = 4
    pipeline_schedule: str = "gpipe"
    pipeline_virtual_stages: int = 1

    @property
    def intermediate_size(self) -> int:
        return 4 * self.hidden_size


def _dense(features, logical_axes, name, dtype):
    return nn.Dense(
        features, dtype=dtype, param_dtype=jnp.float32,
        kernel_init=nn.with_logical_partitioning(
            nn.initializers.normal(0.02), logical_axes),
        name=name)


class CausalSelfAttention(nn.Module):
    cfg: GptConfig
    dtype: Dtype

    @nn.compact
    def __call__(self, x, pad_mask, *, deterministic: bool,
                 decode: bool = False, paged_state=None):
        cfg = self.cfg
        b, s, _ = x.shape
        head_dim = cfg.hidden_size // cfg.num_heads
        q = _dense(cfg.hidden_size, ("embed", "heads"), "query", self.dtype)(x)
        k = _dense(cfg.hidden_size, ("embed", "heads"), "key", self.dtype)(x)
        v = _dense(cfg.hidden_size, ("embed", "heads"), "value", self.dtype)(x)
        q = q.reshape(b, s, cfg.num_heads, head_dim)
        k = k.reshape(b, s, cfg.num_heads, head_dim)
        v = v.reshape(b, s, cfg.num_heads, head_dim)

        if decode and paged_state is not None:
            # Paged decode (serve/kv_cache.py): rows are decode SLOTS, each
            # at its own position paged_state.lengths[i], K/V scattered
            # into pool pages instead of a per-request dense cache. The
            # pools are engine-seeded cache leaves — same softmax/mask
            # numerics as the dense branch below (token-identity pinned by
            # tests/test_serve.py). A PagedBlockState advances each slot
            # up to s tokens at once (suffix prefill / speculative
            # verify); a plain PagedState is the one-token step.
            from distributeddeeplearning_tpu.serve import kv_cache as paged
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
        elif decode:
            # Incremental decoding: a block of s tokens (s = prompt length
            # on the prefill call, 1 per step after) is appended to a
            # (B, max_position, H, D) cache and attends over the live
            # prefix — O(S) per emitted token vs the full-refeed O(S^2)
            # (models/generate.py use_cache=True). Each attention module
            # keeps its own write index, the standard flax cache layout.
            ck = self.variable(
                "cache", "cached_key", jnp.zeros,
                (b, cfg.max_position, cfg.num_heads, head_dim), self.dtype)
            cv = self.variable(
                "cache", "cached_value", jnp.zeros,
                (b, cfg.max_position, cfg.num_heads, head_dim), self.dtype)
            ci = self.variable("cache", "cache_index",
                               lambda: jnp.zeros((), jnp.int32))
            idx = ci.value
            ck.value = jax.lax.dynamic_update_slice(
                ck.value, k.astype(self.dtype), (0, idx, 0, 0))
            cv.value = jax.lax.dynamic_update_slice(
                cv.value, v.astype(self.dtype), (0, idx, 0, 0))
            ci.value = idx + s
            # Query j (global position idx+j) sees cache slots <= idx+j:
            # causal within the written block, everything before it.
            live = (jnp.arange(cfg.max_position)[None, :]
                    <= (idx + jnp.arange(s))[:, None])[None, None]
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, ck.value) \
                * (head_dim ** -0.5)
            scores = jnp.where(live, scores, jnp.finfo(jnp.float32).min)
            probs = jax.nn.softmax(scores.astype(jnp.float32),
                                   axis=-1).astype(self.dtype)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs, cv.value)
            out = out.reshape(b, s, cfg.hidden_size)
        else:
            from distributeddeeplearning_tpu.ops.attention import (
                multihead_attention)
            out = multihead_attention(
                q, k, v, pad_mask, impl=cfg.attention_impl, causal=True,
                dtype=self.dtype, dropout_rate=cfg.dropout_rate,
                dropout_rng=(self.make_rng("dropout")
                             if not deterministic and cfg.dropout_rate > 0
                             else None),
                deterministic=deterministic)
        return _dense(cfg.hidden_size, ("heads", "embed"), "output",
                      self.dtype)(out)


class DecoderBlock(nn.Module):
    """Pre-LN transformer block (GPT-2 ordering)."""

    cfg: GptConfig
    dtype: Dtype

    @nn.compact
    def __call__(self, x, pad_mask, *, deterministic: bool,
                 decode: bool = False, paged_state=None):
        cfg = self.cfg
        h = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=self.dtype,
                         param_dtype=jnp.float32, name="ln1")(x)
        h = CausalSelfAttention(cfg, self.dtype, name="attention")(
            h, pad_mask, deterministic=deterministic, decode=decode,
            paged_state=paged_state)
        x = x + nn.Dropout(cfg.dropout_rate)(h, deterministic=deterministic)
        h = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=self.dtype,
                         param_dtype=jnp.float32, name="ln2")(x)
        # Scope names here and in GptLM are what analysis/anatomy.py reads a
        # compiled step's parts from; what is left bare in a block is the
        # attention half's dropout and residual.
        with jax.named_scope("mlp"):
            h = _dense(cfg.intermediate_size, ("embed", "mlp"), "mlp_in",
                       self.dtype)(h)
            h = nn.gelu(h, approximate=True)  # GPT-2: the tanh approximation
            h = _dense(cfg.hidden_size, ("mlp", "embed"), "mlp_out",
                       self.dtype)(h)
            return x + nn.Dropout(cfg.dropout_rate)(
                h, deterministic=deterministic)


class GptLM(nn.Module):
    """Decoder-only LM; returns (B, S, vocab) f32 logits (tied head)."""

    cfg: GptConfig
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
        if decode and cfg.pipeline_stages > 1:
            raise ValueError("decode (KV-cache) mode is not supported for "
                             "pipelined models; generate with the "
                             "non-pipelined variant")
        if s > cfg.max_position:
            raise ValueError(
                f"sequence length {s} exceeds max_position "
                f"{cfg.max_position}; build the model with seq_len={s}")
        pad_mask = (jnp.ones((b, s), jnp.bool_) if attention_mask is None
                    else attention_mask.astype(jnp.bool_))

        # Zigzag layout (load-balanced causal ring, parallel/ring_attention):
        # the whole transformer runs in zigzag order — ids/mask/positions
        # permuted once here, hidden states unpermuted once before the LM
        # head — so each layer's causal attention is balanced across the
        # seq shards without per-layer relayout. The permutation is a
        # trace-time constant from the ambient mesh's seq size; everything
        # between (LN, MLP, residuals, dropout) is positionwise and thus
        # permutation-oblivious.
        inv = None
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
        if decode and paged_state is not None:
            # Paged decode: every slot sits at its OWN position (the
            # engine's per-slot lengths), so the shared scalar counter the
            # dense branch keeps cannot exist — positions come from the
            # state, shaped (B, s) for a per-row wpe lookup (s == 1 for
            # the step path; block column t sits at lengths + t, columns
            # past n_new are masked garbage whose lookup clips).
            pos_index = paged_state.lengths[:, None] + jnp.arange(s)[None]
            if paged_block:
                pos_index = jnp.clip(pos_index, 0, cfg.max_position - 1)
        elif decode:
            # Positions continue from the decode counter (a top-level cache
            # variable advanced by the block length; per-attention cache
            # indices advance in lockstep) — s = prompt length on prefill,
            # 1 per emitted token after.
            pos_var = self.variable("cache", "position",
                                    lambda: jnp.zeros((), jnp.int32))
            pos_index = pos_var.value + jnp.arange(s)
            pos_var.value = pos_var.value + s
        else:
            pos_index = (jnp.asarray(perm) if inv is not None
                         else jnp.arange(s))

        wte = self.param(
            "wte", nn.with_logical_partitioning(nn.initializers.normal(0.02),
                                                ("vocab", "embed")),
            (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        wpe = self.param(
            "wpe", nn.with_logical_partitioning(nn.initializers.normal(0.01),
                                                (None, "embed")),
            (cfg.max_position, cfg.hidden_size), jnp.float32)
        # embedding_lookup: fsdp-friendly scatter-add backward
        # (ops/embedding.py; VERDICT r4 Missing #5). Shared 1D positions
        # broadcast over the batch; paged per-row (B, 1) positions already
        # carry the batch dim.
        with jax.named_scope("embed"):
            pos_emb = embedding_lookup(wpe, pos_index)
            x = (embedding_lookup(wte, input_ids)
                 + (pos_emb if pos_emb.ndim == 3 else pos_emb[None])
                 ).astype(self.dtype)
            x = nn.Dropout(cfg.dropout_rate)(x, deterministic=deterministic)
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))

        if cfg.pipeline_stages > 1:
            import functools

            from distributeddeeplearning_tpu.models.pipeline import (
                build_pipelined)
            x = build_pipelined(
                functools.partial(DecoderBlock, cfg, self.dtype),
                num_layers=cfg.num_layers, num_stages=cfg.pipeline_stages,
                num_microbatches=cfg.pipeline_microbatches,
                schedule=cfg.pipeline_schedule,
                virtual_stages=cfg.pipeline_virtual_stages,
                remat=cfg.remat, dtype=self.dtype)(
                    x, pad_mask, deterministic=deterministic)
            x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        else:
            for i in range(cfg.num_layers):
                block = DecoderBlock(cfg, self.dtype, name=f"layer{i}")
                if cfg.remat and not decode:
                    x = nn.remat(
                        lambda mdl, h, m: mdl(
                            h, m, deterministic=deterministic))(
                        block, x, pad_mask)
                else:
                    x = block(x, pad_mask, deterministic=deterministic,
                              decode=decode, paged_state=paged_state)
                x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))

        if inv is not None:
            # Back to natural order BEFORE the head: unpermuting the (B,S,H)
            # hidden states costs vocab/hidden (~65x) less traffic than
            # unpermuting logits, and callers (loss, eval, generation) see
            # the standard position-aligned contract.
            x = x[:, jnp.asarray(inv)]
            x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=self.dtype,
                         param_dtype=jnp.float32, name="ln_f")(x)
        with jax.named_scope("head"):
            logits = jnp.einsum("bsh,vh->bsv", x, wte.astype(self.dtype))
            return logits.astype(jnp.float32)


def _fit_positions(cfg: GptConfig, seq_len: Optional[int]) -> GptConfig:
    if seq_len and seq_len > cfg.max_position:
        cfg = dataclasses.replace(cfg, max_position=seq_len)
    return cfg


def gpt2_small(vocab_size: int = 50257, dtype: Dtype = jnp.bfloat16,
               seq_len: Optional[int] = None, **overrides: Any) -> GptLM:
    """GPT-2 124M geometry (12L/768H/12 heads, 1024 positions)."""
    cfg = GptConfig(vocab_size=vocab_size, **overrides)
    return GptLM(_fit_positions(cfg, seq_len), dtype=dtype)


def gpt2_medium(vocab_size: int = 50257, dtype: Dtype = jnp.bfloat16,
                seq_len: Optional[int] = None, **overrides: Any) -> GptLM:
    cfg = GptConfig(vocab_size=vocab_size, hidden_size=1024, num_layers=24,
                    num_heads=16, **overrides)
    return GptLM(_fit_positions(cfg, seq_len), dtype=dtype)


def tiny_gpt(vocab_size: int = 1024, dtype: Dtype = jnp.float32,
             seq_len: Optional[int] = None, **overrides: Any) -> GptLM:
    cfg = GptConfig(vocab_size=vocab_size,
                    **{"hidden_size": 64, "num_layers": 2, "num_heads": 4,
                       "max_position": 128, **overrides})
    return GptLM(_fit_positions(cfg, seq_len), dtype=dtype)
