"""Ring attention — blockwise sequence/context parallelism over the ``seq``
mesh axis.

Long-context scaling the TPU-native way: each device holds one sequence shard
of Q, K, V; K/V blocks rotate around the ``seq`` axis ring with
``lax.ppermute`` (one ICI-neighbour hop per step) while each device
accumulates its queries' attention with an online-softmax running state
(max ``m``, normalizer ``l``, weighted-value ``acc`` — the flash-attention
recurrence). After ``seq`` steps every query has seen every key, yet no
device ever materializes the full (S, S) score matrix or the full K/V — HBM
stays O(S_local) and the permutes overlap with block compute under XLA's
scheduler.

The reference had no long-context machinery at all (SURVEY.md §5.7 — a
CNN-era DP tutorial); this subsystem is the capability the port adds to make
sequence models first-class on TPU. Used inside the GSPMD train step via a
nested ``shard_map`` (models/bert.py) so K/V rotation rides ICI explicitly
while XLA still lays out everything else.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from distributeddeeplearning_tpu import compat
from distributeddeeplearning_tpu.ops.masks import block_causal_mask

# Large-negative instead of -inf: keeps exp() exactly 0 without inf-inf NaN
# hazards in the running-max recurrence.
_NEG = -1e30


def _block_update(q, k, v, kv_mask, m, l, acc, scale, tri=None, drop=None):
    """One online-softmax accumulation step against a K/V block.

    q: (B, Sq, H, D); k/v: (B, Sk, H, D); kv_mask: (B, Sk) True=attend.
    ``tri``: optional (Sq, Sk) bool causal mask for this block pair.
    Running state m, l: (B, H, Sq); acc: (B, H, Sq, D), all float32.

    ``drop``: optional attention-probability dropout as
    (rate, seed, b0, h0, h_total, q0, k0) — rate static, the rest traced
    scalars placing this block in GLOBAL (batch·head, query, key)
    coordinates. The mask is the counter-based hash of those coordinates
    (ops/hash_dropout.py), so every ring step, every shard, and every other
    attention impl realizes the identical mask for the same seed. ``l``
    accumulates undropped p (dense semantics: normalize, then drop);
    backward is plain autodiff through this function, hence consistent.
    """
    keep = jnp.broadcast_to(kv_mask[:, None, None, :],
                            (q.shape[0], 1, q.shape[1], k.shape[1]))
    if tri is not None:
        keep = keep & tri[None, None]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(keep, s, _NEG)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # Re-mask after exp: a fully-masked block would otherwise contribute
    # exp(_NEG - _NEG) = 1 per key.
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(keep, p, 0.0)
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    if drop is not None and drop[0] > 0.0:
        from distributeddeeplearning_tpu.ops.hash_dropout import keep_mask

        rate, seed, b0, h0, h_tot, q0, k0 = drop
        nb, sq, nh = q.shape[0], q.shape[1], q.shape[2]
        sk = k.shape[1]
        bh = ((b0 + jnp.arange(nb))[:, None] * h_tot
              + h0 + jnp.arange(nh)[None, :])                # (B, H)
        rows = q0 + jnp.arange(sq)
        cols = k0 + jnp.arange(sk)
        km = keep_mask(seed, bh[:, :, None, None],
                       rows[None, None, :, None],
                       cols[None, None, None, :], rate)
        p = jnp.where(km, p * (1.0 / (1.0 - rate)), 0.0)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
    return m_new, l_new, acc_new


def ring_attention(q, k, v, kv_mask, *, axis_name: str = "seq",
                   causal: bool = False, dropout=None):
    """Exact attention over a ring of sequence shards (optionally causal).

    Call under ``shard_map`` with the sequence dim sharded on ``axis_name``.
    Shapes (per shard): q/k/v (B, S_local, H, D); kv_mask (B, S_local) bool.
    Returns (B, S_local, H, D) in q.dtype. Collapses to one local block (no
    permutes) when the axis has size 1, so the same code path serves
    single-chip runs.

    ``causal=True`` masks by *global* sequence position: ring step r brings
    shard ``(i - r) mod n``'s K/V to shard i, so each block pair gets the
    (Sq, Sk) triangle of ``kv_pos <= q_pos`` — full for past blocks, the
    diagonal triangle for the local block, empty for future blocks. A
    future block's arrival skips ``_block_update`` entirely via ``lax.cond``
    (its contribution is exactly zero), reclaiming the ~2x FLOP overhead
    the uniform schedule would pay; the ppermutes still run every step, so
    the ring schedule — and hence the collective pattern XLA compiles —
    stays identical on every device (VERDICT r2 Weak #3).
    """
    b, sq, h, d = q.shape
    scale = d ** -0.5
    n = lax.axis_size(axis_name)
    m = jnp.full((b, h, sq), _NEG, jnp.float32)
    l = jnp.zeros((b, h, sq), jnp.float32)
    acc = jnp.zeros((b, h, sq, d), jnp.float32)
    kv_mask = kv_mask.astype(jnp.bool_)
    idx = (lax.axis_index(axis_name) if causal or dropout is not None
           else None)

    def blk_drop(src):
        # Contiguous sharding: shard i holds natural positions
        # [i*sq, (i+1)*sq) — the dropout hash coordinates stay global.
        if dropout is None:
            return None
        return (*dropout, idx * sq, src * sq)

    # Local block first, outside the loop: it both seeds the carry with the
    # right varying-axes type (the NEG/zero inits are unvarying constants,
    # which shard_map's loop typing rejects as a carry) and leaves exactly
    # n-1 permutes in the ring.
    tri = block_causal_mask(idx, idx, sq, sq) if causal else None
    m, l, acc = _block_update(q, k, v, kv_mask, m, l, acc, scale, tri,
                              blk_drop(idx))
    if n > 1:
        perm = [(i, (i + 1) % n) for i in range(n)]

        def body(r, carry):
            m, l, acc, k, v, msk = carry
            # Rotate K/V (and their padding mask) one ICI neighbour along
            # the ring, then fold the arriving block into the running state.
            k, v, msk = lax.ppermute((k, v, msk), axis_name, perm)
            src = (idx - r) % n if idx is not None else None
            if causal:

                def fold(state):
                    tri = block_causal_mask(idx, src, sq, sq)
                    return _block_update(q, k, v, msk, *state, scale, tri,
                                         blk_drop(src))

                # src > idx means every arriving key is in this shard's
                # future: the whole block is masked and contributes nothing.
                # lax.cond keeps it off the execution path (the predicate is
                # a local scalar, so each device branches independently
                # while the ppermute above stays uniform across the ring).
                m, l, acc = lax.cond(src > idx,
                                     lambda state: state, fold, (m, l, acc))
            else:
                m, l, acc = _block_update(q, k, v, msk, m, l, acc, scale,
                                          None, blk_drop(src))
            return m, l, acc, k, v, msk

        m, l, acc, *_ = lax.fori_loop(
            1, n, body, (m, l, acc, k, v, kv_mask))

    out = acc / jnp.maximum(l, 1e-30)[..., None]          # (B, H, Sq, D)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)       # (B, Sq, H, D)


def ring_attention_sharded(q, k, v, kv_mask, *,
                           mesh: Optional[jax.sharding.Mesh] = None,
                           seq_axis: str = "seq",
                           batch_axes=("data", "fsdp"),
                           head_axis: str = "model",
                           causal: bool = False,
                           zigzag: bool = False,
                           dropout_rate: float = 0.0, dropout_seed=None):
    """GSPMD-embeddable wrapper: shard_map over (batch, seq, heads).

    Takes *global* (B, S, H, D) arrays inside a jit-traced program (ambient
    mesh from ``use_mesh``), pins the ring layout — batch over the DP axes,
    sequence over ``seq``, heads over ``model`` — and runs ``ring_attention``
    per shard. Heads stay independent, so head sharding composes freely with
    the sequence ring. ``zigzag=True`` (implies causal) maps
    :func:`zigzag_ring_attention` instead — inputs/outputs must already be
    in zigzag layout (:func:`zigzag_indices`).

    ``dropout_rate`` > 0: attention-probability dropout via the global
    counter-based hash mask (ops/hash_dropout.py) — each shard offsets its
    coordinates by its mesh position, so the realized mask equals the dense
    impl's at any dp x tp x sp sharding.
    """
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("ring_attention_sharded: dropout_rate > 0 needs "
                         "a dropout_seed")
    if mesh is None:
        ambient = jax.sharding.get_abstract_mesh()
        if ambient.empty:
            # No mesh context (single-device apply / notebook use): one local
            # block is the whole ring. Zigzag over one shard with identity
            # permutation is plain causal attention.
            drop = ((float(dropout_rate), dropout_seed, 0, 0, q.shape[2])
                    if dropout_rate > 0.0 else None)
            return _local_attention(q, k, v, kv_mask,
                                    causal=causal or zigzag, dropout=drop)
        mesh_shape = ambient.shape
    else:
        mesh_shape = mesh.shape
    if zigzag and mesh_shape.get(seq_axis, 1) <= 1:
        # One seq shard: the zigzag permutation is the identity and its
        # chunk split would demand an even length for nothing — the plain
        # causal ring (a single local block) is the same computation.
        zigzag, causal = False, True
    qkv_spec = P(batch_axes, seq_axis, head_axis, None)
    mask_spec = P(batch_axes, seq_axis)
    seed_arr = jnp.reshape(
        jnp.asarray(dropout_seed if dropout_seed is not None else 0,
                    jnp.int32), (1,))

    def fn(qs, ks, vs, ms, seed1):
        drop = None
        if dropout_rate > 0.0:
            from distributeddeeplearning_tpu.ops.hash_dropout import (
                shard_bh_offsets)

            b0, h0, h_tot = shard_bh_offsets(batch_axes, head_axis,
                                             qs.shape[0], qs.shape[2])
            drop = (float(dropout_rate), seed1[0], b0, h0, h_tot)
        if zigzag:
            return zigzag_ring_attention(qs, ks, vs, ms,
                                         axis_name=seq_axis, dropout=drop)
        return ring_attention(qs, ks, vs, ms, axis_name=seq_axis,
                              causal=causal, dropout=drop)

    mapped = compat.shard_map(
        fn, mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec, P(None)),
        out_specs=qkv_spec)
    return mapped(q, k, v, kv_mask, seed_arr)


# ---------------------------------------------------------------------------
# Zigzag (load-balanced) causal ring — the latency fix the plain causal
# ring cannot deliver (BASELINE.md r3 note): with contiguous sharding the
# last shard computes every block, so the lockstep ring's critical path is
# unchanged by skipping work elsewhere. Zigzag sharding gives shard i the
# chunk PAIR (i, 2n-1-i) of 2n global chunks — one early (light) and one
# late (heavy) — which makes every shard's causal work equal by
# construction: per ring arrival, each shard folds exactly two chunk-pair
# updates (three on the local step), so the critical path drops from n
# full-block updates to ~n single-chunk pairs (~2x at equal total FLOPs).
# ---------------------------------------------------------------------------

def zigzag_indices(seq_len: int, n_shards: int):
    """Permutation taking the natural sequence to zigzag-shard order.

    ``x[:, perm]`` lays the sequence out so an even split over ``n_shards``
    gives shard i the chunks (i, 2n-1-i); ``inv`` undoes it
    (``y[:, inv]`` returns to natural order).
    """
    import numpy as np

    assert seq_len % (2 * n_shards) == 0, (seq_len, n_shards)
    c = seq_len // (2 * n_shards)
    chunks = np.arange(seq_len).reshape(2 * n_shards, c)
    perm = np.concatenate([
        np.concatenate([chunks[i], chunks[2 * n_shards - 1 - i]])
        for i in range(n_shards)])
    inv = np.argsort(perm)
    return perm, inv


def _zigzag_pairs(i: int, src: int, n: int):
    """Pure-python mirror of the traced schedule: the (q_chunk, kv_chunk)
    pairs shard ``i`` computes when shard ``src``'s K/V arrives. The
    schedule-balance test sums this statically; the traced code below uses
    the same predicates."""
    qlo, qhi = i, 2 * n - 1 - i
    klo, khi = src, 2 * n - 1 - src
    pairs = []
    if klo <= qlo:
        pairs.append((qlo, klo))
    if khi <= qlo:  # provably never (khi >= n > qlo); kept for the mirror
        pairs.append((qlo, khi))
    if klo <= qhi:  # provably always (klo < n <= qhi)
        pairs.append((qhi, klo))
    if khi <= qhi:
        pairs.append((qhi, khi))
    return pairs


def zigzag_ring_attention(q, k, v, kv_mask, *, axis_name: str = "seq",
                          dropout=None):
    """Causal ring attention over zigzag-sharded sequences.

    Call under ``shard_map`` with inputs already in zigzag layout
    (:func:`zigzag_indices`): per shard, the local (B, S_local, H, D)
    arrays are ``concat(chunk_i, chunk_{2n-1-i})``. Output is in the same
    local layout (undo globally with ``inv``). Numerics are exactly causal
    attention in natural order (tests assert vs the dense reference).
    """
    b, sl, h, d = q.shape
    c = sl // 2
    scale = d ** -0.5
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    kv_mask = kv_mask.astype(jnp.bool_)

    def halves(x):
        return x[:, :c], x[:, c:]

    def init():
        return (jnp.full((b, h, c), _NEG, jnp.float32),
                jnp.zeros((b, h, c), jnp.float32),
                jnp.zeros((b, h, c, d), jnp.float32))

    qlo, qhi = halves(q)
    qlo_c, qhi_c = idx, 2 * n - 1 - idx  # global chunk indices

    def fold(state, qh, qc, kh, kc, msk, tri: bool):
        mask = block_causal_mask(qc, kc, c, c) if tri else None
        # Zigzag chunk qc holds NATURAL positions [qc*c, (qc+1)*c): keying
        # the dropout hash by them makes the permuted-layout mask equal the
        # dense impl's natural-order mask element for element.
        drop = (*dropout, qc * c, kc * c) if dropout is not None else None
        return _block_update(qh, kh[0], kh[1], msk, *state, scale, mask,
                             drop)

    # Local arrival (src == idx): seeds the carries with varying-type values
    # (see the non-zigzag ring above) and leaves n-1 permutes in the ring.
    klo, khi = halves(k)
    vlo, vhi = halves(v)
    mlo, mhi = halves(kv_mask)
    lo = fold(init(), qlo, qlo_c, (klo, vlo), qlo_c, mlo, tri=True)
    hi = fold(init(), qhi, qhi_c, (klo, vlo), qlo_c, mlo, tri=False)
    hi = fold(hi, qhi, qhi_c, (khi, vhi), qhi_c, mhi, tri=True)

    if n > 1:
        perm = [(j, (j + 1) % n) for j in range(n)]

        def body(r, carry):
            lo, hi, k, v, msk = carry
            k, v, msk = lax.ppermute((k, v, msk), axis_name, perm)
            src = (idx - r) % n
            klo, khi = halves(k)
            vlo, vhi = halves(v)
            mlo, mhi = halves(msk)
            # Arriving chunk pair (src, 2n-1-src); every computed pair is a
            # FULL block (strict chunk inequality — the only triangles are
            # the local ones above), so tri=False throughout. The two conds
            # mirror _zigzag_pairs: each shard folds exactly two of the
            # three candidate pairs per arrival — balanced by construction.
            lo = lax.cond(
                src < idx,
                lambda s: fold(s, qlo, qlo_c, (klo, vlo), src, mlo,
                               tri=False),
                lambda s: s, lo)
            hi = fold(hi, qhi, qhi_c, (klo, vlo), src, mlo, tri=False)
            hi = lax.cond(
                src > idx,
                lambda s: fold(s, qhi, qhi_c, (khi, vhi), 2 * n - 1 - src,
                               mhi, tri=False),
                lambda s: s, hi)
            return lo, hi, k, v, msk

        lo, hi, *_ = lax.fori_loop(1, n, body, (lo, hi, k, v, kv_mask))

    def finish(state):
        m, l, acc = state
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.transpose(0, 2, 1, 3)

    return jnp.concatenate([finish(lo), finish(hi)], axis=1).astype(q.dtype)


def zigzag_ring_attention_sharded(q, k, v, kv_mask, **kw):
    """GSPMD-embeddable wrapper for :func:`zigzag_ring_attention` — same
    contract as :func:`ring_attention_sharded`, inputs/outputs in zigzag
    layout."""
    return ring_attention_sharded(q, k, v, kv_mask, causal=True,
                                  zigzag=True, **kw)


def _local_attention(q, k, v, kv_mask, *, causal: bool = False,
                     dropout=None):
    """The ring's single-block case without a mesh: one _block_update pass
    (still exact, still O(S) memory in scores per block — here S is global)."""
    b, sq, h, d = q.shape
    m = jnp.full((b, h, sq), _NEG, jnp.float32)
    l = jnp.zeros((b, h, sq), jnp.float32)
    acc = jnp.zeros((b, h, sq, d), jnp.float32)
    tri = block_causal_mask(0, 0, sq, sq) if causal else None
    drop = (*dropout, 0, 0) if dropout is not None else None
    m, l, acc = _block_update(q, k, v, kv_mask.astype(jnp.bool_), m, l, acc,
                              d ** -0.5, tri, drop)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)
