"""Flash attention as a Pallas TPU kernel (forward + custom-VJP backward).

Why a kernel at all: dense attention materializes the (S, S) probability
matrix in HBM — at BERT-base shapes that is B*H*S*S*4 bytes of write+read
traffic per layer, and HBM bandwidth is the TPU's usual bottleneck. These
kernels walk a (batch*heads, visited tiles) grid where each step holds only
(BLOCK, D) tiles of Q/K/V in VMEM — Pallas streams the tiles per grid step —
with the online-softmax running state (m, l, acc) carried across a Q tile's
K tiles in f32 VMEM scratch. HBM traffic is O(S·D) per Q-tile row and VMEM
residency is O(BLOCK·D), so sequence length is bounded by HBM, not VMEM.

Key-padding mask, non-causal (BERT, models/bert.py) or causal
(``causal=True`` — GPT, models/gpt.py). Which (Q tile, K tile) pairs exist
is decided once, statically, by :func:`tile_plan`: the grids are built from
its list of visited tiles (two scalar-prefetch tables say which tile a grid
step works on), so a causal call holds no grid step for a tile above the
diagonal. With the derived 512 x 512 tiles the skip engages from S = 1024
on (3 of 4 tiles visited there, 10 of 16 at S = 2048, 136 of 256 at S =
8192); at S <= 512 one tile holds the triangle and is faster than three
smaller ones (PERF.md, PR 26).
The backward pass recomputes block scores from the saved logsumexp (the
flash recurrence) in one kernel, ``flash_dkv``: dk/dv accumulate over a K
tile's Q tiles, on transposed (BK, BQ) scores so that nothing has to be
transposed, and dq from the same ds into a float32 scratch that holds a
whole Q head, so a tile's scores, probabilities, masks and dP are made
once. Where that scratch passes a budget (long sequences,
:func:`fused_bwd_fits`) a second kernel, ``flash_dq``, accumulates dq over
a Q tile's K tiles instead, and remakes them. The revisited output blocks
stay resident in VMEM across the accumulation.

Kernels run compiled on TPU devices and in Pallas interpret mode elsewhere
(ops/pallas.py decides, at lowering time), so the CPU test mesh exercises
the same code path (SURVEY.md §4).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributeddeeplearning_tpu import compat
from distributeddeeplearning_tpu.ops.masks import (block_band_mask,
                                                   block_causal_mask)
from distributeddeeplearning_tpu.ops.pallas import pallas_call

_NEG = -1e30

# ``checkpoint_name`` of what the forward kernel hands its backward rule, in
# the kernel's own layout ((B*H, S, D) and (B*H, S)), for a recomputed block
# to keep: ``jax.checkpoint_policies.save_only_these_names(FLASH_OUT,
# FLASH_LSE)`` takes the second ``flash_fwd`` out of such a block's backward
# pass. A policy that lists neither keeps nothing, and without remat the
# names lower to nothing.
FLASH_OUT = "flash_attention_out"
FLASH_LSE = "flash_attention_lse"


_PAD_GRANULE = 128  # TPU lane width; also the floor _block can return after
#                     flash_attention pads S to a multiple of it.


def _padded_len(s: int) -> int:
    """The sequence length the kernels run for a call of length ``s``: up to
    the next multiple of the lane width once ``s`` is past one granule."""
    return s + (-s % _PAD_GRANULE if s > _PAD_GRANULE else 0)


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
# The plan: which (Q tile, K tile) pairs the three kernels visit, and in
# which order. Everything here is static (numpy at trace time).
# ---------------------------------------------------------------------------

# Tile sizes when the caller names none, by what the call can see. Causal:
# square tiles, so that some lie wholly above the diagonal and are never
# visited; as large as measured fastest, because a tile pays for its rows
# (the forward's cross-lane row maximum, a grid step) whatever its width: at
# S = 1024, 512 x 512 visits 75 % of the scores and beat 256 x 256 (62.5 %)
# by a third. Not causal: there is no triangle, and a whole-row K tile makes
# the fewest grid steps. Measured on the v5e at the shapes the models run
# (PERF.md, PR 26).
_CAUSAL_TILES = (512, 512)
_FULL_TILES = (512, 1024)


class TilePlan(NamedTuple):
    """What the kernels do at one shape: the tile sizes, the tiles of the
    (S, S) score rectangle, how many of them the grids hold (the grids hold
    nothing else: ``visited`` is grid steps per batch·head), and how many of
    those the causal diagonal crosses (part of their scores is masked: the
    work this tiling still does beyond the triangle). A call with a
    ``window`` (``query - key < window``) visits only the tiles that meet
    the band; ``edge`` counts those the window's far edge crosses."""
    bq: int
    bk: int
    total: int
    visited: int
    diagonal: int
    window: Optional[int] = None
    edge: int = 0


def _needed(i, j, bq: int, bk: int, window: Optional[int] = None):
    """Tile (i, j) holds a pair with key <= query: its first key column is
    not past its last query row. Under a window it must also hold a pair
    with ``query - key < window``: its first query row less its last key
    column is inside the window."""
    need = j * bk <= (i + 1) * bq - 1
    if window is not None:
        need = need & (i * bq - ((j + 1) * bk - 1) < window)
    return need


def _crosses(i, j, bq: int, bk: int):
    """Tile (i, j) holds a pair with key > query: the diagonal crosses a
    visited tile unless it lies strictly under it, ``(j+1)*bk - 1 <=
    i*bq``."""
    return (j + 1) * bk - 1 > i * bq


def _crosses_edge(i, j, bq: int, bk: int, window: int):
    """Tile (i, j) holds a pair with ``query - key >= window``: its last
    query row less its first key column reaches the window's far edge."""
    return (i + 1) * bq - 1 - j * bk >= window


def _tiles(s: int, bq: int, bk: int, causal: bool,
           window: Optional[int] = None):
    """(qi, kj): tile indices of every tile the kernels visit, Q-major (K
    innermost). The grids and the plan's counts are both made from this."""
    qi, kj = np.meshgrid(np.arange(s // bq, dtype=np.int32),
                         np.arange(s // bk, dtype=np.int32), indexing="ij")
    keep = (_needed(qi, kj, bq, bk, window) if causal
            else np.ones_like(qi, dtype=bool))
    return qi[keep], kj[keep]


def tile_plan(s: int, causal: bool, block_q: Optional[int] = None,
              block_k: Optional[int] = None, *,
              window: Optional[int] = None) -> TilePlan:
    """The plan for a call of sequence length ``s`` (padded as the call pads
    it). ``block_q`` / ``block_k`` override the derived tile sizes (the
    largest divisor of the padded ``s`` not above them is taken, as before).
    ``window`` (causal calls only) keeps the tiles that meet the band ``0 <=
    query - key < window``; one that is no shorter than the sequence cuts
    nothing off and plans as no window. Head size and the number of K/V heads
    do not enter: measured on the chip are 64 at S = 1024 and 128 at
    S = 8192 (PERF.md)."""
    if window is not None and not causal:
        raise ValueError("flash attention's window is the causal band "
                         "0 <= query - key < window; it needs causal=True")
    if window is not None and window < 1:
        raise ValueError(f"window must be at least 1, got {window}")
    s = _padded_len(s)
    if window is not None and window >= s:
        window = None
    tq, tk = _CAUSAL_TILES if causal else _FULL_TILES
    bq, bk = _block(s, block_q or tq), _block(s, block_k or tk)
    qi, kj = _tiles(s, bq, bk, causal, window)
    return TilePlan(
        bq, bk, (s // bq) * (s // bk), int(qi.size),
        int(_crosses(qi, kj, bq, bk).sum()) if causal else 0, window,
        0 if window is None
        else int(_crosses_edge(qi, kj, bq, bk, window).sum()))


def _schedule(s: int, plan: TilePlan, causal: bool, k_major: bool = False,
              groups: int = 1, head_major: bool = False):
    """The two scalar-prefetch tables of a kernel: grid step t works on tile
    (qi[t], kj[t]). Q-major for forward and dQ (a Q tile's K tiles follow
    each other, so its accumulators stay in scratch); K-major for dK/dV.

    ``groups`` > 1 (dK/dV under grouped K/V heads, whose grid rows are K/V
    heads): a K tile's run holds its Q tiles once for each of the ``groups``
    Q heads that read it, head after head, and the first table's entry is
    ``g * (s // bq) + qi``, which the index maps and the kernel split
    again. ``head_major`` (the fused backward): the Q heads of a group
    follow each other, each with all of its K tiles' runs, so that one Q
    head's dQ is complete before the next one's begins; with one head a row
    it is the K-major order."""
    qi, kj = _tiles(s, plan.bq, plan.bk, causal, plan.window)
    if groups > 1:
        head = np.repeat(np.arange(groups, dtype=np.int32), qi.size)
        qi = np.tile(qi, groups) + head * (s // plan.bq)
        kj = np.tile(kj, groups)
    if head_major:
        order = np.lexsort((qi, kj, qi // (s // plan.bq)))
        qi, kj = qi[order], kj[order]
    elif k_major:
        order = np.lexsort((qi, kj))  # K tile, then (head, then) Q tile
        qi, kj = qi[order], kj[order]
    return jnp.asarray(qi), jnp.asarray(kj)


def _run_edges(row_ref, t, per: int = 1):
    """(first, last): does grid step ``t`` open / close its run of equal
    entries of ``row_ref``, the table of the tile index that stays put while
    the kernel accumulates (qi for forward and dQ, kj for dK/dV)? Read from
    the table itself, so the kernels hold no second copy of the schedule.
    ``per`` > 1: runs of equal ``entry // per`` (the fused backward's Q head,
    from its table of ``g * nq + qi``)."""
    n = pl.num_programs(1)

    def at(u):
        return row_ref[u] // per if per > 1 else row_ref[u]

    cur = at(t)
    first = jnp.logical_or(t == 0, at(jnp.maximum(t - 1, 0)) != cur)
    last = jnp.logical_or(t == n - 1, at(jnp.minimum(t + 1, n - 1)) != cur)
    return first, last


def _block_keep(seed_ref, pid, i, j, bq: int, bk: int, rate: float,
                transposed: bool = False):
    """The (BQ, BK) keep-mask for block (i, j) of grid row ``pid``
    (= pl.program_id(0), hoisted to the kernel top level — program_id may
    not be bound under a pl.when body), in GLOBAL coordinates — the same
    mask regardless of which kernel (forward, dq, dk/dv) or block geometry
    asks for it. seed_ref (SMEM): [seed, b_start, h_start, h_local,
    h_total] — the last four place this shard's (batch, head) range in the
    global index space so the realized mask is sharding-invariant
    (dense == flash at any dp x tp).

    The coordinates go in as one column of rows and one row of columns:
    keep_mask's coordinate multiplies then run once a row and once a column,
    and only the xor that joins them, and the finalizer, run per element.
    ``transposed`` gives the same mask as (BK, BQ), keys down the rows, for
    the dK/dV kernel: the query coordinates are then the row vector."""
    from distributeddeeplearning_tpu.ops.hash_dropout import keep_mask

    h_n = seed_ref[3]
    bh = ((seed_ref[1] + pid // h_n) * seed_ref[4]
          + seed_ref[2] + pid % h_n)
    q_shape, k_shape = ((1, bq), (bk, 1)) if transposed else ((bq, 1), (1, bk))
    rows = (jax.lax.broadcasted_iota(jnp.uint32, q_shape, int(transposed))
            + (i * bq).astype(jnp.uint32))
    cols = (jax.lax.broadcasted_iota(jnp.uint32, k_shape, 1 - transposed)
            + (j * bk).astype(jnp.uint32))
    return keep_mask(seed_ref[0], jnp.uint32(0) + bh.astype(jnp.uint32),
                     rows, cols, rate)


def _causal_t(i, j, bq: int, bk: int):
    """ops/masks.py::block_causal_mask of tile (i, j), transposed: (BK, BQ)
    bool, key position (down the rows) <= query position (along the
    lanes). A test holds the two equal."""
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0)
    qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
    return kpos <= qpos


def _band_t(i, j, bq: int, bk: int, window: int):
    """ops/masks.py::block_band_mask of tile (i, j), transposed: (BK, BQ).
    A test holds the two equal."""
    dist = (i * bq - j * bk
            + jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1)
            - jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0))
    return dist.astype(jnp.uint32) < jnp.uint32(window)


def _valid(mask_ref, causal: bool, i, j, bq: int, bk: int,
           transposed: bool = False, window: Optional[int] = None):
    """Which scores of tile (i, j) count: the key-padding mask, one vector
    over the keys that broadcasts, and the causal triangle. (A second tile
    body without the triangle for tiles wholly under the diagonal measured
    no faster at S = 1024 and 1.3 % faster at S = 8192: PERF.md, PR 26.)
    Under a ``window`` the triangle is the band of ops/masks.py, at the
    triangle's price. ``transposed``: as (BK, BQ), keys down the rows."""
    if window is not None:
        band = (_band_t if transposed else block_band_mask)(i, j, bq, bk,
                                                            window)
        pad = mask_ref[0, 0][:, None] if transposed else mask_ref[0]
        return (pad != 0) & band
    if transposed:
        # the relayout to a column wants the int32s, not the compared bools
        valid = mask_ref[0, 0][:, None] != 0
        return valid & _causal_t(i, j, bq, bk) if causal else valid
    valid = mask_ref[0] != 0
    return valid & block_causal_mask(i, j, bq, bk) if causal else valid


# ---------------------------------------------------------------------------
# Forward: grid (B*H, visited tiles); m/l/acc scratch carries across a Q
# tile's K tiles.
# ---------------------------------------------------------------------------

def _l_lanes(bk: int) -> int:
    """Width of the forward's running denominator: a K tile of whole lane
    groups keeps one partial sum a lane (see _lane_sums), any other one sum
    a row."""
    return _PAD_GRANULE if bk % _PAD_GRANULE == 0 else 1


def _lane_sums(p, lanes: int):
    """Row sums of ``p`` (BQ, BK) left as ``lanes`` partial sums a row: the
    BK/128 lane groups added elementwise, and the sum across the lanes left
    to the kernel's close, once a Q tile; a cross-lane reduction a row on
    every tile was most of what a tile cost beyond its scores."""
    if lanes == 1:
        return p.sum(axis=-1, keepdims=True)
    return sum(p[:, c:c + lanes] for c in range(0, p.shape[1], lanes))


def _fwd_kernel(qi_ref, kj_ref, seed_ref, q_ref, k_ref, v_ref, mask_ref,
                o_ref, lse_ref, m_scr, l_scr, acc_scr, *, scale: float,
                causal: bool, dropout_rate: float,
                window: Optional[int] = None):
    pid0, t = pl.program_id(0), pl.program_id(1)
    i, j = qi_ref[t], kj_ref[t]
    first, last = _run_edges(qi_ref, t)
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(first)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Matmul operands stay in their storage dtype (bf16 on the training
    # path): the MXU takes bf16 inputs at full rate with f32 accumulation via
    # preferred_element_type — upcasting first would halve MXU throughput and
    # double VMEM traffic for zero precision gain.
    q = q_ref[0]                                          # (BQ, D)
    k = k_ref[0]                                          # (BK, D)
    v = v_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale       # (BQ, BK) f32
    s = jnp.where(_valid(mask_ref, causal, i, j, bq, bk, window=window), s,
                  _NEG)
    m_prev = m_scr[:]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    # A masked score is _NEG, and exp(_NEG - m) is exactly 0 under any real
    # maximum m. Only a row that has seen no valid key yet (m_new still _NEG)
    # would read exp(0) = 1 there: take 0 for its maximum, one select a row
    # and none over the tile.
    p = jnp.exp(s - jnp.where(m_new > _NEG, m_new, 0.0))
    corr = jnp.exp(m_prev - m_new)
    m_scr[:] = m_new
    # l accumulates UNdropped p: dense semantics normalize first (softmax),
    # then drop — o = (softmax ∘ keep/(1-r)) v.
    l_scr[:] = l_scr[:] * corr + _lane_sums(p, l_scr.shape[1])
    if dropout_rate > 0.0:
        keep = _block_keep(seed_ref, pid0, i, j, bq, bk, dropout_rate)
        p = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
    acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(last)
    def _():
        l = l_scr[:].sum(axis=-1, keepdims=True)
        safe_l = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        # Fully-masked rows: zero output, lse pinned to 0 so backward's
        # exp(_NEG - 0) underflows to 0 rather than NaN.
        lse_ref[0, 0] = jnp.where(
            l[:, 0] > 0, m_scr[:][:, 0] + jnp.log(safe_l[:, 0]), 0.0)


def _window_kw(plan: TilePlan) -> dict:
    """The kernels' ``window`` argument, named only where the plan has one:
    a call without a window builds the kernels as before."""
    return {} if plan.window is None else {"window": plan.window}


def _grid_spec(bh: int, tables, in_specs, out_specs, scratch_shapes):
    """Grid (B*H, visited tiles) with the schedule tables and the dropout
    seed as scalar-prefetch operands: index maps and kernels read the tile
    of grid step t from them."""
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(bh, int(tables[0].shape[0])),
        in_specs=in_specs, out_specs=out_specs,
        scratch_shapes=scratch_shapes)


_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def _tile_specs(bq: int, bk: int, d: int, groups: int = 1,
                kv_rows: bool = False, nq: int = 0,
                dv: Optional[int] = None):
    """Block specs by what a block follows: a Q tile and its per-row
    vectors follow qi[t], a K tile and the key mask follow kj[t]. Returns
    (q_tile, k_tile, vec_q, vec_k, o_tile, v_tile): Q, dQ, K and dK are
    ``d`` wide, and V, dV, O and dO ``dv`` wide (``d`` where none is given:
    the two pairs of specs are then the same).

    ``groups`` > 1, grouped K/V heads (Q rows are B*H, K/V rows B*H/groups,
    and Q row r reads K/V row r // groups): no K or V is repeated in memory,
    the index maps send each grid row to the rows it reads. Forward and dQ
    walk the Q rows; dK/dV walks the K/V rows (``kv_rows``) and finds the Q
    row in the schedule's entry ``g * nq + qi`` (see :func:`_schedule`). The
    key mask has a row a Q head, alike within a group."""
    # Rank-1-per-tile operands (mask, lse) ride as (BH, 1, S) so every block
    # shape is rank >= 2 with a compiled-lowering-legal tail: Mosaic requires
    # the last two block dims be (multiples of, or equal to) the array dims —
    # a (1, BK) block over a (BH, S) array is not (VERDICT r1 #6, found on
    # first real-TPU run).
    # (row, tile) of the Q-side blocks, and the rows of K/V and of the mask,
    # for grid row b at step t. Equal heads: all are b and qi[t], as before.
    if groups > 1 and kv_rows:
        def q_at(b, t, qi):
            return b * groups + qi[t] // nq, qi[t] % nq

        def k_row(b):
            return b

        def mask_row(b):
            return b * groups
    else:
        def q_at(b, t, qi):
            return b, qi[t]

        def k_row(b):
            return b // groups if groups > 1 else b

        mask_row = k_row if groups == 1 else (lambda b: b)

    def q_index(b, t, qi, kj, _):
        row, tile = q_at(b, t, qi)
        return row, tile, 0

    def vec_q_index(b, t, qi, kj, _):
        row, tile = q_at(b, t, qi)
        return row, 0, tile

    def k_index(b, t, qi, kj, _):
        return k_row(b), kj[t], 0

    dv = d if dv is None else dv
    q_tile = pl.BlockSpec((1, bq, d), q_index)
    k_tile = pl.BlockSpec((1, bk, d), k_index)
    vec_q = pl.BlockSpec((1, 1, bq), vec_q_index)
    vec_k = pl.BlockSpec((1, 1, bk),
                         lambda b, t, qi, kj, _: (mask_row(b), 0, kj[t]))
    return (q_tile, k_tile, vec_q, vec_k, pl.BlockSpec((1, bq, dv), q_index),
            pl.BlockSpec((1, bk, dv), k_index))


def _fwd(q, k, v, mask, seed, *, scale, plan, causal, dropout_rate):
    bh, s, d = q.shape
    dv = v.shape[2]
    bq, bk = plan.bq, plan.bk
    tables = _schedule(s, plan, causal)
    q_tile, k_tile, vec_q, vec_k, o_tile, v_tile = _tile_specs(
        bq, bk, d, bh // k.shape[0], dv=dv)
    out, lse = pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          dropout_rate=dropout_rate, **_window_kw(plan)),
        name="flash_fwd",
        grid_spec=_grid_spec(
            bh, tables, [q_tile, k_tile, v_tile, vec_k], [o_tile, vec_q],
            [pltpu.VMEM((bq, 1), jnp.float32),
             pltpu.VMEM((bq, _l_lanes(bk)), jnp.float32),
             pltpu.VMEM((bq, dv), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        compiler_params=_PARAMS,
    )(*tables, seed, q, k, v, mask[:, None, :])
    return out, lse.reshape(bh, s)


# ---------------------------------------------------------------------------
# Backward: dq accumulates over a Q tile's K tiles; dk/dv over a K tile's Q
# tiles. Scores are recomputed from the saved lse (flash recurrence).
# ---------------------------------------------------------------------------

def _dq_kernel(qi_ref, kj_ref, seed_ref, q_ref, k_ref, v_ref, mask_ref,
               do_ref, lse_ref, delta_ref, dq_ref, dq_scr, *, scale: float,
               causal: bool, dropout_rate: float,
               window: Optional[int] = None):
    pid0, t = pl.program_id(0), pl.program_id(1)
    i, j = qi_ref[t], kj_ref[t]
    first, last = _run_edges(qi_ref, t)
    bq, bk = q_ref.shape[1], k_ref.shape[1]

    @pl.when(first)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, 0][:, None]
    delta = delta_ref[0, 0][:, None]
    k = k_ref[0]
    v = v_ref[0]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    s = jnp.where(_valid(mask_ref, causal, i, j, bq, bk, window=window), s,
                  _NEG)
    p = jnp.exp(s - lse)                              # (BQ, BK)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if dropout_rate > 0.0:
        # Regenerate the forward's exact mask. delta = sum(do*o) already IS
        # sum_k p*m*dp (o carries the dropped probs), so the flash delta
        # trick needs no dropout correction — only dp does:
        # ds = p * (m*dp - delta).
        keep = _block_keep(seed_ref, pid0, i, j, bq, bk, dropout_rate)
        dp = jnp.where(keep, dp * (1.0 / (1.0 - dropout_rate)), 0.0)
    ds = (p * (dp - delta) * scale).astype(k.dtype)
    dq_scr[:] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(last)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(qi_ref, kj_ref, seed_ref, q_ref, k_ref, v_ref, mask_ref,
                do_ref, lse_ref, delta_ref, *refs, scale: float,
                causal: bool, dropout_rate: float,
                window: Optional[int] = None, groups: int = 1, nq: int = 0,
                with_dq: bool = False):
    """dK and dV, accumulated along a K tile's run; ``with_dq`` (the fused
    backward, :func:`_bwd_fused`): dQ too, from the same ``ds``, into a
    float32 scratch that holds the whole current Q head."""
    if with_dq:
        dq_ref, dk_ref, dv_ref, dk_scr, dv_scr, kt_scr, dq_scr = refs
    else:
        dk_ref, dv_ref, dk_scr, dv_scr = refs
    pid0, t = pl.program_id(0), pl.program_id(1)
    i, j = qi_ref[t], kj_ref[t]          # K-major: j stays, i accumulates
    if groups > 1:
        # Grouped K/V heads: this grid row is a K/V head and the table's
        # entry names the Q head of its group beside the Q tile (_schedule);
        # the dropout hash wants the Q head's row of the (batch, head) space.
        pid0, i = pid0 * groups + i // nq, i % nq
    first, last = _run_edges(kj_ref, t)
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    # Fused under grouped heads the schedule is head-major (_schedule): a K
    # tile comes back once a Q head, so dK and dV are held for the whole row.
    whole_row = with_dq and groups > 1
    if whole_row:
        n = pl.num_programs(1)
        kv_first, kv_last = t == 0, t == n - 1
        rows = pl.ds(pl.multiple_of(j * bk, bk), bk)
    else:
        kv_first, kv_last, rows = first, last, slice(None)

    @pl.when(kv_first)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    # Everything here is (BK, BQ): keys down the rows, queries along the
    # lanes. K and V are then left operands of plain products, dV and dK
    # contract over the lanes of p and ds, and lse / delta are the rows they
    # are stored as: nothing is transposed, neither operands nor results nor
    # the per-query vectors.
    k = k_ref[0]                                      # (BK, D)
    v = v_ref[0]
    q = q_ref[0]                                      # (BQ, D)
    do = do_ref[0]
    s = jax.lax.dot_general(
        k, q, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    s = jnp.where(_valid(mask_ref, causal, i, j, bq, bk, transposed=True,
                         window=window), s, _NEG)
    p = jnp.exp(s - lse_ref[0])                       # (BK, BQ)
    if dropout_rate > 0.0:
        # (i, j) are the same logical (Q-tile, K-tile) indices the forward
        # used (the schedule swaps their nesting, not their meaning), so
        # this regenerates the forward's exact mask.
        keep = _block_keep(seed_ref, pid0, i, j, bq, bk, dropout_rate,
                           transposed=True)
        inv_keep = 1.0 / (1.0 - dropout_rate)
        p_drop = jnp.where(keep, p * inv_keep, 0.0)
    else:
        keep, p_drop = None, p
    dv_scr[rows] += jax.lax.dot_general(
        p_drop.astype(do.dtype), do, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(
        v, do, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if keep is not None:
        dp = jnp.where(keep, dp * inv_keep, 0.0)
    ds = (p * (dp - delta_ref[0]) * scale).astype(q.dtype)
    dk_scr[rows] += jax.lax.dot_general(
        ds, q, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kv_last)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    if not with_dq:
        return
    # dQ as its transpose, (D, BQ) a Q tile: K's tile is transposed once a
    # run, and dQᵀ += Kᵀ·ds is then a plain product on the (BK, BQ) ds that
    # is already here. A Q tile's terms come in ascending K tile, as in
    # _dq_kernel.
    q_first, q_last = _run_edges(qi_ref, t, per=nq)

    @pl.when(first)
    def _():
        kt_scr[:] = k.T

    @pl.when(q_first)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    dq_scr[i] += jax.lax.dot_general(
        kt_scr[:], ds, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(q_last)
    def _():
        for c in range(dq_scr.shape[0]):
            dq_ref[0, c * bq:(c + 1) * bq, :] = dq_scr[c].T.astype(
                dq_ref.dtype)


# The fused backward holds dQ of a whole Q head in float32 VMEM, and under
# grouped K/V heads dK and dV of a whole K/V head beside it. Up to this many
# bytes of such accumulators it runs; past them (long sequences) the two
# kernels do, whose scratch is a tile's. 16 MiB holds every shape the models
# train at: trinity_mini's 8192 tokens on 32/4 heads of 128 take 12.
_FUSED_BWD_BYTES = 16 * 2 ** 20
_FUSED_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=64 * 2 ** 20)


def fused_bwd_bytes(s: int, d: int, dv: int, groups: int) -> int:
    """float32 accumulators the fused backward holds for a whole grid row at
    sequence length ``s`` (padded), keys ``d`` and values ``dv`` wide,
    ``groups`` Q heads a K/V head: dQ of one Q head, and under grouped heads
    dK and dV of the K/V head."""
    return 4 * s * (d + (d + dv if groups > 1 else 0))


def fused_bwd_fits(s: int, d: int, dv: int, groups: int) -> bool:
    """Does the backward pass run as one kernel at these shapes?"""
    return fused_bwd_bytes(s, d, dv, groups) <= _FUSED_BWD_BYTES


def _bwd(scale, plan, causal, dropout_rate, residuals, g):
    q, k, v, mask, seed, out, lse = residuals
    bh, s, d = q.shape
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    # (BH, 1, S) lift for the rank-1-per-tile operands — see _tile_specs.
    mask3, lse3, delta3 = (x[:, None, :] for x in (mask, lse, delta))

    bq, bk = plan.bq, plan.bk
    groups = bh // k.shape[0]
    width_v = v.shape[2]
    operands = (seed, q, k, v, mask3, g, lse3, delta3)
    if fused_bwd_fits(s, d, width_v, groups):
        dq, dk, dv = _bwd_fused(scale, plan, causal, dropout_rate, operands)
        return dq, dk, dv, None, None

    def specs(**kv_rows):
        """(the seven operands' specs, the Q, K and V tiles' own)"""
        q_tile, k_tile, vec_q, vec_k, o_tile, v_tile = _tile_specs(
            bq, bk, d, groups, dv=width_v, **kv_rows)
        return ([q_tile, k_tile, v_tile, vec_k, o_tile, vec_q, vec_q],
                q_tile, k_tile, v_tile)

    in_specs, q_tile, k_tile, v_tile = specs()

    tables = _schedule(s, plan, causal)
    dq = pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          dropout_rate=dropout_rate, **_window_kw(plan)),
        name="flash_dq",
        grid_spec=_grid_spec(bh, tables, in_specs, [q_tile],
                             [pltpu.VMEM((bq, d), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), q.dtype)],
        compiler_params=_PARAMS,
    )(*tables, *operands)[0]

    # dk/dv: K tiles are the revisited outputs and Q the accumulation axis,
    # so the same tiles are walked K-major. Under grouped K/V heads the grid
    # rows are the K/V heads, and a K tile's run sums over the Q tiles of
    # every Q head of its group.
    kw = _window_kw(plan)
    if groups > 1:
        kw.update(groups=groups, nq=s // bq)
        in_specs, _, k_tile, v_tile = specs(kv_rows=True, nq=s // bq)
    tables = _schedule(s, plan, causal, k_major=True, groups=groups)
    dk, dv = pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          dropout_rate=dropout_rate, **kw),
        name="flash_dkv",
        grid_spec=_grid_spec(k.shape[0], tables, in_specs, [k_tile, v_tile],
                             [pltpu.VMEM((bk, d), jnp.float32),
                              pltpu.VMEM((bk, width_v), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=_PARAMS,
    )(*tables, *operands)
    return dq, dk, dv, None, None


def _bwd_fused(scale, plan, causal, dropout_rate, operands):
    """dQ, dK and dV in one K-major kernel (``flash_dkv``): the scores,
    probabilities, masks and dP of a tile are made once. The grid rows are
    the K/V heads; a row walks its Q heads one after the other (head-major,
    :func:`_schedule`), and a Q head's dQ stays in VMEM until its last tile.
    dK and dV stay a K tile's under equal heads, and the row's under
    grouped ones (a K tile comes back once a Q head)."""
    _, q, k, v = operands[:4]
    bh, s, d = q.shape
    bkv, width_v = k.shape[0], v.shape[2]
    groups, nq = bh // bkv, s // plan.bq
    bq, bk = plan.bq, plan.bk
    q_tile, k_tile, vec_q, vec_k, o_tile, v_tile = _tile_specs(
        bq, bk, d, groups, dv=width_v, kv_rows=True, nq=nq)
    dq_row = pl.BlockSpec(
        (1, s, d), lambda b, t, qi, kj, _: (b * groups + qi[t] // nq, 0, 0))
    if groups > 1:
        dk_out, dv_out = (pl.BlockSpec((1, s, w), lambda b, t, qi, kj, _:
                                       (b, 0, 0)) for w in (d, width_v))
        kv_rows = s
    else:
        dk_out, dv_out, kv_rows = k_tile, v_tile, bk
    tables = _schedule(s, plan, causal, groups=groups, head_major=True)
    return pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          dropout_rate=dropout_rate, groups=groups, nq=nq,
                          with_dq=True, **_window_kw(plan)),
        name="flash_dkv",
        grid_spec=_grid_spec(
            bkv, tables,
            [q_tile, k_tile, v_tile, vec_k, o_tile, vec_q, vec_q],
            [dq_row, dk_out, dv_out],
            [pltpu.VMEM((kv_rows, d), jnp.float32),
             pltpu.VMEM((kv_rows, width_v), jnp.float32),
             pltpu.VMEM((d, bk), k.dtype),
             pltpu.VMEM((nq, d, bq), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=_FUSED_PARAMS,
    )(*tables, *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash(q, k, v, mask, seed, scale, plan, causal, dropout_rate):
    out, _ = _fwd(q, k, v, mask, seed, scale=scale, plan=plan,
                  causal=causal, dropout_rate=dropout_rate)
    return out


def _flash_fwd(q, k, v, mask, seed, scale, plan, causal, dropout_rate):
    out, lse = _fwd(q, k, v, mask, seed, scale=scale, plan=plan,
                    causal=causal, dropout_rate=dropout_rate)
    out = checkpoint_name(out, FLASH_OUT)
    lse = checkpoint_name(lse, FLASH_LSE)
    return out, (q, k, v, mask, seed, out, lse)


_flash.defvjp(_flash_fwd, _bwd)


def flash_attention(q, k, v, kv_mask=None, *,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None, causal: bool = False,
                    dropout_rate: float = 0.0, dropout_seed=None,
                    bh_offsets=None, window: Optional[int] = None,
                    scale: Optional[float] = None):
    """Fused attention with a key-padding mask; ``causal=True`` adds the
    autoregressive lower-triangular mask (tiles above the diagonal are not
    in the grid), and ``window`` cuts it to the band ``query - key <
    window`` (tiles wholly outside the band are not in the grid either).
    ``block_q`` / ``block_k`` override the tile sizes that :func:`tile_plan`
    derives from (S, causal); leave them out.

    q: (B, S, H, D), k: (B, S, Hkv, D), v: (B, S, Hkv, Dv) — the models'
    layout, H a multiple of Hkv: Q head h reads K/V head ``h // (H // Hkv)``
    through the kernels' index maps, and dK/dV sum over the Q heads of a
    group inside the kernel. The values may be of another width than the
    queries and keys (latent attention: 192 and 128): the V tiles, the
    accumulator, the result and dV are then ``Dv`` wide, dQ and dK ``D``,
    and the scale is the query width's, ``D ** -0.5``, or the caller's
    ``scale`` (the three kernels multiply the float32 scores by it).
    kv_mask: (B, S) (True/nonzero = attend), or None for all-valid. Returns
    (B, S, H, Dv) in q.dtype. Differentiable w.r.t. q/k/v via the flash
    backward kernels.

    ``dropout_rate`` > 0 applies attention-probability dropout INSIDE the
    kernels via a counter-based hash mask (ops/hash_dropout.py) that the
    backward kernels regenerate exactly — no (S, S) mask ever exists.
    ``dropout_seed``: int32 scalar (required when rate > 0). ``bh_offsets``:
    optional (b_start, h_start, h_total) placing this shard's batch/head
    range in global coordinates so the realized mask is sharding-invariant;
    defaults to the unsharded identity.
    """
    b, s, h, d = q.shape
    if k.shape[:3] != v.shape[:3] or h % k.shape[2] or k.shape[3] != d:
        raise ValueError(f"flash_attention: q {q.shape} cannot share k "
                         f"{k.shape} / v {v.shape}; the K/V heads must "
                         f"divide the Q heads, and the keys be as wide as "
                         f"the queries")
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
    if _padded_len(s) != s:
        pad = _padded_len(s) - s
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for x in (q, k, v))
        kv_mask = jnp.pad(kv_mask.astype(jnp.int32), ((0, 0), (0, pad)))
        s += pad
    kv_mask = jnp.broadcast_to(
        kv_mask.astype(jnp.int32)[:, None, :], (b, h, s)).reshape(b * h, s)

    def to_bh(x):  # (B, S, H, D) -> (B*H, S, D), by x's own heads and width
        return x.transpose(0, 2, 1, 3).reshape(b * x.shape[2], s, x.shape[3])

    out = _flash(to_bh(q), to_bh(k), to_bh(v), kv_mask, seed,
                 d ** -0.5 if scale is None else float(scale),
                 tile_plan(s, causal, block_q, block_k, window=window),
                 causal, float(dropout_rate))
    return out.reshape(b, h, s, v.shape[3]).transpose(0, 2, 1, 3)[:, :s_orig]


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
