"""The chunked delta rule (ops/kda.py, module text) as two Pallas kernels:
:func:`forward` runs ``kda_fwd``, and :func:`backward`, its backward rule,
``kda_bwd``. A grid step is one chunk of eight heads (:func:`_heads_a_step`),
taken together as arrays (heads, C, d): every line below is eight
independent chains, which is what keeps the units busy (on a v5e four heads
a step take 1.17 times as long a head, sixteen 0.98: PERF.md section 6). The
chunks are the grid's inner axis and run in order, first to last forward and
last to first backward, and the eight heads' state (forward) or its gradient
(backward) stays in a VMEM scratch from one chunk to the next. A step first
makes what no state enters (:func:`stateless`), then the chunk's four
products with the state: ``U = T V - (T (K exp G)) S``, ``O = (Q exp G) S +
B U``, ``S' = Diag(exp G_C) S + (K exp(G_C - G))^T U``. The state is held
transposed, (d_v, d_k), so that its decay by ``exp G_C`` is a product with a
row. HBM sees the operands, the result, and the state that enters each chunk
(written forward, read backward); everything else is made and dropped in
VMEM.

**Backward**, a step: the chunk's stateless values and ``U`` remade from the
operands and the entering state, the four products' rules (``dU = B^T dO +
K_out dS'``, ``dS = Diag(exp G_C) dS' + Q_in^T dO - W^T dU`` and the
operands' own), and those cotangents handed to :func:`stateless_bwd`.

**What no state enters**, a chunk-head (C tokens, d channels), float32
throughout:

- ``G = tril(1) g``, exactly (:func:`_sums`).
- The scores level by level (:func:`_levels`): a block of ``2 m`` tokens
  gives its lower left (m, m) quarter as one product of the two factors
  round the cumulative gate ``r`` at its row ``m`` (``exp(G_i - r) exp(r -
  G_j)``, both at most 1; A's rows and B's in one product), and its two
  diagonal quarters to the next level, ``m`` = C / 2 down to 4. The blocks
  of 4 tokens left on the diagonal are summed pair by pair, a column ``j``
  of every block at a time: ``exp(G_i - G_j)`` of the rows below ``j`` is
  evaluated once and serves ``A`` (k with k) and ``B`` (q with k) both. (Why
  so narrow: a pair's sum over the channels is a reduce across a register's
  lanes, 3.3 ns a register on a v5e whatever else runs, where a level's
  product costs a fraction of that a pair: with blocks of 16 walked so the
  kernels took 5.4 and 9.6 ms a layer of the kimi cell where they take 3.3
  and 6.4, PERF.md section 6, PR 35.)
- ``T = (I + Diag(beta) A)^-1 Diag(beta)`` by :func:`unit_lower_inverse`'s
  finite series, then ``T (K exp G)``, ``T V``, ``Q exp G``, ``K exp(G_C -
  G)`` and ``exp G_C``, rounded to the compute type where they leave.

Its **backward rule** reuses ``G``, the factors, the pair decays, ``A`` and
``T`` as they were made, then applies each piece's own rule: the inverse's ``dL = -X^T dX X^T``;
the scores' ``dx_i = sum_j dP_ij y_j E_ij``, ``dy_j = sum_i dP_ij x_i E_ij``
from the same factors and decays, and since every term of a score holds
``exp(G_i - G_j)`` once, ``dG = x dx - y dy`` with no pass of its own
(``B``'s diagonal carries no gate); the decayed operands' terms join the same
``dG``, which goes back through the triangle of ones once.

**Precision.** Every exponent is a difference of cumulative gates inside one
chunk and at most 0. The float32 products of the scores and of the inverse
take three bfloat16 passes (:func:`_mm`: each operand split into a bfloat16
head and tail, ``hi hi + hi lo + lo hi``; Mosaic lowers no
``Precision.HIGH``, so the passes are written out, and a CPU computes the
same three); products that meet q, k and v take their operands in the
compute type and add up in float32; the pairs' sums over the channels are
float32 sums. The state is float32 and meets a product in the compute type,
as ``U`` does; the backward rule's cotangents of ``U`` and of the state are
float32 and meet a product in the compute type.

Block shapes come from the operands (chunks of a power of two of at least 8
tokens); on a CPU the kernels run interpreted (ops/pallas.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributeddeeplearning_tpu.ops.pallas import pallas_call

_F32, _BF16 = jnp.float32, jnp.bfloat16
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary"),
    vmem_limit_bytes=64 * 2 ** 20)


def _dims(kind: str, ndim: int):
    """``lax.dot_general``'s dimension numbers of a matrix product over the
    last two axes, the others batches: "nn" x y, "nt" x y^T, "tn" x^T y."""
    batch = tuple(range(ndim - 2))
    return (((ndim - 1 if kind[0] == "n" else ndim - 2,),
             (ndim - 2 if kind[1] == "n" else ndim - 1,)), (batch, batch))


def _dot(x, y, kind: str = "nn"):
    """A product of operands in the compute type, added up in float32."""
    return jax.lax.dot_general(x, y, _dims(kind, x.ndim),
                               preferred_element_type=_F32)


def _split(x):
    """A float32 array as a bfloat16 head and tail."""
    head = x.astype(_BF16)
    return head, (x - head.astype(_F32)).astype(_BF16)


def _mm(x, y, kind: str = "nn"):
    """A float32 product at three bfloat16 passes, about float32's own
    rounding once the operands are at most 1 in size, as these are."""
    (xh, xl), (yh, yl) = _split(x), _split(y)
    return (_dot(xl, yh, kind) + _dot(xh, yl, kind)) + _dot(xh, yh, kind)


def _iota(shape, axis: int):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _nilpotent_inverse(lower, steps: int):
    """(I + L)^-1 for ``L`` with ``L^(2^steps) = 0``: (I - L)(I + L^2)(I +
    L^4)..., ``steps`` factors."""
    c = lower.shape[-1]
    eye = (_iota((c, c), 0) == _iota((c, c), 1)).astype(_F32)
    inv, power = eye - lower, lower
    for _ in range(steps - 1):
        power = _mm(power, power)
        inv = inv + _mm(inv, power)
    return inv


def unit_lower_inverse(lower, sub: int):
    """(I + L)^-1 of a strictly lower triangular (..., C, C) ``L``: the
    ``sub``-wide diagonal blocks ``I + L_d`` by the finite series ``(I -
    L_d)(I + L_d^2)(I + L_d^4)...`` (``L_d^sub = 0``), then the rest by the
    same series in ``M = (I + L_d)^-1 L_off``, which is nilpotent in blocks
    (``M^(C/sub) = 0``). The diagonal blocks' series runs on the blocks side
    by side, (..., sub, C), against the block-diagonal (C, C) matrix of a
    power: the same products without the rows of zeros, and a power's square
    and the series' next factor in one product."""
    c = lower.shape[-1]
    on_diagonal = _iota((c, c), 0) // sub == _iota((c, c), 1) // sub
    block_of_lane = _iota((sub, c), 1) // sub
    side_by_side = jnp.zeros(lower.shape[:-2] + (sub, c), _F32)
    for a in range(c // sub):
        side_by_side = jnp.where(block_of_lane == a,
                                 lower[..., a * sub:(a + 1) * sub, :],
                                 side_by_side)

    def on_the_diagonal(blocks):
        return jnp.where(on_diagonal,
                         jnp.concatenate([blocks] * (c // sub), -2), 0.0)

    eye = (_iota((sub, c), 0) == _iota((sub, c), 1) % sub).astype(_F32)
    inv = eye - side_by_side
    steps = max(1, (sub - 1).bit_length())
    if steps > 1:
        power = _mm(side_by_side, on_the_diagonal(side_by_side))
        for _ in range(2, steps):
            both = _mm(jnp.concatenate([power, inv], -2),
                       on_the_diagonal(power))
            power, inv = both[..., :sub, :], inv + both[..., sub:, :]
        inv = inv + _mm(inv, on_the_diagonal(power))
    diag_inv = on_the_diagonal(inv)
    if c == sub:
        return diag_inv
    m = _mm(diag_inv, jnp.where(on_diagonal, 0.0, lower))
    return _mm(_nilpotent_inverse(m, max(1, (c // sub - 1).bit_length())),
               diag_inv)


def _triangle(heads: int, c: int, upper: bool = False):
    """tril(1) (heads, C, C), or its transpose: 0 and 1 are bfloat16's."""
    i, j = _iota((heads, c, c), 1), _iota((heads, c, c), 2)
    return (i <= j if upper else i >= j).astype(_BF16)


def _sums(ones, x):
    """``ones @ x`` for a matrix of 0 and 1 and a float32 ``x``, as exact as
    float32 adds: ``x`` in three bfloat16 pieces, which hold all its bits,
    each product exact and added up in float32."""
    head, tail = _split(x)
    rest = (x - head.astype(_F32) - tail.astype(_F32)).astype(_BF16)
    return (_dot(ones, rest) + _dot(ones, tail)) + _dot(ones, head)


# tokens of a block on the diagonal of the scores that are summed pair by
# pair; the wider blocks are halved until they are so narrow
_PAIR = 4
_TILE = 8   # float32's sublanes: a tile of rows


def _levels(c: int):
    """Half-widths of the blocks the scores are halved through, widest
    first: a block of ``2 m`` tokens gives its lower left (m, m) quarter as
    one product of two factors, and its two diagonal quarters to the next
    level."""
    return [m for m in (c >> s for s in range(1, c.bit_length()))
            if m >= _PAIR]


def _by_tile(x):
    """(heads, C, w) -> (heads, C / 8, 8, w)."""
    heads, c, w = x.shape
    return x.reshape(heads, c // _TILE, _TILE, w)


def _whole(x):
    """:func:`_by_tile`'s inverse."""
    heads, tiles, rows, w = x.shape
    return x.reshape(heads, tiles * rows, w)


def _row_of_block(x4, width: int, jj: int):
    """Of rows by tile (heads, tiles, 8, w): every row's copy of row ``jj``
    of its own ``width``-wide block (``width`` divides 8)."""
    row = _iota((1, 1, _TILE, 1), 2)
    out = x4[:, :, jj:jj + 1]
    for first in range(width, _TILE, width):
        out = jnp.where(row >= first, x4[:, :, first + jj:first + jj + 1],
                        out)
    return out


def _factors(cum, m: int):
    """A level's two factors: for the blocks of ``2 m`` tokens, round the
    cumulative gate ``r`` at each block's row ``m``, ``exp(G_i - r)`` for
    the rows ``i`` of its lower half and ``exp(r - G_j)`` for the columns
    ``j`` of its upper half, each 0 on the other half; every exponent is at
    most 0."""
    heads, c, d = cum.shape
    if m >= _TILE:
        ref = jnp.concatenate(
            [jnp.broadcast_to(cum[:, s + m:s + m + 1], (heads, 2 * m, d))
             for s in range(0, c, 2 * m)], 1)
    else:
        ref = _whole(jnp.broadcast_to(
            _row_of_block(_by_tile(cum), 2 * m, m),
            (heads, c // _TILE, _TILE, d)))
    lower = _iota((1, c, 1), 1) % (2 * m) >= m
    f = jnp.exp(jnp.where(lower, cum - ref, ref - cum))
    return jnp.where(lower, f, 0.0), jnp.where(lower, 0.0, f)


def _lower_halves(x, m: int):
    """The rows of the lower halves of the ``2 m``-row blocks, where those
    are whole tiles; else every row."""
    if m < _TILE:
        return x
    return jnp.concatenate([x[:, s + m:s + 2 * m]
                            for s in range(0, x.shape[1], 2 * m)], 1)


def _to_lower_halves(x, m: int, c: int):
    """:func:`_lower_halves`' transpose: its rows back in place, zeros in
    the upper halves."""
    if m < _TILE:
        return x
    zeros = jnp.zeros((x.shape[0], m, x.shape[2]), _F32)
    return jnp.concatenate(
        [piece for s in range(0, c // 2, m)
         for piece in (zeros, x[:, s:s + m])], 1)


def _quarter(c: int, m: int):
    """(1, C, C): the pairs (i, j) a level owns, the lower left quarters of
    its blocks."""
    i, j = _iota((1, c, c), 1), _iota((1, c, c), 2)
    return ((i // (2 * m) == j // (2 * m)) & (i % (2 * m) >= m)
            & (j % (2 * m) < m))


def _pair_walk(c: int):
    """What the pair-by-pair walk shares between its columns: a row's place
    in its ``_PAIR``-wide block (1, 1, 8, 1), and the lane of the block's
    first column (1, tiles, 8, C) beside the lanes themselves."""
    shape = (1, c // _TILE, _TILE, c)
    first = (_iota(shape, 1) * _TILE
             + _iota(shape, 2) // _PAIR * _PAIR)
    return _iota((1, 1, _TILE, 1), 2) % _PAIR, first, _iota(shape, 3)


def _scores(q, k, g, with_b: bool):
    """G, A and (``with_b``) B's part below the diagonal of a step's
    chunk-heads, from q, k in the compute type and g float32, all (heads, C,
    d). Level by level (:func:`_levels`) a product of two factors, then the
    ``_PAIR``-wide blocks that are left on the diagonal pair by pair: a step
    of that walk is one column of every block, ``exp(G_i - G_j)`` of the
    rows below it evaluated once for A and B. Returns (G, k, q float32, A,
    B or None, the levels' factors, the walk's decays a column)."""
    heads, c, d = g.shape
    cum = _sums(_triangle(heads, c), g)
    kf, qf = k.astype(_F32), q.astype(_F32)
    xs = [kf, qf] if with_b else [kf]
    acc = [jnp.zeros((heads, c, c), _F32) for _ in xs]
    factors = {m: _factors(cum, m) for m in _levels(c)}
    for m, (row_factor, col_factor) in factors.items():
        rows = [_lower_halves(x * row_factor, m) for x in xs]
        p = _mm(jnp.concatenate(rows, 1), kf * col_factor, "nt")
        n = rows[0].shape[1]
        owned = _quarter(c, m)
        acc = [jnp.where(owned, _to_lower_halves(p[:, i * n:(i + 1) * n], m,
                                                 c), a)
               for i, a in enumerate(acc)]
    place, first, lanes = _pair_walk(c)
    cum4, x4 = _by_tile(cum), [_by_tile(x) for x in xs]
    acc = [_by_tile(a) for a in acc]
    decays = []
    for jj in range(_PAIR - 1):         # the last column has no row below
        e = jnp.exp(jnp.where(place > jj,
                              cum4 - _row_of_block(cum4, _PAIR, jj),
                              -jnp.inf))
        decays.append(e)
        te = e * _row_of_block(x4[0], _PAIR, jj)
        hit = lanes == first + jj
        acc = [jnp.where(hit, jnp.sum(x * te, -1, keepdims=True), a)
               for x, a in zip(x4, acc)]
    a, b = ([_whole(x) for x in acc] + [None])[:2]
    return cum, kf, qf, a, b, factors, decays


def stateless(q, k, v, g, beta_col, beta_row, sub: int):
    """What a step's chunk-heads need that no state enters, from their
    operands (heads, C, d): q, k, v in the compute type, g float32, beta as
    a column (heads, C, 1) and as a row (heads, 1, C). Returns the operands
    of the state's four products, ``(T (K exp G), T V, B, Q exp G, K exp(G_C
    - G), exp G_C)`` (``T V`` and the decay (heads, 1, d_k) float32, the
    others rounded to q's type), and what :func:`stateless_bwd` reuses of
    their making."""
    c = q.shape[1]
    dtype = q.dtype
    cum, kf, qf, a, b, factors, decays = _scores(q, k, g, True)
    # a token with itself carries no gate
    eye = _iota((1, c, c), 1) == _iota((1, c, c), 2)
    bm = b + jnp.where(eye, jnp.sum(qf * kf, -1, keepdims=True), 0.0)
    x = unit_lower_inverse(beta_col * a, sub)
    td = (x * beta_row).astype(dtype)
    decayed = jnp.exp(cum)
    last = cum[:, c - 1:c]
    to_end = jnp.exp(last - cum)
    k_in = (kf * decayed).astype(dtype)
    results = (_dot(td, k_in).astype(dtype), _dot(td, v), bm.astype(dtype),
               (qf * decayed).astype(dtype), (kf * to_end).astype(dtype),
               jnp.exp(last))
    return results, (cum, kf, qf, a, factors, decays, x, td, k_in, decayed,
                     to_end)


def stateless_bwd(v, beta_col, beta_row, remade, cotangents, sub: int):
    """:func:`stateless`' backward rule, from what it ``remade`` and the six
    results' cotangents (in the results' types and shapes): the gradients
    of q, k (q's type), v (v's type), g, and beta as a column and as a row
    (float32). Each piece by its own rule (module text)."""
    cum, kf, qf, a, factors, decays, x, td, k_in, decayed, to_end = remade
    d_w, d_tv, d_bm, d_qin, d_kout, d_decay = cotangents
    heads, c, d = kf.shape
    dtype = td.dtype
    # T (K exp G) and T V
    d_tv = d_tv.astype(dtype)
    d_t = _dot(d_w, k_in, "nt") + _dot(d_tv, v, "nt")
    d_kin = _dot(td, d_w, "tn")
    dv = _dot(td, d_tv, "tn").astype(v.dtype)
    # T = X Diag(beta), X = (I + L)^-1, L = Diag(beta) A
    d_brow = jnp.sum(d_t * x, 1, keepdims=True)
    d_l = -_mm(_mm(x, d_t * beta_row, "tn"), x, "nt")
    d_bcol = jnp.sum(d_l * a, -1, keepdims=True)
    d_a = beta_col * d_l
    d_b = d_bm.astype(_F32)
    eye = _iota((1, c, c), 1) == _iota((1, c, c), 2)
    d_self = jnp.sum(jnp.where(eye, d_b, 0.0), -1, keepdims=True)
    # the scores, level by level: dx of the rows, dy of the columns
    d_xk, d_xq, d_y = (jnp.zeros_like(kf) for _ in range(3))
    for m, (row_factor, col_factor) in factors.items():
        owned = _quarter(c, m)
        d_rows = jnp.concatenate(
            [_lower_halves(jnp.where(owned, t, 0.0), m) for t in (d_a, d_b)],
            1)
        n = d_rows.shape[1] // 2
        d_x = _mm(d_rows, kf * col_factor)
        d_xk += row_factor * _to_lower_halves(d_x[:, :n], m, c)
        d_xq += row_factor * _to_lower_halves(d_x[:, n:], m, c)
        rows = jnp.concatenate(
            [_lower_halves(t * row_factor, m) for t in (kf, qf)], 1)
        d_y += _mm(d_rows, rows, "tn") * col_factor
    # and the blocks left on the diagonal, pair by pair from the decays
    place, first, lanes = _pair_walk(c)
    k4, q4, da4, db4 = (_by_tile(t) for t in (kf, qf, d_a, d_b))
    d_xk4, d_xq4, d_y4 = (_by_tile(t) for t in (d_xk, d_xq, d_y))
    row = _iota((1, 1, _TILE, 1), 2)
    for jj, e in enumerate(decays):
        hit = lanes == first + jj
        wa, wb = (jnp.sum(jnp.where(hit, t, 0.0), -1, keepdims=True) * e
                  for t in (da4, db4))
        k_j = _row_of_block(k4, _PAIR, jj)
        d_xk4 += wa * k_j
        d_xq4 += wb * k_j
        to_column = wa * k4 + wb * q4
        for block in range(0, _TILE, _PAIR):
            mine = (row >= block) & (row < block + _PAIR)
            d_y4 += jnp.where(
                row == block + jj,
                jnp.sum(jnp.where(mine, to_column, 0.0), 2, keepdims=True),
                0.0)
    d_xk, d_xq, d_y = _whole(d_xk4), _whole(d_xq4), _whole(d_y4)
    # the decayed operands, and every gate's term
    d_qin = d_qin.astype(_F32)
    d_kout = d_kout.astype(_F32)
    dq = (d_xq + d_self * kf + d_qin * decayed).astype(dtype)
    dk = (d_xk + d_y + d_self * qf + d_kin * decayed
          + d_kout * to_end).astype(dtype)
    # (the last row's exponent G_C - G_C is 0 whatever the gates are: its
    # two terms cancel, and are left out before they round)
    last = cum[:, c - 1:c]
    last_row = _iota((1, c, 1), 1) == c - 1
    to_end_term = jnp.where(last_row, 0.0, d_kout * kf * to_end)
    d_last = (jnp.sum(to_end_term, 1, keepdims=True)
              + d_decay * jnp.exp(last))
    d_cum = (kf * (d_xk - d_y) + qf * d_xq
             + (d_kin * kf + d_qin * qf) * decayed - to_end_term
             + jnp.where(last_row, d_last, 0.0))
    dg = _sums(_triangle(heads, c, upper=True), d_cum)
    return dq, dk, dv, dg, d_bcol, d_brow


def _rows(state, w, tv):
    """The state (heads, d_v, d_k) float32 in the compute type, and the
    rows the chunk writes, ``U = T V - (T (K exp G)) S``, in it too."""
    sd = state.astype(w.dtype)
    return sd, (tv - _dot(w, sd, "nt")).astype(w.dtype)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, bcol_ref, brow_ref, first_ref,
                o_ref, entering_ref, last_ref, state_ref, *, sub: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _():
        state_ref[...] = first_ref[...]

    (w, tv, bm, q_in, k_out, decay), _ = stateless(
        q_ref[...], k_ref[...], v_ref[...], g_ref[...], bcol_ref[...],
        brow_ref[...], sub)
    state = state_ref[...]
    entering_ref[...] = state
    sd, ud = _rows(state, w, tv)
    o_ref[...] = (_dot(q_in, sd, "nt") + _dot(bm, ud)).astype(o_ref.dtype)
    state = state * decay + _dot(ud, k_out, "tn")
    state_ref[...] = state

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        last_ref[...] = state


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, bcol_ref, brow_ref, entering_ref,
                do_ref, dlast_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbcol_ref,
                dbrow_ref, dfirst_ref, d_state_ref, *, sub: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _():
        d_state_ref[...] = dlast_ref[...]

    v, bcol, brow = v_ref[...], bcol_ref[...], brow_ref[...]
    (w, tv, bm, q_in, k_out, decay), remade = stateless(
        q_ref[...], k_ref[...], v, g_ref[...], bcol, brow, sub)
    dtype = w.dtype
    state = entering_ref[...]
    sd, ud = _rows(state, w, tv)
    do = do_ref[...].astype(dtype)
    d_state = d_state_ref[...]
    dsd = d_state.astype(dtype)
    # O = (Q exp G) S + B U and S' = Diag(exp G_C) S + (K exp(G_C - G))^T U,
    # back to U = T V - (T (K exp G)) S and to the state that entered
    du = _dot(bm, do, "tn") + _dot(k_out, dsd, "nt")
    dud = du.astype(dtype)
    d_state_ref[...] = (d_state * decay + _dot(do, q_in, "tn")
                        - _dot(dud, w, "tn"))
    cotangents = (_dot(-dud, sd).astype(dtype), du,
                  _dot(do, ud, "nt").astype(dtype), _dot(do, sd).astype(dtype),
                  _dot(ud, dsd).astype(dtype),
                  jnp.sum(state * d_state, 1, keepdims=True))
    (dq_ref[...], dk_ref[...], dv_ref[...], dg_ref[...], dbcol_ref[...],
     dbrow_ref[...]) = stateless_bwd(v, bcol, brow, remade, cotangents, sub)

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        dfirst_ref[...] = d_state_ref[...]


def _heads_a_step(bh: int) -> int:
    """Heads a grid step takes together: up to eight."""
    return max(n for n in range(1, 9) if bh % n == 0)


def _specs(q, v, reverse: bool = False):
    """The grid (heads of a step, chunk: the state's loop innermost, last to
    first with ``reverse``), a step's block of an (n, B*H, rows, width)
    array, its block of a (B*H, d_v, d_k) state, and the blocks of the six
    operands both kernels read: q, k, v, g, beta as a column and as a
    row."""
    n, bh, c, dk = q.shape
    dv = v.shape[-1]
    step = _heads_a_step(bh)

    def block(*tail):
        return pl.BlockSpec(
            (None, step) + tail,
            lambda j, t: (n - 1 - t if reverse else t, j, 0, 0))

    state = pl.BlockSpec((step, dv, dk), lambda j, t: (j, 0, 0))
    return (bh // step, n), block, state, [
        block(c, dk), block(c, dk), block(c, dv), block(c, dk),
        block(c, 1), block(1, c)]


def forward(q, k, v, g, beta, state, sub: int):
    """The chunked delta rule over every chunk (ops/kda.py, module text).
    Operands (n, B*H, C, d), chunk first (beta without d; g and beta
    float32); ``state`` (B*H, d_v, d_k) float32, the entering state
    transposed. Returns o (n, B*H, C, d_v) in v's type, the state that
    enters each chunk (n, B*H, d_v, d_k) and the last (B*H, d_v, d_k),
    float32 and transposed."""
    n, bh, c, dk = q.shape
    if c % _TILE or c & (c - 1) or c % sub:
        raise ValueError(f"a chunk of {c} tokens must be a power of two, at "
                         f"least {_TILE}, and whole sub-chunks of {sub}")
    dv = v.shape[-1]
    grid, block, whole, operands = _specs(q, v)
    return pallas_call(
        functools.partial(_fwd_kernel, sub=sub), name="kda_fwd",
        grid=grid, in_specs=operands + [whole],
        out_specs=[block(c, dv), block(dv, dk), whole],
        out_shape=[jax.ShapeDtypeStruct((n, bh, c, dv), v.dtype),
                   jax.ShapeDtypeStruct((n, bh, dv, dk), _F32),
                   jax.ShapeDtypeStruct((bh, dv, dk), _F32)],
        scratch_shapes=[pltpu.VMEM(whole.block_shape, _F32)],
        compiler_params=_PARAMS,
    )(q, k, v, g, beta[..., None], beta[..., None, :], state)


def backward(q, k, v, g, beta, entering, d_out, d_last, sub: int):
    """:func:`forward`'s backward rule, from its operands, the states that
    entered the chunks and the cotangents of o and of the last state: the
    gradients of q, k, v, g, beta and the entering state."""
    n, bh, c, dk = q.shape
    dv = v.shape[-1]
    grid, block, whole, operands = _specs(q, v, reverse=True)
    dq, dk_, dv_, dg, dbcol, dbrow, d_first = pallas_call(
        functools.partial(_bwd_kernel, sub=sub), name="kda_bwd",
        grid=grid,
        in_specs=operands + [block(dv, dk), block(c, dv), whole],
        out_specs=[block(c, dk), block(c, dk), block(c, dv), block(c, dk),
                   block(c, 1), block(1, c), whole],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g.shape, _F32),
                   jax.ShapeDtypeStruct((n, bh, c, 1), _F32),
                   jax.ShapeDtypeStruct((n, bh, 1, c), _F32),
                   jax.ShapeDtypeStruct((bh, dv, dk), _F32)],
        scratch_shapes=[pltpu.VMEM(whole.block_shape, _F32)],
        compiler_params=_PARAMS,
    )(q, k, v, g, beta[..., None], beta[..., None, :], entering,
      d_out, d_last.astype(_F32))
    return (dq, dk_, dv_, dg.astype(g.dtype),
            (dbcol[..., 0] + dbrow[:, :, 0]).astype(beta.dtype), d_first)
