"""The hyper-connections' passes over the residual streams
(models/hyper_connections.py) as four Pallas kernels.

The streams are one ``(T, n*C)`` array (``T`` tokens, stream ``j`` the lanes
``[j*C, (j+1)*C)``). A grid step is a tile of :data:`ROWS` tokens with all
``n*C`` channels of the streams in VMEM; the per-token coefficients travel as
``(T, 128)`` float32 rows, one lane a coefficient, zeros past the last.

**Input** (:func:`mhc_in`; kernels ``mhc_in_fwd``, ``mhc_in_bwd``). From one
read of the streams, with ``W = scale * Phi`` (``(n*C, m)``, ``m = n^2 +
2n``)::

    r = rsqrt(mean_k x_k^2 + eps)        u = r * (x W)
    pre_j = sigmoid(alpha_pre * u_j + b_j)     h = sum_j pre_j X_j

``u`` is float32 and ``h`` is summed in float32 and rounded once to the
streams' type. Where the streams are bfloat16 the product is exact in one
pass of the matrix unit: ``W`` is split into three bfloat16 pieces, which
hold all its bits, laid side by side as the right operand's columns, and the
three products are added where they leave; float32 streams take the product
at ``Precision.HIGHEST``. The choice follows the streams' type.

**Output** (:func:`mhc_out`; ``mhc_out_fwd``, ``mhc_out_bwd``)::

    X'_i = sum_j H_res[i, j] X_j + H_post[i] y

each stream summed in float32 and rounded once.

**Backward.** ``mhc_out_bwd`` reads ``dX'``, ``X`` and ``y`` once and writes
``dX = H_res^T dX'``, ``dy = sum_i H_post[i] dX'_i`` and the ``n + n^2``
per-token cotangents ``<dX'_i, y>``, ``<dX'_i, X_j>`` (elementwise float32
sums a lane, one cross-lane sum a token and coefficient at the end).
:func:`mhc_in` hands the streams back as a third result, for :func:`mhc_out`
to read: their cotangent is the write's share of ``dX``, and ``mhc_in_bwd``
adds it in the same pass in which it reads ``X`` and ``dh`` once and writes
the final ``dX``: ``dpre_j = <dh, X_j>`` gives the ``pre`` logits'
cotangent, ``du`` (from the Sinkhorn and sigmoid backward outside, on the
small coefficients) gets the ``pre`` columns' share, ``dX`` gets ``pre_j dh +
r du W^T - (r^2 / nC) <du, u> X`` beside the write's share, all float32 and
rounded once, and ``dW^T = sum_t (r du)^T x`` adds up in float32 over the
grid in one block that stays in VMEM. The products of the backward: ``x``
times ``r du`` in three exact bfloat16 pieces of ``r du``; ``r du W^T`` as
``hi W_hi + hi W_lo + lo W_hi`` (``hi``, ``lo`` a float32's bfloat16 head and
tail), the three laid along one contraction of 128.

Both are ``jax.custom_vjp`` whose residuals are their inputs (and ``u``):
under a recomputed block the forward kernels run again and nothing more is
kept. Block shapes come from the operands; on a CPU the kernels run
interpreted (ops/pallas.py). :func:`mhc_in_plain` and :func:`mhc_out_plain`
are the same passes as plain array lines: what the tests hold the kernels to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributeddeeplearning_tpu.ops.pallas import pallas_call

ROWS = 128            # tokens a grid step
_LANES = 128          # a coefficient row's width
_F32, _BF16 = jnp.float32, jnp.bfloat16
_HIGHEST = jax.lax.Precision.HIGHEST
_PARAMS = pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                               vmem_limit_bytes=64 * 2 ** 20)


# --------------------------------------------------------------------------
# the plain formulation
# --------------------------------------------------------------------------

def stream(x, j: int, n: int):
    """Stream ``j`` of ``n``: its lanes of the streams' last axis."""
    c = x.shape[-1] // n
    return x[..., j * c:(j + 1) * c]


def mhc_in_plain(x, scale, phi, alpha_pre, bias_pre, *, eps: float):
    """The input pass as array lines: ``x`` (..., n*C), ``scale`` (n*C,),
    ``phi`` (n*C, m), ``alpha_pre`` a scalar, ``bias_pre`` (n,). Returns
    ``h`` (..., C) in ``x``'s type and ``u`` (..., m) float32."""
    n = bias_pre.shape[0]
    xf = x.astype(_F32)
    xn = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps) * scale
    u = jnp.einsum("...k,km->...m", xn, phi, precision=_HIGHEST)
    pre = jax.nn.sigmoid(alpha_pre * u[..., :n] + bias_pre)
    h = sum(pre[..., j, None] * stream(xf, j, n) for j in range(n))
    return h.astype(x.dtype), u


def mhc_out_plain(x, y, coef):
    """The output pass as array lines: ``x`` (..., n*C), ``y`` (..., C),
    ``coef`` (..., n + n^2) float32, H_post then H_res rows first. Returns
    (..., n*C) in ``x``'s type."""
    n = x.shape[-1] // y.shape[-1]
    yf = y.astype(_F32)
    xs = [stream(x, j, n).astype(_F32) for j in range(n)]
    out = [coef[..., i, None] * yf
           + sum(coef[..., n + i * n + j, None] * xs[j] for j in range(n))
           for i in range(n)]
    return jnp.concatenate(out, axis=-1).astype(x.dtype)


# --------------------------------------------------------------------------
# what the kernels share
# --------------------------------------------------------------------------

def _pieces(dtype) -> int:
    """bfloat16 pieces a float32 operand is split into where it meets the
    streams: three for bfloat16 streams (exact products), one (the float32
    operand itself, at ``HIGHEST``) otherwise."""
    return 3 if jnp.dtype(dtype) == _BF16 else 1


def bf16_pieces(x, count: int, *, kept: bool = False):
    """``count`` bfloat16 pieces of a float32 array, the head first, each
    what the pieces before it leave of ``x``, rounded: three hold all of
    float32's bits, so products with them added up in float32 are exact.
    Inside a kernel a round trip through bfloat16 stays; outside one XLA may
    drop it as excess precision (the pieces after the head would come out
    0), and ``kept`` rounds by ``reduce_precision``, which it keeps."""
    pieces = []
    for _ in range(count):
        rest = x
        for piece in pieces:
            rest = rest - piece.astype(_F32)
        if kept:
            rest = jax.lax.reduce_precision(rest, exponent_bits=8,
                                            mantissa_bits=7)
        pieces.append(rest.astype(_BF16))
    return pieces


def _dot(a, b, pieces: int):
    return jnp.dot(a, b, preferred_element_type=_F32,
                   precision=None if pieces > 1 else _HIGHEST)


def _lane(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)


def _column(a, lanes):
    """(R, 1): the sum of a (R, L) array's columns ``lanes``."""
    lane = _lane(a.shape)
    pick = functools.reduce(jnp.logical_or, [lane == k for k in lanes])
    return jnp.sum(jnp.where(pick, a, 0.0), axis=1, keepdims=True)


def _place(columns, width: int):
    """(R, width): (R, 1) ``columns`` at lanes 0, 1, ..., zeros after."""
    lane = _lane((columns[0].shape[0], width))
    out = jnp.zeros(lane.shape, _F32)
    for k, col in enumerate(columns):
        out = jnp.where(lane == k, col, out)
    return out


def _side_by_side(parts, m: int):
    """(R, L) float32: bfloat16 ``parts`` (R, L), each with its values in
    lanes [0, m), put at lanes [k m, (k + 1) m); a product with a matrix of
    0 and 1, so exact."""
    width = parts[0].shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (width, width), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (width, width), 1)
    out = None
    for k, part in enumerate(parts):
        move = ((col == row + k * m) & (row < m)).astype(_BF16)
        term = jnp.dot(part, move, preferred_element_type=_F32)
        out = term if out is None else out + term
    return out


def _chunk(c: int) -> int:
    """Lanes of a stream a loop step takes: a lane tile, or the whole
    stream where it is narrower than whole tiles."""
    return _LANES if c % _LANES == 0 else c


def _over_chunks(c: int, body, carry=()):
    """``body(offset, carry)`` over a stream's chunks of :func:`_chunk`
    lanes, ``offset`` the chunk's first lane within the stream."""
    w = _chunk(c)
    return jax.lax.fori_loop(
        0, c // w, lambda i, cr: body(pl.multiple_of(i * w, w), cr), carry)


def _lanes(ref, j: int, c: int, off):
    """Stream ``j``'s chunk at ``off`` of a (rows, n*C) block, float32."""
    return ref[:, pl.ds(pl.multiple_of(j * c + off, _chunk(c)),
                        _chunk(c))].astype(_F32)


def _rows(tokens: int) -> int:
    return ROWS if tokens >= ROWS else tokens


def _row_spec(tokens: int, width: int):
    return pl.BlockSpec((_rows(tokens), width), lambda i: (i, 0))


def _whole(shape):
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape))


def _pad_lanes(a):
    """(T, k) -> (T, 128) float32, zeros past ``k``."""
    return jnp.pad(a.astype(_F32), ((0, 0), (0, _LANES - a.shape[1])))


def _weights(w, dtype):
    """``W`` (n*C, m) float32 as the two operands the kernels multiply by:
    forward (n*C, 128), its pieces' columns side by side; backward (128,
    n*C), the rows ``W_hi^T, W_lo^T, W_hi^T`` (or ``W^T`` alone)."""
    m = w.shape[1]
    if _pieces(dtype) == 1:
        fwd, bwd = w, w.T
    else:
        hi, lo, last = bf16_pieces(w, 3, kept=True)
        fwd = jnp.concatenate([hi, lo, last], 1)
        bwd = jnp.concatenate([hi.T, lo.T, hi.T], 0)
    if fwd.shape[1] > _LANES or m > _LANES:
        raise ValueError(f"{m} coefficients do not fit one lane tile")
    return (jnp.pad(fwd, ((0, 0), (0, _LANES - fwd.shape[1]))),
            jnp.pad(bwd, ((0, _LANES - bwd.shape[0]), (0, 0))))


def _pre_params(alpha_pre, bias_pre):
    """[alpha_pre, b_0, ..., b_{n-1}] float32, for SMEM."""
    return jnp.concatenate([jnp.reshape(alpha_pre, (1,)),
                            bias_pre]).astype(_F32)


_SCALARS = pl.BlockSpec(memory_space=pltpu.SMEM)


# --------------------------------------------------------------------------
# the input pass
# --------------------------------------------------------------------------

def _in_fwd_kernel(pre_ref, w_ref, x_ref, h_ref, u_ref, cols_scr, *,
                   n: int, m: int, eps: float):
    rows, width = x_ref.shape
    c, w = width // n, _chunk(width // n)
    pieces = _pieces(x_ref.dtype)
    v = _dot(x_ref[...], w_ref[...], pieces)                 # (rows, 128)

    def squares(off, acc):
        for j in range(n):
            xj = _lanes(x_ref, j, c, off)
            acc = acc + xj * xj
        return acc

    ss = jnp.sum(_over_chunks(c, squares, jnp.zeros((rows, w), _F32)), 1,
                 keepdims=True)
    r = jax.lax.rsqrt(ss * (1.0 / width) + eps)
    u_ref[...] = r * v
    for j in range(n):
        u = r * _column(v, [j + p * m for p in range(pieces)])
        pre = jax.nn.sigmoid(pre_ref[0] * u + pre_ref[1 + j])
        cols_scr[j] = jnp.broadcast_to(pre, (rows, w))

    def read(off, carry):
        acc = cols_scr[0] * _lanes(x_ref, 0, c, off)
        for j in range(1, n):
            acc = acc + cols_scr[j] * _lanes(x_ref, j, c, off)
        h_ref[:, pl.ds(off, w)] = acc.astype(h_ref.dtype)
        return carry

    _over_chunks(c, read)


def _in_bwd_kernel(pre_ref, wt_ref, x_ref, dh_ref, dxo_ref, u_ref, du_ref,
                   dx_ref, dwt_ref, dpre_ref, cols_scr, xv_scr, *, n: int,
                   m: int, eps: float, tokens: int):
    """``dxo``: the write's share of ``dX``; ``u`` / ``du`` the folded
    coefficients' pre-activations and their cotangent from outside, lanes
    [0, m). ``dwt`` (pieces x m rounded up to 8, n*C): the pieces of
    ``dW^T``, added up over the grid. ``dpre``: the ``pre`` logits'
    cotangent, lanes [0, n)."""
    rows, width = x_ref.shape
    c, w = width // n, _chunk(width // n)
    pieces = _pieces(x_ref.dtype)

    @pl.when(pl.program_id(0) == 0)
    def _():
        dwt_ref[...] = jnp.zeros(dwt_ref.shape, _F32)

    def sums(off, acc):
        ss, dots = acc[0], list(acc[1:])
        dh = _lanes(dh_ref, 0, c, off)
        for j in range(n):
            xj = _lanes(x_ref, j, c, off)
            ss = ss + xj * xj
            dots[j] = dots[j] + dh * xj
        return (ss, *dots)

    acc = _over_chunks(c, sums, (jnp.zeros((rows, w), _F32),) * (n + 1))
    ss, *dots = (jnp.sum(a, 1, keepdims=True) for a in acc)
    r = jax.lax.rsqrt(ss * (1.0 / width) + eps)
    u, du = u_ref[...], du_ref[...]
    pre, dlogit = [], []
    for j in range(n):
        p = jax.nn.sigmoid(pre_ref[0] * _column(u, [j])
                           + pre_ref[1 + j])
        pre.append(p)
        dlogit.append(dots[j] * p * (1.0 - p))
    dpre_ref[...] = _place(dlogit, dpre_ref.shape[1])
    du = du + pre_ref[0] * _place(dlogit, du.shape[1])
    dv = r * du                                              # (rows, 128)
    norm = -(r * r * (1.0 / width)) * jnp.sum(du * u, 1, keepdims=True)
    if pieces == 1:
        left, sides = dv, dv
    else:
        hi, lo, last = bf16_pieces(dv, 3)
        left = _side_by_side([hi, hi, lo], m).astype(_BF16)
        sides = _side_by_side([hi, lo, last], m)
    if tokens % rows:     # the last tile's rows past the tokens are not data
        row = pl.program_id(0) * rows + jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0)
        sides = jnp.where(row < tokens, sides, 0.0)
    sides_t = sides.T[:dwt_ref.shape[0]].astype(wt_ref.dtype)
    for j in range(n):
        cols_scr[j] = jnp.broadcast_to(pre[j], (rows, w))
    cols_scr[n] = jnp.broadcast_to(norm, (rows, w))
    for j in range(n):
        # the stream's two products whole, then its cotangent chunk by chunk
        stream = slice(j * c, (j + 1) * c)
        xj = x_ref[:, stream]
        if tokens % rows:
            xj = jnp.where(row < tokens, xj, 0)
        dwt_ref[:, stream] += _dot(sides_t, xj, pieces)
        xv_scr[...] = _dot(left, wt_ref[:, stream], pieces)

        def write(off, carry, j=j):
            lanes = pl.ds(pl.multiple_of(j * c + off, w), w)
            dx = (dxo_ref[:, lanes].astype(_F32)
                  + cols_scr[j] * _lanes(dh_ref, 0, c, off)
                  + xv_scr[:, pl.ds(off, w)]
                  + cols_scr[n] * _lanes(x_ref, j, c, off))
            dx_ref[:, lanes] = dx.astype(dx_ref.dtype)
            return carry

        _over_chunks(c, write)


def _in_call(x, w_fwd, params, eps):
    t, width = x.shape
    n = params.shape[0] - 1
    m = n * n + 2 * n
    rows = _rows(t)
    return pallas_call(
        functools.partial(_in_fwd_kernel, n=n, m=m, eps=eps),
        name="mhc_in_fwd", grid=(pl.cdiv(t, rows),),
        in_specs=[_SCALARS, _whole(w_fwd.shape), _row_spec(t, width)],
        out_specs=[_row_spec(t, width // n), _row_spec(t, _LANES)],
        out_shape=[jax.ShapeDtypeStruct((t, width // n), x.dtype),
                   jax.ShapeDtypeStruct((t, _LANES), _F32)],
        scratch_shapes=[pltpu.VMEM((n, rows, _chunk(width // n)), _F32)],
        compiler_params=_PARAMS,
    )(params, w_fwd, x)


def _fold(pieces_u, m: int, pieces: int):
    """(T, 128) of ``r`` times the pieces' products -> ``u`` (T, m)."""
    u = pieces_u[:, :m]
    for p in range(1, pieces):
        u = u + pieces_u[:, p * m:(p + 1) * m]
    return u


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _mhc_in(x, scale, phi, alpha_pre, bias_pre, eps):
    w_fwd, _ = _weights(scale[:, None] * phi, x.dtype)
    h, u = _in_call(x, w_fwd, _pre_params(alpha_pre, bias_pre), eps)
    return h, _fold(u, phi.shape[1], _pieces(x.dtype)), x


def _mhc_in_fwd(x, scale, phi, alpha_pre, bias_pre, eps):
    h, u, x = _mhc_in(x, scale, phi, alpha_pre, bias_pre, eps)
    return (h, u, x), (x, scale, phi, alpha_pre, bias_pre, u)


def _mhc_in_bwd(eps, residuals, cotangents):
    x, scale, phi, alpha_pre, bias_pre, u = residuals
    dh, du, dxo = cotangents
    t, width = x.shape
    n, m = bias_pre.shape[0], phi.shape[1]
    pieces = _pieces(x.dtype)
    _, w_bwd = _weights(scale[:, None] * phi, x.dtype)
    rows = _rows(t)
    dw_rows = -(-pieces * m // 8) * 8
    dx, dwt, dpre = pallas_call(
        functools.partial(_in_bwd_kernel, n=n, m=m, eps=eps, tokens=t),
        name="mhc_in_bwd", grid=(pl.cdiv(t, rows),),
        in_specs=[_SCALARS, _whole(w_bwd.shape), _row_spec(t, width),
                  _row_spec(t, width // n), _row_spec(t, width),
                  _row_spec(t, _LANES), _row_spec(t, _LANES)],
        out_specs=[_row_spec(t, width), _whole((dw_rows, width)),
                   _row_spec(t, _LANES)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((dw_rows, width), _F32),
                   jax.ShapeDtypeStruct((t, _LANES), _F32)],
        scratch_shapes=[pltpu.VMEM((n + 1, rows, _chunk(width // n)), _F32),
                        pltpu.VMEM((rows, width // n), _F32)],
        compiler_params=_PARAMS,
    )(_pre_params(alpha_pre, bias_pre), w_bwd, x, dh, dxo, _pad_lanes(u),
      _pad_lanes(du))
    dw = _fold(dwt.T, m, pieces)                              # (n*C, m)
    dpre = dpre[:, :n]
    return (dx, (dw * phi).sum(1).astype(scale.dtype),
            (dw * scale[:, None]).astype(phi.dtype),
            jnp.sum(dpre * u[:, :n]).astype(jnp.result_type(alpha_pre)),
            dpre.sum(0).astype(bias_pre.dtype))


_mhc_in.defvjp(_mhc_in_fwd, _mhc_in_bwd)


def mhc_in(x, scale, phi, alpha_pre, bias_pre, *, eps: float):
    """The input pass (module text): ``x`` (B, S, n*C), ``scale`` (n*C,),
    ``phi`` (n*C, m), ``alpha_pre`` a scalar, ``bias_pre`` (n,). Returns the
    sub-layer's input ``h`` (B, S, C) in ``x``'s type, the pre-activations
    ``u`` (B, S, m) float32, and ``x`` itself, which :func:`mhc_out` is to
    read so that the write's share of ``dX`` meets the others in
    ``mhc_in_bwd``."""
    lead = x.shape[:-1]
    h, u, flat = _mhc_in(x.reshape(-1, x.shape[-1]), scale, phi, alpha_pre,
                         bias_pre, eps)
    return (h.reshape(lead + h.shape[-1:]), u.reshape(lead + u.shape[-1:]),
            flat.reshape(x.shape))


# --------------------------------------------------------------------------
# the output pass
# --------------------------------------------------------------------------

def _fill_coefficients(c_ref, cols_scr, n: int):
    """H_post[i] at ``cols_scr[i]``, H_res[i, j] at ``cols_scr[n + i n + j]``,
    each a token's value across the lanes of a chunk."""
    coef = c_ref[...]
    shape = cols_scr.shape[1:]
    for k in range(n + n * n):
        cols_scr[k] = jnp.broadcast_to(_column(coef, [k]), shape)


def _out_fwd_kernel(x_ref, y_ref, c_ref, o_ref, cols_scr, *, n: int):
    c = x_ref.shape[1] // n
    w = _chunk(c)
    _fill_coefficients(c_ref, cols_scr, n)

    def body(off, carry):
        y = _lanes(y_ref, 0, c, off)
        xs = [_lanes(x_ref, j, c, off) for j in range(n)]
        for i in range(n):
            mix = cols_scr[n + i * n] * xs[0]
            for j in range(1, n):
                mix = mix + cols_scr[n + i * n + j] * xs[j]
            o_ref[:, pl.ds(pl.multiple_of(i * c + off, w), w)] = (
                cols_scr[i] * y + mix).astype(o_ref.dtype)
        return carry

    _over_chunks(c, body)


def _out_bwd_kernel(x_ref, y_ref, c_ref, g_ref, dx_ref, dy_ref, dc_ref,
                    cols_scr, acc_scr, *, n: int):
    """``g``: ``dX'``. ``dc``: ``<dX'_i, y>`` at lane i, ``<dX'_i, X_j>`` at
    lane n + i n + j; ``acc_scr`` their elementwise sums over the chunks."""
    c = x_ref.shape[1] // n
    w = _chunk(c)
    _fill_coefficients(c_ref, cols_scr, n)
    acc_scr[...] = jnp.zeros(acc_scr.shape, _F32)

    def body(off, carry):
        y = _lanes(y_ref, 0, c, off)
        xs = [_lanes(x_ref, j, c, off) for j in range(n)]
        gs = [_lanes(g_ref, i, c, off) for i in range(n)]
        dy = cols_scr[0] * gs[0]
        for i in range(1, n):
            dy = dy + cols_scr[i] * gs[i]
        dy_ref[:, pl.ds(off, w)] = dy.astype(dy_ref.dtype)
        for j in range(n):
            dx = cols_scr[n + j] * gs[0]
            for i in range(1, n):
                dx = dx + cols_scr[n + i * n + j] * gs[i]
            dx_ref[:, pl.ds(pl.multiple_of(j * c + off, w), w)] = dx.astype(
                dx_ref.dtype)
        for i in range(n):
            acc_scr[i] += gs[i] * y
            for j in range(n):
                acc_scr[n + i * n + j] += gs[i] * xs[j]
        return carry

    _over_chunks(c, body)
    dc_ref[...] = _place([jnp.sum(acc_scr[k], 1, keepdims=True)
                          for k in range(n + n * n)], dc_ref.shape[1])


def _out_specs(x, y):
    t, width = x.shape
    n = width // y.shape[1]
    rows, w = _rows(t), _chunk(y.shape[1])
    coefs = n + n * n
    return (t, width, n, coefs, _row_spec(t, width), _row_spec(t, y.shape[1]),
            _row_spec(t, _LANES), pltpu.VMEM((coefs, rows, w), _F32))


@jax.custom_vjp
def _mhc_out(x, y, coef):
    t, width, n, _, block, stream, small, cols = _out_specs(x, y)
    return pallas_call(
        functools.partial(_out_fwd_kernel, n=n), name="mhc_out_fwd",
        grid=(pl.cdiv(t, _rows(t)),), in_specs=[block, stream, small],
        out_specs=block, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[cols], compiler_params=_PARAMS,
    )(x, y, _pad_lanes(coef))


def _mhc_out_fwd(x, y, coef):
    return _mhc_out(x, y, coef), (x, y, coef)


def _mhc_out_bwd(residuals, g):
    x, y, coef = residuals
    t, width, n, coefs, block, stream, small, cols = _out_specs(x, y)
    dx, dy, dc = pallas_call(
        functools.partial(_out_bwd_kernel, n=n), name="mhc_out_bwd",
        grid=(pl.cdiv(t, _rows(t)),),
        in_specs=[block, stream, small, block],
        out_specs=[block, stream, small],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct((t, _LANES), _F32)],
        scratch_shapes=[cols, cols], compiler_params=_PARAMS,
    )(x, y, _pad_lanes(coef), g)
    return dx, dy, dc[:, :coefs].astype(coef.dtype)


_mhc_out.defvjp(_mhc_out_fwd, _mhc_out_bwd)


def mhc_out(x, y, coef):
    """The output pass (module text): ``x`` (B, S, n*C), ``y`` (B, S, C),
    ``coef`` (B, S, n + n^2) float32, H_post then H_res rows first. Returns
    ``X'`` (B, S, n*C) in ``x``'s type."""
    flat = _mhc_out(x.reshape(-1, x.shape[-1]), y.reshape(-1, y.shape[-1]),
                    coef.reshape(-1, coef.shape[-1]))
    return flat.reshape(x.shape)
