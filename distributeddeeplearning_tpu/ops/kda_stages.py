"""The pointwise stages on either side of the chunked delta-rule operator
(ops/kda.py), each one fused Pallas pass whose ``BlockSpec`` index maps are
the relayout between the model's ``(B, S, H*D)`` and the operator's
``(groups, group, B*H, C, d)`` (:func:`ops.kda.lay_out`).

**Input stage** (:func:`kda_in`; kernels ``kda_in_fwd``, ``kda_in_bwd``). From
the four projections of a KDA layer, read once as their products wrote them:

    q = l2(silu(conv(q~))) * d^-1/2      k = l2(silu(conv(k~)))
    v = silu(conv(v~))
    g = -exp(A_log[h]) softplus(f + dt_bias) mask

``conv`` the depthwise causal convolution of ``K`` taps (``y_t = sum_j w[j]
x_{t-K+1+j}``, zeros before the first token), ``l2`` a head's ``x /
sqrt(|x|^2 + 1e-6)``. A grid step is one batch row, one group of chunks and
the ``step`` heads of a step (:func:`_heads_a_step`: four at 32 heads of
128; the kernel takes them one after the other, ``head = n * step + m``):
their ``(group * C, step * d)`` block of each projection, and as a second
spec on the same array the ``halo`` rows before it (a tile of 8 or 16 rows,
of which the convolution reads the last ``K - 1``; zeros at the first
group). Everything between is float32 in VMEM: the taps are not rounded and
their ``K`` products are summed before anything is; values are rounded once,
where they leave (q, k, v in the projections' type, g float32). The results
are written as the operands the operator scans over, a step's out block
``(1, group, step, C, d)`` at block ``(group index, 0, b * (H / step) + n,
0, 0)``: rows ``b * H + n * step`` onward of the merged axis.

The backward kernel reads the operands' cotangents in that layout and the
same projections, remakes the intermediates, and writes the projections'
cotangents in the model's layout. The convolution's transpose needs the
cotangent at the convolution's result for the ``K - 1`` rows AFTER the block:
those are remade from a halo on that side, the next block's first rows of
the projection and of the operand's cotangent (zeros after the last group).
The taps', ``dt_bias``'s and ``A_log``'s gradients add up in float32 over the
whole grid in one block that stays in VMEM (zeroed at the grid's first
step), a head a row group.

**Output stage** (:func:`kda_out`; ``kda_out_fwd``, ``kda_out_bwd``), the
mirror: from the operator's result in its layout and the output gate's
projection, ``RMSNorm_d(o) * scale * sigmoid(gate)`` in float32, written
``(B, S, H*D)`` for the output projection.

Both are ``jax.custom_vjp`` whose residuals are their inputs: under a
recomputed block the forward kernels run again and nothing more is kept. A
sequence that is not whole groups is padded with zeros and ``mask = 0`` up
to them, the operator's own rule for a short tail. Block shapes come from
the operands' shapes; on a CPU the kernels run interpreted (ops/pallas.py).
:func:`kda_in_plain` and :func:`kda_out_plain` are the same stages as plain
array lines in the model's layout: what the tests hold the kernels to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributeddeeplearning_tpu.ops import kda
from distributeddeeplearning_tpu.ops.pallas import pallas_call

L2_EPS = 1e-6
_F32 = jnp.float32
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
    vmem_limit_bytes=64 * 2 ** 20)


# --------------------------------------------------------------------------
# the plain formulation
# --------------------------------------------------------------------------

def _short_conv(x, taps):
    """(B, S, N) by (K, N): y_t = sum_j taps[j] x_{t-K+1+j}, in x's type."""
    size, s = taps.shape[0], x.shape[1]
    taps = taps.astype(x.dtype)
    xp = jnp.pad(x, ((0, 0), (size - 1, 0), (0, 0)))
    return sum(taps[j] * xp[:, j:j + s] for j in range(size))


def _l2_normalise(x):
    x = x.astype(_F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def kda_in_plain(projections, taps, a_log, dt_bias, mask):
    """The input stage as array lines. ``projections``: q~, k~, v~, f, each
    (B, S, H*D); ``taps``: three (K, H*D); ``a_log`` (H,), ``dt_bias``
    (H*D,), ``mask`` (B, S). Returns q, k, v (B, S, H, D) in the
    projections' type and g float32."""
    pq, pk, pv, f = projections
    b, s, _ = pq.shape
    h = a_log.shape[0]
    q, k, v = (jax.nn.silu(_short_conv(x, w)).reshape(b, s, h, -1)
               for x, w in zip((pq, pk, pv), taps))
    d = q.shape[-1]
    q = (_l2_normalise(q) * d ** -0.5).astype(pq.dtype)
    k = _l2_normalise(k).astype(pq.dtype)
    g = (-jnp.exp(a_log)[:, None]
         * jax.nn.softplus(f.astype(_F32) + dt_bias).reshape(b, s, h, d)
         * mask[..., None, None])
    return q, k, v, g


def kda_out_plain(o, gate, scale, eps: float):
    """The output stage as array lines: o (B, S, H, D), gate (B, S, H*D),
    scale (D,) float32. Returns (B, S, H*D) in ``o``'s type."""
    b, s, h, d = o.shape
    x = o.astype(_F32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale
    return (x.astype(o.dtype)
            * jax.nn.sigmoid(gate.reshape(b, s, h, d))).reshape(b, s, h * d)


# --------------------------------------------------------------------------
# what the kernels share
# --------------------------------------------------------------------------

def _softplus(x):
    return jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x)))


def _taps_over(w_ref, lanes, x_scr, start: int, rows: int):
    """sum_j w[j] * x[start + j : start + j + rows] of a float32 scratch."""
    acc = w_ref[0:1, lanes] * x_scr[pl.ds(start, rows), :]
    for j in range(1, w_ref.shape[0]):
        acc = acc + w_ref[j:j + 1, lanes] * x_scr[pl.ds(start + j, rows), :]
    return acc


def _halo_rows(dtype) -> int:
    """Rows of a halo block: one tile of the projections' type."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _heads_a_step(h: int, d: int) -> int:
    """Heads a grid step takes. Where a head's columns are whole lane tiles,
    as many as make 512 columns (at 32 heads of 128 four heads a step take
    the forward kernels to 80-87 % and 74 % of the chip's bandwidth where
    one head a step reads 73 % and 54 %, and eight take them no further:
    PERF.md, PR 32); else
    all of them (a block's last dimension is a multiple of 128 or the
    array's own)."""
    if d % 128:
        return h
    return max(n for n in range(1, h + 1) if h % n == 0 and n * d <= 512)


def _specs(b, h, d, group, chunk, groups):
    """What both stages' grids (batch row, group of chunks, heads of a step)
    share: the grid, and a step's block in either layout."""
    step = _heads_a_step(h, d)
    steps = h // step
    return dict(
        grid=(b, groups, steps),
        # model layout (B, S, H*D): a group's rows of the step's heads
        block=pl.BlockSpec((None, group * chunk, step * d),
                           lambda i, j, n: (i, j, n)),
        # operator layout (groups, group, B*H, C, d): its chunks of them
        operand=pl.BlockSpec((None, group, step, chunk, d),
                             lambda i, j, n: (j, 0, i * steps + n, 0, 0)))


def _in_specs(b, h, d, group, chunk, halo, groups):
    """The input stage's: :func:`_specs`, the halos on either side of a
    block, the mask's column and the step's columns of a parameter."""
    sp = _specs(b, h, d, group, chunk, groups)
    rows = group * chunk
    per = rows // halo
    last = groups * per - 1
    steps = sp["grid"][2]
    step = h // steps
    return dict(
        sp,
        # model layout: the tile before the block, the tile after it
        before=pl.BlockSpec(
            (None, halo, step * d),
            lambda i, j, n: (i, jnp.maximum(j * per - 1, 0), n)),
        after=pl.BlockSpec(
            (None, halo, step * d),
            lambda i, j, n: (i, jnp.minimum((j + 1) * per, last), n)),
        # operator layout: the first tile of the next group's first chunk
        operand_after=pl.BlockSpec(
            (None, None, step, halo, d),
            lambda i, j, n: (jnp.minimum(j + 1, groups - 1), 0,
                             i * steps + n, 0, 0)),
        mask=pl.BlockSpec((None, rows, 1), lambda i, j, n: (i, j, 0)),
        # the step's columns of a (rows, H*D) parameter
        columns=lambda r: pl.BlockSpec((r, step * d),
                                       lambda i, j, n: (0, n)))


def _heads(ref, d: int):
    """(head of the step, its lanes) over a model-layout block."""
    return [(m, slice(m * d, (m + 1) * d)) for m in range(ref.shape[-1] // d)]


# --------------------------------------------------------------------------
# the input stage
# --------------------------------------------------------------------------

def _in_fwd_kernel(mask_ref, rate_ref, dt_ref, wq_ref, wk_ref, wv_ref,
                   pq_ref, bq_ref, pk_ref, bk_ref, pv_ref, bv_ref, pf_ref,
                   q_ref, k_ref, v_ref, g_ref, x_scr, *, q_scale: float):
    halo, rows, taps = bq_ref.shape[0], pq_ref.shape[0], wq_ref.shape[0]
    laid = q_ref.shape[:1] + q_ref.shape[2:]                # (group, C, d)
    first = pl.program_id(1) == 0
    for m, lanes in _heads(pq_ref, laid[-1]):
        for i, (p_ref, b_ref, w_ref, o_ref, norm) in enumerate((
                (pq_ref, bq_ref, wq_ref, q_ref, q_scale),
                (pk_ref, bk_ref, wk_ref, k_ref, 1.0),
                (pv_ref, bv_ref, wv_ref, v_ref, None))):
            x = x_scr.at[i]
            x[0:halo, :] = jnp.where(first, 0.0, b_ref[:, lanes].astype(_F32))
            x[halo:, :] = p_ref[:, lanes].astype(_F32)
            c = _taps_over(w_ref, lanes, x, halo - taps + 1, rows)
            s = c * jax.nn.sigmoid(c)
            if norm is not None:
                s = s * (jax.lax.rsqrt(
                    jnp.sum(s * s, -1, keepdims=True) + L2_EPS) * norm)
            o_ref[:, m] = s.reshape(laid).astype(o_ref.dtype)
        g = (rate_ref[:, lanes]
             * _softplus(pf_ref[:, lanes].astype(_F32) + dt_ref[:, lanes])
             * mask_ref[...])
        g_ref[:, m] = g.reshape(laid)


def _in_bwd_kernel(mask_ref, rate_ref, dt_ref, wq_ref, wk_ref, wv_ref,
                   pq_ref, bq_ref, aq_ref, dq_ref, eq_ref,
                   pk_ref, bk_ref, ak_ref, dk_ref, ek_ref,
                   pv_ref, bv_ref, av_ref, dv_ref, ev_ref, pf_ref, dg_ref,
                   dpq_ref, dpk_ref, dpv_ref, dpf_ref, small_ref,
                   x_scr, dc_scr, *, q_scale: float):
    """``b*`` / ``a*``: the projection's tile before / after the block;
    ``d*`` the operand's cotangent, ``e*`` its first tile in the next group.
    ``small_ref`` (H, 3 K + 2, d): a head's taps' gradients (q, k, v), then
    ``dt_bias``'s, then ``sum dg * g`` a channel (``A_log``'s, summed over
    the channels outside)."""
    halo, rows, taps = bq_ref.shape[0], pq_ref.shape[0], wq_ref.shape[0]
    d = dq_ref.shape[-1]
    j, n = pl.program_id(1), pl.program_id(2)
    first, last = j == 0, j == pl.num_programs(1) - 1

    @pl.when((pl.program_id(0) == 0) & first & (n == 0))
    def _():
        small_ref[...] = jnp.zeros(small_ref.shape, _F32)

    for m, lanes in _heads(pq_ref, d):
        head = n * dq_ref.shape[1] + m

        def add(row: int, value):
            small_ref[head, row:row + 1, :] += jnp.sum(value, 0,
                                                       keepdims=True)

        for i, (p_ref, b_ref, a_ref, d_ref, e_ref, w_ref, dp_ref, norm) in (
                enumerate((
                    (pq_ref, bq_ref, aq_ref, dq_ref, eq_ref, wq_ref, dpq_ref,
                     q_scale),
                    (pk_ref, bk_ref, ak_ref, dk_ref, ek_ref, wk_ref, dpk_ref,
                     1.0),
                    (pv_ref, bv_ref, av_ref, dv_ref, ev_ref, wv_ref, dpv_ref,
                     None)))):
            x = x_scr.at[i]
            x[0:halo, :] = jnp.where(first, 0.0, b_ref[:, lanes].astype(_F32))
            x[halo:halo + rows, :] = p_ref[:, lanes].astype(_F32)
            x[halo + rows:, :] = a_ref[:, lanes].astype(_F32)
            # the block's rows and the tile after them
            c = _taps_over(w_ref, lanes, x, halo - taps + 1, rows + halo)
            sig = jax.nn.sigmoid(c)
            ds = jnp.concatenate(
                [d_ref[:, m].astype(_F32).reshape(rows, d),
                 jnp.where(last, 0.0, e_ref[m].astype(_F32))], 0)
            if norm is not None:
                s = c * sig
                r = jax.lax.rsqrt(jnp.sum(s * s, -1, keepdims=True) + L2_EPS)
                unit = s * r
                ds = (norm * r) * (ds - unit * jnp.sum(ds * unit, -1,
                                                       keepdims=True))
            dc_scr[...] = ds * sig * (1.0 + c * (1.0 - sig))
            # dx_u = sum_t w[t] dc_{u+K-1-t}
            dp = w_ref[0:1, lanes] * dc_scr[pl.ds(taps - 1, rows), :]
            for t in range(1, taps):
                dp = dp + (w_ref[t:t + 1, lanes]
                           * dc_scr[pl.ds(taps - 1 - t, rows), :])
            dp_ref[:, lanes] = dp.astype(dp_ref.dtype)
            dc = dc_scr[pl.ds(0, rows), :]
            for t in range(taps):
                add(i * taps + t, dc * x[pl.ds(halo - taps + 1 + t, rows), :])
        f = pf_ref[:, lanes].astype(_F32) + dt_ref[:, lanes]
        dg = dg_ref[:, m].reshape(rows, d) * mask_ref[...]
        df = dg * rate_ref[:, lanes] * jax.nn.sigmoid(f)
        dpf_ref[:, lanes] = df.astype(dpf_ref.dtype)
        add(3 * taps, df)
        add(3 * taps + 1, dg * rate_ref[:, lanes] * _softplus(f))


def _in_operands(projections, taps, a_log, dt_bias, mask):
    """What both kernels read beside the projections: the mask as a float32
    column, ``-exp(A_log)`` a channel, ``dt_bias`` and the taps as rows."""
    d = projections[0].shape[-1] // a_log.shape[0]
    rate = jnp.repeat(-jnp.exp(a_log.astype(_F32)), d)[None]
    return (mask.astype(_F32)[..., None], rate, dt_bias.astype(_F32)[None],
            *(w.astype(_F32) for w in taps))


def _in_sizes(projections, taps, a_log, chunk, group):
    """(B, H, d, groups, halo rows, taps, the specs) of an input stage."""
    b, s, hd = projections[0].shape
    h = a_log.shape[0]
    halo = _halo_rows(projections[0].dtype)
    groups = s // (group * chunk)
    return (b, h, hd // h, groups, halo, taps[0].shape[0],
            _in_specs(b, h, hd // h, group, chunk, halo, groups))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda_in(projections, taps, a_log, dt_bias, mask, chunk, group):
    pq, pk, pv, pf = projections
    b, h, d, groups, halo, size, sp = _in_sizes(projections, taps, a_log,
                                                chunk, group)
    laid = (groups, group, b * h, chunk, d)
    return tuple(pallas_call(
        functools.partial(_in_fwd_kernel, q_scale=d ** -0.5),
        name="kda_in_fwd", grid=sp["grid"],
        in_specs=[sp["mask"], sp["columns"](1), sp["columns"](1)]
        + [sp["columns"](size)] * 3
        + [sp["block"], sp["before"]] * 3 + [sp["block"]],
        out_specs=[sp["operand"]] * 4,
        out_shape=[jax.ShapeDtypeStruct(laid, pq.dtype)] * 3
        + [jax.ShapeDtypeStruct(laid, _F32)],
        scratch_shapes=[pltpu.VMEM((3, halo + group * chunk, d), _F32)],
        compiler_params=_PARAMS,
    )(*_in_operands(projections, taps, a_log, dt_bias, mask),
      pq, pq, pk, pk, pv, pv, pf))


def _kda_in_fwd(projections, taps, a_log, dt_bias, mask, chunk, group):
    return (_kda_in(projections, taps, a_log, dt_bias, mask, chunk, group),
            (projections, taps, a_log, dt_bias, mask))


def _kda_in_bwd(chunk, group, residuals, cotangents):
    projections, taps, a_log, dt_bias, mask = residuals
    pq, pk, pv, pf = projections
    dq, dk, dv, dg = cotangents
    b, h, d, groups, halo, size, sp = _in_sizes(projections, taps, a_log,
                                                chunk, group)
    rows = group * chunk
    model = jax.ShapeDtypeStruct(pq.shape, pq.dtype)
    small_rows = 3 * size + 2
    *d_projections, small = pallas_call(
        functools.partial(_in_bwd_kernel, q_scale=d ** -0.5),
        name="kda_in_bwd", grid=sp["grid"],
        in_specs=[sp["mask"], sp["columns"](1), sp["columns"](1)]
        + [sp["columns"](size)] * 3
        + [sp["block"], sp["before"], sp["after"], sp["operand"],
           sp["operand_after"]] * 3 + [sp["block"], sp["operand"]],
        out_specs=[sp["block"]] * 4
        + [pl.BlockSpec((h, small_rows, d), lambda i, j, n: (0, 0, 0))],
        out_shape=[model] * 4
        + [jax.ShapeDtypeStruct((h, small_rows, d), _F32)],
        scratch_shapes=[pltpu.VMEM((3, rows + 2 * halo, d), _F32),
                        pltpu.VMEM((rows + halo, d), _F32)],
        compiler_params=_PARAMS,
    )(*_in_operands(projections, taps, a_log, dt_bias, mask),
      pq, pq, pq, dq, dq, pk, pk, pk, dk, dk, pv, pv, pv, dv, dv, pf, dg)
    small = jnp.moveaxis(small, 0, 1).reshape(small_rows, h * d)
    d_taps = tuple(small[i * size:(i + 1) * size].astype(w.dtype)
                   for i, w in enumerate(taps))
    d_a_log = small[3 * size + 1].reshape(h, d).sum(-1).astype(a_log.dtype)
    return (tuple(d_projections), d_taps, d_a_log,
            small[3 * size].astype(dt_bias.dtype), None)


_kda_in.defvjp(_kda_in_fwd, _kda_in_bwd)


@functools.partial(jax.jit, static_argnames=("chunk", "group"))
def kda_in(projections, taps, a_log, dt_bias, mask, *, chunk: int = kda.CHUNK,
           group: int = kda.GROUP):
    """The input stage (module text). ``projections``: q~, k~, v~ and f, each
    (B, S, H*D); ``taps``: the three convolutions' (K, H*D); ``a_log`` (H,),
    ``dt_bias`` (H*D,), ``mask`` (B, S), true at real tokens. Returns q, k, v
    in the projections' type and g float32, each laid out (groups, group,
    B*H, C, d) as :func:`ops.kda.lay_out` lays (B, S, H, d) out."""
    group, _, pad = kda.layout(projections[0].shape[1], chunk, group)
    if pad:
        projections = tuple(jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                            for x in projections)
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    return _kda_in(tuple(projections), tuple(taps), a_log, dt_bias, mask,
                   chunk, group)


# --------------------------------------------------------------------------
# the output stage
# --------------------------------------------------------------------------

def _out_fwd_kernel(scale_ref, o_ref, gate_ref, y_ref, *, eps: float):
    rows, d = y_ref.shape[0], o_ref.shape[-1]
    for m, lanes in _heads(y_ref, d):
        o = o_ref[:, m].astype(_F32).reshape(rows, d)
        unit = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
        y = (unit * scale_ref[...]
             * jax.nn.sigmoid(gate_ref[:, lanes].astype(_F32)))
        y_ref[:, lanes] = y.astype(y_ref.dtype)


def _out_bwd_kernel(scale_ref, o_ref, gate_ref, dy_ref, do_ref, dgate_ref,
                    dscale_ref, *, eps: float):
    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0)
             & (pl.program_id(2) == 0))
    def _():
        dscale_ref[...] = jnp.zeros(dscale_ref.shape, _F32)

    rows, d = dy_ref.shape[0], o_ref.shape[-1]
    laid = do_ref.shape[:1] + do_ref.shape[2:]
    for m, lanes in _heads(dy_ref, d):
        dy = dy_ref[:, lanes].astype(_F32)
        o = o_ref[:, m].astype(_F32).reshape(rows, d)
        r = jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
        unit = o * r
        sig = jax.nn.sigmoid(gate_ref[:, lanes].astype(_F32))
        dgate_ref[:, lanes] = (dy * unit * scale_ref[...] * sig
                               * (1.0 - sig)).astype(dgate_ref.dtype)
        dscale_ref[...] += jnp.sum(dy * sig * unit, 0, keepdims=True)
        dn = dy * sig * scale_ref[...]
        do = r * (dn - unit * jnp.mean(dn * unit, -1, keepdims=True))
        do_ref[:, m] = do.reshape(laid).astype(do_ref.dtype)


def _out_specs(o, gate):
    groups, group, bh, chunk, d = o.shape
    b = gate.shape[0]
    sp = _specs(b, bh // b, d, group, chunk, groups)
    return sp, pl.BlockSpec((1, d), lambda i, j, n: (0, 0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _kda_out(o, gate, scale, eps):
    sp, whole = _out_specs(o, gate)
    return pallas_call(
        functools.partial(_out_fwd_kernel, eps=eps), name="kda_out_fwd",
        grid=sp["grid"], in_specs=[whole, sp["operand"], sp["block"]],
        out_specs=sp["block"],
        out_shape=jax.ShapeDtypeStruct(gate.shape, o.dtype),
        compiler_params=_PARAMS,
    )(scale.astype(_F32)[None], o, gate)


def _kda_out_fwd(o, gate, scale, eps):
    return _kda_out(o, gate, scale, eps), (o, gate, scale)


def _kda_out_bwd(eps, residuals, dy):
    o, gate, scale = residuals
    sp, whole = _out_specs(o, gate)
    do, dgate, dscale = pallas_call(
        functools.partial(_out_bwd_kernel, eps=eps), name="kda_out_bwd",
        grid=sp["grid"],
        in_specs=[whole, sp["operand"], sp["block"], sp["block"]],
        out_specs=[sp["operand"], sp["block"], whole],
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype),
                   jax.ShapeDtypeStruct(gate.shape, gate.dtype),
                   jax.ShapeDtypeStruct((1, scale.shape[0]), _F32)],
        compiler_params=_PARAMS,
    )(scale.astype(_F32)[None], o, gate, dy)
    return do, dgate, dscale[0].astype(scale.dtype)


_kda_out.defvjp(_kda_out_fwd, _kda_out_bwd)


@functools.partial(jax.jit, static_argnames=("eps",))
def kda_out(o, gate, scale, *, eps: float):
    """The output stage (module text): ``o`` (groups, group, B*H, C, d) as
    the operator gives it, ``gate`` (B, S, H*D), ``scale`` (d,). Returns
    (B, S, H*D) in ``o``'s type."""
    seq_len = gate.shape[1]
    pad = o.shape[0] * o.shape[1] * o.shape[3] - seq_len
    if pad:
        gate = jnp.pad(gate, ((0, 0), (0, pad), (0, 0)))
    return _kda_out(o, gate, scale, eps)[:, :seq_len]
