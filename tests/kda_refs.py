"""Plain references for the chunked delta rule (ops/kda.py,
ops/kda_chunk.py): what the operator computed as XLA array lines before its
kernels, kept to hold them to.

:func:`prepare` is a group's stateless work (the cumulative gates, the
in-chunk scores, the inverse and the decayed operands) as it stood before
it was a kernel, with ``jax.custom_vjp`` rules for the inverse and the
scores; :func:`xla_groups` is the loop over chunks as XLA ran it before that
was a kernel too: a scan over groups of a scan over a group's chunks, four
products a chunk with the state in float32, each operand rounded where the
kernels round it.
"""

import functools

import jax
import jax.numpy as jnp

_HIGH, _HIGHEST = jax.lax.Precision.HIGH, jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=_HIGH)


def _nilpotent_inverse(lower, steps):
    eye = jnp.eye(lower.shape[-1], dtype=lower.dtype)
    inv, power = eye - lower, lower
    for _ in range(steps - 1):
        power = _mm(power, power)
        inv = inv + _mm(inv, power)
    return inv


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _unit_lower_inverse(lower, sub):
    c = lower.shape[-1]
    block = jnp.arange(c) // sub
    on_diagonal = block[:, None] == block[None, :]
    diag_inv = _nilpotent_inverse(jnp.where(on_diagonal, lower, 0.0),
                                  max(1, (sub - 1).bit_length()))
    if c == sub:
        return diag_inv
    m = _mm(diag_inv, jnp.where(on_diagonal, 0.0, lower))
    return _mm(_nilpotent_inverse(m, max(1, (c // sub - 1).bit_length())),
               diag_inv)


def _unit_lower_inverse_fwd(lower, sub):
    inverse = _unit_lower_inverse(lower, sub)
    return inverse, inverse


def _unit_lower_inverse_bwd(sub, inverse, d_inverse):
    t = jnp.swapaxes(inverse, -1, -2)
    return (-_mm(_mm(t, d_inverse), t),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _pair_factors(x, y, cum, sub):
    c, d = x.shape[-2:]
    lead = x.shape[:-2]
    n = c // sub
    xb, yb, gb = (t.reshape(lead + (n, sub, d)) for t in (x, y, cum))
    diff = gb[..., :, None, :] - gb[..., None, :, :]
    i, j = jnp.arange(sub)[:, None], jnp.arange(sub)[None, :]
    decay = jnp.exp(jnp.where((j < i)[..., None], diff, -jnp.inf))
    first = gb[..., :, :1, :]
    row_factor = jnp.exp(gb - first)
    before = (jnp.arange(c)[None, :] // sub) < jnp.arange(n)[:, None]
    col_factor = jnp.exp(jnp.where(
        before[..., None], first - cum[..., None, :, :], -jnp.inf))
    return xb, yb, decay, row_factor, col_factor


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pair_scores(x, y, cum, sub):
    c = x.shape[-2]
    lead = x.shape[:-2]
    n = c // sub
    xb, yb, decay, row_factor, col_factor = _pair_factors(x, y, cum, sub)
    diag = (xb[..., :, None, :] * yb[..., None, :, :] * decay).sum(-1)
    if n == 1:
        return diag.reshape(lead + (c, c))
    below = jnp.einsum("...aid,...ajd->...aij", xb * row_factor,
                       y[..., None, :, :] * col_factor, precision=_HIGH)
    same = jnp.eye(n, dtype=bool)[:, None, :, None]
    full = jnp.where(same, diag[..., :, :, None, :],
                     below.reshape(lead + (n, sub, n, sub)))
    return full.reshape(lead + (c, c))


def _pair_scores_fwd(x, y, cum, sub):
    return _pair_scores(x, y, cum, sub), (x, y, cum)


def _pair_scores_bwd(sub, residuals, d_scores):
    x, y, cum = residuals
    c = x.shape[-2]
    lead = x.shape[:-2]
    n = c // sub
    xb, yb, decay, row_factor, col_factor = _pair_factors(x, y, cum, sub)
    d4 = d_scores.reshape(lead + (n, sub, n, sub))
    same = jnp.eye(n, dtype=bool)[:, None, :, None]
    d_diag = jnp.where(same, d4, 0.0).sum(-2)
    weighted = d_diag[..., None] * decay
    dx = (weighted * yb[..., None, :, :]).sum(-2)
    dy = (weighted * xb[..., :, None, :]).sum(-3)
    if n > 1:
        d_below = d_scores.reshape(lead + (n, sub, c))
        dx = dx + row_factor * jnp.einsum(
            "...aij,...ajd->...aid", d_below, y[..., None, :, :] * col_factor,
            precision=_HIGH)
        d_cols = jnp.einsum("...aij,...aid->...ajd", d_below,
                            xb * row_factor, precision=_HIGH)
        dy = dy + (d_cols * col_factor).sum(-3).reshape(yb.shape)
    dx, dy = dx.reshape(x.shape), dy.reshape(y.shape)
    return dx, dy, x * dx - y * dy


_pair_scores.defvjp(_pair_scores_fwd, _pair_scores_bwd)


def prepare(q, k, v, g, beta, sub):
    """A group's stateless operands as array lines, operands (..., C, d),
    beta (..., C): ``(T (K exp G), T V, B, Q exp G, K exp(G_C - G), exp
    G_C)``, with ``g``'s type where the operator says float32 (so float64
    operands make it the yardstick)."""
    dtype, wide = q.dtype, g.dtype
    c = q.shape[-2]
    qf, kf = q.astype(wide), k.astype(wide)
    cum = jnp.einsum("ts,...sd->...td", jnp.tril(jnp.ones((c, c), wide)), g,
                     precision=_HIGHEST)
    a = _pair_scores(kf, kf, cum, sub)
    bm = (_pair_scores(qf, kf, cum, sub)
          + (qf * kf).sum(-1)[..., None] * jnp.eye(c, dtype=wide))
    t = _unit_lower_inverse(beta[..., None] * a, sub) * beta[..., None, :]
    td = t.astype(dtype)
    decayed = jnp.exp(cum)
    k_in = (kf * decayed).astype(dtype)
    w = jnp.matmul(td, k_in, preferred_element_type=wide)
    tv = jnp.matmul(td, v, preferred_element_type=wide)
    q_in = (qf * decayed).astype(dtype)
    last = cum[..., -1:, :]
    k_out = (kf * jnp.exp(last - cum)).astype(dtype)
    return (w.astype(dtype), tv, bm.astype(dtype), q_in, k_out,
            jnp.exp(last[..., 0, :]))


def xla_groups(q, k, v, g, beta, state, sub=16):
    """The operator on laid-out operands (ops/kda.py::lay_out: (groups,
    group, B*H, C, d); g and beta float32) from ``state`` (B*H, d_k, d_v)
    float32: (the last state, o (groups, group, B*H, C, d_v) in v's type)."""
    dtype, f32 = q.dtype, jnp.float32

    def chunk(state, x):
        w, tv, bm, q_in, k_out, decay = x
        sd = state.astype(dtype)
        u = tv - jnp.matmul(w, sd, preferred_element_type=f32)
        ud = u.astype(dtype)
        o = (jnp.matmul(q_in, sd, preferred_element_type=f32)
             + jnp.matmul(bm, ud, preferred_element_type=f32))
        state = state * decay[..., None] + jnp.einsum(
            "hck,hcv->hkv", k_out, ud, preferred_element_type=f32)
        return state, o.astype(v.dtype)

    def group(state, xs):
        return jax.lax.scan(chunk, state, prepare(*xs, sub))

    return jax.lax.scan(group, state, (q, k, v, g, beta))
