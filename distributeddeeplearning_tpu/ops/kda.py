"""Gated delta-rule linear attention with a gate a channel (Kimi Delta
Attention, arXiv:2510.26692): no softmax, a ``(d_k, d_v)`` state a head that
is carried along the sequence, decayed channel by channel and corrected by a
delta rule. For one head, with ``g_t <= 0`` (d_k,) and ``beta_t`` a scalar:

    S'  = Diag(exp g_t) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

:func:`kda_recurrent` is that, token by token (a ``lax.scan``; what the tests
hold the chunked form to, and what a decode step is). :func:`kda_chunked` is
the form a sequence is trained with: inside a chunk of ``C`` tokens the
recurrence is a few matrix products, and only the state goes from chunk to
chunk. With ``G_t`` the gates summed from the chunk's start to ``t``
(float32), ``S_0`` the state that enters and ``u_i = beta_i (v_i - S'_i^T
k_i)`` the row each token writes,

    S_t = Diag(exp G_t) S_0 + sum_{i<=t} Diag(exp(G_t - G_i)) k_i u_i^T
    (I + Diag(beta) A) U = Diag(beta) (V - (K * exp G) S_0),
        A_ij = sum_c k_i[c] k_j[c] exp(G_i[c] - G_j[c])   for j < i, else 0
    O    = (Q * exp G) S_0 + B U,
        B_ij = sum_c q_i[c] k_j[c] exp(G_i[c] - G_j[c])   for j <= i, else 0
    S_C  = Diag(exp G_C) S_0 + (K * exp(G_C - G))^T U

so with ``T = (I + Diag(beta) A)^-1 Diag(beta)``, ``U = T V - (T (K * exp
G)) S_0``: ``T V`` and ``T (K * exp G)`` need no state and are made for many
chunks at once, and the loop over chunks holds four products.

**Staying finite.** ``A`` and ``B`` hold ``exp(G_i - G_j)`` with ``j <= i``,
never above 1, but as a product of two factors ``exp(G_i) exp(-G_j)`` the
second overflows float32 once a chunk's gates pass -88 (a gate of -1.6 a
token does it in 64; the model's initial gates reach that). So the
difference is taken before the exponential. A chunk is cut into sub-chunks
of ``sub`` tokens: a block of ``A`` below the diagonal, rows in sub-chunk a
and columns before it, factors round ``r_a``, the cumulative gate at a's
first row, as ``exp(G_i - r_a) exp(r_a - G_j)``, both at most 1; a
block on the diagonal is summed pair by pair, ``exp(G_i - G_j)`` itself. An
underflow to 0 is the true value to rounding. Nothing divides by a decay.

**The inverse.** ``I + Diag(beta) A`` is unit lower triangular. Its ``sub``
wide diagonal blocks ``I + L_d`` are inverted by the finite series ``(I -
L_d)(I + L_d^2)(I + L_d^4)...`` (``L_d^sub = 0``), and the rest by the same
series in ``M = (I + L_d)^-1 L_off``, which is nilpotent in blocks
(``M^(C/sub) = 0``); all in float32 at three bfloat16 passes a product, a
few (C, C, C) products a chunk and head.

**Memory and the backward pass.** Chunks are taken ``group`` at a time under
a ``lax.scan``; inside a group, what needs no state is made for all its
chunks at once and a second scan hands the state through them. The backward
rule (:func:`_groups_bwd`) keeps the operands and the state that enters each
group ((S / (C * group)) x B x H x d_k x d_v float32) and walks the groups
last to first, remaking each from those and differentiating it there, so
neither pass holds more than one group's (B*H, group, sub, sub, d_k) pair
sums. Inside a group the gradient is jax's own of these products, except the
two pieces whose own derivative is far cheaper than their series': the
inverse (``dL = -X^T dX X^T``) and the in-chunk scores (the gates' gradient
is ``x * dx - y * dy``, with no pass of its own). The result and the entering
states are named (``KDA_OUT``, ``KDA_STATES``) for a recomputed block to keep.
A last short chunk is padded with ``g = 0, beta = 0``, which leaves the state
as it is.

Layout: :func:`kda_recurrent` and :func:`kda_chunked` take the models' ``(B,
S, H, D)``; ``g`` is ``(B, S, H, d_k)`` float32, ``beta`` ``(B, S, H)``.
Products take their operands in ``q``'s type and add up in float32; the
state, the gates and the inverse are float32.

**The layout the groups are scanned in, and who writes it.** ``_groups``
scans operands ``(groups, group, B*H, C, d)``: group of chunks, chunk of the
group, batch row and head merged (``b * H + h``), token of the chunk, channel
(:func:`layout` has the counts, :func:`lay_out` the transpose; a sequence is
padded to whole groups with ``g = 0, beta = 0`` and zeros elsewhere).
:func:`kda_groups` is the operator on operands that are already so, and gives
its result so; :func:`kda_chunked` lays out with XLA, calls it, and lays the
result back. A model does neither relayout as a pass of its own: the
pointwise stages on either side of the operator (ops/kda_stages.py: the
short convolutions with SiLU, the L2 norms and the gate before it, the gated
RMSNorm after it) are fused kernels whose block index maps read ``(B, S,
H*D)`` and write this layout, and back. What they owe each other: q, k, v in
the model's compute type and g float32, every padded or masked row with ``g
= 0``; ``beta`` (B, S, H: small) is laid out by :func:`lay_out`; the result
comes back in ``v``'s type, padded rows and all, and the output stage drops
them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

_HIGHEST = jax.lax.Precision.HIGHEST
# of the float32 products that make the in-chunk matrices and the inverse:
# three bfloat16 passes (about float32's own rounding once the operands are
# at most 1 in size, as these are), at half the six of "highest"; their
# results are rounded to the operands' type before they meet q, k and v
_PRECISION = jax.lax.Precision.HIGH
# tokens a chunk of the chunked form, and of the counter that bounds its
# exponents (:func:`min_chunk_log_decay`)
CHUNK = 64
# chunks prepared at once, a group of the outer scan
GROUP = 8
# the collection a layer sows :func:`min_chunk_log_decay` into; the step's
# metrics carry the smallest over the layers (train/steps.py)
KDA_METRICS = "kda_metrics"


def kda_recurrent(q, k, v, g, beta, initial_state=None, *,
                  return_state: bool = False):
    """Token by token, in float32. q, k, g: (B, S, H, d_k); v: (B, S, H,
    d_v); beta: (B, S, H); ``initial_state``: (B, H, d_k, d_v) or None for
    zeros. Returns o (B, S, H, d_v) in ``v``'s type, and the last state with
    ``return_state``."""
    b, s, h, dk = q.shape
    f32 = jnp.float32
    state = (jnp.zeros((b, h, dk, v.shape[-1]), f32) if initial_state is None
             else initial_state.astype(f32))

    def step(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[..., None]
        read = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=_HIGHEST)
        u = beta_t[..., None] * (v_t - read)
        state = state + k_t[..., :, None] * u[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                 precision=_HIGHEST)

    xs = tuple(jnp.moveaxis(x.astype(f32), 1, 0) for x in (q, k, v, g, beta))
    state, out = jax.lax.scan(step, state, xs)
    out = jnp.moveaxis(out, 0, 1).astype(v.dtype)
    return (out, state) if return_state else out


def min_chunk_log_decay(g):
    """The most negative cumulative gate any chunk of the chunked form
    reaches, from the gates as its groups are scanned (:func:`lay_out`: ...,
    C, d_k): the smallest, over chunks, heads and channels, of a chunk's
    summed gates (gates are never positive, so a chunk's sum is its lowest
    point). It is what bounds the chunked form's arithmetic: every
    ``exp`` there is of a difference of cumulative gates inside one chunk."""
    return g.astype(jnp.float32).sum(-2).min()


def _mm(a, b):
    return jnp.matmul(a, b, precision=_PRECISION)


def _nilpotent_inverse(lower, steps: int):
    """(I + L)^-1 for ``L`` with ``L^(2^steps) = 0``: (I - L)(I + L^2)(I +
    L^4)..., ``steps`` factors."""
    eye = jnp.eye(lower.shape[-1], dtype=lower.dtype)
    inv, power = eye - lower, lower
    for _ in range(steps - 1):
        power = _mm(power, power)
        inv = inv + _mm(inv, power)
    return inv


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _unit_lower_inverse(lower, sub: int):
    """(I + L)^-1 of a strictly lower triangular (..., C, C) ``L``: the
    ``sub``-wide diagonal blocks by their own series, then the blocks below
    them (module text). Its backward rule is the inverse's own, ``dL = -X^T
    dX X^T``: two products, where the series' derivative is twenty."""
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


def _pair_factors(x, y, cum, sub: int):
    """The pieces :func:`_pair_scores` and its backward rule share: the
    operands by sub-chunk, the pair decays of the diagonal blocks, and the
    two factors round each row sub-chunk's first cumulative gate."""
    c, d = x.shape[-2:]
    lead = x.shape[:-2]
    n = c // sub
    xb, yb, gb = (t.reshape(lead + (n, sub, d)) for t in (x, y, cum))
    diff = gb[..., :, None, :] - gb[..., None, :, :]
    i, j = jnp.arange(sub)[:, None], jnp.arange(sub)[None, :]
    decay = jnp.exp(jnp.where((j < i)[..., None], diff, -jnp.inf))
    first = gb[..., :, :1, :]                             # (..., n, 1, d)
    row_factor = jnp.exp(gb - first)                      # (..., n, sub, d)
    before = (jnp.arange(c)[None, :] // sub) < jnp.arange(n)[:, None]
    col_factor = jnp.exp(jnp.where(                       # (..., n, C, d)
        before[..., None], first - cum[..., None, :, :], -jnp.inf))
    return xb, yb, decay, row_factor, col_factor


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pair_scores(x, y, cum, sub: int):
    """P_ij = sum_c x_i[c] y_j[c] exp(cum_i[c] - cum_j[c]) for j < i, else 0,
    over the last two axes (C, d) of float32 operands, with every exponent
    at most 0 (module text): diagonal blocks pair by pair, the blocks below
    them as products of the rows of sub-chunk a and the columns before it,
    both round the cumulative gate at a's first row."""
    c = x.shape[-2]
    lead = x.shape[:-2]
    n = c // sub
    xb, yb, decay, row_factor, col_factor = _pair_factors(x, y, cum, sub)
    diag = (xb[..., :, None, :] * yb[..., None, :, :] * decay).sum(-1)
    if n == 1:
        return diag.reshape(lead + (c, c))
    below = jnp.einsum("...aid,...ajd->...aij", xb * row_factor,
                       y[..., None, :, :] * col_factor,
                       precision=_PRECISION)              # (..., n, sub, C)
    # block a of the diagonal to rows and columns a of the (C, C) result
    same = jnp.eye(n, dtype=bool)[:, None, :, None]
    full = jnp.where(same, diag[..., :, :, None, :],
                     below.reshape(lead + (n, sub, n, sub)))
    return full.reshape(lead + (c, c))


def _pair_scores_fwd(x, y, cum, sub):
    return _pair_scores(x, y, cum, sub), (x, y, cum)


def _pair_scores_bwd(sub, residuals, d_scores):
    """dx_i = sum_j dP_ij y_j E_ij and dy_j = sum_i dP_ij x_i E_ij by the
    forward's own factors; every term of P holds exp(G_i - G_j) once, so
    dG = x * dx - y * dy, a channel at a time, with no pass of its own."""
    x, y, cum = residuals
    c = x.shape[-2]
    lead = x.shape[:-2]
    n = c // sub
    xb, yb, decay, row_factor, col_factor = _pair_factors(x, y, cum, sub)
    d4 = d_scores.reshape(lead + (n, sub, n, sub))
    same = jnp.eye(n, dtype=bool)[:, None, :, None]
    d_diag = jnp.where(same, d4, 0.0).sum(-2)             # (..., n, sub, sub)
    weighted = d_diag[..., None] * decay                  # (..., n, i, j, d)
    dx = (weighted * yb[..., None, :, :]).sum(-2)
    dy = (weighted * xb[..., :, None, :]).sum(-3)
    if n > 1:
        d_below = d_scores.reshape(lead + (n, sub, c))
        dx = dx + row_factor * jnp.einsum(
            "...aij,...ajd->...aid", d_below, y[..., None, :, :] * col_factor,
            precision=_PRECISION)
        d_cols = jnp.einsum("...aij,...aid->...ajd", d_below,
                            xb * row_factor, precision=_PRECISION)
        dy = dy + (d_cols * col_factor).sum(-3).reshape(yb.shape)
    dx, dy = dx.reshape(x.shape), dy.reshape(y.shape)
    return dx, dy, x * dx - y * dy


_pair_scores.defvjp(_pair_scores_fwd, _pair_scores_bwd)


def _prepare(q, k, v, g, beta, sub: int):
    """What a group of chunks needs that no state enters. Operands (n, B*H,
    C, d) (beta without d; g and beta float32); everything float32 here.
    Returns the operands of the loop over chunks, chunk first."""
    f32 = jnp.float32
    dtype = q.dtype
    c = q.shape[-2]
    qf, kf = q.astype(f32), k.astype(f32)
    # G_t: the gates summed from the chunk's start, as a product with a
    # triangle of ones (a reduce-window is many passes on the chip)
    cum = jnp.einsum("ts,...sd->...td", jnp.tril(jnp.ones((c, c), f32)), g,
                     precision=_HIGHEST)
    a = _pair_scores(kf, kf, cum, sub)                     # (.., C, C)
    # a token with itself carries no gate, so none in its gradient either
    # (as a difference of cumulative gates it would cancel to rounding only)
    bm = (_pair_scores(qf, kf, cum, sub)
          + (qf * kf).sum(-1)[..., None] * jnp.eye(c, dtype=f32))
    t = _unit_lower_inverse(beta[..., None] * a, sub) * beta[..., None, :]
    td = t.astype(dtype)
    decayed = jnp.exp(cum)
    k_in = (kf * decayed).astype(dtype)                    # K * exp G
    w = jnp.matmul(td, k_in, preferred_element_type=f32)   # T (K exp G)
    tv = jnp.matmul(td, v, preferred_element_type=f32)     # T V
    q_in = (qf * decayed).astype(dtype)
    last = cum[..., -1:, :]
    k_out = (kf * jnp.exp(last - cum)).astype(dtype)       # K exp(G_C - G)
    return (w.astype(dtype), tv, bm.astype(dtype), q_in, k_out,
            jnp.exp(last[..., 0, :]))


def _group(state, xs, sub: int):
    """One group of chunks, operands (n, B*H, C, d): state (B*H, d_k, d_v)
    in, (state out, the group's outputs (n, B*H, C, d_v))."""
    f32 = jnp.float32
    q, k, v, g, beta = xs
    dtype = q.dtype

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

    return jax.lax.scan(chunk, state, _prepare(q, k, v, g, beta, sub))


# ``checkpoint_name`` of what the chunked operator's forward rule hands its
# backward rule beside its own operands, for a recomputed block to keep:
# the result and the state that enters each group of chunks. With both kept
# (``jax.checkpoint_policies.save_only_these_names(KDA_OUT, KDA_STATES)``)
# the block's recomputed forward holds no loop over chunks at all: the
# backward rule remakes each group from its operands and its entering state.
KDA_OUT = "kda_out"
KDA_STATES = "kda_group_states"


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _groups(state, xs, sub):
    """All groups: (last state, outputs (groups, n, B*H, C, d_v))."""
    return jax.lax.scan(lambda st, x: _group(st, x, sub), state, xs)


def _groups_fwd(state, xs, sub):
    def body(st, x):
        new, out = _group(st, x, sub)
        return new, (out, st)

    last, (out, entering) = jax.lax.scan(body, state, xs)
    out = checkpoint_name(out, KDA_OUT)
    entering = checkpoint_name(entering, KDA_STATES)
    return (last, out), (xs, entering)


def _groups_bwd(sub, residuals, cotangents):
    """Back through the groups, last first: each is remade from its operands
    and the state that entered it, and differentiated by jax, so no more
    than one group's intermediates are alive."""
    xs, entering = residuals
    d_last, d_out = cotangents

    def body(d_state, x):
        xs_g, st, d_out_g = x
        _, vjp = jax.vjp(lambda a, b: _group(a, b, sub), st, xs_g)
        d_state, d_xs = vjp((d_state, d_out_g))
        return d_state, d_xs

    d_state, d_xs = jax.lax.scan(body, d_last, (xs, entering, d_out),
                                 reverse=True)
    return d_state, d_xs


_groups.defvjp(_groups_fwd, _groups_bwd)


def layout(s: int, chunk: int = CHUNK, group: int = GROUP):
    """How ``s`` tokens go into groups of chunks: (chunks a group, groups,
    rows of padding after the last token). A sequence shorter than a group
    is one group of its own chunks."""
    n = -(-s // chunk)
    group = min(group, n)
    groups = -(-n // group)
    return group, groups, groups * group * chunk - s


def lay_out(x, chunk: int = CHUNK, group: int = GROUP):
    """(B, S, H, ...) -> (groups, group, B*H, C, ...), the layout the groups
    are scanned in: padded with zeros to whole groups, in one transpose."""
    b, s, h = x.shape[:3]
    group, groups, pad = layout(s, chunk, group)
    x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    x = x.reshape((b, groups, group, chunk) + x.shape[2:])
    x = jnp.moveaxis(x, (1, 2, 4), (0, 1, 3))
    return x.reshape(x.shape[:2] + (b * h,) + x.shape[4:])


def lay_back(x, b: int, s: int):
    """:func:`lay_out`'s inverse for a result: (groups, group, B*H, C, d) ->
    (B, S, H, d), the padded rows dropped."""
    groups, group, bh, chunk, d = x.shape
    x = x.reshape(groups, group, b, bh // b, chunk, d)
    return jnp.moveaxis(x, (0, 1, 3), (1, 2, 4)).reshape(
        b, groups * group * chunk, bh // b, d)[:, :s]


def kda_groups(q, k, v, g, beta, initial_state=None, *, sub: int = 16,
               return_state: bool = False):
    """The chunked form on operands already laid out (:func:`lay_out`;
    ops/kda_stages.py writes them so): q, k, g (groups, group, B*H, C, d_k),
    v (..., d_v), beta (groups, group, B*H, C); g and beta float32, padded
    rows ``g = 0, beta = 0``; ``initial_state`` (B*H, d_k, d_v) or None for
    zeros. Returns o (groups, group, B*H, C, d_v) in ``v``'s type, and the
    last state (B*H, d_k, d_v) with ``return_state``."""
    chunk = q.shape[3]
    if chunk % sub or (chunk // sub) & (chunk // sub - 1):
        raise ValueError(f"chunk {chunk} must be sub {sub} times a power of "
                         f"two")
    state = (jnp.zeros((q.shape[2], q.shape[4], v.shape[4]), jnp.float32)
             if initial_state is None else initial_state.astype(jnp.float32))
    state, out = _groups(state, (q, k, v, g, beta), sub)
    return (out, state) if return_state else out


def kda_chunked(q, k, v, g, beta, initial_state=None, *, chunk: int = CHUNK,
                sub: int = 16, group: int = GROUP,
                return_state: bool = False):
    """The chunked form (module text); same arguments and results as
    :func:`kda_recurrent`. ``chunk`` tokens a chunk (``sub`` times a power
    of two), ``group`` chunks prepared at once. Lays the operands out with
    XLA and runs :func:`kda_groups`."""
    b, s, h, dk = q.shape
    group = layout(s, chunk, group)[0]
    if initial_state is not None:
        initial_state = initial_state.reshape((b * h,)
                                              + initial_state.shape[2:])
    out, state = kda_groups(
        *(lay_out(x, chunk, group)
          for x in (q, k, v, g.astype(jnp.float32),
                    beta.astype(jnp.float32))),
        initial_state, sub=sub, return_state=True)
    out = lay_back(out, b, s)
    state = state.reshape((b, h) + state.shape[1:])
    return (out, state) if return_state else out
