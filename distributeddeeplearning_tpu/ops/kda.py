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
G)) S_0``: ``T V`` and ``T (K * exp G)`` need no state and are made for a
group of chunks at once, and the loop over chunks holds four products.

**Staying finite.** ``A`` and ``B`` hold ``exp(G_i - G_j)`` with ``j <= i``,
never above 1, but as a product of two factors ``exp(G_i) exp(-G_j)`` the
second overflows float32 once a chunk's gates pass -88 (a gate of -1.6 a
token does it in 64; the model's initial gates reach that). So every
exponent is a difference of cumulative gates that is at most 0. The chunk is
halved level by level: a block of ``2 m`` tokens gives its lower left (m, m)
quarter, whose every row comes after its every column, as a product of two
factors round ``r``, the cumulative gate at the block's row ``m``: ``exp(G_i
- r) exp(r - G_j)``, both at most 1; its two quarters on the diagonal go to
the next level, down to blocks of 4 tokens, which are summed pair by pair,
``exp(G_i - G_j)`` itself. An underflow to 0 is the true value to rounding.
Nothing divides by a decay.

**The inverse.** ``I + Diag(beta) A`` is unit lower triangular. Its ``sub``
wide diagonal blocks ``I + L_d`` are inverted by the finite series ``(I -
L_d)(I + L_d^2)(I + L_d^4)...`` (``L_d^sub = 0``), and the rest by the same
series in ``M = (I + L_d)^-1 L_off``, which is nilpotent in blocks
(``M^(C/sub) = 0``); all in float32 at three bfloat16 passes a product.

**What runs where.** Everything above that no state enters -- ``G``, ``A``,
``B``, ``T``, ``T (K exp G)``, ``T V``, ``Q exp G``, ``K exp(G_C - G)`` and
``exp G_C`` -- is one Pallas kernel forward and one backward
(ops/kda_chunk.py, :func:`ops.kda_chunk.prepare`: a grid step is a chunk of
the group and eight heads, and what lies between the operands and those
results never leaves VMEM). Its precision: the gates summed in float32
(exact products with a triangle of ones); the scores' and the inverse's
float32 products at three bfloat16 passes (each operand a bfloat16 head and
tail, ``hi hi + hi lo + lo hi``, written out since Mosaic lowers no
``Precision.HIGH``: a CPU computes the same three); the sums over a pair's
channels in float32; ``T`` rounded to q's type before it meets K and V, and
those products added up in float32. What XLA still runs of the operator is
the loop over a group's chunks (:func:`_group`: four products a chunk with
the state in float32) and the scan over groups.

**Memory and the backward pass.** Chunks are taken ``group`` at a time under
a ``lax.scan``; the kernel makes a group's stateless operands at once (in
HBM a group holds just those six results, (group, B*H, C, d) each) and a
second scan hands the state through its chunks. The backward rule
(:func:`_groups_bwd`) keeps the operands and the state that enters each
group ((S / (C * group)) x B x H x d_k x d_v float32) and walks the groups
last to first, remaking each from those and differentiating it there with
``jax.vjp``: the loop over chunks by jax's own rule, the kernel by its
``custom_vjp``, whose residuals are its operands and whose backward kernel
remakes ``G``, ``A`` and ``T`` in VMEM and applies each piece's own rule
(the inverse's ``dL = -X^T dX X^T``; the scores' gates' gradient ``x * dx - y
* dy``, with no pass of its own). The result and the entering states are
named (``KDA_OUT``, ``KDA_STATES``) for a recomputed block to keep. A last
short chunk is padded with ``g = 0, beta = 0``, which leaves the state as it
is.

Layout: :func:`kda_recurrent` and :func:`kda_chunked` take the models' ``(B,
S, H, D)``; ``g`` is ``(B, S, H, d_k)`` float32, ``beta`` ``(B, S, H)``.
Products with q, k, v or the state take their operands in ``q``'s type and
add up in float32; the state, the gates and the inverse are float32.

**The layout the groups are scanned in, and who writes it.** ``_groups``
scans operands ``(groups, group, B*H, C, d)``: group of chunks, chunk of the
group, batch row and head merged (``b * H + h``), token of the chunk, channel
(:func:`layout` has the counts, :func:`lay_out` the transpose; a sequence is
padded to whole groups with ``g = 0, beta = 0`` and zeros elsewhere).
:func:`kda_groups` is the operator on operands that are already so, and gives
its result so (a group's five operands go to ops/kda_chunk.py's kernels as
they lie, blocks of (heads, C, d)); :func:`kda_chunked` lays out with XLA,
calls it, and lays the result back. A model does neither relayout as a pass of its own: the
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

from distributeddeeplearning_tpu.ops import kda_chunk

_HIGHEST = jax.lax.Precision.HIGHEST
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

    return jax.lax.scan(chunk, state,
                        kda_chunk.prepare(q, k, v, g, beta, sub))


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


@functools.partial(jax.jit, static_argnames=("sub", "return_state"))
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
