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
G)) S_0``: ``T V`` and ``T (K * exp G)`` need no state, and what goes from
chunk to chunk is four products with it.

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

**What runs where.** The whole chunked form is two Pallas kernels
(ops/kda_chunk.py: ``kda_fwd`` and, as the ``jax.custom_vjp`` rule of
:func:`_chunks`, ``kda_bwd``). A grid step is one chunk of eight heads, the
chunks innermost and in order, and the state of those heads lives in VMEM
from the first chunk to the last: a step makes what no state enters -- ``G``,
``A``, ``B``, ``T``, ``T (K exp G)``, ``T V``, ``Q exp G``, ``K exp(G_C -
G)`` and ``exp G_C`` -- and then the chunk's four products with the state,
and only the chunk's result and the state that entered it go to HBM.
Precision: the gates summed in float32 (exact products with a triangle of
ones); the scores' and the inverse's float32 products at three bfloat16
passes (each operand a bfloat16 head and tail, ``hi hi + hi lo + lo hi``,
written out since Mosaic lowers no ``Precision.HIGH``: a CPU computes the
same three); the sums over a pair's channels in float32; ``T`` rounded to
q's type before it meets K and V; the state float32, rounded to q's type
where it meets ``T (K exp G)`` and ``Q exp G``, ``U`` float32 and rounded
where it meets ``B`` and ``K exp(G_C - G)``, every product summed in
float32.

**Memory and the backward pass.** The forward kernel writes the state that
enters each chunk ((S / C) x B x H x d_k x d_v float32, 268 MB a layer of
the kimi cell); the backward kernel walks the chunks last to first with the
state's gradient in VMEM, remakes a chunk's stateless values and its rows
``U`` from its operands and its entering state, applies the four products'
rules and hands their cotangents to the stateless work's own rules (the
inverse's ``dL = -X^T dX X^T``; the scores' gates' gradient ``x * dx - y *
dy``, with no pass of its own), all in VMEM: no forward kernel runs in the
backward pass. The result and the entering states are named (``KDA_OUT``,
``KDA_STATES``) for a recomputed block to keep. A last short chunk is padded
with ``g = 0, beta = 0``, which leaves the state as it is.

Layout: :func:`kda_recurrent` and :func:`kda_chunked` take the models' ``(B,
S, H, D)``; ``g`` is ``(B, S, H, d_k)`` float32, ``beta`` ``(B, S, H)``.
Products with q, k, v or the state take their operands in ``q``'s type and
add up in float32; the state, the gates and the inverse are float32.

**The layout the operator reads, and who writes it.** Operands are
``(groups, group, B*H, C, d)``: group of chunks, chunk of the group, batch
row and head merged (``b * H + h``), token of the chunk, channel
(:func:`layout` has the counts, :func:`lay_out` the transpose; a sequence is
padded to whole groups with ``g = 0, beta = 0`` and zeros elsewhere); the
kernels take the first two axes as one, chunk by chunk.
:func:`kda_groups` is the operator on operands that are already so, and gives
its result so (a chunk's five operands go to the kernels as they lie, blocks
of (heads, C, d)); :func:`kda_chunked` lays out with XLA, calls it, and lays
the result back. A model does neither relayout as a pass of its own: the
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
# chunks a group of the layout (the stages' kernels write it so)
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


# ``checkpoint_name`` of what the chunked operator's forward rule hands its
# backward rule beside its own operands, for a recomputed block to keep:
# the result and the state that enters each chunk. With both kept
# (``jax.checkpoint_policies.save_only_these_names(KDA_OUT, KDA_STATES)``)
# the block's recomputed forward runs no forward kernel: the backward
# kernel remakes each chunk from its operands and its entering state.
KDA_OUT = "kda_out"
KDA_STATES = "kda_states"


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _chunks(q, k, v, g, beta, state, sub):
    """Every chunk, operands (n, B*H, C, d) and the entering state (B*H,
    d_v, d_k) transposed: (outputs (n, B*H, C, d_v), last state)."""
    out, _, last = kda_chunk.forward(q, k, v, g, beta, state, sub)
    return out, last


def _chunks_fwd(q, k, v, g, beta, state, sub):
    out, entering, last = kda_chunk.forward(q, k, v, g, beta, state, sub)
    out = checkpoint_name(out, KDA_OUT)
    entering = checkpoint_name(entering, KDA_STATES)
    return (out, last), (q, k, v, g, beta, entering)


def _chunks_bwd(sub, residuals, cotangents):
    return kda_chunk.backward(*residuals, *cotangents, sub)


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


def layout(s: int, chunk: int = CHUNK, group: int = GROUP):
    """How ``s`` tokens go into groups of chunks: (chunks a group, groups,
    rows of padding after the last token). A sequence shorter than a group
    is one group of its own chunks."""
    n = -(-s // chunk)
    group = min(group, n)
    groups = -(-n // group)
    return group, groups, groups * group * chunk - s


def lay_out(x, chunk: int = CHUNK, group: int = GROUP):
    """(B, S, H, ...) -> (groups, group, B*H, C, ...), the layout the
    operator reads: padded with zeros to whole groups, in one transpose."""
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
    groups, group, bh, chunk, dk = q.shape
    if chunk % sub or (chunk // sub) & (chunk // sub - 1):
        raise ValueError(f"chunk {chunk} must be sub {sub} times a power of "
                         f"two")
    # the kernels take the state transposed, (B*H, d_v, d_k), and the
    # chunks in one row: the layout's first two axes merged
    state = (jnp.zeros((bh, v.shape[4], dk), jnp.float32)
             if initial_state is None
             else jnp.swapaxes(initial_state.astype(jnp.float32), 1, 2))
    out, state = _chunks(*(x.reshape((groups * group,) + x.shape[2:])
                           for x in (q, k, v, g, beta)), state, sub)
    out = out.reshape(q.shape[:2] + out.shape[1:])
    return (out, jnp.swapaxes(state, 1, 2)) if return_state else out


def kda_chunked(q, k, v, g, beta, initial_state=None, *, chunk: int = CHUNK,
                sub: int = 16, group: int = GROUP,
                return_state: bool = False):
    """The chunked form (module text); same arguments and results as
    :func:`kda_recurrent`. ``chunk`` tokens a chunk (``sub`` times a power
    of two), ``group`` chunks a group of the layout. Lays the operands out
    with XLA and runs :func:`kda_groups`."""
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
