"""The state-space recurrence of Mamba-2 (state-space duality,
arXiv:2405.21060): a ``(P, N)`` state a head, decayed by ONE scalar a head and
token, written by the token's input times a ``B`` vector and read by a ``C``
vector, with ``B`` and ``C`` shared by the heads of a group. For head ``h`` of
group ``g``, with ``dt_t >= 0`` and ``A_h < 0``:

    H_t = exp(dt_t A_h) H_{t-1} + dt_t x_t B_t^T     (H_0 the entering state)
    y_t = H_t C_t + D_h x_t

:func:`ssd_chunked` is the form a sequence is trained with; the tests hold
it to the recurrence token by token. With ``a_t = dt_t A_h`` and ``G_t`` its
sum from the chunk's start to ``t`` (float32), a chunk of ``L`` tokens is

    Y_diag = (C B^T o exp(G_i - G_j) [j <= i]) (dt x)   the chunk's own writes
    Y_off  = exp(G) o (C H_in)                           what entered, read
    S_c    = B^T (exp(G_L - G) o dt x)                   what the chunk writes

and the states that enter the chunks come from the chunks' writes ``S_c`` by
one product with a lower triangle of decays over the chunks: ``H_in[z] =
exp(Q_z) H_0 + sum_{c < z} exp(Q_z - Q_{c+1}) S_c``, ``Q_z`` the summed decays
of the chunks before ``z``. Every exponent is a difference of summed decays
that is at most 0, so nothing overflows, and nothing divides by a decay. Row
``z = chunks`` of the same product is the last state.

Precision: the decays summed and exponentiated in float32; the products with
x, B, C and the chunks' states take their operands in ``x``'s type (``dt x``
and the decayed writes rounded to it) and add up in float32; the product over
the chunks is float32 at "highest". The sequence is padded to whole chunks
with ``dt = 0`` and zeros, which neither decays the state nor writes to it.

Layout: the models' ``(B, S, H, P)`` for x, ``(B, S, H)`` for dt, ``(B, S, G,
N)`` for B and C, with head ``h`` reading group ``h // (H / G)``; ``A`` and
``D`` ``(H,)``; a state ``(B, H, P, N)``. The backward pass is autodiff's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HIGHEST = jax.lax.Precision.HIGHEST
# tokens a chunk of the chunked form (the family's ``chunk_size``), and of the
# counter that bounds its exponents (:func:`min_chunk_log_decay`)
CHUNK = 128
# the collection a layer sows :func:`min_chunk_log_decay` into; the step's
# metrics carry the smallest over the layers (train/steps.py)
SSD_METRICS = "ssd_metrics"


def _heads_of_groups(x, groups: int):
    """(B, S, H, ...) -> (B, S, G, H/G, ...)."""
    b, s, h = x.shape[:3]
    return x.reshape((b, s, groups, h // groups) + x.shape[3:])


def min_chunk_log_decay(dt, a, chunk: int = CHUNK):
    """The most negative summed ``dt A`` any chunk of the chunked form
    reaches, over chunks and heads (the sequence padded to whole chunks with
    ``dt = 0``): what bounds its arithmetic, since every ``exp`` inside a
    chunk is of a difference of summed decays of that chunk."""
    bsz, s, h = dt.shape
    la = jnp.pad(dt.astype(jnp.float32) * a.astype(jnp.float32),
                 ((0, 0), (0, -s % chunk), (0, 0)))
    return la.reshape(bsz, -1, chunk, h).sum(2).min()


def ssd_chunked(x, dt, a, b, c, d=None, initial_state=None, *,
                chunk: int = CHUNK, return_state: bool = False):
    """The chunked form (module text). Returns y (B, S, H, P) in ``x``'s
    type, and the last state (B, H, P, N) in float32 with ``return_state``."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    rep = h // g
    f32, cdt = jnp.float32, x.dtype
    pad = -s % chunk
    nc = (s + pad) // chunk

    def chunks(v):   # (B, S, ...) -> (B, chunks, L, ...), zeros after S
        v = jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        return v.reshape((bsz, nc, chunk) + v.shape[2:])

    xg = chunks(_heads_of_groups(x, g))                     # (B,C,L,G,J,P)
    dtg = chunks(_heads_of_groups(dt.astype(f32), g))       # (B,C,L,G,J)
    bc, cc = chunks(b.astype(cdt)), chunks(c.astype(cdt))   # (B,C,L,G,N)
    xdt = (xg.astype(f32) * dtg[..., None]).astype(cdt)
    cum = jnp.cumsum(dtg * a.astype(f32).reshape(g, rep), axis=2)
    cum_t = jnp.moveaxis(cum, 2, -1)                        # (B,C,G,J,L)

    # the chunk's own writes: scores a group, decays a head
    scores = jnp.einsum("bclgn,bcsgn->bcgls", cc, bc,
                        preferred_element_type=f32)
    causal = jnp.tril(jnp.ones((chunk, chunk), jnp.bool_))
    decay = jnp.exp(jnp.where(causal, cum_t[..., :, None]
                              - cum_t[..., None, :], -jnp.inf))
    mixed = (scores[:, :, :, None] * decay).astype(cdt)    # (B,C,G,J,L,L)
    y = jnp.einsum("bcgjls,bcsgjp->bclgjp", mixed, xdt,
                   preferred_element_type=f32)

    # what each chunk writes, and the states that enter the chunks
    to_end = jnp.exp(cum[:, :, -1:] - cum)                  # (B,C,L,G,J)
    written = (xdt.astype(f32) * to_end[..., None]).astype(cdt)
    states = jnp.einsum("bclgn,bclgjp->bcgjpn", bc, written,
                        preferred_element_type=f32)
    q = jnp.concatenate([jnp.zeros_like(cum[:, :1, -1]),
                         jnp.cumsum(cum[:, :, -1], axis=1)], axis=1)
    q = jnp.moveaxis(q, 1, -1)                              # (B,G,J,C+1)
    before = (jnp.arange(nc + 1)[:, None] > jnp.arange(nc)[None, :])
    across = jnp.exp(jnp.where(before, q[..., :, None] - q[..., None, 1:],
                               -jnp.inf))                   # (B,G,J,C+1,C)
    entering = jnp.einsum("bgjzc,bcgjpn->bzgjpn", across, states,
                          precision=_HIGHEST)
    if initial_state is not None:
        h0 = initial_state.astype(f32).reshape(bsz, g, rep, p, n)
        entering = entering + (jnp.exp(jnp.moveaxis(q, -1, 1))[..., None, None]
                               * h0[:, None])

    # the state that entered, read by each token
    y = y + jnp.einsum("bclgn,bcgjpn->bclgjp", cc,
                       entering[:, :nc].astype(cdt),
                       preferred_element_type=f32) * jnp.exp(cum)[..., None]
    y = y.reshape(bsz, nc * chunk, h, p)[:, :s]
    if d is not None:
        y = y + x.astype(f32) * d.astype(f32)[:, None]
    y = y.astype(x.dtype)
    if not return_state:
        return y
    return y, entering[:, nc].reshape(bsz, h, p, n)
