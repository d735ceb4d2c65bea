"""Manifold-constrained hyper-connections (mHC; DeepSeek, arXiv:2512.24880):
a residual path of ``n`` streams in place of one.

A token's state is ``X`` in R^{n x C}. Round a sub-layer ``F`` (attention with
its input norm, or the feed-forward with its):

    x~ = RMSNorm(vec(X))                  over all n*C channels, scale in R^{nC}
    u  = x~ Phi                           Phi in R^{nC x (n^2 + 2n)}
    H~ = alpha * u + b                    split (pre | post | res): n, n, n^2;
                                          one alpha a group, b static
    H_pre = sigmoid(H~_pre);  H_post = 2 sigmoid(H~_post)
    H_res = Sinkhorn(H~_res)              exp(clip), then `iters` times: each
                                          row by its sum + eps, each column by
                                          its sum + eps: doubly stochastic
    y  = F(H_pre X)                       a C-wide input, as F has anywhere
    X' = H_res X + H_post^T y             stream i: sum_j H_res[i, j] X_j
                                                    + H_post[i] y

The coefficients are made in float32, as a router's scores are, and the
gradient goes through every Sinkhorn iteration.

Layout. The streams travel as ONE (B, S, n*C) array, stream ``j`` the
channels ``[j*C, (j+1)*C)``: ``vec(X)`` is then the array itself, the norm and
the product with Phi run over its last axis, a stream is a slice of lanes at a
multiple of 128, and nothing has an axis of 4 among its two minor ones, which
the TPU's tiled layouts would pad to 8 or 16 rows. The passes over the
streams are Pallas kernels (ops/mhc.py): one read of the streams gives the
norm, the product with Phi and the sub-layer's input ``H_pre X``
(:func:`ops.mhc.mhc_in`), one more read writes ``X'`` (:func:`write`), and
the backward rule reads them once a pass. The Sinkhorn iterations run on the
coefficients with the TOKENS minor, (n^2 + n, B, S), between the two (a
(.., 4, 4) array of float32 is padded to 8 x 128 a token, and 40 of them are
kept for the iterations' backward pass); the kernels take them as a row a
token.

:class:`HyperConnection` makes the coefficients and the sub-layer's input;
:func:`write` puts the sub-layer's result back. Both run under the scope
``mhc`` (the part ``residual_mhc`` of analysis/anatomy.py), and so do
:func:`spread` and :func:`collect` at the two ends of the stack.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from distributeddeeplearning_tpu.models.llama import Held
from distributeddeeplearning_tpu.ops import mhc as kernels

SCOPE = "mhc"
# the collection a hyper-connection sows :func:`row_sum_gap` into; the step's
# metrics carry the largest over the step's hyper-connections
MHC_METRICS = "mhc_metrics"
ALPHA_INIT = 0.01
RES_DIAGONAL_INIT = 4.0


def sinkhorn(logits, *, iters: int, eps: float, clamp: tuple):
    """``logits``: (n, n, ...), rows first. exp of the clamped logits, then
    ``iters`` times each row divided by its sum + ``eps`` and each column by
    its sum + ``eps``; differentiated as written. The n x n entries are
    arrays of their own, so a row's sum is an addition of n arrays and no
    ``reduce``: an iteration is then one elementwise pass each way, where
    reductions over an axis of 4 came out as some hundred small passes a
    hyper-connection. The iterations are a ``scan``: unrolled, the ten
    hyper-connections of five layers tripled the step's compile time."""
    n = logits.shape[0]

    def iteration(m, _):
        rows = [sum(m[i]) + eps for i in range(n)]
        m = [[m[i][j] / rows[i] for j in range(n)] for i in range(n)]
        cols = [sum(m[i][j] for i in range(n)) + eps for j in range(n)]
        return [[m[i][j] / cols[j] for j in range(n)] for i in range(n)], None

    m, _ = jax.lax.scan(
        iteration, [[jnp.exp(jnp.clip(logits[i, j], *clamp))
                     for j in range(n)] for i in range(n)],
        None, length=iters)
    return jnp.stack([jnp.stack(row) for row in m])


def row_sum_gap(h_res):
    """The largest |row sum - 1| of (n, n, ...) mixing matrices: the columns
    were normalised last, so this is how far from doubly stochastic the
    iterations left them."""
    return jnp.abs(h_res.sum(1) - 1.0).max()


def spread(x, n: int):
    """(B, S, C) -> (B, S, n*C): the embedding copied to the n streams."""
    with jax.named_scope(SCOPE):
        return jnp.concatenate([x] * n, axis=-1)


def collect(x, n: int):
    """(B, S, n*C) -> (B, S, C): the streams summed, after the last layer."""
    with jax.named_scope(SCOPE):
        total = sum(kernels.stream(x, j, n).astype(jnp.float32)
                    for j in range(n))
        return total.astype(x.dtype)


def write(x, y, coef):
    """``X' = H_res X + H_post^T y``: (B, S, n*C) in ``x``'s type, each
    stream summed in float32. ``x``: the streams as :class:`HyperConnection`
    hands them back; ``coef`` as it returns it: H_post (n), H_res (n^2, rows
    first)."""
    with jax.named_scope(SCOPE):
        return kernels.mhc_out(x, y, coef)


def static_bias_init(n: int):
    """b at the start: H_pre = 1 / n (the sub-layer reads the streams' mean),
    H_post = 1, and B_res a diagonal of ``RES_DIAGONAL_INIT`` on zeros, so
    that with Phi = 0 a layer starts near ``x + F(x)`` on every stream."""
    def init(key, shape, dtype=jnp.float32):
        del key
        assert shape == (n * n + 2 * n,)
        return jnp.concatenate([
            jnp.full((n,), math.log(1.0 / (n - 1)), dtype),   # logit(1 / n)
            jnp.zeros((n,), dtype),
            (RES_DIAGONAL_INIT * jnp.eye(n, dtype=dtype)).reshape(-1)])
    return init


class HyperConnection(nn.Module):
    """One hyper-connection round a sub-layer: ``(h, coef, x) = hc(X)`` gives
    the sub-layer's input, the coefficients :func:`write` puts its result
    back with, and the streams for :func:`write` to read (``X`` itself: so
    the write's share of the streams' cotangent reaches the input pass's
    backward kernel, which adds it in the pass it makes anyway). Parameters:
    ``norm/scale`` (n*C), ``phi/kernel`` (n*C, n^2 + 2n), ``bias`` (n^2 +
    2n) and ``alpha`` (3: pre, post, res)."""

    streams: int
    sinkhorn_iters: int
    eps: float
    clamp: tuple
    rms_eps: float

    @nn.compact
    def __call__(self, x):
        n = self.streams
        m = n * n + 2 * n
        f32 = jnp.float32
        scale = Held("scale", (x.shape[-1],), nn.initializers.ones,
                      name="norm")()
        phi = Held("kernel", (x.shape[-1], m), nn.initializers.normal(0.02),
                    ("embed", None), name="phi")()
        bias = self.param("bias", static_bias_init(n), (m,), f32)
        alpha = self.param("alpha", nn.initializers.constant(ALPHA_INIT),
                           (3,), f32)
        with jax.named_scope(SCOPE):
            h, u, x = kernels.mhc_in(x, scale, phi, alpha[0], bias[:n],
                                     eps=self.rms_eps)
            # H_post and H_res from their pre-activations, tokens minor
            by_group = alpha[np.repeat(np.arange(1, 3), (n, n * n))]
            logits = (by_group[:, None, None] * jnp.moveaxis(u[..., n:], -1, 0)
                      + bias[n:, None, None])
            post = 2.0 * jax.nn.sigmoid(logits[:n])
            res = sinkhorn(logits[n:].reshape((n, n) + logits.shape[1:]),
                           iters=self.sinkhorn_iters, eps=self.eps,
                           clamp=self.clamp)
            self.sow(MHC_METRICS, "row_sum_gap",
                     jax.lax.stop_gradient(row_sum_gap(res)))
            coef = jnp.moveaxis(jnp.concatenate(
                [post, res.reshape((n * n,) + logits.shape[1:])]), 0, -1)
            return h, coef, x
