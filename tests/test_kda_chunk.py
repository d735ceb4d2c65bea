"""The two kernels of a chunk's stateless work (ops/kda_chunk.py:
``kda_chunk_fwd`` and ``kda_chunk_bwd``, interpreted here) held to the plain
array lines they replaced: ``_prepare`` below is ops/kda.py's as it stood
before the kernels (PR 34's tree), with ``jax.vjp`` of it for the gradients.
The forward kernel's six results and the backward kernel's five gradients
from float32 operands, at heads of 16 channels (chunks of 16 and of 64) and
at the cell's 128 channels with C = 64 and sub = 16; at the model's gates, at
0 and at -20 a token (where a whole chunk's ``exp(-G)`` would overflow); with
a padded tail (``g = 0, beta = 0``); and once in bfloat16, where the kernels
round what they write.

The yardstick is the plain lines run in float64 on the same values. In
float32 they are no yardstick for a gate's gradient at -20 a token: they add
the last row's two terms of ``exp(G_C - G)``, each of the size of 1, to a
gradient of the size of exp(-20) before the two cancel, and what is left is
rounding (0.99 of the true gradient's largest entry, read on PR 35's tree
against float64); the backward kernel leaves that pair out, as it is 0
whatever the gates are. The kernels' float32 products are three bfloat16
passes on every platform (a CPU runs the plain lines' ``Precision.HIGH`` as
whole float32), so they part from float64 by those passes' rounding."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.ops import kda_chunk

F32, BF16 = jnp.float32, jnp.bfloat16
_HIGH, _HIGHEST = jax.lax.Precision.HIGH, jax.lax.Precision.HIGHEST
RESULTS = ("w", "tv", "bm", "q_in", "k_out", "decay")
LEAVES = ("q", "k", "v", "g", "beta")


# --------------------------------------------------------------------------
# the plain reference: ops/kda.py::_prepare and what it called, PR 34's tree
# --------------------------------------------------------------------------

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


def _prepare(q, k, v, g, beta, sub):
    """ops/kda.py::_prepare of PR 34's tree, with ``g``'s type where that
    said float32 (so float64 operands make it the yardstick)."""
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


# --------------------------------------------------------------------------
# the cases
# --------------------------------------------------------------------------

def operands(n, bh, c, d, gate, dtype=F32, tail=0, seed=0):
    """A group's operands (n, B*H, C, d) as the input stage writes them: q
    and k of unit length (q by d^-1/2 more), gates of the model's spread or
    a constant, beta a sigmoid; the last ``tail`` rows of the last chunk
    padded (``g = 0, beta = 0``, zeros elsewhere). And the six cotangents,
    of the size of 1, that a bfloat16 holds exactly."""
    ks = jax.random.split(jax.random.key(seed + c + d), 11)
    q, k, v = (jax.random.normal(ks[i], (n, bh, c, d)) for i in range(3))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * d ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    if gate == "model":
        g = -jnp.exp(1.5 * jax.random.normal(ks[3], (n, bh, c, d)) - 1.0)
    else:
        g = jnp.full((n, bh, c, d), float(gate))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (n, bh, c)))
    if tail:
        real = (jnp.arange(n * c).reshape(n, 1, c) < n * c - tail)
        q, k, v, g = (x * real[..., None] for x in (q, k, v, g))
        beta = beta * real
    shapes = [(n, bh, c, d), (n, bh, c, d), (n, bh, c, c), (n, bh, c, d),
              (n, bh, c, d), (n, bh, d)]
    cts = tuple(jax.random.normal(ks[5 + i], s).astype(BF16).astype(F32)
                for i, s in enumerate(shapes))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta), cts


# (chunks, B*H, C, d, gates, padded rows): B*H = 6 is one grid step of six
# heads, 8 one of eight; the cell's chunk-head once at the model's gates
CASES = ([(2, 6, 16, 16, gate, 5) for gate in ("model", 0.0, -20.0)]
         + [(2, 6, 64, 16, gate, 0) for gate in ("model", -20.0)]
         + [(1, 8, 64, 128, "model", 0), (2, 2, 64, 128, 0.0, 40)])
IDS = [f"n{n}-bh{bh}-c{c}-d{d}-g{g}-tail{t}" for n, bh, c, d, g, t in CASES]


def _vjp_of(fn, sub):
    def run(args, cts):
        out, vjp = jax.vjp(lambda *a: fn(*a, sub), *args)
        return out, vjp(tuple(c.astype(o.dtype) for c, o in zip(cts, out)))
    return jax.jit(run)


def both(args, cts, sub=16):
    """((results, gradients) of the kernels, of the plain lines in float64
    on the same values)."""
    got = _vjp_of(kda_chunk.prepare, sub)(args, cts)
    with jax.enable_x64(True):
        wide = [tuple(jnp.asarray(np.asarray(x, np.float64)) for x in xs)
                for xs in (args, cts)]
        want = jax.tree_util.tree_map(np.asarray,
                                      _vjp_of(_prepare, sub)(*wide))
    return got, want


@pytest.fixture(scope="module", params=CASES, ids=IDS)
def float32_case(request):
    n, bh, c, d, gate, tail = request.param
    return both(*operands(n, bh, c, d, gate, tail=tail))


def close(got, want, tolerance, what):
    assert got.shape == want.shape, what
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    assert np.isfinite(got).all(), what
    np.testing.assert_allclose(
        got, want, rtol=0, atol=tolerance * np.abs(want).max() + 1e-30,
        err_msg=what)


@pytest.mark.parametrize("result", range(6), ids=RESULTS)
def test_the_forward_kernels_results(float32_case, result):
    """Each of the six against the plain lines', to 2e-5 of its largest
    entry: the three passes of the scores' and the inverse's products."""
    (got, _), (want, _) = float32_case
    close(got[result], want[result], 2e-5, RESULTS[result])


@pytest.mark.parametrize("leaf", range(5), ids=LEAVES)
def test_the_backward_kernels_gradients(float32_case, leaf):
    """Each of the five against ``jax.vjp`` of the plain lines under the
    same six cotangents. A gate's gradient at -20 a token is of the size of
    exp(-20), and is held to its own scale."""
    (_, got), (_, want) = float32_case
    close(got[leaf], want[leaf], 5e-5, LEAVES[leaf])


def test_bfloat16_operands_round_where_the_plain_lines_round():
    """bfloat16 q, k, v: each result in the type the plain lines give it
    from such operands, and within a rounding to bfloat16 of the float64
    lines on the same values."""
    args, cts = operands(2, 4, 64, 128, "model", dtype=BF16, tail=7)
    (got, got_grads), (want, want_grads) = both(args, cts)
    assert [x.dtype for x in got] == [BF16, F32, BF16, BF16, BF16, F32]
    assert [x.dtype for x in got_grads] == [BF16, BF16, BF16, F32, F32]
    for name, g, w in zip(RESULTS, got, want):
        close(g, w, 2.0 ** -8, name)
    # T is rounded to bfloat16 before it meets K and V, forward and back
    for name, g, w in zip(LEAVES, got_grads, want_grads):
        close(g, w, 2.0 ** -6, name)


def test_a_padded_chunk_leaves_the_state_as_it_is():
    """A chunk of padded rows alone: nothing is written (``T`` is 0, so ``T
    K`` and ``T V`` are) and the chunk's decay is 1."""
    args, _ = operands(1, 2, 16, 16, "model", tail=16)
    w, tv, bm, q_in, k_out, decay = kda_chunk.prepare(*args, 16)
    assert float(jnp.abs(w).max()) == 0.0 and float(jnp.abs(tv).max()) == 0.0
    assert float(jnp.abs(decay - 1.0).max()) == 0.0


def test_every_exponent_is_at_most_nought():
    """At -20 a token over 64 tokens ``exp(-G)`` is inf; the kernels' results
    and gradients stay finite, the decay is exp(-1280) = 0 exactly."""
    args, cts = operands(1, 2, 64, 16, -20.0)
    out, vjp = jax.vjp(lambda *a: kda_chunk.prepare(*a, 16), *args)
    assert all(bool(jnp.isfinite(x).all()) for x in out)
    assert float(jnp.abs(out[5]).max()) == 0.0
    assert all(bool(jnp.isfinite(x).all()) for x in vjp(cts))
