"""The body functions of the chunked delta rule's two kernels
(ops/kda_chunk.py: :func:`stateless`, what a chunk needs that no state
enters, and :func:`stateless_bwd`, its backward rule; ``kda_fwd`` and
``kda_bwd`` run them on every chunk in VMEM) held to the plain array lines
they replaced: tests/kda_refs.py::prepare, ops/kda.py's as it stood before
the kernels, with ``jax.vjp`` of it for the gradients. The six
results and the five gradients from float32 operands, at heads of 16
channels (chunks of 16 and of 64) and at the cell's 128 channels with C = 64
and sub = 16; at the model's gates, at 0 and at -20 a token (where a whole
chunk's ``exp(-G)`` would overflow); with a padded tail (``g = 0, beta =
0``); and once in bfloat16, where the bodies round what they hand on. A
group's chunk-heads go through the bodies as one step's heads.

The yardstick is the plain lines run in float64 on the same values. In
float32 they are no yardstick for a gate's gradient at -20 a token: they add
the last row's two terms of ``exp(G_C - G)``, each of the size of 1, to a
gradient of the size of exp(-20) before the two cancel, and what is left is
rounding (0.99 of the true gradient's largest entry, read on PR 35's tree
against float64); the backward body leaves that pair out, as it is 0
whatever the gates are. The bodies' float32 products are three bfloat16
passes on every platform (a CPU runs the plain lines' ``Precision.HIGH`` as
whole float32), so they part from float64 by those passes' rounding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.ops import kda_chunk
from tests.kda_refs import prepare as plain_prepare

F32, BF16 = jnp.float32, jnp.bfloat16
RESULTS = ("w", "tv", "bm", "q_in", "k_out", "decay")
LEAVES = ("q", "k", "v", "g", "beta")


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


# (chunks, B*H, C, d, gates, padded rows): the cell's chunk-head once at the
# model's gates
CASES = ([(2, 6, 16, 16, gate, 5) for gate in ("model", 0.0, -20.0)]
         + [(2, 6, 64, 16, gate, 0) for gate in ("model", -20.0)]
         + [(1, 8, 64, 128, "model", 0), (2, 2, 64, 128, 0.0, 40)])
IDS = [f"n{n}-bh{bh}-c{c}-d{d}-g{g}-tail{t}" for n, bh, c, d, g, t in CASES]


def _bodies(q, k, v, g, beta, sub):
    """(the six results, their backward rule) of the kernels' bodies on a
    group's operands (n, B*H, C, d), its n * B*H chunk-heads taken as one
    grid step's heads."""
    n, bh = q.shape[:2]

    def flat(x):
        return x.reshape((n * bh,) + x.shape[2:])

    col, row = flat(beta[..., None]), flat(beta[..., None, :])
    results, remade = kda_chunk.stateless(flat(q), flat(k), flat(v), flat(g),
                                          col, row, sub)

    def rule(cts):
        cts = tuple(c.reshape(r.shape).astype(r.dtype)
                    for c, r in zip(cts, results))
        dq, dk, dv, dg, dcol, drow = kda_chunk.stateless_bwd(
            flat(v), col, row, remade, cts, sub)
        return tuple(x.reshape(q.shape[:2] + x.shape[1:])
                     for x in (dq, dk, dv, dg)) + (
            (dcol[..., 0] + drow[:, 0]).reshape(beta.shape),)

    out = tuple(r.reshape((n, bh) + r.shape[1:]) for r in results)
    return out[:5] + (out[5][:, :, 0],), rule


def _vjp_of(fn, sub):
    def run(args, cts):
        out, vjp = jax.vjp(lambda *a: fn(*a, sub), *args)
        return out, vjp(tuple(c.astype(o.dtype) for c, o in zip(cts, out)))
    return jax.jit(run)


def kernels(args, cts, sub=16):
    """(results, gradients) of the kernels' bodies."""
    def run(args, cts):
        out, rule = _bodies(*args, sub)
        return out, rule(cts)
    return jax.jit(run)(args, cts)


def both(args, cts, sub=16):
    """((results, gradients) of the kernels' bodies, of the plain lines in
    float64 on the same values)."""
    got = kernels(args, cts, sub)
    with jax.enable_x64(True):
        wide = [tuple(jnp.asarray(np.asarray(x, np.float64)) for x in xs)
                for xs in (args, cts)]
        want = jax.tree_util.tree_map(np.asarray,
                                      _vjp_of(plain_prepare, sub)(*wide))
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
    w, tv, bm, q_in, k_out, decay = jax.jit(
        lambda *a: _bodies(*a, 16)[0])(*args)
    assert float(jnp.abs(w).max()) == 0.0 and float(jnp.abs(tv).max()) == 0.0
    assert float(jnp.abs(decay - 1.0).max()) == 0.0


def test_every_exponent_is_at_most_nought():
    """At -20 a token over 64 tokens ``exp(-G)`` is inf; the kernels' results
    and gradients stay finite, the decay is exp(-1280) = 0 exactly."""
    args, cts = operands(1, 2, 64, 16, -20.0)
    out, grads = kernels(args, cts)
    assert all(bool(jnp.isfinite(x).all()) for x in out)
    assert float(jnp.abs(out[5]).max()) == 0.0
    assert all(bool(jnp.isfinite(x).all()) for x in grads)
