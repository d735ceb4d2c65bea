"""The hyper-connections' four kernels (ops/mhc.py: the input pass and the
write-back, each forward and backward; interpreted here) held to the plain
array lines beside them (``mhc_in_plain``, ``mhc_out_plain``): every result
and every gradient leaf, four streams of 64 and 128 channels, float32 and
bfloat16 streams, token counts under one tile, of whole tiles and of a tile
and a part, and a pass recomputed under ``jax.checkpoint`` against one kept.

The input pass's third result is its input, handed on to the write-back: its
cotangent (the write's share of ``dX``) enters ``mhc_in_bwd`` and leaves in
the final ``dX``; the plain lines add it as a sum. In float32 the kernels and
the lines agree to float32's rounding (products at ``HIGHEST`` on both
sides). In bfloat16 both round what they write once; the kernels sum the
cotangents of the streams in float32 where the lines add the write's share
in bfloat16, so the yardstick is the lines on float32 copies of the same
inputs, and the kernels stay within the lines' own band against it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.ops import mhc

N, EPS = 4, 1e-6
F32, BF16 = jnp.float32, jnp.bfloat16
# (streams' type, tokens, channels a stream): 80 tokens are under a tile
# (the block is the whole array), 256 two whole tiles, 200 a tile and a
# part (the grid's last step reads rows past the tokens)
CASES = [(F32, 200, 64), (F32, 80, 128), (F32, 256, 128), (BF16, 200, 128),
         (BF16, 80, 64)]
IDS = [f"{jnp.dtype(d).name}-t{t}-c{c}" for d, t, c in CASES]
IN_RESULTS = ("h", "u")
IN_LEAVES = ("x", "scale", "phi", "alpha_pre", "bias_pre")
OUT_LEAVES = ("x", "y", "coef")
FLOAT32_TOLERANCE = 1e-5      # of the largest entry


def operands(dtype, t: int, c: int):
    """Inputs at the scale a layer sees (unit streams, Phi of a few tenths so
    every coefficient moves) and cotangents a bfloat16 holds exactly."""
    m = N * N + 2 * N
    ks = jax.random.split(jax.random.key(t * 1000 + c), 10)
    x = jax.random.normal(ks[0], (2, t // 2, N * c)).astype(dtype)
    scale = 1.0 + 0.1 * jax.random.normal(ks[1], (N * c,))
    phi = 0.3 * jax.random.normal(ks[2], (N * c, m))
    alpha, bias = jnp.float32(0.7), 0.3 * jax.random.normal(ks[3], (N,))
    y = jax.random.normal(ks[4], (2, t // 2, c)).astype(dtype)
    coef = jax.random.uniform(ks[5], (2, t // 2, N + N * N))

    def exact(key, shape):
        return jax.random.normal(key, shape).astype(BF16).astype(F32)

    in_cts = (exact(ks[6], y.shape), exact(ks[7], (2, t // 2, m)),
              exact(ks[8], x.shape))
    return (x, scale, phi, alpha, bias), (y, coef), in_cts, exact(ks[9],
                                                                 x.shape)


def kernels_in(*args):
    return mhc.mhc_in(*args, eps=EPS)


def plain_in(x, *args):
    return (*mhc.mhc_in_plain(x, *args, eps=EPS), x)


def in_vjp(fn, args, cts):
    """(h, u) and the five leaves' gradient at (dh, du, the third result's
    cotangent)."""
    out, vjp = jax.vjp(fn, *args)
    dtypes = [o.dtype for o in out]
    return out[:2], vjp(tuple(ct.astype(d) for ct, d in zip(cts, dtypes)))


def out_vjp(fn, args, g):
    out, vjp = jax.vjp(fn, *args)
    return out, vjp(g.astype(out.dtype))


def widened(args):
    return tuple(a.astype(F32) if a.dtype == BF16 else a for a in args)


def error(got, want) -> float:
    got, want = (np.asarray(v, np.float64) for v in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.fixture(scope="module", params=CASES, ids=IDS)
def case(request):
    """Errors of the largest entry, kernels and (for bfloat16) plain lines on
    the case's inputs, against the plain lines on float32 copies."""
    dtype, t, c = request.param
    args_in, (y, coef), in_cts, g = operands(dtype, t, c)
    args_out = (args_in[0], y, coef)
    want_in = in_vjp(plain_in, widened(args_in), in_cts)
    want_out = out_vjp(mhc.mhc_out_plain, widened(args_out), g)
    sides = {"kernels": (kernels_in, mhc.mhc_out)}
    if dtype == BF16:
        sides["plain"] = (plain_in, mhc.mhc_out_plain)
    errors = {}
    for side, (fn_in, fn_out) in sides.items():
        results, grads = jax.jit(lambda a, ct: in_vjp(fn_in, a, ct))(
            args_in, in_cts)
        out, out_grads = jax.jit(lambda a, ct: out_vjp(fn_out, a, ct))(
            args_out, g)
        errors[side] = {
            **{f"in/{k}": error(v, w)
               for k, v, w in zip(IN_RESULTS, results, want_in[0])},
            **{f"in/d{k}": error(v, w)
               for k, v, w in zip(IN_LEAVES, grads, want_in[1])},
            "out/x_out": error(out, want_out[0]),
            **{f"out/d{k}": error(v, w)
               for k, v, w in zip(OUT_LEAVES, out_grads, want_out[1])}}
    return dtype, errors


NAMES = ([f"in/{k}" for k in IN_RESULTS] + [f"in/d{k}" for k in IN_LEAVES]
         + ["out/x_out"] + [f"out/d{k}" for k in OUT_LEAVES])


@pytest.mark.parametrize("name", NAMES)
def test_each_result_and_leaf_is_the_plain_lines(case, name):
    """Float32: within 1e-5 of the largest entry. bfloat16: within the
    plain lines' own band against float32, or float32's where the plain
    lines read nothing (``u`` and the parameters' leaves: the kernels'
    product with Phi is exact in three bfloat16 pieces)."""
    dtype, errors = case
    got = errors["kernels"][name]
    if dtype == F32:
        assert got <= FLOAT32_TOLERANCE, (name, got)
    else:
        band = errors["plain"][name]
        assert got <= max(1.25 * band, FLOAT32_TOLERANCE), (name, got, band)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
def test_a_recomputed_pass_gives_the_kept_ones_gradient(dtype):
    """Both passes round a stand-in sub-layer, kept and under
    ``jax.checkpoint``: the recomputed forward kernels give the residuals the
    kept ones gave, so every gradient leaf is the same."""
    args_in, (y, coef), _, g = operands(dtype, 200, 128)

    def layer(x, scale, phi, alpha, bias, y, coef):
        h, u, x = mhc.mhc_in(x, scale, phi, alpha, bias, eps=EPS)
        sub = (jnp.tanh(h.astype(F32)) + y.astype(F32)).astype(x.dtype)
        out = mhc.mhc_out(x, sub, coef + jnp.tanh(u[..., N:]))
        return (out.astype(F32) * g).sum()

    argnums = tuple(range(7))
    kept = jax.jit(jax.grad(layer, argnums))(*args_in, y, coef)
    again = jax.jit(jax.grad(jax.checkpoint(layer), argnums))(*args_in, y,
                                                             coef)
    for a, b in zip(again, kept):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_the_third_result_is_the_streams_and_its_cotangent_reaches_dx():
    """``mhc_in`` hands its input back unchanged; a cotangent on it alone
    comes back as ``dX`` to the last bit (float32 sums of a bfloat16 value
    and zeros), and no parameter's leaf moves."""
    args_in, _, _, g = operands(BF16, 80, 64)
    _, _, x = kernels_in(*args_in)
    np.testing.assert_array_equal(np.asarray(x, np.float32),
                                  np.asarray(args_in[0], np.float32))
    m = args_in[2].shape[1]
    zeros = (jnp.zeros((2, 40, 64), F32), jnp.zeros((2, 40, m), F32))
    _, grads = in_vjp(kernels_in, args_in, zeros + (g,))
    np.testing.assert_array_equal(np.asarray(grads[0], np.float32),
                                  np.asarray(g, np.float32))
    for leaf in grads[1:]:
        assert float(jnp.abs(leaf).max()) == 0.0
