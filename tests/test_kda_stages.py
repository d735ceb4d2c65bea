"""The fused stages round the chunked delta rule (ops/kda_stages.py: conv +
SiLU + L2 norm + gate in, gated RMSNorm out, each one Pallas pass whose index
maps are the relayout; interpreted here) held to the plain formulation beside
them (``kda_in_plain`` / ``kda_out_plain`` and ``ops.kda.lay_out``): values
and every gradient leaf, float32 and bfloat16 inputs, a whole number of
groups and a short tail, a mask padded on the left and at the end, heads
narrower than a lane tile (all heads one grid step), two heads of a whole
tile (one step still) and the cell's grid in small: eight heads of 128, four
a step, two steps a group of chunks, so ``head = n * step + m``, the operand
block ``i * steps + n`` and the small gradients' block, zeroed at the grid's
first step and added to at every other, are held by values too.

The kernels compute in float32 and round once where values leave; the plain
lines in bfloat16 round tap by tap. So the yardstick is the plain formulation
on float32 copies of the same inputs, and a bfloat16 case may part from it by
the one rounding of what it writes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.ops import kda
from distributeddeeplearning_tpu.ops import kda_stages as stages

B, TAPS, CHUNK, GROUP = 2, 4, 16, 2          # a grid step: 32 rows
F32, BF16 = jnp.float32, jnp.bfloat16
IN_LEAVES = ("q_proj", "k_proj", "v_proj", "f_proj", "q_taps", "k_taps",
             "v_taps", "A_log", "dt_bias")
OUT_LEAVES = ("o", "gate", "scale")
# (dtype, tokens, mask, heads, head width): 96 tokens are three whole groups
# (both halos lie inside the sequence), 80 leave a tail of 16 padded rows
CASES = [(dtype, s, mask, 2, 16)
         for dtype in (F32, BF16) for s in (96, 80)
         for mask in ("left", "end")] + [(BF16, 80, "left", 2, 128),
                                         (BF16, 80, "left", 8, 128)]
IDS = [f"{jnp.dtype(t).name}-s{s}-{m}-h{h}-d{d}" for t, s, m, h, d in CASES]


def tolerance(dtype):
    """Of the largest entry: float32's sums in another order, or one step of
    bfloat16 (both sides round what they write)."""
    return 2e-5 if dtype == F32 else 2.0 ** -7


def lay(x):
    return kda.lay_out(x, CHUNK, GROUP)


def in_operands(dtype, s, mask_kind, h, d):
    ks = jax.random.split(jax.random.key(s + d), 12)
    mask = jnp.ones((B, s), bool)
    mask = (mask.at[0, :5].set(False) if mask_kind == "left"
            else mask.at[1, -9:].set(False))
    # the model zeroes a padded token's input, so its projections are 0
    proj = tuple((jax.random.normal(ks[i], (B, s, h * d))
                  * mask[..., None]).astype(dtype) for i in range(4))
    taps = tuple(0.5 * jax.random.normal(ks[4 + i], (TAPS, h * d))
                 for i in range(3))
    a_log = jnp.log(jax.random.uniform(ks[7], (h,), minval=1.0, maxval=16.0))
    dt_bias = jax.random.normal(ks[8], (h * d,))
    # cotangents a bfloat16 holds exactly, zero in the padded tail
    weights = tuple(lay(jax.random.normal(ks[9 + i // 2], (B, s, h, d))
                        .astype(BF16).astype(F32)) * 2.0 ** i
                    for i in range(4))
    return (proj, taps, a_log, dt_bias), mask, weights


@pytest.fixture(scope="module", params=CASES, ids=IDS)
def input_stage(request):
    dtype, s, mask_kind, h, d = request.param
    args, mask, weights = in_operands(dtype, s, mask_kind, h, d)
    real = lay(jnp.ones((B, s, h, 1)))       # 0 in the tail's padded rows

    def fused(proj, taps, a_log, dt_bias):
        return stages.kda_in(proj, taps, a_log, dt_bias, mask, chunk=CHUNK,
                             group=GROUP)

    def plain(proj, taps, a_log, dt_bias):
        outs = stages.kda_in_plain(tuple(p.astype(F32) for p in proj), taps,
                                   a_log, dt_bias, mask)
        return tuple(lay(o) for o in outs)

    def run(fn):
        def loss(*a):
            return sum((o.astype(F32) * w).sum()
                       for o, w in zip(fn(*a), weights))
        values = tuple(o.astype(F32) * real for o in fn(*args))
        grads = jax.grad(loss, argnums=(0, 1, 2, 3))(*args)
        return values, jax.tree_util.tree_leaves(grads)

    return dtype, run(fused), run(plain), fused(*args)


def close(got, want, dtype):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(got).all()
    scale = float(np.abs(want).max())
    assert scale > 0
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=tolerance(dtype) * scale)


@pytest.mark.parametrize("operand", range(4), ids=("q", "k", "v", "g"))
def test_input_stage_values(input_stage, operand):
    """On the real rows; a tail's padded rows are the operator's to ignore
    (``g = 0, beta = 0`` there: the first of them still see the last real
    tokens through the convolution, as a masked token inside a row does)."""
    dtype, (got, _), (want, _), raw = input_stage
    close(got[operand], want[operand], F32 if operand == 3 else dtype)
    assert raw[operand].dtype == (F32 if operand == 3 else dtype)


@pytest.mark.parametrize("leaf", range(9), ids=IN_LEAVES)
def test_input_stage_gradients(input_stage, leaf):
    dtype, (_, got), (_, want), _ = input_stage
    assert got[leaf].dtype == want[leaf].dtype == (dtype if leaf < 4 else F32)
    close(got[leaf], want[leaf], dtype if leaf < 4 else F32)


def test_gates_of_masked_and_padded_rows_are_nought():
    (proj, taps, a_log, dt_bias), mask, _ = in_operands(F32, 80, "left", 2,
                                                        16)
    g = stages.kda_in(proj, taps, a_log, dt_bias, mask, chunk=CHUNK,
                      group=GROUP)[3]
    live = lay(jnp.broadcast_to(mask[..., None, None], (B, 80, 2, 16)))
    assert float(jnp.abs(jnp.where(live, 0.0, g)).max()) == 0.0
    assert float(jnp.where(live, g, -1.0).max()) < 0.0
    # what the model sows: a chunk's summed gates, the lowest
    plain = stages.kda_in_plain(proj, taps, a_log, dt_bias, mask)[3]
    want = jnp.pad(plain, ((0, 0), (0, 16), (0, 0), (0, 0))).reshape(
        B, 6, CHUNK, 2, 16).sum(2).min()
    assert float(kda.min_chunk_log_decay(g)) == pytest.approx(float(want),
                                                              rel=1e-6)


def test_the_first_block_reads_zeros_before_the_sequence():
    """The halo before a row's first group is zeros, not the rows the index
    map's clamp happens to fetch, and a second batch row sees nothing of the
    first: token 0 is the last tap's alone, token 1 the last two's."""
    h, d = 2, 16
    (proj, taps, a_log, dt_bias), _, _ = in_operands(F32, 96, "end", h, d)
    mask = jnp.ones((B, 96), bool)
    proj = tuple(1e3 + p for p in proj)      # nothing nearby is small
    v = stages.kda_in(proj, taps, a_log, dt_bias, mask, chunk=CHUNK,
                      group=GROUP)[2]
    x, w = proj[2], taps[2]
    for row in range(B):
        for t, c in ((0, w[3] * x[row, 0]),
                     (1, w[3] * x[row, 1] + w[2] * x[row, 0])):
            got = v[0, 0, row * h:(row + 1) * h, t].reshape(-1)
            np.testing.assert_allclose(np.asarray(got),
                                       np.asarray(jax.nn.silu(c)), rtol=1e-5,
                                       atol=1e-3)


OUT_CASES = [(dtype, s, 2, 16) for dtype in (F32, BF16)
             for s in (96, 80)] + [(BF16, 80, 2, 128), (BF16, 80, 8, 128)]


@pytest.fixture(scope="module", params=OUT_CASES,
                ids=[f"{jnp.dtype(t).name}-s{s}-h{h}-d{d}"
                     for t, s, h, d in OUT_CASES])
def output_stage(request):
    dtype, s, h, d = request.param
    eps = 1e-5
    ks = jax.random.split(jax.random.key(s), 4)
    o = jax.random.normal(ks[0], (B, s, h, d)).astype(dtype)
    gate = jax.random.normal(ks[1], (B, s, h * d)).astype(dtype)
    scale = 1.0 + 0.1 * jax.random.normal(ks[2], (d,))
    w = jax.random.normal(ks[3], (B, s, h * d)).astype(BF16).astype(F32)

    def fused(o, gate, scale):
        return stages.kda_out(lay(o), gate, scale, eps=eps)

    def plain(o, gate, scale):
        return stages.kda_out_plain(o.astype(F32), gate.astype(F32), scale,
                                    eps)

    def run(fn):
        grads = jax.grad(lambda *a: (fn(*a).astype(F32) * w).sum(),
                         argnums=(0, 1, 2))(o, gate, scale)
        return fn(o, gate, scale), grads

    return dtype, run(fused), run(plain)


def test_output_stage_values(output_stage):
    dtype, (got, _), (want, _) = output_stage
    assert got.dtype == dtype and got.shape == want.shape
    close(got, want, dtype)


@pytest.mark.parametrize("leaf", range(3), ids=OUT_LEAVES)
def test_output_stage_gradients(output_stage, leaf):
    dtype, (_, got), (_, want) = output_stage
    assert got[leaf].dtype == (dtype if leaf < 2 else F32)
    close(got[leaf], want[leaf], dtype if leaf < 2 else F32)


def test_the_laid_out_operator_is_the_chunked_one():
    """``kda_chunked`` lays out with XLA and runs ``kda_groups``; called on
    operands a stage laid out, ``kda_groups`` gives the same bits."""
    ks = jax.random.split(jax.random.key(5), 5)
    s, h, d = 80, 2, 16
    q, k, v = (jax.random.normal(ks[i], (B, s, h, d)) for i in range(3))
    g = -jnp.exp(jax.random.normal(ks[3], (B, s, h, d)) - 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, s, h)))
    want, state = kda.kda_chunked(q, k, v, g, beta, chunk=CHUNK, group=GROUP,
                                  return_state=True)
    got, last = kda.kda_groups(*(lay(x) for x in (q, k, v, g, beta)),
                               return_state=True)
    assert got.shape == (3, GROUP, B * h, CHUNK, d)
    assert kda.layout(s, CHUNK, GROUP) == (GROUP, 3, 16)
    np.testing.assert_array_equal(np.asarray(kda.lay_back(got, B, s)),
                                  np.asarray(want))
    np.testing.assert_array_equal(np.asarray(last.reshape(state.shape)),
                                  np.asarray(state))
