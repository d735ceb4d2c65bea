"""The chunked delta-rule operator (ops/kda.py::kda_chunked) held to the
token-by-token recurrence beside it: values and all five gradients in
float32, at chunks of 16 and 64, at a sequence that is no multiple of the
chunk, at gates of 0 and of -20 a token (where exp(-G) of a whole chunk would
overflow), at beta 0 and 1; a state handed on equals one long sequence."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.ops import kda, kda_chunk
from tests.kda_refs import xla_groups

B, H, DK, DV = 2, 3, 8, 12
NAMES = ("q", "k", "v", "g", "beta")


def operands(s, gate, beta, seed=1, b=B, h=H):
    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (b, s, h, DK))
    k = jax.random.normal(ks[1], (b, s, h, DK))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, s, h, DV))
    if gate == "model":   # spread as the model's initial gates are
        g = -jnp.exp(1.5 * jax.random.normal(ks[3], (b, s, h, DK)) - 1.0)
    else:
        g = jnp.full((b, s, h, DK), float(gate), jnp.float32)
    if beta == "model":
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    else:
        beta = jnp.full((b, s, h), float(beta), jnp.float32)
    return q, k, v, g, beta, jax.random.normal(ks[5], (b, s, h, DV))


CASES = [(gate, beta, chunk, s)
         for gate in ("model", 0.0, -20.0) for beta in ("model", 0.0, 1.0)
         for chunk, s in ((16, 100), (64, 100), (64, 128))]


@functools.lru_cache(maxsize=None)
def _compiled(chunk):
    """(values, values and gradients) of the recurrence (``chunk`` None) or
    the chunked form, compiled once a chunk size and sequence length: the
    cases differ in their operands' values alone."""
    fn = (kda.kda_recurrent if chunk is None else
          functools.partial(kda.kda_chunked, chunk=chunk, group=2))
    return jax.jit(fn), jax.jit(jax.value_and_grad(
        lambda q, k, v, g, beta, w: (fn(q, k, v, g, beta) * w).sum(),
        argnums=(0, 1, 2, 3, 4)))


@pytest.fixture(scope="module", params=CASES,
                ids=[f"g{g}-b{b}-c{c}-s{s}" for g, b, c, s in CASES])
def both(request):
    gate, beta, chunk, s = request.param
    *args, w = operands(s, gate, beta)
    with jax.default_matmul_precision("highest"):
        want = _compiled(None)[0](*args)
        got = _compiled(chunk)[0](*args)
        _, want_grads = _compiled(None)[1](*args, w)
        _, got_grads = _compiled(chunk)[1](*args, w)
    return got, want, got_grads, want_grads


def test_values(both):
    """To 6e-5 of the largest entry. The chunked form's float32 products are
    three bfloat16 passes, which its kernels (ops/kda_chunk.py) take on
    every platform: the worst case here reads 2.4e-5 (gates of 0, where
    nothing decays the scores). The array lines before them asked XLA for
    ``Precision.HIGH``, which a CPU runs as whole float32: 1.9e-6 on PR 34's
    tree, under the 1e-5 this pin was."""
    got, want, _, _ = both
    assert bool(jnp.isfinite(got).all())
    scale = float(jnp.abs(want).max()) or 1.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=6e-5 * scale)


@pytest.mark.parametrize("leaf", range(5), ids=NAMES)
def test_gradients(both, leaf):
    """Each against the recurrence's own. A gate's gradient at -20 a token is
    of the size of exp(-20): it is held to its own scale too, which the
    chunked form keeps because a token's product with itself carries no gate
    (ops/kda_chunk.py). To 1.5e-4 of the largest entry: the three passes
    read, worst case of each leaf, q 3.3e-5, k 3.2e-5, v 1.8e-5, g 6.0e-5,
    beta 2.5e-5 (PR 34's tree on a CPU, whole float32: 2.7e-6, 2.6e-6,
    1.7e-6, 3.2e-6, 2.0e-6, under the 2e-5 this pin was)."""
    _, _, got, want = both
    assert bool(jnp.isfinite(got[leaf]).all())
    scale = float(jnp.abs(want[leaf]).max())
    np.testing.assert_allclose(np.asarray(got[leaf]), np.asarray(want[leaf]),
                               rtol=0, atol=1.5e-4 * scale + 1e-30)


def test_nothing_overflows_where_a_chunks_decay_would():
    """exp(-G) over 64 tokens at -20 a token is exp(1280): the factored form
    'k * exp(-G)' is inf there, and the chunked operator never forms it."""
    *args, _ = operands(128, -20.0, 1.0)
    assert not np.isfinite(np.exp(np.float32(20.0 * 64)))
    out, state = kda.kda_chunked(*args, chunk=64, return_state=True)
    assert bool(jnp.isfinite(out).all()) and bool(jnp.isfinite(state).all())
    laid = kda.lay_out(args[3], 64)
    assert float(kda.min_chunk_log_decay(laid)) == -1280.0


@pytest.mark.parametrize("cut", [64, 40, 100])
def test_a_state_handed_on_equals_one_long_sequence(cut):
    *args, _ = operands(160, "model", "model", seed=4)
    with jax.default_matmul_precision("highest"):
        whole, last = kda.kda_chunked(*args, chunk=16, return_state=True)
        first, state = kda.kda_chunked(*(a[:, :cut] for a in args), chunk=16,
                                       return_state=True)
        second, end = kda.kda_chunked(*(a[:, cut:] for a in args), state,
                                      chunk=16, return_state=True)
        step, _ = kda.kda_recurrent(*(a[:, cut:cut + 1] for a in args),
                                    state, return_state=True)
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate([first, second], 1)), np.asarray(whole),
        rtol=0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(end), np.asarray(last), rtol=0,
                               atol=1e-5)
    # one token through the recurrence from that state: a decode step
    np.testing.assert_allclose(np.asarray(step[:, 0]),
                               np.asarray(whole[:, cut]), rtol=0, atol=1e-5)


def test_beta_nought_writes_nothing_and_a_padded_tail_changes_nothing():
    *args, _ = operands(100, "model", 0.0)
    out, state = kda.kda_chunked(*args, chunk=64, return_state=True)
    assert float(jnp.abs(out).max()) == 0.0
    assert float(jnp.abs(state).max()) == 0.0
    # 100 tokens pad to 128: the state after them is the recurrence's at 100
    *args, _ = operands(100, "model", "model")
    with jax.default_matmul_precision("highest"):
        _, got = kda.kda_chunked(*args, chunk=64, return_state=True)
        _, want = kda.kda_recurrent(*args, return_state=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5 * float(jnp.abs(want).max()))


def test_bfloat16_operands_keep_float32_state_and_gates():
    q, k, v, g, b, _ = operands(128, "model", "model")
    bf = jnp.bfloat16
    got = kda.kda_chunked(q.astype(bf), k.astype(bf), v.astype(bf), g, b,
                          chunk=64)
    want = kda.kda_recurrent(q.astype(bf), k.astype(bf), v.astype(bf), g, b)
    assert got.dtype == bf
    err = jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)).max()
    assert float(err) < 0.03 * float(jnp.abs(want.astype(jnp.float32)).max())


# ---------------------------------------------------------------------------
# The two kernels (ops/kda_chunk.py) against the recurrence and against the
# loop over chunks as XLA ran it (tests/kda_refs.py::xla_groups): value, last
# state and every gradient, the entering state's among them
# ---------------------------------------------------------------------------

# (B, H, S, chunk, group, entering state, last state handed on, operands'
# type): 8 chunks in 4 groups; 100 tokens padded to 128; a state handed in;
# one handed in and on, its gradient taken; B*H = 6, a grid step of six
# heads; bfloat16 q, k, v, rounded where the loop rounds
FUSED_CASES = {
    "groups": (1, 8, 128, 16, 2, False, False, jnp.float32),
    "padded-tail": (1, 8, 100, 32, 2, False, False, jnp.float32),
    "initial-state": (1, 8, 64, 16, 4, True, False, jnp.float32),
    "return-state": (1, 8, 96, 32, 4, True, True, jnp.float32),
    "six-heads": (2, 3, 64, 16, 2, False, True, jnp.float32),
    "bfloat16": (1, 8, 100, 32, 2, True, True, jnp.bfloat16),
}


def _laid_loop(q, k, v, g, beta, state, *, chunk, group):
    """tests/kda_refs.py::xla_groups on the model's layout: (o, last)."""
    b, s, h, _ = q.shape
    laid = [kda.lay_out(x, chunk, group) for x in (q, k, v, g, beta)]
    last, o = xla_groups(*laid, state.reshape((b * h,) + state.shape[2:]))
    return kda.lay_back(o, b, s), last.reshape(state.shape)


@pytest.mark.parametrize("case", FUSED_CASES)
def test_the_kernels_hold_to_the_recurrence_and_the_xla_loop(case):
    """float32: to 6e-5 of the largest entry in value and last state, 1.5e-4
    in a gradient (test_values, test_gradients), against either yardstick.
    bfloat16 operands: the same type out, value and last state within
    2^-7 of the loop's and 0.03 of the recurrence's (a rounding or two to
    bfloat16 of o and of the rows a chunk writes, and the recurrence rounds
    nothing), the gradients within 2^-5 of the loop's (whose derivative
    rounds the cotangents of the rows and of the state to bfloat16, where
    the backward kernel keeps them float32) and 0.05 of the
    recurrence's."""
    b, h, s, chunk, group, entering, on, dtype = FUSED_CASES[case]
    q, k, v, g, beta, w = operands(s, "model", "model", seed=11, b=b, h=h)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    state = (0.5 * jax.random.normal(jax.random.key(12), (b, h, DK, DV))
             if entering else jnp.zeros((b, h, DK, DV)))
    w_last = jax.random.normal(jax.random.key(13), (b, h, DK, DV)) * on
    forms = {
        "kernels": functools.partial(kda.kda_chunked, chunk=chunk,
                                     group=group, return_state=True),
        "recurrence": functools.partial(kda.kda_recurrent,
                                        return_state=True),
        "loop": functools.partial(_laid_loop, chunk=chunk, group=group)}
    leaves = (0, 1, 2, 3, 4, 5) if entering else (0, 1, 2, 3, 4)

    def loss(fn):
        def value(*a):
            o, last = fn(*a)
            return ((o.astype(jnp.float32) * w).sum() + (last * w_last).sum(),
                    (o, last))
        return jax.jit(jax.value_and_grad(value, argnums=leaves,
                                          has_aux=True))

    got = {}
    with jax.default_matmul_precision("highest"):
        for name, fn in forms.items():
            (_, (out, last)), grads = loss(fn)(q, k, v, g, beta, state)
            got[name] = [out, last, *grads]
    kernels = got["kernels"]
    assert kernels[0].dtype == dtype
    assert [x.dtype for x in kernels[2:]][:5] == [dtype] * 3 + [
        jnp.float32] * 2
    wide = dtype == jnp.float32
    for name, value_tol, grad_tol in (
            ("recurrence", 6e-5 if wide else 0.03, 1.5e-4 if wide else 0.05),
            ("loop", 6e-5 if wide else 2.0 ** -7,
             1.5e-4 if wide else 2.0 ** -5)):
        for i, (x, want) in enumerate(zip(kernels, got[name])):
            x, want = (np.asarray(a, np.float64) for a in (x, want))
            assert np.isfinite(x).all(), (name, i)
            tol = value_tol if i < 2 else grad_tol
            np.testing.assert_allclose(
                x, want, rtol=0, atol=tol * np.abs(want).max() + 1e-30,
                err_msg=f"{name}: {('o', 'last') + NAMES + ('state',)}"
                        f"[{i}]")


def test_the_chunk_must_be_whole_sub_chunks():
    *args, _ = operands(32, 0.0, 1.0)
    with pytest.raises(ValueError, match="power of two"):
        kda.kda_chunked(*args, chunk=48)


def test_the_inverse_of_a_unit_lower_triangle():
    """ops/kda_chunk.py::unit_lower_inverse against numpy's, at entries as large
    as keys that all point one way give (beta 1, no decay)."""
    rng = np.random.default_rng(0)
    for scale in (0.1, 1.0):
        lower = np.tril(rng.uniform(-scale, scale, (3, 64, 64)), -1)
        lower = lower.astype(np.float32)
        got = kda_chunk.unit_lower_inverse(jnp.asarray(lower), 16)
        want = np.linalg.inv(np.eye(64) + lower.astype(np.float64))
        np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                                   atol=1e-4 * np.abs(want).max())


# ---------------------------------------------------------------------------
# What a recomputed block keeps: the forward rule names the result and the
# states that enter the chunks (KDA_OUT, KDA_STATES); a policy that lists
# both takes the forward kernel out of the block's recomputed forward, as
# FLASH_OUT / FLASH_LSE take the flash forward kernel out.
# ---------------------------------------------------------------------------

_names = jax.checkpoint_policies.save_only_these_names
# loops of XLA's in the gradient's compiled program: none, the chunks are
# walked inside the kernels. And the kernels (ops/kda_chunk.py), whose grid
# is a loop each where they are interpreted: forward and backward, and the
# recomputed forward's where the block keeps no states
LOOPS_CASES = [
    pytest.param("kept", 0, 2, id="not-recomputed"),
    pytest.param(None, 0, 3, id="recomputed-no-policy"),
    pytest.param(_names(kda.KDA_OUT), 0, 3, id="result-without-states"),
    pytest.param(_names(kda.KDA_OUT, kda.KDA_STATES), 0, 2,
                 id="result-and-states"),
]


def _block_loss(policy):
    def block(q, k, v, g, beta):
        return jnp.tanh(kda.kda_chunked(1.5 * q, k, v, g, beta, chunk=16))

    if policy != "kept":
        block = jax.checkpoint(block, policy=policy)
    return lambda *a: (block(*a) ** 2).sum()


@pytest.fixture(scope="module")
def block_inputs():
    return operands(512, "model", "model", seed=7)[:5]


@pytest.fixture(scope="module")
def kept_block_grads(block_inputs):
    return jax.grad(_block_loss("kept"), argnums=(0, 1, 2, 3, 4))(
        *block_inputs)


@pytest.mark.parametrize("policy,loops,kernels", LOOPS_CASES)
def test_a_recomputed_block_runs_the_loops_its_policy_says(
        block_inputs, kept_block_grads, policy, loops, kernels):
    import re
    grad = jax.jit(jax.grad(_block_loss(policy), argnums=(0, 1, 2, 3, 4)))
    text = grad.lower(*block_inputs).compile().as_text()
    assert len(re.findall(r" while\(", text)) == loops + kernels
    for got, want in zip(grad(*block_inputs), kept_block_grads):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=0,
            atol=1e-5 * float(jnp.abs(want).max()))
