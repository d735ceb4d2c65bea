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

B, H, DK, DV = 2, 3, 8, 12
NAMES = ("q", "k", "v", "g", "beta")


def operands(s, gate, beta, seed=1):
    ks = jax.random.split(jax.random.key(seed), 6)
    q = jax.random.normal(ks[0], (B, s, H, DK))
    k = jax.random.normal(ks[1], (B, s, H, DK))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, s, H, DV))
    if gate == "model":   # spread as the model's initial gates are
        g = -jnp.exp(1.5 * jax.random.normal(ks[3], (B, s, H, DK)) - 1.0)
    else:
        g = jnp.full((B, s, H, DK), float(gate), jnp.float32)
    if beta == "model":
        b = jax.nn.sigmoid(jax.random.normal(ks[4], (B, s, H)))
    else:
        b = jnp.full((B, s, H), float(beta), jnp.float32)
    return q, k, v, g, b, jax.random.normal(ks[5], (B, s, H, DV))


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
# states that enter the groups of chunks (KDA_OUT, KDA_STATES); a policy that
# lists both takes the loops over chunks out of the block's recomputed
# forward, as FLASH_OUT / FLASH_LSE take the forward kernel out.
# ---------------------------------------------------------------------------

_names = jax.checkpoint_policies.save_only_these_names
# loops in the gradient's compiled program at 4 groups of 8 chunks: forward
# (groups, chunks), backward (groups, a group's chunks remade, and back
# through them): 5; a recomputed forward adds its 2. And the kernels of a
# group's stateless work (ops/kda_chunk.py), whose grid is a loop each where
# they are interpreted: forward, remade, backward, and the recomputed
# forward's
LOOPS_CASES = [
    pytest.param("kept", 5, 3, id="not-recomputed"),
    pytest.param(None, 7, 4, id="recomputed-no-policy"),
    pytest.param(_names(kda.KDA_OUT), 7, 4, id="result-without-states"),
    pytest.param(_names(kda.KDA_OUT, kda.KDA_STATES), 5, 3,
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
