"""The chunked state-space operator (ops/ssd.py::ssd_chunked) held to the
token-by-token recurrence (tests/ssd_refs.py): the result, the last state
and every gradient (x, dt, A, B, C, D and the entering state) in float32, at a
sequence that is no multiple of the chunk, with and without an entering
state, at one and at two groups of B / C, at decays that would overflow
exp(-G) of a whole chunk; a state handed on equals one long sequence; and
bfloat16 operands within bfloat16's band."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.ops import ssd
from tests.ssd_refs import ssd_recurrent

B, H, P, N = 2, 4, 8, 6
NAMES = ("x", "dt", "a", "b", "c", "d", "state")
chunked = jax.jit(ssd.ssd_chunked, static_argnames=("chunk", "return_state"))


def operands(s, groups, decay="model", seed=1, dtype=jnp.float32):
    """Drawn by numpy: drawn by JAX op by op, each draw compiles."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32))

    x = normal(B, s, H, P)
    if decay == "model":   # the model's initial steps and rates
        dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.1),
                                            (B, s, H))).astype(np.float32))
        a = -jnp.arange(1.0, H + 1.0)
    else:                  # a chunk's summed decay far below -88
        dt = jnp.full((B, s, H), 1.0)
        a = jnp.full((H,), float(decay))
    b, c = normal(B, s, groups, N), normal(B, s, groups, N)
    d, state = normal(H), normal(B, H, P, N)
    w, w_state = normal(B, s, H, P), normal(B, H, P, N)
    x, b, c = (v.astype(dtype) for v in (x, b, c))
    return (x, dt, a, b, c, d, state), (w, w_state)


# (groups, sequence, chunk, entering state, decay)
CASES = [(2, 100, 32, True, "model"),     # a padded tail and a state
         (1, 48, 16, False, -6.0)]        # one group, whole chunks, no
                                          # state; a chunk's decay -96 a head
IDS = ["g2-s100-c32-state", "g1-s48-c16-strong-decay"]


@functools.lru_cache(maxsize=None)
def _compiled(chunk, with_state):
    """(result, last state, gradients of their sum weighted by the
    cotangents) of the recurrence (``chunk`` None) or the chunked form, as
    one program."""
    fn = (ssd_recurrent if chunk is None
          else functools.partial(ssd.ssd_chunked, chunk=chunk))

    def run(x, dt, a, b, c, d, state):
        return fn(x, dt, a, b, c, d, state if with_state else None,
                  return_state=True)

    def both(args, cotangents):
        out, pull = jax.vjp(run, *args)
        return out, pull(cotangents)

    return jax.jit(both)


@pytest.fixture(scope="module", params=CASES, ids=IDS)
def both(request):
    groups, s, chunk, with_state, decay = request.param
    args, cotangents = operands(s, groups, decay)
    with jax.default_matmul_precision("highest"):
        want, want_g = _compiled(None, with_state)(args, cotangents)
        got, got_g = _compiled(chunk, with_state)(args, cotangents)
    return got, want, got_g, want_g, with_state, decay != "model"


def _close(got, want, tol):
    got, want = np.asarray(got), np.asarray(want)
    scale = float(np.abs(want).max()) or 1.0
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=0, atol=tol * scale)


@pytest.mark.parametrize("which", [0, 1], ids=["result", "last_state"])
def test_values(both, which):
    """To 1e-5 of the largest entry: float32 throughout on the CPU."""
    got, want, *_ = both
    _close(got[which], want[which], 1e-5)


@pytest.mark.parametrize("leaf", range(len(NAMES)), ids=NAMES)
def test_gradients(both, leaf):
    """Every operand's gradient, through the result and the last state, to
    2e-5 of its largest entry; the entering state's is nought where none
    entered, in both forms. Where a chunk's decay sums to -96 the decay's own
    gradients (dt's, A's) are sums over the chunk of terms of both signs
    that cancel, each with float32's rounding of a summed decay near -96 in
    its exponent: A's reads 1.2e-4 off a float64 recurrence there, the
    token-by-token float32 recurrence 1e-7, so those two are held to 3e-4."""
    _, _, got_g, want_g, with_state, strong = both
    got, want = got_g[leaf], want_g[leaf]
    if NAMES[leaf] == "state" and not with_state:
        assert not np.asarray(got).any() and not np.asarray(want).any()
        return
    _close(got, want, 3e-4 if strong and NAMES[leaf] in ("dt", "a")
           else 2e-5)


def test_a_state_handed_on_equals_one_long_sequence():
    """Cut after token 70, inside a chunk of 32: the state the head hands on
    carries the tail to one long sequence's result and last state."""
    cut = 70
    (x, dt, a, b, c, d, state), _ = operands(100, 2)
    run = functools.partial(chunked, chunk=32, return_state=True)
    with jax.default_matmul_precision("highest"):
        whole, last = run(x, dt, a, b, c, d, state)
        head, mid = run(*(v[:, :cut] for v in (x, dt)), a,
                        *(v[:, :cut] for v in (b, c)), d, state)
        tail, end = run(*(v[:, cut:] for v in (x, dt)), a,
                        *(v[:, cut:] for v in (b, c)), d, mid)
    _close(jnp.concatenate([head, tail], axis=1), whole, 1e-5)
    _close(end, last, 1e-5)


def test_bfloat16_operands_keep_the_result_within_their_band():
    """x, B and C in bfloat16, dt and the state float32: the result comes
    back in bfloat16 within 2 % of the largest entry of the float32
    recurrence on the same (rounded) operands, the last state float32."""
    args, cotangents = operands(100, 2, dtype=jnp.bfloat16)
    y, last = chunked(*args, chunk=32, return_state=True)
    assert y.dtype == jnp.bfloat16 and last.dtype == jnp.float32
    up = tuple(v.astype(jnp.float32) for v in args)
    with jax.default_matmul_precision("highest"):
        (want, want_last), _ = _compiled(None, True)(up, cotangents)
    _close(y.astype(jnp.float32), want, 2e-2)
    _close(last, want_last, 2e-2)


@pytest.mark.parametrize("chunk,want", [(2, -4.0), (4, -8.0), (8, -10.0)])
def test_the_counter_is_the_least_summed_decay_of_a_chunk(chunk, want):
    """Five tokens, head 1 at -2 a token: chunks of 2 sum to -4 at most, of 4
    to -8, and one chunk of 8 holds all five (-10) and three padded ones."""
    dt = jnp.array([[[1.0, 0.5]] * 5])                  # (1, 5, 2)
    a = jnp.array([-1.0, -4.0])
    assert float(ssd.min_chunk_log_decay(dt, a, chunk=chunk)) == want
