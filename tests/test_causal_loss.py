"""train/losses.py's causal cross entropy: a batch of one sequence picks the
label's logit by a select (no gather), larger batches by optax's gather; value
and gradient are optax's either way."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributeddeeplearning_tpu.train import losses


@pytest.mark.parametrize("b,s,v,masked", [(1, 9, 11, False), (3, 8, 50, True),
                                          (2, 16, 7, True)])
def test_causal_loss_is_optaxs(b, s, v, masked):
    key = jax.random.key(b * 100 + s)
    logits = 3.0 * jax.random.normal(key, (b, s, v))
    ids = jax.random.randint(jax.random.fold_in(key, 1), (b, s), 0, v)
    mask = None
    if masked:
        mask = (jnp.arange(s)[None, :] < jnp.arange(s - b, s)[:, None] + 1
                ).astype(jnp.int32)

    def want(logits):
        per = optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], ids[:, 1:])
        w = (jnp.ones_like(per) if mask is None
             else (mask[:, :-1] * mask[:, 1:]).astype(jnp.float32))
        return (per * w).sum() / jnp.maximum(w.sum(), 1.0)

    got, g_got = jax.value_and_grad(
        lambda x: losses.causal_lm_loss(x, ids, mask))(logits)
    ref, g_ref = jax.value_and_grad(want)(logits)
    assert float(got) == pytest.approx(float(ref), rel=1e-6)
    np.testing.assert_allclose(np.asarray(g_got), np.asarray(g_ref),
                               rtol=0, atol=1e-6)
