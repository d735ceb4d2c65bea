"""Faults planted under the timed path, to show that `correct` comes out
false: the tests run them at a tiny size, `calibrate.py` reads them on the
chip at the cell's own size. A training fault wraps the program's compiled
train step; a serving fault is planted in the engine it is handed.
"""

from __future__ import annotations


def unchanged_state(step):
    """The step computes, then hands back the state it was given."""
    import jax
    import jax.numpy as jnp

    def faulty(state, batch, rng):
        kept = jax.tree_util.tree_map(jnp.copy, state)  # the step donates
        _, metrics = step(state, batch, rng)
        return kept, metrics
    return faulty


def half_batch(step):
    """Half of the batch is left out: its rows are replaced by the other
    half's, so the mean is taken over the rest."""
    import jax
    import jax.numpy as jnp

    def faulty(state, batch, rng):
        def halve(x):
            n = x.shape[0] // 2
            y = jnp.concatenate([x[:n], x[:n]], axis=0)
            return jax.device_put(y, x.sharding)
        return step(state, jax.tree_util.tree_map(halve, batch), rng)
    return faulty


def altered_token(engine):
    """Every seventh token is altered where the engine produces it (the
    stream records the next token id instead)."""
    from distributeddeeplearning_tpu.serve import engine as engine_mod

    original = engine_mod.Request.emit
    vocab = engine.config.vocab_size
    count = [0]

    def emit(self, token, now):
        count[0] += 1
        if count[0] % 7 == 0:
            token = (int(token) + 1) % vocab
        return original(self, token, now)

    engine_mod.Request.emit = emit


TRAIN = {"unchanged_state": unchanged_state, "half_batch": half_batch}
SERVE = {"altered_token": altered_token}
ALL = {**TRAIN, **SERVE}
