"""What the ZeRO parity tests hold a sharded step to, and why (the one place).

The tests run ``resnet18_thin`` for three steps on the 8-device CPU mesh under
each ``optimizer_sharding`` stage and compare parameters. What was measured
(PR 29, this XLA:CPU, float32):

- Layout and collectives are exact. ``psum`` and ``psum_scatter`` followed by
  ``all_gather`` give the same bits (both sum left to right over the eight
  devices), chunking and gathering are the identity, and the averaged
  gradients of the replicated and the sharded step are the same bits.
- The sharded stages are one trajectory: zero1, zero2 and zero3 agree in every
  bit of parameters and optimizer state, for SGD-momentum and for AdamW.
- Without a multiply that feeds an add in the update (SGD-momentum, no weight
  decay) the replicated step agrees with them in every bit too.
- What parts the replicated step from the sharded ones is how the compiler
  rounds such a multiply-add: once (fused) in one program, twice in the other.
  With weight decay 1e-4 the decayed gradients ``g + wd * p`` after one step
  differ in 1191 elements of three leaves; in every one of them the replicated
  program holds the once-rounded value and the sharded programs the
  twice-rounded one. AdamW's ``b1 * mu + (1 - b1) * g`` does the same from the
  second step on (the first starts from zero moments).

So the seed is one rounding of a term far below a parameter's last bit
(``lr * wd * |p| * 2**-24``): a step can flip the last bit of a parameter and
no more. What the next gradient makes of a flipped bit is the network's
affair (two examples a device under BatchNorm, ReLUs and pooling that can
change their choice) and is read, not derived:

- SGD-momentum multiplies the gradient's answer by the rate. Read after three
  steps: half a last bit of the leaf's largest element (7.45e-9 at 0.21). Held
  to one last bit of the leaf's largest element a step.
- Adam divides by ``sqrt(nu)``: its step is the rate times a direction in
  [-1, 1] whatever the gradient's size, so where the gradient is near zero an
  absolute 1e-8 is a visible share of the direction. Read: nothing after one
  step, one last bit after two, 7.6e-4 of one step of the rate after three
  (2.3e-3 with weight decay 0). Held to one last bit for two steps, then to
  ``ADAM_DIRECTION_SHIFT`` = 2**-8 of the rate a further step. That number
  is an empirical ceiling, not a derived bound: 1.7 times the larger of the
  two readings, and 2**-8 of what a wrong shard would show. (A derived one
  needs the gap of the two programs' gradients over ``sqrt(nu) + eps``
  element by element, which these tests do not keep.) A failure's message
  says so.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import numpy as np

from distributeddeeplearning_tpu import data as datalib
from distributeddeeplearning_tpu.config import (
    DataConfig, OptimizerConfig, ParallelConfig, TrainConfig)
from distributeddeeplearning_tpu.models import model_spec
from distributeddeeplearning_tpu.train import loop

STEPS = 3
ADAM_DIRECTION_SHIFT = 2.0 ** -8
ADAM_NOTE = (" (AdamW from the third step: an empirical ceiling of 2**-8 of "
             "the rate a step, read 7.6e-4 of it with weight decay 0.01 and "
             "2.3e-3 with 0; not a derived bound)")

OPTIMIZERS = {
    "sgd_momentum": dict(name="sgd", learning_rate=0.1, momentum=0.9,
                         weight_decay=1e-4),
    "adamw": dict(name="adamw", learning_rate=1e-3, weight_decay=0.01),
    # No multiply feeds an add: nothing for the compiler to round twice.
    "sgd_momentum_no_decay": dict(name="sgd", learning_rate=0.1,
                                  momentum=0.9, weight_decay=0.0),
}


def cfg(opt_kw, sharding, **kw):
    base = dict(
        model="resnet18_thin", global_batch_size=16, dtype="float32",
        log_every=10**9, parallel=ParallelConfig(data=8),
        data=DataConfig(synthetic=True, image_size=32, num_classes=10),
        optimizer=OptimizerConfig(schedule="constant", **opt_kw),
        optimizer_sharding=sharding)
    base.update(kw)
    return TrainConfig(**base)


def build(config, total_steps=4):
    spec = model_spec(config.model)
    _mesh, _model, batch_shd, state, train_step, _sched, rng = loop.build(
        config, total_steps)
    source = datalib.make_source(config, spec.input_kind, batch_shd,
                                 objective=spec.objective)
    return state, train_step, source, rng


def full_params(state, train_step):
    """Replicated full-shape params on the host whatever the stage (zero3
    states hold 1/N chunks; the converter gathers them)."""
    conv = getattr(train_step, "zero_converter", None)
    if conv is not None:
        state = conv.full_params_state(state)
    return jax.device_get(state.params)


class Trajectory(NamedTuple):
    state: object          # the live state after STEPS steps
    train_step: object
    params: tuple          # full params on the host after each step


@functools.lru_cache(maxsize=None)
def trajectory(optimizer: str, sharding: str) -> Trajectory:
    """STEPS steps of one optimizer under one stage, run once a process."""
    state, train_step, source, rng = build(
        cfg(OPTIMIZERS[optimizer], sharding), STEPS)
    params = []
    for i in range(STEPS):
        state, _ = train_step(state, source.batch(i), rng)
        params.append(full_params(state, train_step))
    return Trajectory(state, train_step, tuple(params))


def allowed_gap(optimizer: str, step: int, leaf_max: float) -> float:
    """The most a float32 parameter leaf whose largest element is
    ``leaf_max`` may differ from the replicated path's after ``step`` steps
    (module docstring)."""
    opt = OPTIMIZERS[optimizer]
    if not opt["weight_decay"] and opt["name"] == "sgd":
        return 0.0
    last_bit = float(np.spacing(np.float32(leaf_max)))
    if opt["name"] == "sgd":
        return step * last_bit
    return (last_bit
            + max(step - 2, 0) * opt["learning_rate"] * ADAM_DIRECTION_SHIFT)


def assert_matches_replicated(optimizer: str, sharding: str) -> None:
    replicated = trajectory(optimizer, "none")
    sharded = trajectory(optimizer, sharding)
    for step in range(1, STEPS + 1):
        adam_ceiling = OPTIMIZERS[optimizer]["name"] == "adamw" and step > 2
        ref, got = replicated.params[step - 1], sharded.params[step - 1]
        flat = jax.tree_util.tree_flatten_with_path(ref)[0]
        for (path, r), g in zip(flat, jax.tree_util.tree_leaves(got)):
            gap = float(np.max(np.abs(r - g)))
            allowed = allowed_gap(optimizer, step, float(np.max(np.abs(r))))
            assert gap <= allowed, (
                f"{sharding} against replicated, {optimizer}, step {step}, "
                f"{jax.tree_util.keystr(path)}: {gap:.3e} > {allowed:.3e}"
                + (ADAM_NOTE if adam_ceiling else ""))
