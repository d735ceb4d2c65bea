"""Plain optimizers for the training references, and the way back from an
optimizer's state after one step to the gradient it was given.

`adamw`: Loshchilov & Hutter 2019, bias-corrected moments, decoupled weight
decay on the leaves the model's `decays(name)` names. `sgd`: momentum SGD with
the weight decay added to the gradient of those leaves (coupled, as in Goyal
et al. 2017). Learning rates are constant: the checked steps are the first
three. Parameters are flat dicts of '/'-joined names.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def init(opt: dict, params: dict) -> dict:
    zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
    if opt["name"] == "adamw":
        return {"mu": zeros, "nu": dict(zeros), "t": 0}
    if opt["name"] == "sgd":
        return {"trace": zeros}
    raise ValueError(f"no plain reference for optimizer {opt['name']!r}")


@jax.jit
def _adamw(params, grads, mu, nu, decay, lr, b1, b2, eps, wd, t):
    def leaf(p, g, m, v, d):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        return p - lr * (u + wd * d * p), m, v
    out = {k: leaf(params[k], grads[k], mu[k], nu[k], decay[k])
           for k in params}
    return ({k: v[0] for k, v in out.items()},
            {k: v[1] for k, v in out.items()},
            {k: v[2] for k, v in out.items()})


@jax.jit
def _sgd(params, grads, trace, decay, lr, momentum, wd):
    def leaf(p, g, tr, d):
        tr = momentum * tr + g + wd * d * p
        return p - lr * tr, tr
    out = {k: leaf(params[k], grads[k], trace[k], decay[k]) for k in params}
    return ({k: v[0] for k, v in out.items()},
            {k: v[1] for k, v in out.items()})


def step(opt: dict, decays, params: dict, grads: dict, state: dict):
    """One update. `decays(name)` says which leaves take weight decay."""
    decay = {k: jnp.float32(bool(decays(k))) for k in params}
    if opt["name"] == "adamw":
        t = state["t"] + 1
        p, mu, nu = _adamw(params, grads, state["mu"], state["nu"], decay,
                           opt["learning_rate"], opt["beta1"], opt["beta2"],
                           opt["eps"], opt["weight_decay"], jnp.float32(t))
        return p, {"mu": mu, "nu": nu, "t": t}
    p, trace = _sgd(params, grads, state["trace"], decay,
                    opt["learning_rate"], opt["momentum"],
                    opt["weight_decay"])
    return p, {"trace": trace}


def _norm(x):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))


@jax.jit
def _adamw_first_norms(moment, scale):
    return {k: _norm(m / scale) for k, m in moment.items()}


@jax.jit
def _sgd_first_norms(moment, params0, decay, wd):
    return {k: _norm(m - wd * decay[k] * params0[k])
            for k, m in moment.items()}


def first_gradient_norms(opt: dict, decays, moment: dict, params0) -> dict:
    """Leaf norms of the gradient the optimizer was given at its first step,
    worked out from its state after that step: Adam's first moment is
    (1 - beta1) * g; SGD's momentum buffer is g plus the decay term of the
    starting weights (`params0()` makes them, and is called only there).

    One program, in which a leaf's gradient lives only inside its own
    reduction: no tree of the parameters' size is made beside the state. The
    scalars go in as arguments: a division by a constant is compiled as a
    product with the constant's rounded reciprocal, which gives other bits."""
    if opt["name"] == "adamw":
        return _adamw_first_norms(moment, jnp.float32(1 - opt["beta1"]))
    decay = {k: jnp.float32(bool(decays(k))) for k in moment}
    return _sgd_first_norms(moment, params0(), decay,
                            jnp.float32(opt["weight_decay"]))


def moment_field(opt: dict) -> str:
    """Name of the field, in the program's optax state, that holds that
    moment."""
    return {"adamw": "mu", "sgd": "trace"}[opt["name"]]
