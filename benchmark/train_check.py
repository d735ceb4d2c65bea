"""What a training cell's `correct` compares, and how.

The program's first three steps (taken in set-up, through the window's own
compiled step and feed) against the plain float32 reference's first three
steps from the same seed. Three numbers, each a gap relative to the reference:

- `loss_gap`: the largest |loss - reference loss| / reference loss of the
  three steps;
- `grad_gap`: worst leaf of | ||g|| - ||g_ref|| | / max(||g_ref||, median leaf's
  ||g_ref||), for the first gradient as the optimizer got it;
- `change_gap`: the same measure for ||p3 - p0||, the parameters' change
  after three steps, over the leaves whose reference gradient is not nought
  to rounding (at least a thousandth of the median leaf's).

A cell's traffic file gives each number its limit (`limits`); PERF.md gives
the readings behind each.

The reference runs after the window, with the program's state freed.
"""

from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp

from benchmark.references import optim

STEPS = 3


def leaf_norms(tree: dict) -> dict:
    return {k: float(jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))))
            for k, v in tree.items()}


def reference_readings(ref, sz: dict, traffic: dict, opt: dict, seed_key,
                       rng_key, quant=None) -> dict:
    """Losses, first-gradient leaf norms and parameter-change leaf norms of
    the first `STEPS` plain float32 training steps from the seed (`quant`:
    the lower-precision control's rounding of every product's operands)."""
    kw = {} if quant is None else {"quant": quant}
    grad_fn = ref.make_grad_fn(sz, traffic, **kw)
    params = jax.jit(lambda k: ref.init_params(sz, k))(seed_key)
    p0 = params
    extra = ref.init_extra(sz)
    state = optim.init(opt, params)
    gen = jax.jit(lambda k, i: ref.make_batch(traffic, sz, k, i))
    out = {"loss": []}
    with jax.default_matmul_precision("highest"):
        for i in range(STEPS):
            batch = gen(seed_key, jnp.int32(i))
            loss, grads, extra = grad_fn(params, extra, batch,
                                         jax.random.fold_in(rng_key, i))
            if i == 0:
                out["grad"] = leaf_norms(grads)
            params, state = optim.step(opt, ref.decays, params, grads, state)
            out["loss"].append(float(loss))
    out["change"] = leaf_norms({k: params[k] - p0[k] for k in params})
    return out


def _leaf_gaps(got: dict, want: dict, keep=None) -> dict:
    med = statistics.median(want.values())
    return {name: abs(got[name] - w) / max(w, med, 1e-30)
            for name, w in want.items() if keep is None or name in keep}


def compare(program: dict, reference: dict) -> dict:
    """The three gaps, with the leaf or step at which each is widest."""
    gaps = [abs(p - r) / abs(r)
            for p, r in zip(program["loss"], reference["loss"])]
    med = statistics.median(reference["grad"].values())
    moving = {k for k, g in reference["grad"].items() if g >= 1e-3 * med}
    grad = _leaf_gaps(program["grad"], reference["grad"])
    change = _leaf_gaps(program["change"], reference["change"], keep=moving)
    grad_at = max(grad, key=grad.get)
    change_at = max(change, key=change.get)
    return {"loss_gap": max(gaps), "grad_gap": grad[grad_at],
            "change_gap": change[change_at],
            "at": {"loss_gap": f"step {gaps.index(max(gaps)) + 1}",
                   "grad_gap": grad_at, "change_gap": change_at},
            "leaves_left_out": sorted(set(reference["grad"]) - moving)}


def verdict(numbers: dict, limits: dict):
    """(correct, [[name, value, limit], ...]) for the numbers that have a
    limit. A number that is not finite fails."""
    rows, ok = [], True
    for name, limit in limits.items():
        value = numbers[name]
        rows.append([name, value, limit])
        if not value <= limit:
            ok = False
    return ok, rows
