#!/usr/bin/env python
"""On-hardware validation of the chunked delta rule's two kernels
(ops/kda_chunk.py, ``kda_fwd`` / ``kda_bwd``): run the COMPILED operator on
the TPU at the kimi cell's shapes (one sequence of 8192 tokens, 32 heads of
128 channels, bf16 q, k and v, chunks of 64, sub-chunks of 16), compare its
result and its five gradients with

- the recurrence token by token (ops/kda.py::kda_recurrent) in float32 at
  precision "highest" on the same values, a block of 64 tokens recomputed
  at a time for the gradient: the exact arithmetic;
- the loop over chunks as XLA ran it before the kernels
  (tests/kda_refs.py::xla_groups, its stateless work as array lines), on
  the same operands: the same roundings by another road;
- and, with ``--against FILE``, the result and gradients another checkout's
  operator saved with ``--save FILE`` (the parent's: ``--package-root``
  names the checkout whose package is imported);

then time the operator's forward and its gradient on one layer, a call at a
time (host clock, median of 10 calls after two).

    python tools/validate_kda_tpu.py --package-root <parent> --save /tmp/p.npz
    python tools/validate_kda_tpu.py --against /tmp/p.npz
    python tools/validate_kda_tpu.py --rehearse    # 256 tokens, 4 heads, CPU

Prints one JSON line per check; exits nonzero off-TPU and on any mismatch.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
B, S, H, D, CHUNK = 1, 8192, 32, 128, 64
LEAVES = ("q", "k", "v", "g", "beta")


def _operands():
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.key(2147483659), 6)
    q, k, v = (jax.random.normal(ks[i], (B, S, H, D)) for i in range(3))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * D ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = -jnp.exp(1.5 * jax.random.normal(ks[3], (B, S, H, D)) - 1.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, H)))
    bf = jnp.bfloat16
    weight = jax.random.normal(ks[5], (B, S, H, D)).astype(bf)
    return (q.astype(bf), k.astype(bf), v.astype(bf), g, beta), weight


def _value_and_grads(fn):
    """(o, the five gradients of sum(o * weight)) as one program."""
    import jax

    def run(args, weight):
        out, vjp = jax.vjp(fn, *args)
        return out, vjp(weight.astype(out.dtype))
    return jax.jit(run)


def _recurrence(q, k, v, g, beta):
    """kda_recurrent in float32, by blocks of a chunk whose insides are
    recomputed for the gradient (8192 states of 2 MB would not fit)."""
    import jax
    import jax.numpy as jnp
    from distributeddeeplearning_tpu.ops import kda

    f32 = jnp.float32
    blocks = S // CHUNK

    @jax.checkpoint
    def block(state, x):
        o, state = kda.kda_recurrent(*x, state, return_state=True)
        return state, o

    xs = tuple(x.astype(f32).reshape((B, blocks, CHUNK) + x.shape[2:])
               .swapaxes(0, 1) for x in (q, k, v, g, beta))
    state = jnp.zeros((B, H, D, D), f32)
    with jax.default_matmul_precision("highest"):
        _, o = jax.lax.scan(block, state, xs)
    return o.swapaxes(0, 1).reshape(B, S, H, D)


def _xla_loop(q, k, v, g, beta):
    import jax.numpy as jnp
    from distributeddeeplearning_tpu.ops import kda
    from tests.kda_refs import xla_groups

    laid = [kda.lay_out(x) for x in (q, k, v, g, beta)]
    _, o = xla_groups(*laid, jnp.zeros((B * H, D, D), jnp.float32))
    return kda.lay_back(o, B, S)


def _errors(got, want):
    import numpy as np
    out = {}
    for name, x, w in zip(("o",) + LEAVES, got, want):
        x, w = (np.asarray(a, np.float64) for a in (x, w))
        out[name] = (float(np.abs(x - w).max() / (np.abs(w).max() + 1e-300))
                     if np.isfinite(x).all() else float("inf"))
    return out


def _flat(result):
    out, grads = result
    return [out, *grads]


def _timed(fn, *args, iters=10):
    import jax
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(iters):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - start) * 1e3)
    return statistics.median(times)


def time_operator(label: str):
    """A layer's forward, and its forward with the gradient, on the laid-out
    operands the model hands the operator (ops/kda.py::kda_groups)."""
    import jax
    from distributeddeeplearning_tpu.ops import kda

    args, weight = _operands()
    laid = [kda.lay_out(x) for x in args]
    w = kda.lay_out(weight)
    fwd = jax.jit(lambda *a: kda.kda_groups(*a))
    both = _value_and_grads(lambda *a: kda.kda_groups(*a))
    f = _timed(fwd, *laid)
    b = _timed(both, laid, w)
    print(json.dumps({"check": "times", "operator": label,
                      "fwd_ms_a_layer": f, "fwd_and_bwd_ms_a_layer": b,
                      "bwd_ms_a_layer": b - f}), flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--package-root", default=ROOT,
                        help="the checkout whose package is measured")
    parser.add_argument("--save", default="",
                        help="save the operator's result and gradients "
                             "here, time it, and compare nothing")
    parser.add_argument("--against", default="",
                        help="a --save file to compare with")
    parser.add_argument("--rehearse", action="store_true",
                        help="256 tokens and 4 heads, on any platform")
    args = parser.parse_args(argv)
    global S, H
    if args.rehearse:
        S, H = 256, 4
    sys.path[:0] = [os.path.abspath(args.package_root), ROOT]
    import jax
    import numpy as np
    from distributeddeeplearning_tpu.ops import kda

    if jax.default_backend() != "tpu" and not args.rehearse:
        print(json.dumps({"ok": False, "why": "no TPU"}))
        return 1
    operands, weight = _operands()
    got = [np.asarray(x).astype(np.float32) for x in _flat(_value_and_grads(
        lambda *a: kda.kda_chunked(*a, chunk=CHUNK))(operands, weight))]
    if args.save:
        np.savez(args.save, *got)
        time_operator(args.package_root)
        print(json.dumps({"ok": True, "saved": args.save}))
        return 0
    ok = True
    # the kernels against the exact arithmetic, and the XLA loop against it
    # on the same values: the kernels round where the loop rounds (T, the
    # decayed operands and the state in q's type where they meet a product,
    # products summed in float32), so each result and gradient is held to
    # the loop's own distance from the exact arithmetic, with a quarter of
    # room for roundings that fall the other way, and a floor of 1e-3 for
    # the quantities the two read nearly exactly
    exact = _flat(_value_and_grads(_recurrence)(operands, weight))
    loop = [np.asarray(x) for x in _flat(_value_and_grads(_xla_loop)(
        operands, weight))]
    mine, theirs = _errors(got, exact), _errors(loop, exact)
    held = {n: mine[n] <= 1.25 * theirs[n] + 1e-3 for n in mine}
    ok &= all(held.values())
    print(json.dumps({"check": "against_the_recurrence", "ok": all(
        held.values()), "kernels": mine, "xla_loop": theirs}), flush=True)
    # the kernels against the loop itself: o by a rounding or two of o and
    # of the rows the chunk writes (2^-7 of its largest entry); a gradient
    # by the cotangents that the loop's derivative rounds to bfloat16 where
    # the backward kernel keeps them float32 (2^-5, as the stateless
    # kernels were held to their array lines on this chip)
    near = _errors(got, loop)
    limits = {n: 2.0 ** -7 if n == "o" else 2.0 ** -5 for n in near}
    print(json.dumps({"check": "against_the_xla_loop", "ok": all(
        near[n] <= limits[n] for n in near), "error_of_largest": near,
        "limits": limits}), flush=True)
    ok &= all(near[n] <= limits[n] for n in near)
    if args.against:
        saved = np.load(args.against)
        parent = [saved[f"arr_{i}"] for i in range(len(saved.files))]
        near = _errors(got, parent)
        print(json.dumps({"check": "against_the_saved_operator", "ok": all(
            near[n] <= limits[n] for n in near), "error_of_largest": near,
            "limits": limits, "saved_against_the_recurrence": _errors(
                parent, exact)}), flush=True)
        ok &= all(near[n] <= limits[n] for n in near)
    time_operator(args.package_root)
    print(json.dumps({"ok": bool(ok)}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
