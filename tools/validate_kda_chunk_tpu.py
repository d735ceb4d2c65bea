#!/usr/bin/env python
"""On-hardware validation of the chunked delta rule's two kernels
(ops/kda_chunk.py): run the COMPILED ``kda_chunk_fwd`` / ``kda_chunk_bwd`` on
the TPU at the kimi cell's block shapes (bf16 q, k, v; heads of 128; chunks
of 64; sub-chunks of 16), compare their six results and five gradients with
the plain array lines they replaced (tests/test_kda_chunk.py keeps them) on
one group of 8 chunks, and time both: the kernels on a layer's 128 chunks x
32 heads, the plain lines on one group (a layer is 16 of them).

    python tools/validate_kda_chunk_tpu.py [--heads 1,2,4,16] [--skip-plain]

A second check runs both on float32 operands against the plain lines in
float64 on the host: what the three bfloat16 passes of either's products
cost. ``--heads`` times further numbers of heads a grid step beside the
module's own, which is how its default was chosen. Prints one JSON line per
check; exits nonzero off-TPU and on any mismatch.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests"))

import jax
import jax.numpy as jnp
import numpy as np

import test_kda_chunk as plain
from distributeddeeplearning_tpu.ops import kda_chunk

BH, C, D, SUB = 32, 64, 128, 16


def _operands(n):
    return plain.operands(n, BH, C, D, "model", dtype=jnp.bfloat16, tail=7)


def _vjp_of(fn):
    return plain._vjp_of(fn, SUB)


def _timed(fn, *args, iters=10):
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / iters * 1e3


def check_correctness():
    args, cts = _operands(8)
    got, got_grads = _vjp_of(kda_chunk.prepare)(args, cts)
    want, want_grads = _vjp_of(plain._prepare)(args, cts)
    worst, ok = {}, True
    for names, gs, ws, tol in ((plain.RESULTS, got, want, 2.0 ** -7),
                               (plain.LEAVES, got_grads, want_grads,
                                2.0 ** -5)):
        for name, g, w in zip(names, gs, ws):
            g, w = (np.asarray(x, np.float32) for x in (g, w))
            err = float(np.abs(g - w).max() / (np.abs(w).max() + 1e-30))
            worst[name] = err
            ok &= bool(np.isfinite(g).all()) and err <= tol
    print(json.dumps({"check": "correctness", "ok": ok,
                      "error_of_largest": worst}), flush=True)
    return ok


def check_float32():
    """float32 operands, where nothing but the products' passes rounds: the
    kernels and the plain lines, both on the chip, against the plain lines
    in float64 on the host."""
    args, cts = plain.operands(2, 8, C, D, "model", tail=7)
    got = _vjp_of(kda_chunk.prepare)(args, cts)
    old = _vjp_of(plain._prepare)(args, cts)
    with jax.enable_x64(True), jax.default_device(jax.devices("cpu")[0]):
        wide = [tuple(jnp.asarray(np.asarray(x, np.float64)) for x in xs)
                for xs in (args, cts)]
        want = jax.tree_util.tree_map(np.asarray,
                                      _vjp_of(plain._prepare)(*wide))
    errors = {}
    for side, (outs, grads) in (("kernels", got), ("plain_lines", old)):
        errors[side] = {
            name: float(np.abs(np.asarray(x, np.float64) - w).max()
                        / (np.abs(w).max() + 1e-300))
            for names, xs, ws in ((plain.RESULTS, outs, want[0]),
                                  (plain.LEAVES, grads, want[1]))
            for name, x, w in zip(names, xs, ws)}
    # with float32 q, k, v the products that meet them are one bfloat16
    # pass on the chip for both (2e-3): the kernels are held to the lines
    ok = all(e <= 1.5 * errors["plain_lines"][name] + 1e-5
             for name, e in errors["kernels"].items())
    print(json.dumps({"check": "float32_against_float64", "ok": ok,
                      "error_of_largest": errors}), flush=True)
    return ok


def time_kernels(heads):
    if heads:
        kda_chunk._heads_a_step = lambda bh: heads
    args, cts = _operands(128)
    fwd = jax.jit(lambda *a: kda_chunk.prepare(*a, SUB))
    both = _vjp_of(kda_chunk.prepare)
    f = _timed(fwd, *args)
    b = _timed(both, args, cts)
    print(json.dumps({
        "check": "kernel_times", "heads_a_step": heads or "default",
        "chunk_heads": 128 * BH,
        "fwd_ms_a_layer": f, "fwd_and_bwd_ms_a_layer": b,
        "bwd_ms_a_layer": b - f}), flush=True)


def time_plain():
    args, cts = _operands(8)
    fwd = jax.jit(lambda *a: plain._prepare(*a, SUB))
    both = _vjp_of(plain._prepare)
    f = _timed(fwd, *args)
    b = _timed(both, args, cts)
    print(json.dumps({
        "check": "plain_times", "groups_a_layer": 16,
        "fwd_ms_a_layer": 16 * f, "fwd_and_bwd_ms_a_layer": 16 * b}),
        flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--heads", default="")
    parser.add_argument("--skip-plain", action="store_true")
    args = parser.parse_args(argv)
    if jax.default_backend() != "tpu":
        print(json.dumps({"ok": False, "why": "no TPU"}))
        return 1
    ok = check_correctness() and check_float32()
    time_kernels(0)
    for h in filter(None, args.heads.split(",")):
        time_kernels(int(h))
    if not args.skip_plain:
        time_plain()
    print(json.dumps({"ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
