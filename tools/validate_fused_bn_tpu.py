#!/usr/bin/env python
"""On-hardware validation + timing of the fused BatchNorm kernel families.

Stages, each printing one JSON line:

1. correctness — COMPILED kernels vs a plain jnp float32-statistics
   reference, forward and gradients: fused BN(+residual)+ReLU
   (ops/fused_batchnorm.py, ``--fused-bn``) and the matmul with BN
   prologue/statistics epilogue (ops/fused_linear_bn.py, ``--fused-block``)
   at real ResNet50 activation shapes;
2. step-time A/B (unless --skip-bench) — resnet50 synthetic training step,
   fused_bn off vs on.

Exits nonzero off-TPU and on a correctness failure:
    python tools/validate_fused_bn_tpu.py [--batch-size 512] [--steps 20]

``check_correctness`` / ``check_linear_bn`` are what chip_smoke.py runs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def _rel_errs(got, want, names) -> dict:
    errs = {}
    for a, b, name in zip(got, want, names):
        a32, b32 = np.asarray(a, np.float32), np.asarray(b, np.float32)
        errs[name] = float(np.max(np.abs(a32 - b32))
                           / max(float(np.max(np.abs(b32))), 1e-6))
    return errs


def check_correctness(m: int = 64 * 28 * 28, c: int = 512) -> bool:
    """bn_act_res_train at an (M, C) activation — default a mid-network
    ResNet50 shape, (B=64, H=W=28, C=512)."""
    from distributeddeeplearning_tpu.ops import fused_batchnorm as fbn

    eps = 1e-5
    x = jax.random.normal(jax.random.key(0), (m, c), jnp.bfloat16)
    res = jax.random.normal(jax.random.key(1), (m, c), jnp.bfloat16)
    gamma = (jax.random.normal(jax.random.key(2), (c,)) * 0.2 + 1.0)
    beta = jax.random.normal(jax.random.key(3), (c,)) * 0.1
    w = jax.random.normal(jax.random.key(4), (m, c), jnp.float32)

    def ref(x, g, b, r):
        xf = x.astype(jnp.float32)
        mean = xf.mean(axis=0)
        var = ((xf - mean) ** 2).mean(axis=0)
        y = (xf - mean) * jax.lax.rsqrt(var + eps) * g + b
        return jnp.maximum(y + r.astype(jnp.float32), 0.0)

    # w is an argument, not a closure: a closed-over array becomes a
    # constant of the executable (hundreds of MB at these shapes).
    def loss_fused(x, g, b, r, w):
        y, _, _ = fbn.bn_act_res_train(x, g, b, r, True, eps)
        return jnp.sum(y.astype(jnp.float32) * w)

    def loss_ref(x, g, b, r, w):
        return jnp.sum(ref(x, g, b, r) * w)

    t0 = time.perf_counter()
    yf = jax.jit(lambda *a: fbn.bn_act_res_train(*a, True, eps)[0])(
        x, gamma, beta, res)
    yr = jax.jit(ref)(x, gamma, beta, res)
    fwd_err = float(jnp.max(jnp.abs(yf.astype(jnp.float32) - yr)))
    gf = jax.jit(jax.grad(loss_fused, argnums=(0, 1, 2, 3)))(
        x, gamma, beta, res, w)
    gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2, 3)))(
        x, gamma, beta, res, w)
    errs = _rel_errs(gf, gr, ("dx", "dgamma", "dbeta", "dres"))
    # bf16 storage tolerance; bf16 output ULP at O(10) magnitudes
    ok = all(e < 3e-2 for e in errs.values()) and fwd_err < 0.1
    print(json.dumps({
        "check": "fused_bn_correctness", "shape": [m, c], "ok": bool(ok),
        "fwd_max_abs_err": round(fwd_err, 5),
        "grad_rel_err": {k: round(v, 5) for k, v in errs.items()},
        "wall_s": round(time.perf_counter() - t0, 1)}), flush=True)
    return ok


def check_linear_bn(m: int = 50176, k: int = 1024, n: int = 256) -> bool:
    """bn_linear_stats (BN+ReLU prologue, matmul, Σy/Σy² epilogue) at an
    (M, K) x (K, N) bottleneck 1x1 conv — default stage-3 conv1 at
    batch 256."""
    from distributeddeeplearning_tpu.ops.fused_linear_bn import (
        bn_linear_stats)

    x = jax.random.normal(jax.random.key(0), (m, k), jnp.bfloat16)
    w = (jax.random.normal(jax.random.key(1), (k, n)) * k ** -0.5
         ).astype(jnp.bfloat16)
    mu = jax.random.normal(jax.random.key(2), (k,)) * 0.1
    inv = jax.random.uniform(jax.random.key(3), (k,), minval=0.5,
                             maxval=1.5)
    gamma = jax.random.normal(jax.random.key(4), (k,)) * 0.2 + 1.0
    beta = jax.random.normal(jax.random.key(5), (k,)) * 0.1
    # Cotangents for y, Σy and Σy² of comparable weight. (A tiny constant
    # weight on Σy would hide below bfloat16's step next to an O(1) dy — the
    # kernel feeds dY = dy + ds + 2y·dss to the MXU in bfloat16 — while its
    # M-fold sum would still dominate dβ/dγ: an ill-conditioned test, which
    # XLA's excess-precision reference then "fails" at random.)
    cy = jax.random.normal(jax.random.key(6), (m, n), jnp.float32)
    cs = jax.random.normal(jax.random.key(7), (n,)) * 0.5
    css = jax.random.normal(jax.random.key(8), (n,)) * 0.05

    def ref(x, mu, inv, gamma, beta, w):
        a = jnp.maximum(
            (x.astype(jnp.float32) - mu) * (inv * gamma) + beta, 0.0
        ).astype(x.dtype)
        y = jnp.dot(a, w, preferred_element_type=jnp.float32
                    ).astype(x.dtype)
        yf = y.astype(jnp.float32)
        return y, yf.sum(axis=0), (yf * yf).sum(axis=0)

    def loss(fn, cy, x, mu, inv, gamma, beta, w):
        y, s, ss = fn(x, mu, inv, gamma, beta, w)
        return (jnp.sum(y.astype(jnp.float32) * cy) + jnp.sum(s * cs)
                + jnp.sum(ss * css))

    def fused(*a):
        return bn_linear_stats(*a, True, True)

    t0 = time.perf_counter()
    args = (x, mu, inv, gamma, beta, w)
    out_f, out_r = jax.jit(fused)(*args), jax.jit(ref)(*args)
    fwd = _rel_errs(out_f, out_r, ("y", "sum", "sumsq"))
    argnums = (1, 2, 3, 4, 5, 6)  # cy (argument 0) is data, not a weight
    gf = jax.jit(jax.grad(lambda *a: loss(fused, *a), argnums))(cy, *args)
    gr = jax.jit(jax.grad(lambda *a: loss(ref, *a), argnums))(cy, *args)
    errs = _rel_errs(gf, gr, ("dx", "dmu", "dinv", "dgamma", "dbeta", "dw"))
    ok = (all(e < 3e-2 for e in errs.values())
          and all(e < 2e-2 for e in fwd.values()))
    print(json.dumps({
        "check": "fused_linear_bn_correctness", "shape": [m, k, n],
        "ok": bool(ok),
        "fwd_rel_err": {k_: round(v, 5) for k_, v in fwd.items()},
        "grad_rel_err": {k_: round(v, 5) for k_, v in errs.items()},
        "wall_s": round(time.perf_counter() - t0, 1)}), flush=True)
    return ok


def bench_step(fused: bool, batch_size: int, steps: int) -> float:
    from distributeddeeplearning_tpu import data as datalib
    from distributeddeeplearning_tpu.config import (
        DataConfig, ParallelConfig, TrainConfig)
    from distributeddeeplearning_tpu.models import model_spec
    from distributeddeeplearning_tpu.train import loop

    n_dev = jax.device_count()
    cfg = TrainConfig(
        model="resnet50", global_batch_size=batch_size * n_dev,
        dtype="bfloat16", log_every=10**9, fused_bn=fused,
        parallel=ParallelConfig(data=n_dev), data=DataConfig(synthetic=True))
    spec = model_spec(cfg.model)
    mesh, model, batch_shd, state, train_step, sched, rng = loop.build(cfg, 64)
    source = datalib.make_source(cfg, spec.input_kind, batch_shd)
    i = 0
    metrics = None
    for _ in range(5):
        state, metrics = train_step(state, source.batch(i), rng)
        i += 1
    jax.block_until_ready(metrics)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = train_step(state, source.batch(i), rng)
        i += 1
    jax.block_until_ready(metrics)
    dt = (time.perf_counter() - t0) / steps
    return cfg.global_batch_size / dt / n_dev


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--batch-size", type=int, default=512)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--skip-bench", action="store_true")
    args = p.parse_args(argv)
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(json.dumps({"error": f"need TPU, got {platform}"}))
        return 1

    ok = check_correctness() and check_linear_bn()
    if not args.skip_bench:
        base = bench_step(False, args.batch_size, args.steps)
        fused = bench_step(True, args.batch_size, args.steps)
        print(json.dumps({
            "check": "fused_bn_step_ab", "batch_per_chip": args.batch_size,
            "imgs_per_sec_per_chip": {"unfused": round(base, 1),
                                      "fused": round(fused, 1)},
            "speedup": round(fused / base, 3)}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
