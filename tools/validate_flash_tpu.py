#!/usr/bin/env python
"""On-hardware validation of the Pallas flash-attention kernels: run the
COMPILED forward+backward on the TPU and compare against a dense float32
reference — plain, causal, and with the in-kernel hash dropout against the
materialized ``dense_keep_mask`` reference — then (unless --skip-timing)
time flash against dense, and read each kernel's device time from a trace.

    python tools/validate_flash_tpu.py [--shape 8,512,12,64] [--causal]
        [--skip-timing] [--tiles 512x512,256x256]

With no ``--shape`` it does all of that at the two shapes the models run:
the benchmark cell's (16,1024,12,64, causal) and BERT's (8,512,12,64, not
causal); both with a key-padding mask and dropout 0.1 in the kernel times.
``--tiles`` times further tile sizes (block_q x block_k overrides) beside
the derived ones, which is how a default in ``ops/flash_attention.py`` is
chosen.

Prints one JSON line per check; exits nonzero off-TPU and on any mismatch.
``check_correctness`` is what chip_smoke.py runs at the train phase's shape.
"""

from __future__ import annotations

import argparse
import collections
import functools
import glob
import json
import os
import re
import sys
import tempfile
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

RATE, SEED = 0.1, 20260731


def dense_ref(q, k, v, mask, *, causal=False, keep=None):
    """softmax(QK^T)V in float32; ``keep`` (B,H,S,S) applies dropout the
    way the kernels do: after the softmax, kept probs scaled by 1/(1-r)."""
    s_len, d = q.shape[1], q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (d ** -0.5)
    valid = mask[:, None, None, :]
    if causal:
        valid = valid & jnp.tril(jnp.ones((s_len, s_len), bool))[None, None]
    p = jax.nn.softmax(jnp.where(valid, s, jnp.finfo(jnp.float32).min),
                       axis=-1)
    if keep is not None:
        p = jnp.where(keep, p / (1.0 - RATE), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))


def _inputs(shape):
    b, s, h, d = shape
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
               for _ in range(3))
    # Padding mask with ragged valid lengths, incl. one fully-valid row.
    lens = np.r_[s, rng.integers(s // 4, s, b - 1)]
    mask = jnp.asarray(np.arange(s)[None, :] < lens[:, None])
    return q, k, v, mask


def check_correctness(shape=(8, 512, 12, 64), causal=False) -> bool:
    """Compiled flash vs the dense reference at ``shape`` (B,S,H,D), bf16:
    forward and gradients, without and with dropout. One JSON line each."""
    from distributeddeeplearning_tpu.ops.flash_attention import (
        flash_attention)
    from distributeddeeplearning_tpu.ops.hash_dropout import dense_keep_mask

    b, s, h, _ = shape
    q, k, v, mask = _inputs(shape)
    valid = mask[:, :, None, None].astype(jnp.float32)
    ok = True
    for label, rate in (("", 0.0), ("dropout_", RATE)):
        flash = functools.partial(
            flash_attention, causal=causal, dropout_rate=rate,
            dropout_seed=jnp.int32(SEED) if rate else None)

        # The (B,H,S,S) keep mask is built inside the jitted reference: as
        # a closed-over array it would be a 200 MB constant of the program.
        def ref(q, k, v, mask, rate=rate):
            keep = (dense_keep_mask(jnp.int32(SEED), b, h, s, s, RATE)
                    if rate else None)
            return dense_ref(q, k, v, mask, causal=causal, keep=keep)

        def loss(fn, q, k, v):
            return (fn(q, k, v, mask).astype(jnp.float32) * valid).sum()

        out_f = jax.jit(flash)(q, k, v, mask).astype(jnp.float32)
        out_r = jax.jit(ref)(q, k, v, mask)
        fwd_err = float(jnp.abs((out_f - out_r) * valid).max())
        ok_fwd = fwd_err < 2e-2  # bf16 inputs, f32 accumulation
        rec = {"check": f"flash_{label}forward", "shape": list(shape),
               "causal": causal, "max_abs_err": fwd_err, "ok": ok_fwd}
        if rate:
            rec["dropped_frac_ref"] = round(1.0 - float(jax.jit(
                lambda: dense_keep_mask(jnp.int32(SEED), b, h, s, s,
                                        RATE).mean())()), 4)
        print(json.dumps(rec), flush=True)

        gf = jax.jit(jax.grad(functools.partial(loss, flash),
                              argnums=(0, 1, 2)))(q, k, v)
        gr = jax.jit(jax.grad(functools.partial(loss, ref),
                              argnums=(0, 1, 2)))(q, k, v)
        errs, ok_bwd = {}, True
        for name, a, r in zip(("dq", "dk", "dv"), gf, gr):
            a, r = a.astype(jnp.float32), r.astype(jnp.float32)
            errs[name] = float(jnp.abs(a - r).max()
                               / jnp.maximum(jnp.abs(r).max(), 1.0))
            ok_bwd &= errs[name] < 3e-2
        print(json.dumps({"check": f"flash_{label}backward",
                          "shape": list(shape), "causal": causal,
                          "rel_err": errs, "ok": ok_bwd}), flush=True)
        ok &= ok_fwd and ok_bwd
    return ok


def _timed(fn, *args, iters=20):
    jax.block_until_ready(fn(*args))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def time_kernels(shape, causal) -> None:
    from distributeddeeplearning_tpu.ops.flash_attention import (
        flash_attention)

    q, k, v, mask = _inputs(shape)
    flash = jax.jit(functools.partial(flash_attention, causal=causal))
    flash_do = jax.jit(functools.partial(
        flash_attention, causal=causal, dropout_rate=RATE,
        dropout_seed=jnp.int32(SEED)))
    dense = jax.jit(functools.partial(dense_ref, causal=causal))

    def grad_of(fn):
        return jax.jit(jax.grad(
            lambda q, k, v: fn(q, k, v, mask).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))

    print(json.dumps({
        "check": "timing", "shape": list(shape), "causal": causal,
        "device_kind": jax.devices()[0].device_kind,
        "fwd_ms": {"flash": round(_timed(flash, q, k, v, mask) * 1e3, 3),
                   "flash_dropout": round(
                       _timed(flash_do, q, k, v, mask) * 1e3, 3),
                   "dense": round(_timed(dense, q, k, v, mask) * 1e3, 3)},
        "fwd_bwd_ms": {
            "flash": round(_timed(grad_of(flash), q, k, v) * 1e3, 3),
            "dense": round(_timed(grad_of(dense), q, k, v) * 1e3, 3)},
    }), flush=True)


KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def kernel_times(shape, causal, block_q=None, block_k=None,
                 iters=10) -> None:
    """The plan at ``shape`` and the device time of each of the three
    kernels, from a profiler trace of ``iters`` forward+backward calls with
    the key-padding mask and dropout in the kernels: ms a call, and us a
    visited tile. The kernels are found by the names they were given
    (ops/flash_attention.py), as the benchmark's ``device_ms.flash_*`` do."""
    from distributeddeeplearning_tpu.ops.flash_attention import (
        flash_attention, tile_plan)

    b, s, h, _ = shape
    q, k, v, mask = _inputs(shape)
    step = jax.jit(jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, mask, causal=causal, block_q=block_q, block_k=block_k,
            dropout_rate=RATE, dropout_seed=jnp.int32(SEED),
        ).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
    jax.block_until_ready(step(q, k, v))  # compile + warm
    with tempfile.TemporaryDirectory() as log_dir:
        with jax.profiler.trace(log_dir):
            for _ in range(iters):
                out = step(q, k, v)
            jax.block_until_ready(out)
        path = sorted(glob.glob(os.path.join(
            log_dir, "**", "*.xplane.pb"), recursive=True))[-1]
        data = jax.profiler.ProfileData.from_file(path)
    ns = collections.Counter()
    for plane in data.planes:
        if not re.match(r"^/device:TPU:\d+$", plane.name):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                found = re.search(r"flash_(fwd|dq|dkv)\b", ev.name)
                ns[found.group(0) if found else "other"] += ev.duration_ns
    plan = tile_plan(s, causal, block_q, block_k)
    ms = {name: ns[name] / iters / 1e6 for name in KERNELS}
    print(json.dumps({
        "check": "kernel_times", "shape": list(shape), "causal": causal,
        "plan": plan._asdict(),
        "visited_share": round(plan.visited / plan.total, 4),
        "ms_a_call": {n: round(t, 4) for n, t in ms.items()},
        "us_a_tile": {n: round(t * 1e3 / (b * h * plan.visited), 3)
                      for n, t in ms.items()},
        "sum_ms": round(sum(ms.values()), 4),
        "other_ms": round(ns["other"] / iters / 1e6, 4),
    }), flush=True)


# What the models run: the benchmark cell's attention (gpt2_small, 16 x
# 1024, causal) and BERT-base's (8 x 512, key-padding mask only).
MODEL_SHAPES = (((16, 1024, 12, 64), True), ((8, 512, 12, 64), False))


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--shape", default=None,
                   help="B,S,H,D (default: the two shapes the models run)")
    p.add_argument("--causal", action="store_true")
    p.add_argument("--skip-timing", action="store_true")
    p.add_argument("--tiles", default="",
                   help="further block_q x block_k to time, e.g. "
                        "512x512,256x256")
    args = p.parse_args(argv)
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(json.dumps({"error": f"need TPU, got {platform}"}))
        return 1
    cases = MODEL_SHAPES if args.shape is None else (
        (tuple(int(x) for x in args.shape.split(",")), args.causal),)
    tiles = [(None, None)] + [tuple(int(x) for x in t.split("x"))
                              for t in args.tiles.split(",") if t]
    ok = True
    for shape, causal in cases:
        ok &= check_correctness(shape, causal)
        if not args.skip_timing:
            time_kernels(shape, causal)
            for block_q, block_k in tiles:
                kernel_times(shape, causal, block_q, block_k)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
