#!/usr/bin/env python
"""On-hardware validation of the Pallas flash-attention kernels: run the
COMPILED forward+backward on the TPU and compare against a dense float32
reference — plain, causal, and with the in-kernel hash dropout against the
materialized ``dense_keep_mask`` reference — then (unless --skip-timing)
time flash against dense, and read each kernel's device time from a trace.

    python tools/validate_flash_tpu.py [--shape 8,512,12,64] [--causal]
        [--window 2048] [--skip-timing] [--tiles 512x512,256x256]

With no ``--shape`` it does all of that at the shapes the models run
(``MODEL_SHAPES``): the gpt2 cell's (16,1024,12,64, causal) and BERT's
(8,512,12,64, not causal), both with a key-padding mask and dropout 0.1 in
the kernel times, latent attention's in the kimi_linear and xing4 cells
(1,8192,32,192/128 and 1,4096,32,192/128, causal: values 128 wide), and the
trinity_mini cell's sliding and full layers (1,8192,32/4,128, causal, with
and without a window of 2048). A heads field ``32/4`` is 32 Q heads on 4 K/V
heads, and ``--window`` cuts the causal triangle to a band.

The backward pass is one kernel (``flash_dkv`` carries dQ) where its
accumulators fit ``ops/flash_attention.py::fused_bwd_fits``, and two
(``flash_dq`` beside it) past that. Both are run at every shape: the
gradients of the one are held to the other's (``rel_err_two_kernels``), and
each path's kernels are timed (``"path"`` of a ``kernel_times`` line).

The dense reference goes a head at a time, so S = 8192 fits; where the
(B,H,S,S) dropout mask of the reference would not, the dropout comparison
is left out and the kernels are timed without dropout.
``--tiles`` times further tile sizes (block_q x block_k overrides) beside
the derived ones, which is how a default in ``ops/flash_attention.py`` is
chosen.

Prints one JSON line per check; exits nonzero off-TPU and on any mismatch.
``check_correctness`` is what chip_smoke.py runs at the train phase's shape.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import functools
import glob
import importlib
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
FLASH = "distributeddeeplearning_tpu.ops.flash_attention"
PATHS = ("fused", "two_kernels")


@contextlib.contextmanager
def backward_path(path: str):
    """Trace the flash backward as ``path`` whatever the shapes: the module's
    budget for the fused kernel is set to nothing for ``two_kernels``, and
    left as it is for ``fused`` (every shape of ``MODEL_SHAPES`` fits it)."""
    module = importlib.import_module(FLASH)
    kept = module._FUSED_BWD_BYTES
    if path == "two_kernels":
        module._FUSED_BWD_BYTES = -1
    try:
        yield
    finally:
        module._FUSED_BWD_BYTES = kept


def dense_ref(q, k, v, mask, *, causal=False, keep=None, window=None):
    """softmax(QK^T)V in float32, a Q head at a time (Q head h on K/V head
    h // (H // Hkv)), each recomputed when differentiated; ``keep``
    (B,H,S,S) applies dropout the way the kernels do: after the softmax,
    kept probs scaled by 1/(1-r)."""
    s_len, h, d = q.shape[1:]
    groups = h // k.shape[2]
    valid = mask[:, None, :]
    if causal:
        valid = valid & jnp.tril(jnp.ones((s_len, s_len), bool))[None]
    if window is not None:
        valid = valid & ~jnp.tril(jnp.ones((s_len, s_len), bool),
                                  -window)[None]

    @jax.checkpoint
    def head(i):
        qh = q[:, :, i].astype(jnp.float32)
        kh, vh = (x[:, :, i // groups].astype(jnp.float32) for x in (k, v))
        s = jnp.einsum("bqd,bkd->bqk", qh, kh) * (d ** -0.5)
        p = jax.nn.softmax(jnp.where(valid, s, jnp.finfo(jnp.float32).min),
                           axis=-1)
        if keep is not None:
            p = jnp.where(keep[:, i], p / (1.0 - RATE), 0.0)
        return jnp.einsum("bqk,bkd->bqd", p, vh)

    return jnp.moveaxis(jax.lax.map(head, jnp.arange(h)), 0, 2)


MASK_ELEMENTS_MAX = 2 ** 30  # the reference's (B,H,S,S) dropout mask


def _dropout_fits(shape) -> bool:
    b, s, h, _ = shape
    return b * h * s * s <= MASK_ELEMENTS_MAX


def _inputs(shape, kv_heads=None, v_dim=None):
    b, s, h, d = shape
    rng = np.random.default_rng(0)
    kv_shape = (b, s, kv_heads or h, d)
    q, k, v = (jnp.asarray(rng.standard_normal(sh), jnp.bfloat16)
               for sh in (shape, kv_shape, kv_shape[:3] + (v_dim or d,)))
    # Padding mask with ragged valid lengths, incl. one fully-valid row.
    lens = np.r_[s, rng.integers(s // 4, s, b - 1)]
    mask = jnp.asarray(np.arange(s)[None, :] < lens[:, None])
    return q, k, v, mask


def _rel_err(a, r) -> float:
    """max |a - r| over max(max |r|, 1), in float32."""
    a, r = a.astype(jnp.float32), r.astype(jnp.float32)
    return float(jnp.abs(a - r).max() / jnp.maximum(jnp.abs(r).max(), 1.0))


def _fits(q, k, v) -> bool:
    """Does the fused backward run at these (B,S,H,D) inputs' shapes?"""
    module = importlib.import_module(FLASH)
    s = module._padded_len(q.shape[1])
    return module.fused_bwd_fits(s, q.shape[3], v.shape[3],
                                 q.shape[2] // k.shape[2])


def check_correctness(shape=(8, 512, 12, 64), causal=False, window=None,
                      kv_heads=None, v_dim=None) -> bool:
    """Compiled flash vs the dense reference at ``shape`` (B,S,H,D), bf16:
    forward and gradients, without and with dropout, and the gradients of
    the fused backward against those of the two kernels. One JSON line
    each."""
    from distributeddeeplearning_tpu.ops.flash_attention import (
        flash_attention)
    from distributeddeeplearning_tpu.ops.hash_dropout import dense_keep_mask

    b, s, h, _ = shape
    q, k, v, mask = _inputs(shape, kv_heads, v_dim)
    valid = mask[:, :, None, None].astype(jnp.float32)
    ok = True
    cases = (("", 0.0), ("dropout_", RATE))
    for label, rate in cases if _dropout_fits(shape) else cases[:1]:
        flash = functools.partial(
            flash_attention, causal=causal, window=window, dropout_rate=rate,
            dropout_seed=jnp.int32(SEED) if rate else None)

        # The (B,H,S,S) keep mask is built inside the jitted reference: as
        # a closed-over array it would be a 200 MB constant of the program.
        def ref(q, k, v, mask, rate=rate):
            keep = (dense_keep_mask(jnp.int32(SEED), b, h, s, s, RATE)
                    if rate else None)
            return dense_ref(q, k, v, mask, causal=causal, keep=keep,
                             window=window)

        def loss(fn, q, k, v):
            return (fn(q, k, v, mask).astype(jnp.float32) * valid).sum()

        out_f = jax.jit(flash)(q, k, v, mask).astype(jnp.float32)
        out_r = jax.jit(ref)(q, k, v, mask)
        fwd_err = float(jnp.abs((out_f - out_r) * valid).max())
        ok_fwd = fwd_err < 2e-2  # bf16 inputs, f32 accumulation
        rec = {"check": f"flash_{label}forward", "shape": list(shape),
               "kv_heads": k.shape[2], "v_dim": v.shape[3],
               "causal": causal, "window": window,
               "max_abs_err": fwd_err, "ok": ok_fwd}
        if rate:
            rec["dropped_frac_ref"] = round(1.0 - float(jax.jit(
                lambda: dense_keep_mask(jnp.int32(SEED), b, h, s, s,
                                        RATE).mean())()), 4)
        print(json.dumps(rec), flush=True)

        grads = {}
        for path in PATHS:
            with backward_path(path):
                grads[path] = jax.jit(jax.grad(functools.partial(
                    loss, flash), argnums=(0, 1, 2)))(q, k, v)
        gr = jax.jit(jax.grad(functools.partial(loss, ref),
                              argnums=(0, 1, 2)))(q, k, v)
        errs, errs_two, ok_bwd = {}, {}, True
        for name, a, t, r in zip(("dq", "dk", "dv"), grads["fused"],
                                 grads["two_kernels"], gr):
            errs[name] = _rel_err(a, r)
            errs_two[name] = _rel_err(a, t)
            ok_bwd &= errs[name] < 3e-2 and errs_two[name] < 1e-2
        print(json.dumps({"check": f"flash_{label}backward",
                          "shape": list(shape), "kv_heads": k.shape[2],
                          "v_dim": v.shape[3], "causal": causal,
                          "window": window, "fused": _fits(q, k, v),
                          "rel_err": errs,
                          "rel_err_two_kernels": errs_two,
                          "ok": ok_bwd}), flush=True)
        ok &= ok_fwd and ok_bwd
    return ok


def _timed(fn, *args, iters=20):
    jax.block_until_ready(fn(*args))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def time_kernels(shape, causal, window=None, kv_heads=None,
                 v_dim=None) -> None:
    from distributeddeeplearning_tpu.ops.flash_attention import (
        flash_attention)

    q, k, v, mask = _inputs(shape, kv_heads, v_dim)
    flash = jax.jit(functools.partial(flash_attention, causal=causal,
                                      window=window))
    flash_do = jax.jit(functools.partial(
        flash_attention, causal=causal, window=window, dropout_rate=RATE,
        dropout_seed=jnp.int32(SEED)))
    dense = jax.jit(functools.partial(dense_ref, causal=causal,
                                      window=window))

    def grad_of(fn):
        return jax.jit(jax.grad(
            lambda q, k, v: fn(q, k, v, mask).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))

    print(json.dumps({
        "check": "timing", "shape": list(shape), "kv_heads": k.shape[2],
        "causal": causal, "window": window,
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


def kernel_times(shape, causal, block_q=None, block_k=None, iters=10,
                 window=None, kv_heads=None, v_dim=None,
                 path="fused") -> None:
    """The plan at ``shape`` and the device time of each of the kernels of
    the backward ``path`` (``PATHS``), from a profiler trace of ``iters``
    forward+backward calls with the key-padding mask and dropout in the
    kernels: ms a call, and us a visited tile. The kernels are found by the
    names they were given (ops/flash_attention.py), as the benchmark's
    ``device_ms.flash_*`` do."""
    from distributeddeeplearning_tpu.ops.flash_attention import (
        flash_attention, tile_plan)

    b, s, h, _ = shape
    q, k, v, mask = _inputs(shape, kv_heads, v_dim)
    rate = RATE if _dropout_fits(shape) else 0.0
    step = jax.jit(jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, mask, causal=causal, window=window, block_q=block_q,
            block_k=block_k, dropout_rate=rate,
            dropout_seed=jnp.int32(SEED) if rate else None,
        ).astype(jnp.float32).sum(), argnums=(0, 1, 2)))
    with backward_path(path):
        jax.block_until_ready(step(q, k, v))  # compile + warm
    with tempfile.TemporaryDirectory() as log_dir:
        with jax.profiler.trace(log_dir):
            for _ in range(iters):
                out = step(q, k, v)
            jax.block_until_ready(out)
        xplane = sorted(glob.glob(os.path.join(
            log_dir, "**", "*.xplane.pb"), recursive=True))[-1]
        data = jax.profiler.ProfileData.from_file(xplane)
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
    plan = tile_plan(s, causal, block_q, block_k, window=window)
    ms = {name: ns[name] / iters / 1e6 for name in KERNELS}
    print(json.dumps({
        "check": "kernel_times", "path": path, "shape": list(shape),
        "kv_heads": k.shape[2], "v_dim": v.shape[3], "causal": causal,
        "window": window, "dropout": rate,
        "plan": plan._asdict(),
        "visited_share": round(plan.visited / plan.total, 4),
        "ms_a_call": {n: round(t, 4) for n, t in ms.items()},
        "us_a_tile": {n: round(t * 1e3 / (b * h * plan.visited), 3)
                      for n, t in ms.items()},
        "sum_ms": round(sum(ms.values()), 4),
        "other_ms": round(ns["other"] / iters / 1e6, 4),
    }), flush=True)


# What the models run: the gpt2 cell's attention (16 x 1024, causal), BERT-
# base's (8 x 512, key-padding mask only), latent attention's in the
# kimi_linear and xing4 cells (32 heads, queries and keys 192 wide, values
# 128, one causal sequence of 8192 and of 4096), and the trinity_mini cell's
# sliding and full layers (32 Q heads on 4 K/V heads of 128, one causal
# sequence of 8192, a window of 2048 and none).
# (shape, causal, values' width if another, K/V heads if fewer, window)
MODEL_SHAPES = (((16, 1024, 12, 64), True, None, None, None),
                ((8, 512, 12, 64), False, None, None, None),
                ((1, 8192, 32, 192), True, 128, None, None),
                ((1, 4096, 32, 192), True, 128, None, None),
                ((1, 8192, 32, 128), True, None, 4, 2048),
                ((1, 8192, 32, 128), True, None, 4, None))


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--shape", default=None,
                   help="B,S,H,D, with H/Hkv for fewer K/V heads and D/Dv "
                        "for values of another width (default: the shapes "
                        "the models run)")
    p.add_argument("--causal", action="store_true")
    p.add_argument("--window", type=int, default=None,
                   help="query - key < WINDOW (needs --causal)")
    p.add_argument("--skip-timing", action="store_true")
    p.add_argument("--tiles", default="",
                   help="further block_q x block_k to time, e.g. "
                        "512x512,256x256")
    args = p.parse_args(argv)
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(json.dumps({"error": f"need TPU, got {platform}"}))
        return 1
    if args.shape is not None:
        b, s, heads, d = args.shape.split(",")
        heads, _, kv = heads.partition("/")
        d, _, dv = d.partition("/")
        cases = (((int(b), int(s), int(heads), int(d)), args.causal,
                  int(dv) if dv else None, int(kv) if kv else None,
                  args.window),)
    else:
        cases = MODEL_SHAPES
    tiles = [(None, None)] + [tuple(int(x) for x in t.split("x"))
                              for t in args.tiles.split(",") if t]
    ok = True
    for shape, causal, v_dim, kv_heads, window in cases:
        kw = dict(window=window, kv_heads=kv_heads, v_dim=v_dim)
        ok &= check_correctness(shape, causal, **kw)
        if not args.skip_timing:
            if _dropout_fits(shape):  # its dense side makes (B,H,S,S) too
                time_kernels(shape, causal, **kw)
            for block_q, block_k in tiles:
                for path in PATHS:
                    kernel_times(shape, causal, block_q, block_k, path=path,
                                 **kw)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
