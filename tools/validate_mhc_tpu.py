#!/usr/bin/env python
"""On-hardware validation of the hyper-connections' four kernels
(ops/mhc.py): run the COMPILED ``mhc_in_fwd`` / ``mhc_in_bwd`` /
``mhc_out_fwd`` / ``mhc_out_bwd`` on the TPU at the xing4 cell's shapes
(4096 tokens, four bf16 streams of 3584 channels), compare their results and
gradients with the plain array lines they replaced (``mhc_in_plain``,
``mhc_out_plain``), and time each kernel alone beside the plain lines.

    python tools/validate_mhc_tpu.py [--tokens 4096] [--width 3584]

Units: what ``rsqrt``, ``sigmoid``, a division and a long bfloat16 product
give inside a kernel on this chip, to explain a mismatch. Correctness: the
kernels and the plain lines on the bf16 operands, each against the plain
lines on float32 copies, as a share of the largest entry; the kernels are
held to the plain lines' own band. Timing: each kernel's device time from
a profiler trace, its call on the host's clock (the custom rules' small XLA
work round it included), its bytes moved (operands and results as the
kernel reads and writes them) and that as a share of the chip's 819 GB/s
in the device time. Prints one JSON line per check; exits nonzero off-TPU
and on any mismatch.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from distributeddeeplearning_tpu.ops import mhc

N, EPS, BANDWIDTH = 4, 1e-6, 819e9
F32, BF16 = jnp.float32, jnp.bfloat16


def operands(t: int, c: int, dtype=BF16):
    """The two passes' inputs and cotangents, drawn at the scale a layer
    sees: streams and sub-layer output of unit size, Phi of 0.02."""
    m = N * N + 2 * N
    ks = jax.random.split(jax.random.key(0), 9)
    x = jax.random.normal(ks[0], (1, t, N * c)).astype(dtype)
    scale = 1.0 + 0.1 * jax.random.normal(ks[1], (N * c,))
    phi = 0.02 * jax.random.normal(ks[2], (N * c, m))
    alpha, bias = jnp.float32(0.01), jnp.log(jnp.full((N,), 1 / 3.0))
    y = jax.random.normal(ks[3], (1, t, c)).astype(dtype)
    coef = jax.random.uniform(ks[4], (1, t, N + N * N))
    d_out = jax.random.normal(ks[5], (1, t, N * c)).astype(dtype)
    dh = jax.random.normal(ks[6], (1, t, c)).astype(dtype)
    du = 1e-3 * jax.random.normal(ks[7], (1, t, m)) * (
        jnp.arange(m) >= N)                     # the pre columns' comes inside
    return (x, scale, phi, alpha, bias), (y, coef), (d_out, dh, du)


def _in_vjp(fn, args, cts):
    """The input pass's results and its vjp at (dh, du, the write's share
    of dX): the plain lines take the write's share as a plain sum."""
    d_out, dh, du = cts

    def f(*a):
        h, u, *rest = fn(*a)
        return h, u, (rest[0] if rest else a[0])

    out, vjp = jax.vjp(f, *args)
    return out[:2], vjp((dh, du, d_out))


def _rel(got, want) -> float:
    got, want = (np.asarray(v, np.float64) for v in (got, want))
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


def check_correctness(t: int, c: int) -> bool:
    args, (y, coef), cts = operands(t, c)
    wide = tuple(a.astype(F32) if a.dtype == BF16 else a for a in args)
    wide_cts = tuple(v.astype(F32) for v in cts)

    def kernels(*a):
        return mhc.mhc_in(*a, eps=EPS)

    def plain(*a):
        return mhc.mhc_in_plain(*a, eps=EPS)

    want = jax.jit(lambda a, ct: _in_vjp(plain, a, ct))(wide, wide_cts)
    errors = {}
    for side, fn in (("kernels", kernels), ("plain_lines", plain)):
        (h, u), grads = jax.jit(lambda a, ct: _in_vjp(fn, a, ct))(args, cts)
        errors[side] = {"h": _rel(h, want[0][0]), "u": _rel(u, want[0][1])}
        for name, g, w in zip(("dx", "dscale", "dphi", "dalpha", "dbias"),
                              grads, want[1]):
            errors[side][name] = _rel(g, w)
    w_out, w_vjp = jax.vjp(mhc.mhc_out_plain, args[0].astype(F32),
                           y.astype(F32), coef)
    w_grads = w_vjp(cts[0].astype(F32))
    for side, fn in (("kernels", mhc.mhc_out), ("plain_lines",
                                                 mhc.mhc_out_plain)):
        out, grads = jax.jit(lambda x, y, c, g: (
            fn(x, y, c), jax.vjp(fn, x, y, c)[1](g)))(args[0], y, coef,
                                                     cts[0])
        errors[side]["x_out"] = _rel(out, w_out)
        for name, g, w in zip(("out_dx", "out_dy", "out_dcoef"), grads,
                              w_grads):
            errors[side][name] = _rel(g, w)
    # the plain lines' own band: bf16's rounding where they round, float32's
    # where they do not (u and the parameters' leaves)
    ok = all(e <= 1.25 * errors["plain_lines"][k] + 1e-5
             for k, e in errors["kernels"].items())
    print(json.dumps({"check": "correctness", "ok": ok,
                      "error_of_largest": errors}), flush=True)
    return ok


def check_units() -> None:
    """What the chip's units give inside a kernel, against float64 on the
    host, as the largest relative error: ``rsqrt``, ``sigmoid`` (over
    [-12, 12], and over [-4, 4], where a layer's pre-activations lie), a
    division, and a bfloat16 product over 14336 channels added up in
    float32 (as a share of the largest entry)."""
    from jax.experimental import pallas as pl

    from distributeddeeplearning_tpu.ops.pallas import pallas_call

    a = jnp.exp(jax.random.uniform(jax.random.key(1), (8, 128), minval=-8.0,
                                   maxval=8.0))
    z = jax.random.uniform(jax.random.key(2), (8, 128), minval=-12.0,
                           maxval=12.0)
    x = jax.random.normal(jax.random.key(3), (128, 14336)).astype(BF16)
    w = jax.random.normal(jax.random.key(4), (14336, 128)).astype(BF16)

    def kernel(a_ref, z_ref, x_ref, w_ref, o_ref, p_ref):
        o_ref[0] = jax.lax.rsqrt(a_ref[...])
        o_ref[1] = jax.nn.sigmoid(z_ref[...])
        o_ref[2] = z_ref[...] / a_ref[...]
        p_ref[...] = jnp.dot(x_ref[...], w_ref[...],
                             preferred_element_type=F32)

    whole = [pl.BlockSpec(s.shape, lambda: (0,) * len(s.shape))
             for s in (a, z, x, w)]
    out, prod = jax.jit(pallas_call(
        kernel, name="units",
        in_specs=whole,
        out_specs=[pl.BlockSpec((3, 8, 128), lambda: (0, 0, 0)),
                   pl.BlockSpec((128, 128), lambda: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((3, 8, 128), F32),
                   jax.ShapeDtypeStruct((128, 128), F32)]))(a, z, x, w)
    a64, z64 = np.asarray(a, np.float64), np.asarray(z, np.float64)
    rs, sg = 1.0 / np.sqrt(a64), 1.0 / (1.0 + np.exp(-z64))
    out = np.asarray(out, np.float64)
    exact = np.asarray(x, np.float64) @ np.asarray(w, np.float64)

    def rel(got, want, where=True):
        return float(np.where(where, np.abs(got / want - 1.0), 0.0).max())

    print(json.dumps({
        "check": "units", "rsqrt": rel(out[0], rs), "sigmoid": rel(out[1], sg),
        "sigmoid_within_4": rel(out[1], sg, np.abs(z64) <= 4.0),
        "divide": rel(out[2], z64 / a64),
        "bf16_dot_f32_sum": _rel(prod, exact)}), flush=True)


def _timed(fn, *args, iters: int = 20) -> float:
    """ms a call, calls queued back to back."""
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    start = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) / iters * 1e3


def _device_ms(fn, *args, name: str, iters: int = 10) -> float:
    """Device ms a call of the kernel ``name``, from a profiler trace of
    ``iters`` calls: the operations the trace names by it (ops/mhc.py gives
    each kernel its name, as the benchmark's parts find them)."""
    jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory() as log_dir:
        with jax.profiler.trace(log_dir):
            for _ in range(iters):
                out = fn(*args)
            jax.block_until_ready(out)
        path = sorted(glob.glob(os.path.join(
            log_dir, "**", "*.xplane.pb"), recursive=True))[-1]
        data = jax.profiler.ProfileData.from_file(path)
    ns = sum(ev.duration_ns for plane in data.planes
             if re.match(r"^/device:TPU:\d+$", plane.name)
             for line in plane.lines if line.name == "XLA Ops"
             for ev in line.events if re.match(rf"%?{name}\b", ev.name))
    return ns / iters / 1e6


def _nbytes(*shapes) -> int:
    return sum(int(np.prod(s)) * jnp.dtype(d).itemsize for s, d in shapes)


def time_kernels(t: int, c: int) -> None:
    args, (y, coef), (d_out, dh, du) = operands(t, c)
    x, scale, phi, alpha, bias = args
    m, w = phi.shape[1], N * c
    flat = x.reshape(t, w)
    h, u, _ = mhc._mhc_in(flat, scale, phi, alpha, bias, EPS)
    in_res = (flat, scale, phi, alpha, bias, u)
    out_res = (flat, y.reshape(t, c), coef.reshape(t, -1))
    streams, stream, row = (t, w), (t, c), (t, 128)
    rows_dw = -(-3 * m // 8) * 8
    calls = {
        "mhc_in_fwd": (
            jax.jit(lambda *a: mhc._mhc_in(*a, EPS)[:2]),
            (flat, scale, phi, alpha, bias),
            _nbytes((streams, BF16), ((w, 128), BF16), (stream, BF16),
                    (row, F32))),
        "mhc_in_bwd": (
            jax.jit(lambda r, ct: mhc._mhc_in_bwd(EPS, r, ct)),
            (in_res, (dh.reshape(stream), du.reshape(t, m),
                      d_out.reshape(streams))),
            _nbytes((streams, BF16), (stream, BF16), (streams, BF16),
                    (row, F32), (row, F32), ((128, w), BF16),
                    (streams, BF16), ((rows_dw, w), F32), (row, F32))),
        "mhc_out_fwd": (
            jax.jit(lambda *a: mhc._mhc_out(*a)), out_res,
            _nbytes((streams, BF16), (stream, BF16), (row, F32),
                    (streams, BF16))),
        "mhc_out_bwd": (
            jax.jit(mhc._mhc_out_bwd), (out_res, d_out.reshape(streams)),
            _nbytes((streams, BF16), (stream, BF16), (row, F32),
                    (streams, BF16), (streams, BF16), (stream, BF16),
                    (row, F32))),
    }
    for name, (fn, fargs, nbytes) in calls.items():
        device_ms = _device_ms(fn, *fargs, name=name)
        print(json.dumps({
            "check": "kernel_time", "kernel": name,
            "host_ms_a_call": _timed(fn, *fargs), "device_ms": device_ms,
            "bytes": nbytes, "share_of_bandwidth": nbytes / BANDWIDTH
            / (device_ms * 1e-3)}), flush=True)


def time_plain(t: int, c: int) -> None:
    """The plain lines the kernels replaced, one hyper-connection: the
    input pass, the output pass, and both passes' gradients."""
    args, (y, coef), (d_out, dh, du) = operands(t, c)
    fwd_in = jax.jit(lambda *a: mhc.mhc_in_plain(*a, eps=EPS))
    fwd_out = jax.jit(mhc.mhc_out_plain)
    both_in = jax.jit(lambda a, ct: _in_vjp(
        lambda *b: mhc.mhc_in_plain(*b, eps=EPS), a, ct))
    both_out = jax.jit(lambda x, y, coef, g: jax.vjp(
        mhc.mhc_out_plain, x, y, coef)[1](g))
    kern_in = jax.jit(lambda a, ct: _in_vjp(
        lambda *b: mhc.mhc_in(*b, eps=EPS), a, ct))
    kern_out = jax.jit(lambda x, y, coef, g: jax.vjp(
        mhc.mhc_out, x, y, coef)[1](g))
    cts = (d_out, dh, du)
    print(json.dumps({
        "check": "one_hyper_connection_ms",
        "plain": {"in_fwd": _timed(fwd_in, *args),
                  "out_fwd": _timed(fwd_out, args[0], y, coef),
                  "in_fwd_and_vjp": _timed(both_in, args, cts),
                  "out_vjp": _timed(both_out, args[0], y, coef, d_out)},
        "kernels": {"in_fwd_and_vjp": _timed(kern_in, args, cts),
                    "out_vjp": _timed(kern_out, args[0], y, coef, d_out)}}),
        flush=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--tokens", type=int, default=4096)
    parser.add_argument("--width", type=int, default=3584)
    parser.add_argument("--skip-plain", action="store_true")
    args = parser.parse_args(argv)
    if jax.default_backend() != "tpu":
        print(json.dumps({"ok": False, "why": "no TPU"}))
        return 1
    check_units()
    ok = check_correctness(args.tokens, args.width)
    time_kernels(args.tokens, args.width)
    if not args.skip_plain:
        time_plain(args.tokens, args.width)
    print(json.dumps({"ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
