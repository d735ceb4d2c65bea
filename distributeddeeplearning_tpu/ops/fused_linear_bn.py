"""Matmul with BN prologue/epilogue — conv-epilogue fusion for 1x1 convs.

Why (BASELINE.md "On-chip A/B", 2026-07-31): standalone inter-conv BN
kernels lose to XLA end to end — they add full activation passes while
XLA's epilogue fusions get elementwise BN work for free inside passes the
convolutions already make. The only Pallas shape that can win fuses the BN
work INTO the matmul: this module's ops stream the raw previous-layer
output through VMEM, normalize it on the VPU as a *prologue* (no separate
apply pass, no materialized normalized tensor), feed the MXU, and
accumulate the output's per-channel Σy/Σy² as an *epilogue* (no separate
statistics pass). A ResNet bottleneck's 1x1 convolutions are exactly
matmuls over M = B·H·W rows, so they take this path; 3x3/7x7 convolutions
stay on the XLA conv path.

Math. With per-input-channel vectors μ, inv (=rsqrt(var+ε)), γ, β:

    x̂ = (x_raw − μ)·inv          a = relu(x̂·γ + β)        y = a @ w
    s = Σ_m y                     ss = Σ_m y²               (per out-channel)

μ and inv are *differentiable inputs* (the caller derives them from the
previous op's s/ss outputs), so unlike a self-contained BatchNorm VJP the
backward here needs no −mean/−x̂·cov correction terms inside the kernel:

    dY = dy + ds + 2·y·dss        (epilogue-sum cotangents folded in)
    da = dY @ wᵀ                  dzl = da·1[a>0]
    dx_raw = dzl·γ·inv            (pure elementwise — written by the da
                                   kernel's epilogue, no separate pass)
    dβ = Σ_m dzl                  dγ = Σ_m dzl·x̂   (da-kernel epilogue)
    dμ = −γ·inv·dβ                dinv = γ·dγ/inv   (vector math, outside)
    dw = aᵀ @ dY                  (second kernel; a, dY recomputed in its
                                   prologue from streamed x_raw, y tiles)

So training traffic is two matmuls forward-equivalents backward and ONE
matmul forward, with every BN read riding a tile the MXU already needs.
Statistics are taken over y as stored (bf16) so they match exactly what
the next layer's prologue will normalize.

All kernels read bf16, accumulate float32 (MXU preferred_element_type and
VMEM scratch), and run compiled on TPU devices, in interpret mode elsewhere
(ops/pallas.py decides).
"""

from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributeddeeplearning_tpu.ops.fused_batchnorm import _tile
from distributeddeeplearning_tpu.ops.pallas import pallas_call


def _tiles(m: int, k: int, n: int):
    return _tile(m, 512), _tile(k, 512), _tile(n, 512)


# ---------------------------------------------------------------------------
# Forward: y = prologue(x) @ w, epilogue Σy / Σy²
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, w_ref, mu_ref, inv_ref, g_ref, b_ref,
                y_ref, s_ref, ss_ref, acc, s_scr, ss_scr, *,
                relu: bool, bn: bool, nk: int):
    mi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when((ki == 0) & (mi == 0))
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)
        ss_scr[...] = jnp.zeros_like(ss_scr)

    a = x_ref[...]
    if bn:
        af = (a.astype(jnp.float32) - mu_ref[...]) * (inv_ref[...]
                                                      * g_ref[...])
        af = af + b_ref[...]
        if relu:
            af = jnp.maximum(af, 0.0)
        a = af.astype(x_ref.dtype)
    acc[...] += jax.lax.dot(a, w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _():
        y = acc[...].astype(y_ref.dtype)
        y_ref[...] = y
        yf = y.astype(jnp.float32)
        s_scr[...] += yf.sum(axis=0, keepdims=True)
        ss_scr[...] += (yf * yf).sum(axis=0, keepdims=True)

    @pl.when((ki == nk - 1) & (mi == pl.num_programs(1) - 1))
    def _():
        s_ref[...] = s_scr[...]
        ss_ref[...] = ss_scr[...]


def _fwd(x, mu, inv, gamma, beta, w, relu, bn):
    m, k = x.shape
    n = w.shape[1]
    tm, tk, tn = _tiles(m, k, n)
    nk = k // tk
    xs = pl.BlockSpec((tm, tk), lambda ni, mi, ki: (mi, ki))
    ws = pl.BlockSpec((tk, tn), lambda ni, mi, ki: (ki, ni))
    vk = pl.BlockSpec((1, tk), lambda ni, mi, ki: (0, ki))
    ys = pl.BlockSpec((tm, tn), lambda ni, mi, ki: (mi, ni))
    vn = pl.BlockSpec((1, tn), lambda ni, mi, ki: (0, ni))
    y, s, ss = pallas_call(
        functools.partial(_fwd_kernel, relu=relu, bn=bn, nk=nk),
        name="linear_bn_fwd",
        grid=(n // tn, m // tm, nk),
        in_specs=[xs, ws, vk, vk, vk, vk],
        out_specs=[ys, vn, vn],
        out_shape=[jax.ShapeDtypeStruct((m, n), x.dtype),
                   jax.ShapeDtypeStruct((1, n), jnp.float32),
                   jax.ShapeDtypeStruct((1, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32),
                        pltpu.VMEM((1, tn), jnp.float32),
                        pltpu.VMEM((1, tn), jnp.float32)],
    )(x, w, mu[None], inv[None], gamma[None], beta[None])
    return y, s[0], ss[0]


# ---------------------------------------------------------------------------
# Backward kernel 1: da = dY @ wᵀ; epilogue writes dx directly + dβ/dγ sums
# ---------------------------------------------------------------------------

def _bwd_dx_kernel(dy_ref, y_ref, ds_ref, dss_ref, w_ref, x_ref,
                   mu_ref, inv_ref, g_ref, b_ref,
                   dx_ref, db_ref, dg_ref, acc, db_scr, dg_scr, *,
                   relu: bool, bn: bool, nn: int):
    mi, ni = pl.program_id(1), pl.program_id(2)

    @pl.when(ni == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when((ni == 0) & (mi == 0))
    def _():
        db_scr[...] = jnp.zeros_like(db_scr)
        dg_scr[...] = jnp.zeros_like(dg_scr)

    y = y_ref[...].astype(jnp.float32)
    dyf = (dy_ref[...].astype(jnp.float32) + ds_ref[...]
           + 2.0 * y * dss_ref[...])
    # Contract over the out-channel axis of both dY (tm,tn) and w (tk,tn).
    acc[...] += jax.lax.dot_general(
        dyf.astype(dy_ref.dtype), w_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ni == nn - 1)
    def _():
        da = acc[...]
        if bn:
            xh = ((x_ref[...].astype(jnp.float32) - mu_ref[...])
                  * inv_ref[...])
            dzl = da
            if relu:
                z = xh * g_ref[...] + b_ref[...]
                dzl = jnp.where(z > 0, da, 0.0)
            dx_ref[...] = (dzl * (g_ref[...] * inv_ref[...])).astype(
                dx_ref.dtype)
            db_scr[...] += dzl.sum(axis=0, keepdims=True)
            dg_scr[...] += (dzl * xh).sum(axis=0, keepdims=True)
        else:
            dx_ref[...] = da.astype(dx_ref.dtype)

    @pl.when((ni == nn - 1) & (mi == pl.num_programs(1) - 1))
    def _():
        db_ref[...] = db_scr[...]
        dg_ref[...] = dg_scr[...]


def _bwd_dx(dy, y, ds, dss, w, x, mu, inv, gamma, beta, relu, bn):
    m, k = x.shape
    n = w.shape[1]
    tm, tk, tn = _tiles(m, k, n)
    nn = n // tn
    dys = pl.BlockSpec((tm, tn), lambda ki, mi, ni: (mi, ni))
    ws = pl.BlockSpec((tk, tn), lambda ki, mi, ni: (ki, ni))
    xs = pl.BlockSpec((tm, tk), lambda ki, mi, ni: (mi, ki))
    vn = pl.BlockSpec((1, tn), lambda ki, mi, ni: (0, ni))
    vk = pl.BlockSpec((1, tk), lambda ki, mi, ni: (0, ki))
    dx, db, dg = pallas_call(
        functools.partial(_bwd_dx_kernel, relu=relu, bn=bn, nn=nn),
        name="linear_bn_dx",
        grid=(k // tk, m // tm, nn),
        in_specs=[dys, dys, vn, vn, ws, xs, vk, vk, vk, vk],
        out_specs=[xs, vk, vk],
        out_shape=[jax.ShapeDtypeStruct((m, k), x.dtype),
                   jax.ShapeDtypeStruct((1, k), jnp.float32),
                   jax.ShapeDtypeStruct((1, k), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((tm, tk), jnp.float32),
                        pltpu.VMEM((1, tk), jnp.float32),
                        pltpu.VMEM((1, tk), jnp.float32)],
    )(dy, y, ds[None], dss[None], w, x, mu[None], inv[None],
      gamma[None], beta[None])
    return dx, db[0], dg[0]


# ---------------------------------------------------------------------------
# Backward kernel 2: dw = aᵀ @ dY, both operands recomputed in the prologue
# ---------------------------------------------------------------------------

def _bwd_dw_kernel(x_ref, mu_ref, inv_ref, g_ref, b_ref,
                   dy_ref, y_ref, ds_ref, dss_ref,
                   dw_ref, acc, *, relu: bool, bn: bool, nm: int):
    mi = pl.program_id(2)

    @pl.when(mi == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    a = x_ref[...]
    if bn:
        af = ((a.astype(jnp.float32) - mu_ref[...])
              * (inv_ref[...] * g_ref[...]) + b_ref[...])
        if relu:
            af = jnp.maximum(af, 0.0)
        a = af.astype(x_ref.dtype)
    y = y_ref[...].astype(jnp.float32)
    dyf = (dy_ref[...].astype(jnp.float32) + ds_ref[...]
           + 2.0 * y * dss_ref[...])
    # aᵀ @ dY: contract the row (M) axis of both tiles.
    acc[...] += jax.lax.dot_general(
        a, dyf.astype(dy_ref.dtype),
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(mi == nm - 1)
    def _():
        dw_ref[...] = acc[...].astype(dw_ref.dtype)


def _bwd_dw(x, mu, inv, gamma, beta, dy, y, ds, dss, relu, bn):
    m, k = x.shape
    n = dy.shape[1]
    tm, tk, tn = _tiles(m, k, n)
    nm = m // tm
    xs = pl.BlockSpec((tm, tk), lambda ki, ni, mi: (mi, ki))
    dys = pl.BlockSpec((tm, tn), lambda ki, ni, mi: (mi, ni))
    vk = pl.BlockSpec((1, tk), lambda ki, ni, mi: (0, ki))
    vn = pl.BlockSpec((1, tn), lambda ki, ni, mi: (0, ni))
    ws = pl.BlockSpec((tk, tn), lambda ki, ni, mi: (ki, ni))
    return pallas_call(
        functools.partial(_bwd_dw_kernel, relu=relu, bn=bn, nm=nm),
        name="linear_bn_dw",
        grid=(k // tk, n // tn, nm),
        in_specs=[xs, vk, vk, vk, vk, dys, dys, vn, vn],
        out_specs=ws,
        out_shape=jax.ShapeDtypeStruct((k, n), dy.dtype),
        scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
    )(x, mu[None], inv[None], gamma[None], beta[None], dy, y,
      ds[None], dss[None])


# ---------------------------------------------------------------------------
# The public custom-VJP op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def bn_linear_stats(x, mu, inv, gamma, beta, w, relu: bool = True,
                    bn: bool = True):
    """y = relu((x−μ)·inv·γ + β) @ w with per-out-channel (Σy, Σy²).

    With ``bn=False`` the prologue is the identity (x is consumed as-is;
    μ/inv/γ/β are ignored but must still be (N_in,)-shaped arrays) — the
    shape used for matmuls whose input is already materialized, keeping
    only the statistics epilogue. Returns ``(y, s, ss)``.
    """
    return _fwd(x, mu, inv, gamma, beta, w, relu, bn)


def _vjp_fwd(x, mu, inv, gamma, beta, w, relu, bn):
    y, s, ss = _fwd(x, mu, inv, gamma, beta, w, relu, bn)
    return (y, s, ss), (x, mu, inv, gamma, beta, w, y)


def _vjp_bwd(relu, bn, saved, cots):
    x, mu, inv, gamma, beta, w, y = saved
    dy, ds, dss = cots
    dx, db, dg = _bwd_dx(dy, y, ds, dss, w, x, mu, inv, gamma, beta,
                         relu, bn)
    dw = _bwd_dw(x, mu, inv, gamma, beta, dy, y, ds, dss, relu, bn)
    if not bn:
        zero = jnp.zeros_like(mu)
        return (dx, zero, zero, zero, zero, dw)
    dmu = -gamma * inv * db
    dinv = gamma * dg / inv
    return (dx, dmu, dinv, dg.astype(gamma.dtype), db.astype(beta.dtype),
            dw)


bn_linear_stats.defvjp(_vjp_fwd, _vjp_bwd)


def linear_stats(x, w):
    """y = x @ w with (Σy, Σy²) — the bn=False shape, for matmuls whose
    input is already a materialized activation."""
    zeros = jnp.zeros((x.shape[1],), jnp.float32)
    return bn_linear_stats(x, zeros, zeros, zeros, zeros, w, False, False)
