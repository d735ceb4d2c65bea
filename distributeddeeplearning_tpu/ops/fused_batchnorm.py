"""Fused BatchNorm(+residual)+ReLU — Pallas kernels for the BN bandwidth tax.

Why this exists (BASELINE.md "Where the step goes", measured on-chip at
batch 512): ResNet50's convolutions take ~41 ms of a 209 ms step at ~78%
MXU efficiency, while ~113 ms goes to BatchNorm statistics / dγ/dβ/dx
``convert_reduce`` fusions and ~47 ms to BN-apply/ReLU/residual elementwise
passes — all HBM-bandwidth-bound reads of the ~12 GB of activations. XLA
schedules these as several separate fusion passes; the arithmetic minimum
is far fewer:

- forward: ONE pass computing per-channel Σx and Σx² together (XLA's
  pattern reads x for mean and again for variance in separate fusions on
  some schedules), then ONE normalize+scale+shift[+residual]+ReLU pass;
- backward: ONE pass computing dβ = Σ dz and dγ = Σ dz·x̂ together (dz is
  the ReLU-masked cotangent, recomputed in-register from dy and y), then
  ONE elementwise pass for dx (and the residual cotangent, free in the
  same pass).

Every kernel reads bf16 activations and accumulates float32 in VMEM
scratch, so numerics match the unfused float32-statistics BatchNorm to
rounding (tests/test_fused_batchnorm.py asserts fwd+grads vs the flax
composition). Kernels run compiled on TPU devices and in Pallas interpret
mode elsewhere (ops/pallas.py decides).

The module :class:`FusedBatchNormAct` is variable-compatible with
``flax.linen.BatchNorm`` (params ``scale``/``bias``, batch_stats
``mean``/``var``, float32, same momentum/eps semantics and biased variance),
so checkpoints and param-count tests are unaffected by toggling the fusion
flag (models/resnet.py ``fused_bn``).

Running statistics are returned with stop_gradient applied — like flax's
mutable batch_stats, they are state updates, not differentiable outputs.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributeddeeplearning_tpu.ops.pallas import pallas_call


def _tile(size: int, target: int) -> int:
    """Largest divisor of ``size`` <= target (shapes here are built from
    powers of two and small odd spatial factors; no padding logic)."""
    t = min(size, target)
    while size % t:
        t -= 1
    return t


def _fold_factor(m: int, c: int) -> int:
    """Lane-folding factor for narrow-channel layers.

    TPU tiles the minor dimension to 128 lanes; a (M, 64) bf16 tensor is
    stored 2x padded, so every kernel pass streams (and every saved
    residual holds) twice the real bytes — measured on-chip, this put the
    stem kernels at half the HBM roofline and pushed batch-512 residency
    past HBM (the padding alone turned 784M stem tensors into 1.53G).
    Viewing the buffer as (M/f, C*f) with f = 128//C is a row-major
    bitcast — element (i, c) lands at row i//f, lane (i%f)*C + c — so
    channel identity survives as lane%C and per-channel sums fold back
    with one (f, C) reshape-sum. No data moves; padding disappears."""
    if c >= 128 or 128 % c:
        return 1
    f = 128 // c
    while m % f:
        f //= 2
    return f


def _fold(x2d, f: int):
    m, c = x2d.shape
    return x2d if f == 1 else x2d.reshape(m // f, c * f)


def _unfold(x2d, f: int):
    mf, cf = x2d.shape
    return x2d if f == 1 else x2d.reshape(mf * f, cf // f)


def _tile_vec(v, f: int):
    """Replicate a per-channel vector across the f folded sub-rows so lane
    l of the folded view sees the parameter for channel l % C."""
    return v if f == 1 else jnp.tile(v, f)


def _fold_sum(v, f: int):
    """Collapse a folded per-lane reduction (C*f,) back to per-channel (C,)."""
    return v if f == 1 else v.reshape(f, -1).sum(axis=0)


# ---------------------------------------------------------------------------
# Forward: per-channel sum/sumsq in one pass over (M, C)
# ---------------------------------------------------------------------------

def _stats_kernel(x_ref, sum_ref, sumsq_ref, s_scr, ss_scr):
    m = pl.program_id(1)

    @pl.when(m == 0)
    def _():
        s_scr[...] = jnp.zeros_like(s_scr)
        ss_scr[...] = jnp.zeros_like(ss_scr)

    x = x_ref[...].astype(jnp.float32)
    s_scr[...] += x.sum(axis=0, keepdims=True)
    ss_scr[...] += (x * x).sum(axis=0, keepdims=True)

    @pl.when(m == pl.num_programs(1) - 1)
    def _():
        sum_ref[...] = s_scr[...]
        sumsq_ref[...] = ss_scr[...]


def bn_stats(x2d: jax.Array):
    """(M, C) -> (mean, var) per channel, float32, biased variance."""
    m_true, c_true = x2d.shape
    f = _fold_factor(m_true, c_true)
    x2d = _fold(x2d, f)
    m, c = x2d.shape
    tm, tc = _tile(m, 1024), _tile(c, 512)
    s, ss = pallas_call(
        _stats_kernel,
        name="bn_stats",
        grid=(c // tc, m // tm),
        in_specs=[pl.BlockSpec((tm, tc), lambda ci, mi: (mi, ci))],
        out_specs=[pl.BlockSpec((1, tc), lambda ci, mi: (0, ci)),
                   pl.BlockSpec((1, tc), lambda ci, mi: (0, ci))],
        out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, tc), jnp.float32),
                        pltpu.VMEM((1, tc), jnp.float32)],
    )(x2d)
    mean = _fold_sum(s[0], f) / m_true
    var = _fold_sum(ss[0], f) / m_true - mean * mean
    return mean, jnp.maximum(var, 0.0)


# ---------------------------------------------------------------------------
# Forward: normalize + scale/shift (+ residual) (+ ReLU) in one pass
# ---------------------------------------------------------------------------

def _apply_kernel(x_ref, mean_ref, inv_ref, gamma_ref, beta_ref, o_ref, *,
                  relu: bool, res_ref=None):
    x = x_ref[...].astype(jnp.float32)
    y = (x - mean_ref[...]) * (inv_ref[...] * gamma_ref[...]) + beta_ref[...]
    if res_ref is not None:
        y = y + res_ref[...].astype(jnp.float32)
    if relu:
        y = jnp.maximum(y, 0.0)
    o_ref[...] = y.astype(o_ref.dtype)


def bn_apply(x2d, mean, inv, gamma, beta, residual2d=None, *, relu: bool):
    m_true, c_true = x2d.shape
    f = _fold_factor(m_true, c_true)
    x2d = _fold(x2d, f)
    if residual2d is not None:
        residual2d = _fold(residual2d, f)
    mean, inv = _tile_vec(mean, f), _tile_vec(inv, f)
    gamma, beta = _tile_vec(gamma, f), _tile_vec(beta, f)
    m, c = x2d.shape
    tm, tc = _tile(m, 1024), _tile(c, 512)
    vec = pl.BlockSpec((1, tc), lambda mi, ci: (0, ci))
    tile = pl.BlockSpec((tm, tc), lambda mi, ci: (mi, ci))
    operands = [x2d, mean[None], inv[None], gamma[None], beta[None]]
    in_specs = [tile, vec, vec, vec, vec]
    if residual2d is not None:
        operands.append(residual2d)
        in_specs.append(tile)

        def kernel(x, mn, iv, g, b, r, o):
            _apply_kernel(x, mn, iv, g, b, o, relu=relu, res_ref=r)
    else:
        def kernel(x, mn, iv, g, b, o):
            _apply_kernel(x, mn, iv, g, b, o, relu=relu)
    return _unfold(pallas_call(
        kernel,
        name="bn_apply",
        grid=(m // tm, c // tc),
        in_specs=in_specs,
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((m, c), x2d.dtype),
    )(*operands), f)


# ---------------------------------------------------------------------------
# Backward pass 1: dβ = Σ dz, dγ = Σ dz·x̂ in one pass
# (dz = dy ⊙ 1[y>0] recomputed in-register; x̂ = (x-μ)·inv)
# ---------------------------------------------------------------------------

def _bwd_reduce_kernel(dy_ref, x_ref, mean_ref, inv_ref,
                       dbeta_ref, dgamma_ref, db_scr, dg_scr, *,
                       y_ref=None):
    """``y_ref`` present only for relu layers — the ReLU mask is the only
    use of y, and declaring it unconditionally would stream a dead
    full-activation read from HBM on the relu=False (downsample-BN) path."""
    m = pl.program_id(1)

    @pl.when(m == 0)
    def _():
        db_scr[...] = jnp.zeros_like(db_scr)
        dg_scr[...] = jnp.zeros_like(dg_scr)

    dy = dy_ref[...].astype(jnp.float32)
    if y_ref is not None:
        dy = jnp.where(y_ref[...].astype(jnp.float32) > 0, dy, 0.0)
    xh = (x_ref[...].astype(jnp.float32) - mean_ref[...]) * inv_ref[...]
    db_scr[...] += dy.sum(axis=0, keepdims=True)
    dg_scr[...] += (dy * xh).sum(axis=0, keepdims=True)

    @pl.when(m == pl.num_programs(1) - 1)
    def _():
        dbeta_ref[...] = db_scr[...]
        dgamma_ref[...] = dg_scr[...]


def bn_bwd_reduce(dy2d, y2d, x2d, mean, inv, *, relu: bool):
    m_true, c_true = x2d.shape
    f = _fold_factor(m_true, c_true)
    dy2d, x2d = _fold(dy2d, f), _fold(x2d, f)
    if relu:
        y2d = _fold(y2d, f)
    mean, inv = _tile_vec(mean, f), _tile_vec(inv, f)
    m, c = x2d.shape
    tm, tc = _tile(m, 1024), _tile(c, 512)
    vec = pl.BlockSpec((1, tc), lambda ci, mi: (0, ci))
    tile = pl.BlockSpec((tm, tc), lambda ci, mi: (mi, ci))
    operands = [dy2d, x2d, mean[None], inv[None]]
    in_specs = [tile, tile, vec, vec]
    if relu:
        operands.append(y2d)
        in_specs.append(tile)

        def kernel(dy, x, mn, iv, y, db_o, dg_o, db_s, dg_s):
            _bwd_reduce_kernel(dy, x, mn, iv, db_o, dg_o, db_s, dg_s,
                               y_ref=y)
    else:
        def kernel(dy, x, mn, iv, db_o, dg_o, db_s, dg_s):
            _bwd_reduce_kernel(dy, x, mn, iv, db_o, dg_o, db_s, dg_s)
    db, dg = pallas_call(
        kernel,
        name="bn_bwd_reduce",
        grid=(c // tc, m // tm),
        in_specs=in_specs,
        out_specs=[vec, vec],
        out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, tc), jnp.float32),
                        pltpu.VMEM((1, tc), jnp.float32)],
    )(*operands)
    return _fold_sum(db[0], f), _fold_sum(dg[0], f)


# ---------------------------------------------------------------------------
# Backward pass 2: dx = γ·inv·(dz - dβ/M - x̂·dγ/M), dres = dz — one pass
# ---------------------------------------------------------------------------

def _bwd_dx_kernel(dy_ref, x_ref, mean_ref, inv_ref, c1_ref, c2_ref,
                   c3_ref, dx_ref, *, y_ref=None, dres_ref=None):
    """``y_ref`` only for relu layers (its sole use is the ReLU mask — see
    :func:`_bwd_reduce_kernel`); ``dres_ref`` only for fused-residual ones."""
    dz = dy_ref[...].astype(jnp.float32)
    if y_ref is not None:
        dz = jnp.where(y_ref[...].astype(jnp.float32) > 0, dz, 0.0)
    xh = (x_ref[...].astype(jnp.float32) - mean_ref[...]) * inv_ref[...]
    dx = c1_ref[...] * (dz - c2_ref[...] - xh * c3_ref[...])
    dx_ref[...] = dx.astype(dx_ref.dtype)
    if dres_ref is not None:
        dres_ref[...] = dz.astype(dres_ref.dtype)


def bn_bwd_dx(dy2d, y2d, x2d, mean, inv, gamma, dbeta, dgamma, *,
              relu: bool, want_dres: bool):
    m_true, c_true = x2d.shape
    f = _fold_factor(m_true, c_true)
    dy2d, x2d = _fold(dy2d, f), _fold(x2d, f)
    if relu:
        y2d = _fold(y2d, f)
    c1 = _tile_vec(gamma * inv, f)
    c2 = _tile_vec(dbeta / m_true, f)
    c3 = _tile_vec(dgamma / m_true, f)
    mean, inv = _tile_vec(mean, f), _tile_vec(inv, f)
    m, c = x2d.shape
    tm, tc = _tile(m, 1024), _tile(c, 512)
    vec = pl.BlockSpec((1, tc), lambda mi, ci: (0, ci))
    tile = pl.BlockSpec((tm, tc), lambda mi, ci: (mi, ci))
    operands = [dy2d, x2d, mean[None], inv[None], c1[None], c2[None],
                c3[None]]
    in_specs = [tile, tile, vec, vec, vec, vec, vec]
    if relu:
        operands.append(y2d)
        in_specs.append(tile)
    out_shape = [jax.ShapeDtypeStruct((m, c), x2d.dtype)]
    out_specs = [tile]
    if want_dres:
        out_shape.append(jax.ShapeDtypeStruct((m, c), x2d.dtype))
        out_specs.append(tile)
    n_in = len(operands)

    def kernel(*refs):
        dy, x, mn, iv, a, b, d = refs[:7]
        y = refs[7] if relu else None
        outs = refs[n_in:]
        _bwd_dx_kernel(dy, x, mn, iv, a, b, d, outs[0], y_ref=y,
                       dres_ref=outs[1] if want_dres else None)

    out = pallas_call(
        kernel,
        name="bn_bwd_dx",
        grid=(m // tm, c // tc),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
    )(*operands)
    return ((_unfold(out[0], f), _unfold(out[1], f)) if want_dres
            else (_unfold(out[0], f), None))


# ---------------------------------------------------------------------------
# Differentiable train-mode op (custom VJP over the kernels)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def bn_act_train(x2d, gamma, beta, relu: bool, eps: float):
    """y = [relu](x̂·γ + β) with batch statistics; returns (y, mean, var).

    mean/var are the biased batch statistics (for the running-stat update);
    their cotangents are ignored by the VJP — callers must treat them as
    state (stop_gradient), exactly like flax's mutable batch_stats.
    """
    y, mean, var, _ = _bn_fwd(x2d, gamma, beta, relu, eps)
    return y, mean, var


def _bn_fwd(x2d, gamma, beta, relu, eps, residual2d=None):
    mean, var = bn_stats(x2d)
    inv = jax.lax.rsqrt(var + eps)
    y = bn_apply(x2d, mean, inv, gamma.astype(jnp.float32),
                 beta.astype(jnp.float32), residual2d, relu=relu)
    return y, mean, var, inv


def _bn_act_fwd(x2d, gamma, beta, relu, eps):
    y, mean, var, inv = _bn_fwd(x2d, gamma, beta, relu, eps)
    return (y, mean, var), (x2d, y, mean, inv, gamma)


def _bn_act_bwd(relu, eps, saved, cots):
    x2d, y, mean, inv, gamma = saved
    dy, _, _ = cots  # mean/var cotangents are state, not gradients
    dbeta, dgamma = bn_bwd_reduce(dy, y, x2d, mean, inv, relu=relu)
    dx, _ = bn_bwd_dx(dy, y, x2d, mean, inv, gamma.astype(jnp.float32),
                      dbeta, dgamma, relu=relu, want_dres=False)
    return (dx, dgamma.astype(gamma.dtype),
            dbeta.astype(gamma.dtype))


bn_act_train.defvjp(_bn_act_fwd, _bn_act_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def bn_act_res_train(x2d, gamma, beta, residual2d, relu: bool, eps: float):
    """Same as :func:`bn_act_train` with a fused residual add before ReLU
    (the block-exit pattern ``relu(bn(conv(x)) + shortcut)``)."""
    y, mean, var, _ = _bn_fwd(x2d, gamma, beta, relu, eps, residual2d)
    return y, mean, var


def _bn_act_res_fwd(x2d, gamma, beta, residual2d, relu, eps):
    y, mean, var, inv = _bn_fwd(x2d, gamma, beta, relu, eps, residual2d)
    return (y, mean, var), (x2d, y, mean, inv, gamma)


def _bn_act_res_bwd(relu, eps, saved, cots):
    x2d, y, mean, inv, gamma = saved
    dy, _, _ = cots
    dbeta, dgamma = bn_bwd_reduce(dy, y, x2d, mean, inv, relu=relu)
    dx, dres = bn_bwd_dx(dy, y, x2d, mean, inv, gamma.astype(jnp.float32),
                         dbeta, dgamma, relu=relu, want_dres=True)
    return (dx, dgamma.astype(gamma.dtype),
            dbeta.astype(gamma.dtype), dres)


bn_act_res_train.defvjp(_bn_act_res_fwd, _bn_act_res_bwd)


# ---------------------------------------------------------------------------
# Flax module, variable-compatible with nn.BatchNorm
# ---------------------------------------------------------------------------

class FusedBatchNormAct(nn.Module):
    """Drop-in BN[+residual][+ReLU] with the fused Pallas path in training.

    Variable layout matches ``nn.BatchNorm`` exactly (params ``scale`` and
    ``bias``; batch_stats ``mean``/``var``; float32; biased variance in the
    running update), so toggling models/resnet.py's ``fused_bn`` flag does
    not change checkpoints or parameter counts. Inference mode uses plain
    jnp (running stats, no reductions — XLA already fuses that well).
    """

    use_running_average: bool = False
    momentum: float = 0.9
    epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    relu: bool = True
    scale_init: Any = nn.initializers.ones

    @nn.compact
    def __call__(self, x, residual=None):
        c = x.shape[-1]
        scale = self.param("scale", self.scale_init, (c,), self.param_dtype)
        bias = self.param("bias", nn.initializers.zeros, (c,),
                          self.param_dtype)
        ra_mean = self.variable("batch_stats", "mean",
                                lambda s: jnp.zeros(s, jnp.float32), (c,))
        ra_var = self.variable("batch_stats", "var",
                               lambda s: jnp.ones(s, jnp.float32), (c,))
        x = jnp.asarray(x, self.dtype)
        x2d = x.reshape(-1, c)
        res2d = (jnp.asarray(residual, self.dtype).reshape(-1, c)
                 if residual is not None else None)

        if self.use_running_average:
            inv = jax.lax.rsqrt(ra_var.value + self.epsilon)
            y = ((x2d.astype(jnp.float32) - ra_mean.value)
                 * (inv * scale.astype(jnp.float32))
                 + bias.astype(jnp.float32))
            if res2d is not None:
                y = y + res2d.astype(jnp.float32)
            if self.relu:
                y = jnp.maximum(y, 0.0)
            return y.astype(self.dtype).reshape(x.shape)

        if res2d is None:
            y2d, mean, var = bn_act_train(
                x2d, scale, bias, self.relu, self.epsilon)
        else:
            y2d, mean, var = bn_act_res_train(
                x2d, scale, bias, res2d, self.relu, self.epsilon)
        mean = jax.lax.stop_gradient(mean)
        var = jax.lax.stop_gradient(var)
        if not self.is_initializing():
            ra_mean.value = (self.momentum * ra_mean.value
                             + (1.0 - self.momentum) * mean)
            ra_var.value = (self.momentum * ra_var.value
                            + (1.0 - self.momentum) * var)
        return y2d.reshape(x.shape)
