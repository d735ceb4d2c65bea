"""Bottleneck block over the fused matmul+BN ops (conv-epilogue fusion).

The TPU-first answer to the BN bandwidth tax (BASELINE.md profile +
on-chip A/B): a ResNet bottleneck's 1x1 convolutions run as Pallas
matmuls that carry the BatchNorm work in their prologue/epilogue
(ops/fused_linear_bn.py), so per block:

- conv1 computes bn1's Σ/Σ² in its epilogue  → bn1 statistics pass gone;
- conv3 normalizes conv2's raw output in its prologue and computes bn3's
  Σ/Σ² in its epilogue → bn2 apply pass (read+write) AND bn3 statistics
  pass gone; bn2's backward reductions ride conv3's backward matmul;
- the downsample 1x1 computes its BN's Σ/Σ² in its epilogue.

What stays on XLA: the 3x3 conv (not a matmul), bn1's apply (its output
must materialize as the 3x3's input), and the block exit
relu(bn3_apply + downsample_bn_apply) — one elementwise pass XLA fuses
well, and its output must materialize as the residual carrier anyway.

Variable layout is IDENTICAL to models/resnet.py's BottleneckBlock
(params conv{1,2,3}/kernel, downsample_conv/kernel, bn*/{scale,bias};
batch_stats bn*/{mean,var}; same momentum/eps/zero-init-γ3 semantics),
so the same checkpoint drives either path and tests can compare the two
numerically with shared weights. Eval mode (running averages) uses the
classic composition — inference BN is elementwise and XLA-optimal.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributeddeeplearning_tpu.ops.fused_linear_bn import (
    bn_linear_stats, linear_stats)


class _Kernel(nn.Module):
    """Bare conv-kernel parameter holder, name/shape-compatible with
    ``nn.Conv`` so checkpoints transfer between paths."""

    shape: tuple
    init: Any = nn.initializers.variance_scaling(2.0, "fan_out", "normal")

    @nn.compact
    def __call__(self):
        return self.param("kernel", self.init, self.shape, jnp.float32)


class _BNVars(nn.Module):
    """BN parameter/state holder matching ``nn.BatchNorm``'s layout."""

    features: int
    scale_init: Any = nn.initializers.ones

    @nn.compact
    def __call__(self):
        c = self.features
        scale = self.param("scale", self.scale_init, (c,), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (c,), jnp.float32)
        ra_mean = self.variable("batch_stats", "mean",
                                lambda s: jnp.zeros(s, jnp.float32), (c,))
        ra_var = self.variable("batch_stats", "var",
                               lambda s: jnp.ones(s, jnp.float32), (c,))
        return scale, bias, ra_mean, ra_var


class FusedBottleneckBlock(nn.Module):
    """Drop-in BottleneckBlock with 1x1 convs on the fused matmul+BN path."""

    filters: int
    strides: int
    dtype: Any = jnp.bfloat16
    momentum: float = 0.9
    epsilon: float = 1e-5
    # SyncBN: mesh axis name(s) to pmean statistics over (shard_map DP
    # path only). The epilogue sums are per-shard; syncing is two (C,)
    # pmeans per BN — negligible next to the gradient allreduce.
    axis_name: Any = None
    def _stats(self, s, ss, m: int):
        mean, ex2 = s / m, ss / m
        if self.axis_name is not None:
            mean = jax.lax.pmean(mean, self.axis_name)
            ex2 = jax.lax.pmean(ex2, self.axis_name)
        var = jnp.maximum(ex2 - mean * mean, 0.0)
        return mean, var

    def _update_running(self, ra_mean, ra_var, mean, var):
        if not self.is_initializing():
            ra_mean.value = (self.momentum * ra_mean.value
                             + (1.0 - self.momentum)
                             * jax.lax.stop_gradient(mean))
            ra_var.value = (self.momentum * ra_var.value
                            + (1.0 - self.momentum)
                            * jax.lax.stop_gradient(var))

    @nn.compact
    def __call__(self, x, *, train: bool = True):
        f = self.filters
        cin = x.shape[-1]
        need_ds = cin != f * 4 or self.strides != 1
        w1 = _Kernel((1, 1, cin, f), name="conv1")()
        w2k = _Kernel((3, 3, f, f), name="conv2")()
        w3 = _Kernel((1, 1, f, f * 4), name="conv3")()
        g1, b1, rm1, rv1 = _BNVars(f, name="bn1")()
        g2, b2, rm2, rv2 = _BNVars(f, name="bn2")()
        g3, b3, rm3, rv3 = _BNVars(
            f * 4, scale_init=nn.initializers.zeros, name="bn3")()
        if need_ds:
            wd = _Kernel((1, 1, cin, f * 4), name="downsample_conv")()
            gd, bd, rmd, rvd = _BNVars(f * 4, name="downsample_bn")()

        x = jnp.asarray(x, self.dtype)
        if not train:
            return self._eval_path(
                x, w1, w2k, w3, (g1, b1, rm1, rv1), (g2, b2, rm2, rv2),
                (g3, b3, rm3, rv3),
                (wd, gd, bd, rmd, rvd) if need_ds else None)

        eps = self.epsilon
        b, h, w_sp = x.shape[0], x.shape[1], x.shape[2]
        x2d = x.reshape(-1, cin)

        # conv1 (1x1) + bn1-stats epilogue.
        y1, s1, ss1 = linear_stats(
            x2d, w1.reshape(cin, f).astype(self.dtype))
        m1 = y1.shape[0]
        mean1, var1 = self._stats(s1, ss1, m1)
        self._update_running(rm1, rv1, mean1, var1)
        inv1 = jax.lax.rsqrt(var1 + eps)

        # bn1 apply must materialize (it feeds the XLA 3x3) — one
        # elementwise pass, XLA-fused.
        a1 = jnp.maximum(
            (y1.astype(jnp.float32) - mean1) * (inv1 * g1) + b1, 0.0
        ).astype(self.dtype).reshape(b, h, w_sp, f)

        # conv2: XLA 3x3 (stride lives here, v1.5), raw output y2.
        y2 = jax.lax.conv_general_dilated(
            a1, w2k.astype(self.dtype),
            window_strides=(self.strides, self.strides),
            padding=[(1, 1), (1, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=self.dtype)
        # Output spatial dims come from the strided conv itself — with
        # odd inputs ceil(h/2) != h//2, and the ::stride shortcut slice
        # agrees with the conv, not with floor division.
        h_out, w_out = y2.shape[1], y2.shape[2]
        y2d = y2.reshape(-1, f)
        m2 = y2d.shape[0]
        # bn2 statistics: one XLA multi-output reduce over y2 (its
        # apply pass is what conv3's prologue absorbs).
        y2f = y2d.astype(jnp.float32)
        mean2, var2 = self._stats(y2f.sum(axis=0),
                                  (y2f * y2f).sum(axis=0), m2)
        self._update_running(rm2, rv2, mean2, var2)
        inv2 = jax.lax.rsqrt(var2 + eps)

        # conv3 (1x1): bn2-apply prologue + bn3-stats epilogue.
        y3, s3, ss3 = bn_linear_stats(
            y2d, mean2, inv2, g2, b2,
            w3.reshape(f, f * 4).astype(self.dtype), True, True)
        mean3, var3 = self._stats(s3, ss3, m2)
        self._update_running(rm3, rv3, mean3, var3)
        inv3 = jax.lax.rsqrt(var3 + eps)

        # Shortcut path.
        if need_ds:
            xs = x[:, ::self.strides, ::self.strides, :] \
                if self.strides != 1 else x
            xs2d = xs.reshape(-1, cin)
            yd, sd, ssd = linear_stats(
                xs2d, wd.reshape(cin, f * 4).astype(self.dtype))
            meand, vard = self._stats(sd, ssd, yd.shape[0])
            self._update_running(rmd, rvd, meand, vard)
            invd = jax.lax.rsqrt(vard + eps)
            shortcut = ((yd.astype(jnp.float32) - meand) * (invd * gd) + bd)
        else:
            shortcut = x2d.astype(jnp.float32)

        # Block exit: bn3-apply + residual + ReLU — one elementwise pass,
        # materialized because it is the next block's input AND residual.
        out = jnp.maximum(
            (y3.astype(jnp.float32) - mean3) * (inv3 * g3) + b3 + shortcut,
            0.0).astype(self.dtype)
        return out.reshape(b, h_out, w_out, f * 4)

    def _eval_path(self, x, w1, w2k, w3, bn1, bn2, bn3, ds):
        """Running-average inference: the classic composition (elementwise
        BN, XLA-fused); numerics identical to the unfused block's eval."""
        eps = self.epsilon
        f = self.filters

        def apply_bn(y, vars_, relu):
            g, bb, rm, rv = vars_
            inv = jax.lax.rsqrt(rv.value + eps)
            out = (y.astype(jnp.float32) - rm.value) * (inv * g) + bb
            if relu:
                out = jnp.maximum(out, 0.0)
            return out.astype(self.dtype)

        y = jax.lax.conv_general_dilated(
            x, w1.astype(self.dtype), (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=self.dtype)
        y = apply_bn(y, bn1, True)
        y = jax.lax.conv_general_dilated(
            y, w2k.astype(self.dtype), (self.strides, self.strides),
            [(1, 1), (1, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=self.dtype)
        y = apply_bn(y, bn2, True)
        y = jax.lax.conv_general_dilated(
            y, w3.astype(self.dtype), (1, 1), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=self.dtype)
        if ds is not None:
            wd, gd, bd, rmd, rvd = ds
            sc = jax.lax.conv_general_dilated(
                x, wd.astype(self.dtype),
                (self.strides, self.strides), "VALID",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                preferred_element_type=self.dtype)
            sc = apply_bn(sc, (gd, bd, rmd, rvd), False)
        else:
            sc = x
        g3, b3, rm3, rv3 = bn3
        inv3 = jax.lax.rsqrt(rv3.value + eps)
        out = ((y.astype(jnp.float32) - rm3.value) * (inv3 * g3) + b3
               + sc.astype(jnp.float32))
        return jnp.maximum(out, 0.0).astype(self.dtype)
