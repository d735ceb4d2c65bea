"""ResNet v1.5 family in Flax, TPU-first.

Covers the reference's ResNet-50 ImageNet trainers and the deeper ResNet-152
acceptance config (BASELINE.json:5,7-9). Design notes for the MXU:

- NHWC layout end-to-end (XLA:TPU's native conv layout; no transposes).
- compute in ``dtype`` (bfloat16 by default) with float32 parameters and
  float32 BatchNorm statistics — the standard TPU mixed-precision policy.
- v1.5 variant (stride-2 on the 3x3 conv of downsampling bottlenecks), the
  variant used by the throughput benchmarks the north star targets.
- No data-dependent control flow: the whole forward is one traceable graph.

Parameter counts match torchvision's resnet{18,34,50,101,152} exactly
(tests/test_models.py asserts this), which substitutes for reference-parity
checks while /root/reference is empty (SURVEY.md §4 "Numerics").
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence

import flax.linen as nn
import jax.numpy as jnp

ModuleDef = Any


class BottleneckBlock(nn.Module):
    """1x1 -> 3x3 -> 1x1 bottleneck with expansion 4 (ResNet-50/101/152)."""

    filters: int
    strides: int
    conv: ModuleDef
    norm_act: Callable

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1), name="conv1")(x)
        y = self.norm_act(y, name="bn1")
        # v1.5: stride lives on the 3x3, not the first 1x1. Explicit (1,1)
        # padding: XLA's SAME pads (0,1) at stride 2, torch pads (1,1) —
        # symmetric keeps us numerically identical to the reference-era
        # torch trainers (tests/test_torch_parity.py).
        y = self.conv(self.filters, (3, 3), strides=(self.strides, self.strides),
                      padding=[(1, 1), (1, 1)], name="conv2")(y)
        y = self.norm_act(y, name="bn2")
        y = self.conv(self.filters * 4, (1, 1), name="conv3")(y)
        if residual.shape[-1] != self.filters * 4 or self.strides != 1:
            residual = self.conv(self.filters * 4, (1, 1),
                                 strides=(self.strides, self.strides),
                                 name="downsample_conv")(x)
            residual = self.norm_act(residual, name="downsample_bn",
                                     relu=False)
        # Block exit: BN + residual add + ReLU in one fused pass.
        return self.norm_act(y, name="bn3", residual=residual,
                             scale_init=nn.initializers.zeros)


class BasicBlock(nn.Module):
    """3x3 -> 3x3 residual block (ResNet-18/34)."""

    filters: int
    strides: int
    conv: ModuleDef
    norm_act: Callable

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), strides=(self.strides, self.strides),
                      padding=[(1, 1), (1, 1)], name="conv1")(x)
        y = self.norm_act(y, name="bn1")
        y = self.conv(self.filters, (3, 3), name="conv2")(y)
        if residual.shape[-1] != self.filters or self.strides != 1:
            residual = self.conv(self.filters, (1, 1),
                                 strides=(self.strides, self.strides),
                                 name="downsample_conv")(x)
            residual = self.norm_act(residual, name="downsample_bn",
                                     relu=False)
        return self.norm_act(y, name="bn2", residual=residual,
                             scale_init=nn.initializers.zeros)


class ResNet(nn.Module):
    """ImageNet ResNet. ``stage_sizes`` picks the depth; NHWC in, logits out."""

    stage_sizes: Sequence[int]
    block: ModuleDef
    num_classes: int = 1000
    width: int = 64
    dtype: Any = jnp.bfloat16
    # Pallas fused BN(+residual)+ReLU kernels (ops/fused_batchnorm.py) for
    # the BN bandwidth tax (BASELINE.md profile: 113 ms of a 209 ms batch-512
    # step in BN-statistics/dγ/dβ/dx reductions). Same variable layout and
    # numerics as the unfused path; off by default until measured on-chip.
    fused_bn: bool = False
    # Conv-epilogue fusion (ops/fused_linear_bn.py): bottleneck 1x1 convs
    # run as Pallas matmuls carrying BN statistics in their epilogue and
    # bn2's apply in conv3's prologue (models/fused_block.py). Bottleneck
    # nets only; variable-compatible with the unfused path.
    fused_block: bool = False
    # Cross-replica BatchNorm (torch SyncBatchNorm semantics): mesh axis
    # name(s) to pmean the batch statistics over. Only meaningful inside
    # the shard_map DP train step, where those axes are bound; None keeps
    # the default per-shard statistics (per-GPU BN under Horovod).
    bn_axis_name: Any = None

    @nn.compact
    def __call__(self, x, *, train: bool = True):
        conv = functools.partial(
            nn.Conv, use_bias=False, dtype=self.dtype, param_dtype=jnp.float32,
            kernel_init=nn.initializers.variance_scaling(
                2.0, "fan_out", "normal"),
            padding="SAME")

        def norm_act(y, *, name, residual=None, relu=True,
                     scale_init=nn.initializers.ones):
            """BN [+ residual add] [+ ReLU] — one fused Pallas pass when
            ``fused_bn``, the classic composition otherwise. Both create
            identical variables under ``name``."""
            if self.fused_bn:
                if self.bn_axis_name is not None:
                    raise ValueError(
                        "sync_bn is not supported with fused_bn (the fused "
                        "kernel computes statistics inside its custom VJP); "
                        "use --sync-bn with the default BN or --fused-block")
                from distributeddeeplearning_tpu.ops.fused_batchnorm import (
                    FusedBatchNormAct)
                return FusedBatchNormAct(
                    use_running_average=not train, momentum=0.9, epsilon=1e-5,
                    dtype=self.dtype, relu=relu, scale_init=scale_init,
                    name=name)(y, residual=residual)
            y = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                             epsilon=1e-5, dtype=self.dtype,
                             param_dtype=jnp.float32, scale_init=scale_init,
                             axis_name=self.bn_axis_name if train else None,
                             name=name)(y)
            if residual is not None:
                y = y + residual
            return nn.relu(y) if relu else y

        x = jnp.asarray(x, self.dtype)
        # Explicit (3,3): torch's symmetric stem padding (SAME would pad
        # (2,3) on 224 at stride 2 — a one-pixel shift vs the reference).
        x = conv(self.width, (7, 7), strides=(2, 2),
                 padding=[(3, 3), (3, 3)], name="conv_stem")(x)
        x = norm_act(x, name="bn_stem")
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        use_fused_block = self.fused_block and self.block is BottleneckBlock
        if self.fused_block and not use_fused_block:
            raise ValueError("fused_block requires bottleneck blocks "
                             "(resnet50/101/152); basic blocks have no 1x1 "
                             "convolutions to fuse")
        for i, num_blocks in enumerate(self.stage_sizes):
            for j in range(num_blocks):
                strides = 2 if i > 0 and j == 0 else 1
                name = f"stage{i + 1}_block{j + 1}"
                if use_fused_block:
                    from distributeddeeplearning_tpu.models.fused_block \
                        import FusedBottleneckBlock
                    x = FusedBottleneckBlock(
                        filters=self.width * 2 ** i, strides=strides,
                        dtype=self.dtype, axis_name=self.bn_axis_name,
                        name=name)(x, train=train)
                else:
                    x = self.block(filters=self.width * 2 ** i,
                                   strides=strides, conv=conv,
                                   norm_act=norm_act, name=name)(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=self.dtype,
                     param_dtype=jnp.float32,
                     kernel_init=nn.initializers.variance_scaling(
                         1.0, "fan_in", "truncated_normal"),
                     name="classifier")(x)
        return jnp.asarray(x, jnp.float32)


def resnet18(num_classes: int = 1000, dtype: Any = jnp.bfloat16,
            fused_bn: bool = False, fused_block: bool = False,
            bn_axis_name: Any = None) -> ResNet:
    return ResNet([2, 2, 2, 2], BasicBlock, num_classes, dtype=dtype,
                  fused_bn=fused_bn, fused_block=fused_block,
                  bn_axis_name=bn_axis_name)


def resnet18_thin(num_classes: int = 1000, dtype: Any = jnp.bfloat16,
                  fused_bn: bool = False, fused_block: bool = False,
            bn_axis_name: Any = None) -> ResNet:
    """Width-16 ResNet-18 (1/16th the conv FLOPs): the CPU-tractable stand-in
    for convergence-recipe demonstrations (tools/convergence_lars.py) and
    fast tests — same depth, blocks, and BN structure as the real thing."""
    return ResNet([2, 2, 2, 2], BasicBlock, num_classes, width=16,
                  dtype=dtype, fused_bn=fused_bn, fused_block=fused_block,
                  bn_axis_name=bn_axis_name)


def resnet26_thin(num_classes: int = 1000, dtype: Any = jnp.bfloat16,
                  fused_bn: bool = False, fused_block: bool = False,
            bn_axis_name: Any = None) -> ResNet:
    """Width-16 bottleneck ResNet-26 ([2,2,2,2] Bottleneck): the
    CPU-tractable stand-in with the SAME block structure as resnet50 —
    what fused_block tests and bottleneck recipe demos run on."""
    return ResNet([2, 2, 2, 2], BottleneckBlock, num_classes, width=16,
                  dtype=dtype, fused_bn=fused_bn, fused_block=fused_block,
                  bn_axis_name=bn_axis_name)


def resnet34(num_classes: int = 1000, dtype: Any = jnp.bfloat16,
            fused_bn: bool = False, fused_block: bool = False,
            bn_axis_name: Any = None) -> ResNet:
    return ResNet([3, 4, 6, 3], BasicBlock, num_classes, dtype=dtype,
                  fused_bn=fused_bn, fused_block=fused_block,
                  bn_axis_name=bn_axis_name)


def resnet50(num_classes: int = 1000, dtype: Any = jnp.bfloat16,
            fused_bn: bool = False, fused_block: bool = False,
            bn_axis_name: Any = None) -> ResNet:
    return ResNet([3, 4, 6, 3], BottleneckBlock, num_classes, dtype=dtype,
                  fused_bn=fused_bn, fused_block=fused_block,
                  bn_axis_name=bn_axis_name)


def resnet101(num_classes: int = 1000, dtype: Any = jnp.bfloat16,
            fused_bn: bool = False, fused_block: bool = False,
            bn_axis_name: Any = None) -> ResNet:
    return ResNet([3, 4, 23, 3], BottleneckBlock, num_classes, dtype=dtype,
                  fused_bn=fused_bn, fused_block=fused_block,
                  bn_axis_name=bn_axis_name)


def resnet152(num_classes: int = 1000, dtype: Any = jnp.bfloat16,
            fused_bn: bool = False, fused_block: bool = False,
            bn_axis_name: Any = None) -> ResNet:
    return ResNet([3, 8, 36, 3], BottleneckBlock, num_classes, dtype=dtype,
                  fused_bn=fused_bn, fused_block=fused_block,
                  bn_axis_name=bn_axis_name)
