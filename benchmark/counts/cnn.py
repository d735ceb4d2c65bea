"""Operations a CNN's training step requires per image.

The forward constant is copied from `distributeddeeplearning_tpu/models/
flops.py` (`_CNN_FWD_FLOPS_224`: 2 x the canonical convolution and classifier
multiply-accumulate sums at 224x224, torchvision geometry; ISSUE 24 records
that XLA's own count agrees within 5 %). Backward is twice forward; BatchNorm,
ReLU, pooling and the optimizer are not counted: they are not MXU work and are
the standard omission of model-FLOP utilisation.
"""

from __future__ import annotations

FORWARD_OPS_224 = {"resnet50_v1.5": 8.18e9}


def train_ops_per_example(config: dict, traffic: dict) -> float:
    scale = (traffic["image_size"] / 224.0) ** 2
    return 3.0 * FORWARD_OPS_224[config["architecture"]] * scale
