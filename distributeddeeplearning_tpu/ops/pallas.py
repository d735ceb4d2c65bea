"""The one place that decides how a Pallas kernel runs.

A kernel is compiled by Mosaic when the program is lowered for TPU devices
and runs in Pallas interpret mode on any other platform (the CPU test
mesh). The choice is made by ``jax.lax.platform_dependent`` at lowering
time, from the platform of the devices the program is actually placed on —
not from ``jax.default_backend()`` at trace time — so a step compiled for
TPU devices always contains the ``tpu_custom_call`` and never an
interpreted stand-in, and no kernel module re-derives the rule.
"""

from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, *, name: str, **kwargs):
    """``pl.pallas_call(kernel, name=name, **kwargs)``: Mosaic on TPU,
    interpret mode elsewhere. Only the branch for the lowering platform is
    lowered. ``name`` says what the kernel computes: it is the kernel's name
    in the compiled program and the ``jax.named_scope`` round the call, so a
    device trace and ``analysis/anatomy.py`` find the kernel by it."""
    compiled = pl.pallas_call(kernel, name=name, **kwargs)
    interpreted = pl.pallas_call(kernel, name=name, interpret=True, **kwargs)

    def call(*args):
        with jax.named_scope(name):
            return jax.lax.platform_dependent(
                *args, tpu=compiled, default=interpreted)

    return call
