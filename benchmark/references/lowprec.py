"""The lower-precision control: the plain reference with every matrix
product's (or convolution's) operands rounded to 8-bit floating point, the
step below the bfloat16 the configurations compute in, and the step that would
tempt a later PR. Forward operands go to e4m3 and the cotangents that flow
back through them to e5m2, each with one scale per tensor (absolute maximum
mapped to the format's largest finite value), as fp8 training recipes do
(Micikevicius et al. 2022). The products themselves stay float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _fake_quant(x, dtype):
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(x.dtype) * scale


@jax.custom_vjp
def fp8(x):
    return _fake_quant(x, jnp.float8_e4m3fn)


def _fwd(x):
    return fp8(x), None


def _bwd(_, g):
    return (_fake_quant(g, jnp.float8_e5m2),)


fp8.defvjp(_fwd, _bwd)

