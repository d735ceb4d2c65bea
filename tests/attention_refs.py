"""Shared oracle + fixtures for the attention test suites (ring, flash):
one dense softmax(QK^T)V reference so both kernels validate against the
identical ground truth."""

import jax
import jax.numpy as jnp


def dense_reference(q, k, v, kv_mask=None, causal=False):
    """softmax(QK^T/sqrt(d))V with optional key-padding mask and causal
    triangle; (B,S,H,D) io. The ONE oracle for ring/flash/zigzag suites."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :], s, -1e30)
    if causal:
        n = q.shape[1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool))[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def random_qkv(key, b=2, s=32, h=4, d=8, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    shape = (b, s, h, d)
    return (jax.random.normal(kq, shape, dtype),
            jax.random.normal(kk, shape, dtype),
            jax.random.normal(kv, shape, dtype))


FLASH_KERNELS = ("flash_fwd", "flash_dq", "flash_dkv")


def flash_kernel_calls(fn, *args):
    """How often each flash kernel stands in ``fn``'s program, lowered for
    the TPU platform from the CPU (nothing compiles, no libtpu is loaded):
    there a kernel is one Mosaic call under its own name, and what a
    recomputed block's policy kept is already out of its backward pass."""
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    return {name: text.count(f'kernel_name = "{name}"')
            for name in FLASH_KERNELS}
