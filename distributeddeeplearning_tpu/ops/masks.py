"""Shared attention-mask helpers for the blockwise kernels.

One source of truth for the global-position causal triangle used by both
the Pallas flash kernels (ops/flash_attention.py, per grid block) and ring
attention (parallel/ring_attention.py, per ring step). Built from
``broadcasted_iota`` so it lowers inside Pallas kernel bodies and plain
jitted code alike.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def block_causal_mask(q_block, k_block, sq: int, sk: int):
    """(sq, sk) bool: global kv position <= global q position, for the
    query block at index ``q_block`` (rows sized sq) against the key block
    at index ``k_block`` (cols sized sk). Block indices may be traced."""
    qpos = q_block * sq + lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
    kpos = k_block * sk + lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
    return kpos <= qpos


def block_band_mask(q_block, k_block, sq: int, sk: int, window: int):
    """(sq, sk) bool: the causal triangle cut to a window, ``0 <= q - kv <
    window`` in global positions. One unsigned comparison decides both ends
    (a key past the query wraps to a huge distance), so a tile pays the same
    for the band as :func:`block_causal_mask` takes for the triangle: the
    window's edge costs nothing on the tiles it does not cross."""
    dist = (q_block * sq - k_block * sk
            + lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
            - lax.broadcasted_iota(jnp.int32, (sq, sk), 1))
    return dist.astype(jnp.uint32) < jnp.uint32(window)
