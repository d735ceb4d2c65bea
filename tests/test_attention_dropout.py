"""Attention-probability dropout parity across impls (VERDICT r3 #6).

The counter-based hash mask (ops/hash_dropout.py) is keyed on GLOBAL
coordinates, so every impl — dense, flash (in-kernel, backward regenerates),
ring, zigzag — must realize the IDENTICAL mask for the same seed, at any
sharding. That makes these exact-equality tests, not statistical ones: the
reference is dense softmax with the same hash mask materialized, and
forward AND gradients must match to float tolerance.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributeddeeplearning_tpu.config import ParallelConfig
from distributeddeeplearning_tpu.ops import flash_attention
from distributeddeeplearning_tpu.ops.hash_dropout import dense_keep_mask
from distributeddeeplearning_tpu.parallel import mesh as meshlib
from distributeddeeplearning_tpu.parallel import ring_attention as ring
from tests.attention_refs import random_qkv

RATE = 0.35
SEED = jnp.int32(12345)


def dropped_dense_reference(q, k, v, kv_mask=None, *, causal=False,
                            rate=RATE, seed=SEED):
    """softmax -> hash-mask dropout -> V; the one oracle every impl must
    equal exactly (same mask by construction, not by chance)."""
    b, s, h, d = q.shape
    scale = d ** -0.5
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if kv_mask is not None:
        sc = jnp.where(kv_mask[:, None, None, :], sc, -1e30)
    if causal:
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None, None],
                       sc, -1e30)
    p = jax.nn.softmax(sc.astype(jnp.float32), axis=-1)
    km = dense_keep_mask(seed, b, h, s, s, rate)
    p = jnp.where(km, p / (1.0 - rate), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def test_mask_statistics_and_determinism():
    km = dense_keep_mask(SEED, 4, 4, 64, 64, RATE)
    frac_dropped = 1.0 - float(km.mean())
    assert abs(frac_dropped - RATE) < 0.01
    km2 = dense_keep_mask(SEED, 4, 4, 64, 64, RATE)
    np.testing.assert_array_equal(np.asarray(km), np.asarray(km2))
    # Different seeds decorrelate.
    km3 = dense_keep_mask(jnp.int32(999), 4, 4, 64, 64, RATE)
    assert 0.3 < float((km != km3).mean()) < 0.6


@pytest.mark.core
@pytest.mark.parametrize("causal", [False, True])
def test_flash_dropout_matches_reference_fwd_and_grad(causal):
    q, k, v = random_qkv(jax.random.key(0), s=64, h=2, d=16)
    mask = np.ones((2, 64), bool)
    mask[0, -7:] = False
    mask = jnp.asarray(mask)

    def f_flash(q, k, v):
        return flash_attention(q, k, v, mask, block_q=32, block_k=32,
                               causal=causal, dropout_rate=RATE,
                               dropout_seed=SEED)

    def f_ref(q, k, v):
        return dropped_dense_reference(q, k, v, mask, causal=causal)

    np.testing.assert_allclose(np.asarray(f_flash(q, k, v)),
                               np.asarray(f_ref(q, k, v)),
                               rtol=1e-5, atol=1e-5)
    gf = jax.grad(lambda *a: (f_flash(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    gr = jax.grad(lambda *a: (f_ref(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    for a, b, name in zip(gf, gr, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_flash_dropout_block_size_invariant():
    """The realized mask is a pure function of global coordinates — kernel
    tiling must not change training semantics."""
    q, k, v = random_qkv(jax.random.key(1), s=64, h=2, d=16)
    outs = [flash_attention(q, k, v, block_q=bq, block_k=bk,
                            dropout_rate=RATE, dropout_seed=SEED)
            for bq, bk in ((16, 16), (32, 64), (64, 32))]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=1e-5, atol=1e-5)


# (S, block_q, block_k): square tiles, and both ways of bq != bk.
KEEP_GEOMETRIES = [(256, 128, 128), (512, 128, 256), (512, 256, 128)]


@pytest.mark.parametrize("offsets", [None, (2, 1, 4)],
                         ids=["unsharded", "bh_offsets"])
@pytest.mark.parametrize("s,bq,bk", KEEP_GEOMETRIES)
def test_block_keep_bit_identical_to_dense_mask(s, bq, bk, offsets):
    """The kernels' keep-mask, built from one column of row coordinates and
    one row of column coordinates, is bit for bit ``dense_keep_mask`` on
    EVERY tile of a (bh, S, S) problem: interior, diagonal and last tiles
    alike, with and without this shard's (batch, head) offsets."""
    from distributeddeeplearning_tpu.ops.flash_attention import _block_keep

    b_local, h_local = 2, 2
    b_start, h_start, h_total = offsets or (0, 0, h_local)
    dense = np.asarray(dense_keep_mask(
        SEED, b_start + b_local, h_total, s, s, RATE))
    # what the kernels read from SMEM: [seed, b_start, h_start, h, h_total]
    seed = jnp.asarray([SEED, b_start, h_start, h_local, h_total], jnp.int32)
    tile = jax.jit(lambda pid, i, j: _block_keep(seed, pid, i, j, bq, bk,
                                                 RATE))
    for pid in range(b_local * h_local):
        b, h = b_start + pid // h_local, h_start + pid % h_local
        for i in range(s // bq):
            for j in range(s // bk):
                got = np.asarray(tile(jnp.int32(pid), jnp.int32(i),
                                      jnp.int32(j)))
                assert got.shape == (bq, bk) and got.dtype == np.bool_
                np.testing.assert_array_equal(
                    got, dense[b, h, i * bq:(i + 1) * bq,
                               j * bk:(j + 1) * bk],
                    err_msg=f"pid {pid} tile ({i}, {j})")


@pytest.mark.parametrize("s,bq,bk", [(128, 32, 32), (128, 16, 64),
                                     (128, 64, 32), (300, 128, 128)])
def test_flash_causal_dropout_skipping_geometries(s, bq, bk):
    """Causal + dropout + key-padding mask where the plan skips tiles and
    runs others without the causal mask: forward and q/k/v gradients equal
    the dense softmax with the same materialized mask."""
    q, k, v = random_qkv(jax.random.key(8), s=s, h=2, d=16)
    mask = jnp.asarray(np.arange(s)[None, :]
                       < np.asarray([s, s - s // 3])[:, None])

    def f_flash(q, k, v):
        return flash_attention(q, k, v, mask, block_q=bq, block_k=bk,
                               causal=True, dropout_rate=RATE,
                               dropout_seed=SEED)

    def f_ref(q, k, v):
        return dropped_dense_reference(q, k, v, mask, causal=True)

    np.testing.assert_allclose(np.asarray(f_flash(q, k, v)),
                               np.asarray(f_ref(q, k, v)),
                               rtol=1e-5, atol=1e-5)
    gf = jax.grad(lambda *a: (f_flash(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    gr = jax.grad(lambda *a: (f_ref(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    for a, b, name in zip(gf, gr, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


@pytest.mark.core
def test_ring_dropout_matches_reference(devices8):
    """Ring over 4 seq shards with dropout == dense-with-same-mask, fwd and
    grads — the mask follows global positions through the ring schedule."""
    q, k, v = random_qkv(jax.random.key(2), s=32, h=4, d=8)
    mask = jnp.asarray(np.ones((2, 32), bool))
    mesh = meshlib.make_mesh(ParallelConfig(seq=4))

    def f_ring(q, k, v):
        return ring.ring_attention_sharded(
            q, k, v, mask, causal=True, dropout_rate=RATE,
            dropout_seed=SEED)

    def f_ref(q, k, v):
        return dropped_dense_reference(q, k, v, mask, causal=True)

    with meshlib.use_mesh(mesh):
        out = jax.jit(f_ring)(q, k, v)
        gz = jax.jit(jax.grad(
            lambda *a: (f_ring(*a) ** 2).sum(), argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(f_ref(q, k, v)),
                               rtol=1e-5, atol=1e-5)
    gr = jax.grad(lambda *a: (f_ref(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    for a, b, name in zip(gz, gr, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_zigzag_dropout_matches_reference(devices8):
    """Zigzag layout keys the hash by NATURAL positions: permute in,
    attend with dropout, unpermute out == dense-with-same-mask."""
    q, k, v = random_qkv(jax.random.key(3), s=32, h=4, d=8)
    mask = jnp.asarray(np.ones((2, 32), bool))
    perm, inv = ring.zigzag_indices(32, 4)
    mesh = meshlib.make_mesh(ParallelConfig(seq=4))
    with meshlib.use_mesh(mesh):
        out_z = jax.jit(lambda a, b, c: ring.zigzag_ring_attention_sharded(
            a[:, perm], b[:, perm], c[:, perm], mask[:, perm],
            dropout_rate=RATE, dropout_seed=SEED))(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out_z)[:, inv],
        np.asarray(dropped_dense_reference(q, k, v, mask, causal=True)),
        rtol=1e-5, atol=1e-5)


def test_flash_dropout_sharding_invariant(devices8):
    """dp x tp sharding must not change the realized mask: the sharded
    flash call (shard offsets into global coordinates) equals the
    unsharded one exactly."""
    q, k, v = random_qkv(jax.random.key(4), s=32, h=4, d=8)
    unsharded = flash_attention(q, k, v, block_q=32, block_k=32,
                                dropout_rate=RATE, dropout_seed=SEED)
    from distributeddeeplearning_tpu.ops.flash_attention import (
        flash_attention_sharded)

    mesh = meshlib.make_mesh(ParallelConfig(data=2, model=2))
    with meshlib.use_mesh(mesh):
        sharded = jax.jit(lambda a, b, c: flash_attention_sharded(
            a, b, c, None, block_q=32, block_k=32,
            dropout_rate=RATE, dropout_seed=SEED))(q, k, v)
    np.testing.assert_allclose(np.asarray(sharded), np.asarray(unsharded),
                               rtol=1e-5, atol=1e-5)


def test_dispatch_requires_rng():
    from distributeddeeplearning_tpu.ops.attention import (
        multihead_attention)

    q, k, v = random_qkv(jax.random.key(5), s=16, h=2, d=8)
    with pytest.raises(ValueError, match="dropout_rng"):
        multihead_attention(q, k, v, None, impl="dense", causal=False,
                            dtype=jnp.float32, dropout_rate=0.1,
                            deterministic=False)


def test_dispatch_impl_parity_same_rng():
    """Through the model-facing dispatch: dense and flash with the SAME rng
    key produce identical outputs under dropout — the cross-impl semantics
    the r3 UserWarning could only apologize for."""
    from distributeddeeplearning_tpu.ops.attention import (
        multihead_attention)

    q, k, v = random_qkv(jax.random.key(6), s=64, h=2, d=16)
    rng = jax.random.key(7)
    outs = [multihead_attention(q, k, v, None, impl=impl, causal=False,
                                dtype=jnp.float32, dropout_rate=RATE,
                                dropout_rng=rng, deterministic=False)
            for impl in ("dense", "flash")]
    np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(outs[1]),
                               rtol=1e-5, atol=1e-5)
    # And deterministic=True ignores dropout entirely (exact no-drop path).
    a = multihead_attention(q, k, v, None, impl="flash", causal=False,
                            dtype=jnp.float32, dropout_rate=RATE,
                            deterministic=True)
    b = multihead_attention(q, k, v, None, impl="dense", causal=False,
                            dtype=jnp.float32, deterministic=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-5)
