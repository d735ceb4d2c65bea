"""Pallas flash-attention kernel correctness (ops/flash_attention.py).

Runs in Pallas interpret mode on CPU (the kernels' own fallback on non-TPU
backends), checking the fused forward and the custom-VJP backward against the
dense softmax(QK^T)V reference — the same oracle the ring-attention tests use.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import functools
import importlib

from distributeddeeplearning_tpu.ops import flash_attention
from distributeddeeplearning_tpu.ops.flash_attention import (FLASH_LSE,
                                                             FLASH_OUT)
from tests.attention_refs import (dense_reference, flash_kernel_calls,
                                  random_qkv)

random_qkv = functools.partial(random_qkv, s=64, h=2, d=16)


@pytest.mark.parametrize("s,block", [(64, 128), (64, 16), (128, 32)])
def test_forward_matches_dense(s, block):
    q, k, v = random_qkv(jax.random.key(0), s=s)
    out = flash_attention(q, k, v, block_q=block, block_k=block)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense_reference(q, k, v)),
        rtol=1e-5, atol=1e-5)


def test_forward_respects_padding_mask():
    q, k, v = random_qkv(jax.random.key(1))
    b, s = q.shape[:2]
    mask = np.ones((b, s), bool)
    mask[:, -13:] = False
    mask[1, 3] = False
    mask = jnp.asarray(mask)
    out = flash_attention(q, k, v, mask, block_q=16, block_k=16)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense_reference(q, k, v, mask)),
        rtol=1e-5, atol=1e-5)


def test_grads_match_dense():
    q, k, v = random_qkv(jax.random.key(2), s=32)
    mask = jnp.asarray(np.concatenate(
        [np.ones((2, 28), bool), np.zeros((2, 4), bool)], axis=1))

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, mask, block_q=8, block_k=8)
        return (o * o).sum()

    def loss_dense(q, k, v):
        o = dense_reference(q, k, v, mask)
        return (o * o).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def _ragged_mask(b, s, lens):
    return jnp.asarray(np.arange(s)[None, :] < np.asarray(lens)[:, None])


# (S, block_q, block_k, valid keys per batch row or None). Tile sizes small
# enough that the causal plan drops tiles above the diagonal and visits
# tiles wholly under it beside those it crosses; bq != bk both ways; a
# key-padding mask whose edge falls in an interior tile (row 0) and in a
# diagonal one (row 1); S off the lane width (padded to 384 inside, the pad
# masked).
CAUSAL_CASES = [
    pytest.param(128, 32, 32, None, id="s128-32x32"),
    pytest.param(128, 16, 64, None, id="s128-16x64"),
    pytest.param(128, 64, 16, None, id="s128-64x16"),
    pytest.param(128, 32, 32, (128, 40), id="s128-32x32-masked"),
    pytest.param(128, 64, 32, (50, 100), id="s128-64x32-masked"),
    pytest.param(300, 128, 128, None, id="s300-padded"),
    pytest.param(300, 128, 128, (300, 140), id="s300-padded-masked"),
    pytest.param(1024, None, None, None, id="s1024-derived"),
]


@pytest.mark.parametrize("s,block_q,block_k,lens", CAUSAL_CASES)
def test_causal_forward_and_grads_match_dense(s, block_q, block_k, lens):
    from distributeddeeplearning_tpu.ops.flash_attention import tile_plan

    q, k, v = random_qkv(jax.random.key(5), s=s)
    mask = None if lens is None else _ragged_mask(q.shape[0], s, lens)
    plan = tile_plan(s, True, block_q, block_k)
    # the case does exercise the skip, on interior and diagonal tiles
    assert plan.visited < plan.total
    assert plan.diagonal < plan.visited

    def flash(q, k, v):
        return flash_attention(q, k, v, mask, causal=True,
                               block_q=block_q, block_k=block_k)

    def dense(q, k, v):
        return dense_reference(q, k, v, mask, causal=True)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(dense(q, k, v)),
                               rtol=1e-5, atol=1e-5)
    gf = jax.grad(lambda *a: (flash(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    gd = jax.grad(lambda *a: (dense(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    for a, b, name in zip(gf, gd, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


@pytest.mark.parametrize("i,j,bq,bk", [(0, 0, 32, 32), (3, 1, 16, 64),
                                       (1, 2, 64, 32), (2, 2, 128, 128)])
def test_transposed_causal_rule_is_the_shared_one(i, j, bq, bk):
    """The dK/dV kernel works on (BK, BQ) tiles and builds its triangle in
    that orientation; it is ops/masks.py::block_causal_mask, the rule the
    other two kernels and ring attention use, transposed."""
    from distributeddeeplearning_tpu.ops.flash_attention import _causal_t
    from distributeddeeplearning_tpu.ops.masks import block_causal_mask

    np.testing.assert_array_equal(
        np.asarray(_causal_t(i, j, bq, bk)),
        np.asarray(block_causal_mask(i, j, bq, bk)).T)


def _plan_by_hand(s, bq, bk, causal):
    """(total, visited, diagonal) counted pair by pair from the definition:
    a tile is visited if it holds a pair with key <= query, and is diagonal
    if it also holds one with key > query."""
    total = visited = diagonal = 0
    for i in range(s // bq):
        for j in range(s // bk):
            total += 1
            if not causal:
                visited += 1
                continue
            below = j * bk <= i * bq + bq - 1   # min key <= max query
            above = j * bk + bk - 1 > i * bq    # max key > min query
            visited += below
            diagonal += below and above
    return total, visited, diagonal


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("s", [128, 512, 1024, 2048, 8192])
def test_tile_plan_counts(s, causal):
    """The plan the kernels' grids are built from: counts at the derived
    tile sizes equal a count by hand, the schedule tables hold exactly the
    visited tiles (each once, in both orders), and from S = 1024 on a causal
    call never visits the whole rectangle (at S = 512 one 512 x 512 tile
    measured faster on the v5e than three of 256 x 256: PERF.md, PR 26)."""
    from distributeddeeplearning_tpu.ops.flash_attention import (
        _schedule, tile_plan)

    plan = tile_plan(s, causal)
    assert s % plan.bq == 0 and s % plan.bk == 0
    assert min(plan.bq, plan.bk) >= 128  # Mosaic's (1, 1, b) blocks
    assert (plan.total, plan.visited, plan.diagonal) == _plan_by_hand(
        s, plan.bq, plan.bk, causal)
    for k_major in (False, True):
        qi, kj = (np.asarray(t) for t in _schedule(s, plan, causal, k_major))
        assert len(qi) == plan.visited
        assert len(set(zip(qi.tolist(), kj.tolist()))) == plan.visited
        run = kj if k_major else qi   # the tile that stays while one sums
        assert (np.diff(run) >= 0).all()
    if causal and s >= 1024:
        assert plan.visited / plan.total <= 0.75
    if causal and s >= 8192:
        assert plan.visited / plan.total <= 0.54
    if not causal:
        assert plan.visited == plan.total and plan.diagonal == 0


@pytest.mark.parametrize("block_q,block_k", [(128, 128), (256, 128),
                                             (128, 512), (512, 512)])
def test_tile_plan_overrides(block_q, block_k):
    """block_q / block_k stay explicit overrides; the counts follow them."""
    from distributeddeeplearning_tpu.ops.flash_attention import tile_plan

    plan = tile_plan(1024, True, block_q, block_k)
    assert (plan.bq, plan.bk) == (block_q, block_k)
    assert (plan.total, plan.visited, plan.diagonal) == _plan_by_hand(
        1024, block_q, block_k, True)


def test_non_power_of_two_seq_padded_not_degenerate():
    """S=197 (ViT-with-CLS shape; prime) used to resolve _block to 1 — a
    degenerate 197-step grid. flash_attention now pads S to a lane multiple
    (256) so blocks stay >= 128, and the padded rows/keys must not leak
    into the result or the gradients."""
    from distributeddeeplearning_tpu.ops.flash_attention import _block

    s = 197
    q, k, v = random_qkv(jax.random.key(4), s=s)
    out = flash_attention(q, k, v, block_q=128, block_k=128)
    assert out.shape == q.shape
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(dense_reference(q, k, v)),
        rtol=1e-5, atol=1e-5)
    # causal too (the causal block-skip indexes blocks; padding must not
    # shift the diagonal).
    out_c = flash_attention(q, k, v, block_q=128, block_k=128, causal=True)
    np.testing.assert_allclose(
        np.asarray(out_c),
        np.asarray(dense_reference(q, k, v, causal=True)),
        rtol=1e-5, atol=1e-5)

    gf = jax.grad(lambda *a: (flash_attention(
        *a, block_q=128, block_k=128) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda *a: (dense_reference(*a) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
    # The invariant the pad exists to protect — and the loud warning any
    # future direct kernel caller sees instead of the silent cliff. A
    # modestly-smaller block (48 for target 64) stays silent: that is a
    # working configuration, not a cliff.
    assert _block(256, 128) == 128
    with pytest.warns(UserWarning, match="degenerated"):
        assert _block(197, 128) == 1
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert _block(96, 64) == 48


def test_bfloat16_forward():
    q, k, v = random_qkv(jax.random.key(3), dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=32, block_k=32)
    assert out.dtype == jnp.bfloat16
    ref = dense_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.slow
def test_bert_flash_end_to_end_sharded():
    """Tiny BERT trains with flash attention on a dp x tp mesh through the
    GSPMD path — the kernel runs per-shard under shard_map."""
    from distributeddeeplearning_tpu.config import (
        DataConfig, OptimizerConfig, ParallelConfig, TrainConfig)
    from distributeddeeplearning_tpu.train import loop
    from distributeddeeplearning_tpu.utils.logging import MetricLogger

    cfg = TrainConfig(
        model="bert_tiny", global_batch_size=8, dtype="float32",
        log_every=10**9, attention_impl="flash",
        parallel=ParallelConfig(data=2, model=2),
        data=DataConfig(dataset="mlm", seq_len=32, vocab_size=512),
        optimizer=OptimizerConfig(name="adamw", learning_rate=1e-4,
                                  schedule="constant", label_smoothing=0.0))
    summary = loop.run(cfg, total_steps=2, logger=MetricLogger(enabled=False))
    assert summary["final_step"] == 2
    assert np.isfinite(summary["final_metrics"]["loss"])


def test_bert_flash_matches_dense_forward():
    """Full-model: BertMLM logits with flash == dense impl (single device)."""
    from distributeddeeplearning_tpu.models import bert

    ids = jax.random.randint(jax.random.key(4), (2, 32), 0, 256)
    mask = jnp.ones((2, 32), jnp.int32).at[:, -5:].set(0)
    dense = bert.tiny_bert_mlm(vocab_size=256)
    flash = bert.tiny_bert_mlm(vocab_size=256, attention_impl="flash")
    variables = dense.init(
        {"params": jax.random.key(0), "dropout": jax.random.key(0)},
        ids, train=False)
    out_d = dense.apply(variables, ids, attention_mask=mask, train=False)
    out_f = flash.apply(variables, ids, attention_mask=mask, train=False)
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# A window in the causal mask, grouped K/V heads, head size 128 (PR 27)
# ---------------------------------------------------------------------------

def _band_reference(q, k, v, window):
    """Dense softmax attention, causal and cut to ``query - key < window``
    (None: causal only), Q head h on K/V head h // (H // Hkv)."""
    s, h = q.shape[1], q.shape[2]
    rep = h // k.shape[2]
    k, v = (jnp.repeat(x, rep, axis=2) for x in (k, v))
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    keep = j <= i
    if window is not None:
        keep = keep & (i - j < window)
    p = jax.nn.softmax(jnp.where(keep[None, None], sc, -1e30), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


GROUPED_CASES = [
    # s, h, hkv, d, window, block
    pytest.param(128, 4, 4, 16, 48, 32, id="window"),
    pytest.param(128, 4, 4, 16, 32, 32, id="window-on-a-tile-edge"),
    pytest.param(128, 4, 4, 16, 1, 32, id="window-of-one"),
    pytest.param(128, 8, 2, 16, None, 32, id="grouped"),
    pytest.param(128, 8, 1, 16, 40, 32, id="grouped-window-one-kv-head"),
    pytest.param(128, 8, 2, 128, 40, 32, id="grouped-window-d128"),
    pytest.param(300, 4, 2, 16, 100, 128, id="grouped-window-padded-s"),
    pytest.param(1024, 2, 1, 16, 300, None, id="derived-tiles"),
    pytest.param(128, 4, 2, 16, 500, 32, id="window-wider-than-s"),
]


@pytest.mark.parametrize("s,h,hkv,d,window,block", GROUPED_CASES)
def test_window_and_grouped_heads_match_dense(s, h, hkv, d, window, block):
    """Forward and all three gradients; dK and dV sum over a group's Q heads
    inside the kernel."""
    ks = jax.random.split(jax.random.key(6), 3)
    q = jax.random.normal(ks[0], (2, s, h, d))
    k = jax.random.normal(ks[1], (2, s, hkv, d))
    v = jax.random.normal(ks[2], (2, s, hkv, d))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=block, block_k=block)

    def dense(q, k, v):
        return _band_reference(q, k, v, window)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(dense(q, k, v)),
                               rtol=1e-5, atol=1e-5)
    gf = jax.grad(lambda *a: (flash(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    gd = jax.grad(lambda *a: (dense(*a) ** 2).sum(), argnums=(0, 1, 2))(
        q, k, v)
    for a, b, name in zip(gf, gd, ("dq", "dk", "dv")):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_grouped_heads_with_dropout_match_the_dense_impl():
    """The dK/dV kernel walks K/V heads and keys the dropout hash by the Q
    head it is summing: the same mask as the forward's, and as the dense
    impl's over repeated heads."""
    from distributeddeeplearning_tpu.ops.attention import multihead_attention

    ks = jax.random.split(jax.random.key(8), 3)
    q = jax.random.normal(ks[0], (2, 128, 8, 16))
    k = jax.random.normal(ks[1], (2, 128, 2, 16))
    v = jax.random.normal(ks[2], (2, 128, 2, 16))

    def run(impl):
        def f(q, k, v):
            out = multihead_attention(
                q, k, v, None, impl=impl, causal=True, dtype=jnp.float32,
                window=50, dropout_rate=0.2, deterministic=False,
                dropout_rng=jax.random.key(3))
            return (out ** 2).sum(), out
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    (_, out_f), gf = run("flash")
    (_, out_d), gd = run("dense")
    np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_d),
                               rtol=1e-5, atol=1e-5)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("i,j,bq,bk,window", [
    (0, 0, 32, 32, 8), (3, 1, 16, 64, 40), (1, 2, 64, 32, 100),
    (5, 1, 128, 128, 512), (4, 0, 64, 64, 257)])
def test_band_rule_is_the_shared_one(i, j, bq, bk, window):
    """ops/masks.py::block_band_mask is the causal triangle and the window
    at once, and the dK/dV kernel's transposed build of it is the same."""
    from distributeddeeplearning_tpu.ops.flash_attention import _band_t
    from distributeddeeplearning_tpu.ops.masks import (block_band_mask,
                                                       block_causal_mask)

    band = np.asarray(block_band_mask(i, j, bq, bk, window))
    qpos = i * bq + np.arange(bq)[:, None]
    kpos = j * bk + np.arange(bk)[None, :]
    np.testing.assert_array_equal(
        band, np.asarray(block_causal_mask(i, j, bq, bk))
        & (qpos - kpos < window))
    np.testing.assert_array_equal(np.asarray(_band_t(i, j, bq, bk, window)),
                                  band.T)


def _band_plan_by_hand(s, bq, bk, window):
    """(visited, diagonal, edge) pair by pair from the definition."""
    visited = diagonal = edge = 0
    for i in range(s // bq):
        for j in range(s // bk):
            q = np.arange(i * bq, (i + 1) * bq)[:, None]
            k = np.arange(j * bk, (j + 1) * bk)[None, :]
            inside = (k <= q) & (q - k < window)
            if inside.any():
                visited += 1
                diagonal += bool((k > q).any())
                edge += bool((q - k >= window).any())
    return visited, diagonal, edge


@pytest.mark.parametrize("s,window,visited", [
    (1024, None, 3), (8192, None, 136), (8192, 2048, 70)])
def test_tile_plan_visited_counts_of_the_models_shapes(s, window, visited):
    """By hand, at the derived 512 x 512 tiles: S = 1024 visits 3 of 4
    tiles; S = 8192 visits 16 * 17 / 2 = 136 of 256; under a window of 2048
    a row of Q tiles meets its own tile and the four before it (the fourth
    holds the pairs 1537 .. 2047 apart), so rows 0-3 visit 1 + 2 + 3 + 4 and
    the other twelve 5 each: 70. A call without a window plans as PR 26's.
    The fused backward's table is head-major: a grid row's Q heads one
    after the other, each with its tiles K-major, so a Q head's dQ is whole
    before the next begins; with one head a row it is the dK/dV order."""
    from distributeddeeplearning_tpu.ops.flash_attention import (
        _schedule, tile_plan)

    plan = tile_plan(s, True, window=window)
    assert (plan.bq, plan.bk, plan.visited) == (512, 512, visited)
    if window is None:
        assert plan == tile_plan(s, True) and plan.edge == 0
        return
    assert plan.window == window and (plan.diagonal, plan.edge) == (16, 12)
    for groups in (1, 8):
        qi, kj = (np.asarray(t) for t in
                  _schedule(s, plan, True, k_major=True, groups=groups))
        assert len(qi) == groups * visited
        assert (np.diff(kj) >= 0).all()       # a K tile's run is one run
        heads = qi // (s // plan.bq)
        assert sorted(set(heads.tolist())) == list(range(groups))
        hq, hk = (np.asarray(t) for t in
                  _schedule(s, plan, True, groups=groups, head_major=True))
        head = hq // (s // plan.bq)
        assert (np.diff(head) >= 0).all()     # a Q head's tiles are one run
        pairs = list(zip(head.tolist(), hk.tolist(), hq.tolist()))
        assert pairs == sorted(pairs)         # and K-major within it
        assert sorted(zip(qi.tolist(), kj.tolist())) == sorted(
            zip(hq.tolist(), hk.tolist()))    # the same tiles
        if groups == 1:
            np.testing.assert_array_equal(hq, qi)
            np.testing.assert_array_equal(hk, kj)


@pytest.mark.parametrize("s,bq,bk,window", [
    (1024, 128, 128, 300), (1024, 256, 128, 128), (2048, 512, 512, 513),
    (2048, 128, 512, 1), (1024, 512, 512, 2048)])
def test_tile_plan_under_a_window_counts_by_hand(s, bq, bk, window):
    from distributeddeeplearning_tpu.ops.flash_attention import tile_plan

    plan = tile_plan(s, True, bq, bk, window=window)
    assert (plan.visited, plan.diagonal, plan.edge) == _band_plan_by_hand(
        s, bq, bk, window)


def test_a_window_needs_a_causal_call():
    q, k, v = random_qkv(jax.random.key(9))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=8)
    q, k, v = random_qkv(jax.random.key(9), h=3)
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, k[:, :, :2], v[:, :, :2], causal=True)


# ---------------------------------------------------------------------------
# What a recomputed block keeps: the forward rule names the kernel's result
# and its log-sum-exp (FLASH_OUT, FLASH_LSE), and a policy that lists both
# takes the second forward kernel out of the block's backward pass.
# ---------------------------------------------------------------------------

def _block_loss(policy):
    """A stand-in for a transformer block round the kernel: projections in
    and out, recomputed under ``policy`` ("kept" = not recomputed at all)."""
    def block(x, w):
        q, k, v = (jnp.einsum("bshd,de->bshe", x, w[i]) for i in range(3))
        return jnp.tanh(flash_attention(q, k, v, causal=True))

    if policy != "kept":
        block = jax.checkpoint(block, policy=policy)
    return lambda x, w: (block(x, w) ** 2).sum()


_names = jax.checkpoint_policies.save_only_these_names
RECOMPUTED_CASES = [
    pytest.param("kept", 1, id="not-recomputed"),
    pytest.param(None, 2, id="recomputed-no-policy"),
    pytest.param(_names("somebody_elses"), 2, id="policy-of-other-names"),
    pytest.param(_names(FLASH_OUT), 2, id="result-without-lse"),
    pytest.param(_names(FLASH_LSE), 2, id="lse-without-result"),
    pytest.param(_names(FLASH_OUT, FLASH_LSE), 1, id="result-and-lse"),
]


@pytest.fixture(scope="module")
def block_inputs():
    x = jax.random.normal(jax.random.key(11), (2, 64, 2, 16))
    w = jax.random.normal(jax.random.key(12), (3, 16, 16)) * 0.25
    return x, w


@pytest.fixture(scope="module")
def kept_block_grads(block_inputs):
    return jax.grad(_block_loss("kept"), argnums=(0, 1))(*block_inputs)


@pytest.mark.parametrize("policy,forwards", RECOMPUTED_CASES)
def test_a_recomputed_block_runs_the_forward_kernel_as_its_policy_says(
        block_inputs, kept_block_grads, policy, forwards):
    """The names alone change nothing; both of them kept, the backward
    kernels read the first pass's buffers. The gradients are the same bits
    whatever was kept."""
    grad = jax.grad(_block_loss(policy), argnums=(0, 1))
    assert flash_kernel_calls(grad, *block_inputs) == {
        "flash_fwd": forwards, "flash_dq": 0, "flash_dkv": 1}
    for got, want in zip(grad(*block_inputs), kept_block_grads):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("remat,forwards", [(False, 1), (True, 2)],
                         ids=["kept", "recomputed"])
def test_gpt_blocks_recomputed_without_a_policy_run_the_forward_twice(
        remat, forwards):
    """``gpt_tiny``'s ``nn.remat`` passes no policy: a user who switched
    ``--remat`` on to save memory keeps the block's input and nothing as
    large again."""
    from distributeddeeplearning_tpu.models import model_spec

    model = model_spec("gpt_tiny").build(
        vocab_size=256, dtype=jnp.float32, attention_impl="flash",
        remat=remat)
    ids = jax.random.randint(jax.random.key(0), (2, 64), 0, 256)
    params = model.init(jax.random.key(1), ids, train=False)["params"]
    layers = model.cfg.num_layers
    calls = flash_kernel_calls(
        jax.grad(lambda p: (model.apply({"params": p}, ids,
                                        train=False) ** 2).mean()), params)
    assert calls == {"flash_fwd": forwards * layers, "flash_dq": 0,
                     "flash_dkv": layers}


# ---------------------------------------------------------------------------
# Values of another width than the queries and keys (latent attention: 192
# and 128): V tiles, the accumulator, the result and dV at the value width,
# dQ and dK at the key width, the scale from the query width.
# ---------------------------------------------------------------------------

UNEQUAL_CASES = [
    # s, h, hkv, d, dv, block, lens
    pytest.param(128, 2, 2, 24, 16, 32, None, id="narrower-values"),
    pytest.param(192, 2, 2, 16, 32, 64, None, id="wider-values"),
    pytest.param(256, 4, 2, 24, 16, 64, [256, 200], id="grouped-ragged"),
    pytest.param(100, 2, 2, 24, 16, 32, None, id="padded-sequence"),
]


def _unequal_qkv(s, h, hkv, d, dv, b=2):
    kq, kk, kv, kw = jax.random.split(jax.random.key(21), 4)
    return (jax.random.normal(kq, (b, s, h, d)),
            jax.random.normal(kk, (b, s, hkv, d)),
            jax.random.normal(kv, (b, s, hkv, dv)),
            jax.random.normal(kw, (b, s, h, dv)))


@pytest.mark.parametrize("s,h,hkv,d,dv,block,lens", UNEQUAL_CASES)
def test_values_of_another_width_match_dense(s, h, hkv, d, dv, block, lens):
    q, k, v, w = _unequal_qkv(s, h, hkv, d, dv)
    mask = None if lens is None else _ragged_mask(2, s, lens)

    def dense(q, k, v):
        kk, vv = (jnp.repeat(x, h // hkv, axis=2) for x in (k, v))
        return dense_reference(q, kk, vv, mask, causal=True)

    def flash(q, k, v):
        return flash_attention(q, k, v, mask, causal=True, block_q=block,
                               block_k=block)

    got, want = flash(q, k, v), dense(q, k, v)
    assert got.shape == (2, s, h, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    grads = [jax.grad(lambda *a, f=f: (f(*a) * w).sum(), argnums=(0, 1, 2))(
        q, k, v) for f in (flash, dense)]
    for name, a, b in zip("qkv", *grads):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5,
                                   rtol=5e-5, err_msg=f"d{name}")


def test_keys_must_be_as_wide_as_the_queries():
    q, k, v, _ = _unequal_qkv(64, 2, 2, 24, 16)
    with pytest.raises(ValueError, match="as wide as"):
        flash_attention(q, v, v, causal=True)
    from distributeddeeplearning_tpu.ops.attention import multihead_attention
    for impl in ("ring", "zigzag"):
        with pytest.raises(ValueError, match="values as wide as"):
            multihead_attention(q, k, v, None, impl=impl, causal=True,
                                dtype=jnp.float32)


def _unequal_block_loss(policy):
    """`_block_loss` with values half as wide as the queries and keys."""
    def block(x, w, wv):
        q, k = (jnp.einsum("bshd,de->bshe", x, w[i]) for i in range(2))
        v = jnp.einsum("bshd,de->bshe", x, wv)
        return jnp.tanh(flash_attention(q, k, v, causal=True))

    if policy != "kept":
        block = jax.checkpoint(block, policy=policy)
    return lambda x, w, wv: (block(x, w, wv) ** 2).sum()


@pytest.mark.parametrize("policy,forwards", [
    pytest.param(None, 2, id="recomputed-no-policy"),
    pytest.param(_names(FLASH_OUT, FLASH_LSE), 1, id="result-and-lse")])
def test_the_kept_result_keeps_its_meaning_at_unequal_widths(
        block_inputs, policy, forwards):
    x, w = block_inputs
    wv = jax.random.normal(jax.random.key(13), (16, 8)) * 0.25
    want = jax.grad(_unequal_block_loss("kept"), argnums=(0, 1, 2))(x, w, wv)
    grad = jax.grad(_unequal_block_loss(policy), argnums=(0, 1, 2))
    assert flash_kernel_calls(grad, x, w, wv) == {
        "flash_fwd": forwards, "flash_dq": 0, "flash_dkv": 1}
    for got, kept in zip(grad(x, w, wv), want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(kept))


def _kernel_types(fn, *args):
    """{kernel: (operand types, result types)} of ``fn``'s program lowered
    for the TPU platform: the tensors each Mosaic call reads and writes."""
    import re
    text = jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    out = {}
    for line in text.splitlines():
        m = re.search(r'kernel_name = "(flash_\w+)"', line)
        if m:
            sig = line.rsplit(" : ", 1)[1]
            ins, outs = sig.split(" -> ")
            out[m.group(1)] = (re.findall(r"tensor<([^>]+)>", ins)[3:],
                               re.findall(r"tensor<([^>]+)>", outs))
    return out


def test_equal_widths_trace_to_the_kernels_they_traced_to():
    """The benchmark's two older cells call with one width; what each kernel
    reads there is what it read before values could be of another width
    (the shapes PERF_LEDGER.jsonl names the kernels by), and at 192 / 128
    only the V side, the result and dV change. The backward pass is one
    kernel, ``flash_dkv``, which writes dQ beside dK and dV."""
    def grads(d, dv, hkv):
        shapes = [(1, 1024, 4, d), (1, 1024, hkv, d), (1, 1024, hkv, dv)]
        args = [jnp.zeros(s, jnp.bfloat16) for s in shapes]
        return _kernel_types(
            jax.grad(lambda q, k, v: flash_attention(
                q, k, v, causal=True).astype(jnp.float32).sum(),
                argnums=(0, 1, 2)), *args)

    def expect(d, dv, hkv):
        q, o = f"4x1024x{d}xbf16", f"4x1024x{dv}xbf16"
        k, v = f"{hkv}x1024x{d}xbf16", f"{hkv}x1024x{dv}xbf16"
        mask, vec = "4x1x1024xi32", "4x1x1024xf32"
        backward = [q, k, v, mask, o, vec, vec]
        return {"flash_fwd": ([q, k, v, mask], [o, vec]),
                "flash_dkv": (backward, [q, k, v])}

    assert grads(64, 64, 4) == expect(64, 64, 4)       # gpt2's heads
    assert grads(128, 128, 1) == expect(128, 128, 1)   # trinity's, grouped
    assert grads(192, 128, 4) == expect(192, 128, 4)   # latent attention's


# ---------------------------------------------------------------------------
# One backward kernel: flash_dkv carries dQ where its accumulators fit the
# budget (fused_bwd_fits), from the ds it makes for dK; past the budget the
# two kernels run. Both paths against each other and the dense reference.
# ---------------------------------------------------------------------------

FLASH_MODULE = importlib.import_module(
    "distributeddeeplearning_tpu.ops.flash_attention")


def _dense_keep_reference(q, k, v, mask, causal, window, keep, rate):
    """Dense softmax attention in float32: Q head h on K/V head h // (H //
    Hkv), the key-padding ``mask``, causal and cut to ``query - key <
    window``, and dropout by ``keep`` (B, H, S, S) after the softmax."""
    s, h = q.shape[1], q.shape[2]
    k, v = (jnp.repeat(x, h // k.shape[2], axis=2) for x in (k, v))
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    valid = (j <= i) if causal else jnp.ones((s, s), bool)
    if window is not None:
        valid = valid & (i - j < window)
    valid = valid[None, None] & mask[:, None, None, :]
    p = jax.nn.softmax(jnp.where(valid, sc, -1e30), axis=-1)
    if keep is not None:
        p = jnp.where(keep, p / (1.0 - rate), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


FUSED_CASES = [
    # s, h, hkv, d, dv, causal, window, valid keys a row, dropout, block;
    # S = 200 is padded to 256 inside, and S = 64 is one K tile, which a
    # grid row's Q heads then share
    pytest.param(200, 4, 2, 24, 16, True, None, (200, 150), 0.0, 64,
                 id="causal-masked-grouped-values-of-another-width-padded"),
    pytest.param(128, 2, 2, 16, 16, False, None, (128, 70), 0.2, 32,
                 id="full-masked-dropout"),
    pytest.param(64, 4, 1, 16, 16, True, 20, None, 0.2, 64,
                 id="grouped-window-dropout-one-k-tile"),
]


@pytest.mark.parametrize("s,h,hkv,d,dv,causal,window,lens,rate,block",
                         FUSED_CASES)
def test_fused_backward_matches_two_kernels_and_dense(
        monkeypatch, s, h, hkv, d, dv, causal, window, lens, rate, block):
    """dQ, dK and dV of the fused kernel against the two kernels' (dQ sums
    the same terms in the same order) and against the dense reference,
    whose dropout is ops/hash_dropout.py::dense_keep_mask: the mask the
    fused kernel regenerates is that one. The two paths differentiate one
    forward call, which is not jitted: each call of its vjp traces the
    backward rule anew, and so takes the path the budget gives then."""
    from distributeddeeplearning_tpu.ops.hash_dropout import dense_keep_mask

    q, k, v, w = _unequal_qkv(s, h, hkv, d, dv)
    mask = _ragged_mask(2, s, lens or (s, s))
    seed = jnp.int32(1234)

    def flash(q, k, v):
        return flash_attention(q, k, v, mask, causal=causal, window=window,
                               block_q=block, block_k=block,
                               dropout_rate=rate,
                               dropout_seed=seed if rate else None)

    def dense(q, k, v):
        keep = dense_keep_mask(seed, 2, h, s, s, rate) if rate else None
        return _dense_keep_reference(q, k, v, mask, causal, window, keep,
                                     rate)

    assert FLASH_MODULE.fused_bwd_fits(FLASH_MODULE._padded_len(s), d, dv,
                                       h // hkv)
    out, flash_vjp = jax.vjp(flash, q, k, v)
    want_out, dense_vjp = jax.vjp(jax.jit(dense), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_out),
                               rtol=2e-5, atol=2e-5)
    fused, want = flash_vjp(w), dense_vjp(w)
    dq_kernel, traced = FLASH_MODULE._dq_kernel, []

    def counted(*args, **kw):
        traced.append(1)
        return dq_kernel(*args, **kw)

    with monkeypatch.context() as m:
        m.setattr(FLASH_MODULE, "_FUSED_BWD_BYTES", -1)
        m.setattr(FLASH_MODULE, "_dq_kernel", counted)
        two = flash_vjp(w)
    assert traced  # the second pass did run the two kernels
    for name, a, t, r in zip(("dq", "dk", "dv"), fused, two, want):
        assert a.shape == r.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(t), rtol=1e-5,
                                   atol=1e-5, err_msg=f"{name}: two kernels")
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=1e-4,
                                   atol=1e-4, err_msg=f"{name}: dense")


def test_the_budget_decides_the_backward_kernels_by_the_shapes():
    """Every shape the cells train at fits the fused kernel's budget (12
    MiB at trinity_mini's 8192 tokens on 32/4 heads of 128, the largest);
    long sequences do not, and their gradient's program holds the two
    kernels. The traced program follows the shapes alone."""
    fits, size = FLASH_MODULE.fused_bwd_fits, FLASH_MODULE.fused_bwd_bytes
    assert size(8192, 128, 128, 8) == 12 * 2 ** 20
    assert size(8192, 192, 128, 1) == 6 * 2 ** 20
    for shape in ((1024, 64, 64, 1), (512, 64, 64, 1), (8192, 128, 128, 8),
                  (8192, 192, 128, 1), (4096, 192, 128, 1)):
        assert fits(*shape), shape
    for shape in ((65536, 128, 128, 1), (16384, 128, 128, 8),
                  (131072, 64, 64, 1)):
        assert not fits(*shape), shape

    def calls(s, hkv):
        shapes = [(1, s, 8, 128), (1, s, hkv, 128), (1, s, hkv, 128)]
        args = [jax.ShapeDtypeStruct(x, jnp.bfloat16) for x in shapes]
        return flash_kernel_calls(jax.grad(lambda q, k, v: flash_attention(
            q, k, v, causal=True).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)), *args)

    assert calls(8192, 1) == {"flash_fwd": 1, "flash_dq": 0, "flash_dkv": 1}
    assert calls(16384, 1) == {"flash_fwd": 1, "flash_dq": 1, "flash_dkv": 1}
    assert calls(16384, 8) == {"flash_fwd": 1, "flash_dq": 0, "flash_dkv": 1}
