"""ZeRO-1 optimizer-state sharding (parallel/zero.py + the explicit-DP
train step): layout round-trips, collective equivalence, replicated-vs-
sharded trajectory parity, 1/N residency, and cross-degree checkpoint
resume through the gather-on-save canonical format.

Parity tolerances: for elementwise optimizers (SGD-momentum, AdamW)
reduce-scatter hands each shard the same psum chunk values the all-reduce
produced, bit for bit; what parts zero1 from the replicated path is how the
compiler rounds a multiply that feeds an add in the update, and
tests/zero_parity.py states what that is held to (bitwise where the update
has no such term). Norm-based transforms (LAMB's trust ratio,
global-norm clipping) compute ``sqrt(psum(partial sums))``, whose fp
summation ORDER differs from the replicated full-leaf norm by ~1e-7 rel;
one step stays ~1e-6 while longer runs amplify that seed chaotically
through the network (a replicated-vs-replicated control with a 1e-7
perturbation of the clip threshold diverges identically: 6e-8 -> 6e-5 in
two steps), so multi-step LAMB asserts a bounded, not tight, gap."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.config import ParallelConfig
from distributeddeeplearning_tpu.parallel import zero
from distributeddeeplearning_tpu.train import loop
from tests import zero_parity
from tests.zero_parity import build as _build, cfg as _cfg

DATA_AXES = ("data", "fsdp")


def _leaves(tree):
    return jax.tree_util.tree_leaves(tree)


def _max_abs_diff(a, b) -> float:
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(_leaves(a), _leaves(b)))


# --------------------------------------------------------------------------
# Layout: pure host-side math, no devices.
# --------------------------------------------------------------------------

def _demo_tree():
    k = jax.random.key(0)
    ks = jax.random.split(k, 4)
    return {
        "a": {"kernel": jax.random.normal(ks[0], (3, 3, 7, 5))},  # 315 = 8*39+3
        "b": {"bias": jax.random.normal(ks[1], (13,))},
        "c": {"kernel": jax.random.normal(ks[2], (17, 9))},       # 153
        "d": {"scale": jax.random.normal(ks[3], (16,))},          # exact /8
    }


def test_layout_chunk_sizes_and_padding():
    tree = _demo_tree()
    layout = zero.build_layout(tree, 8)
    flat, _ = jax.tree_util.tree_flatten(tree)
    assert layout.num_leaves == len(flat)
    for i, shape in enumerate(layout.plan.shapes):
        numel = int(np.prod(shape)) if shape else 1
        assert layout.chunk_sizes[i] == -(-numel // 8)
        assert layout.padded_size(i) >= numel
        assert layout.padded_size(i) % 8 == 0
    assert "1/8 per shard" in layout.describe()


def test_to_chunked_roundtrip_exact():
    tree = _demo_tree()
    layout = zero.build_layout(tree, 8)
    chunked = zero.to_chunked(tree, layout)
    # every chunked leaf is flat, padded to a multiple of 8, zero-padded
    for leaf, shape, c in zip(_leaves(chunked), layout.plan.shapes,
                              layout.chunk_sizes):
        numel = int(np.prod(shape)) if shape else 1
        assert leaf.shape == (8 * c,)
        assert float(jnp.abs(leaf[numel:]).max()) == 0.0 if numel < 8 * c \
            else True
    back = zero.from_chunked(chunked, layout)
    assert jax.tree_util.tree_structure(back) == \
        jax.tree_util.tree_structure(tree)
    assert _max_abs_diff(back, tree) == 0.0


def test_chunked_struct_matches_real_chunking():
    tree = _demo_tree()
    layout = zero.build_layout(tree, 8)
    struct = zero.chunked_struct(tree, layout)
    real = zero.to_chunked(tree, layout)
    for s, r in zip(_leaves(struct), _leaves(real)):
        assert s.shape == r.shape and s.dtype == r.dtype


def test_layout_from_options_validates_dtype():
    struct = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), _demo_tree())
    from distributeddeeplearning_tpu.config import AllReduceConfig
    layout, payload = zero.layout_from_options(
        struct, 8, options=AllReduceConfig(bucket_mb=0.001))
    assert payload is None  # float32 payload = no cast
    assert len(layout.plan.buckets) > 1  # tiny bucket forces multiple
    _, bf16 = zero.layout_from_options(
        struct, 8, options=AllReduceConfig(dtype="bfloat16"))
    assert bf16 == jnp.bfloat16


# --------------------------------------------------------------------------
# Collectives on the 8-device mesh.
# --------------------------------------------------------------------------

def _mesh8(devices8):
    from jax.sharding import Mesh
    return Mesh(np.array(devices8).reshape(8, 1), DATA_AXES)


def test_reduce_scatter_equals_allreduce_chunks(devices8):
    """reduce_scatter's shard-k chunk == chunk k of the psum'd padded leaf,
    and all_gather_chunks reassembles exactly the psum tree: bit for bit,
    because on this backend ``psum`` and ``psum_scatter`` both sum the eight
    shards left to right (so the order of the sum is not what parts zero1
    from the replicated path: tests/zero_parity.py)."""
    from jax.sharding import PartitionSpec as P
    from distributeddeeplearning_tpu import compat

    mesh = _mesh8(devices8)
    tree = _demo_tree()
    # per-shard distinct grads: leaf stacked over a leading device axis
    stacked = jax.tree_util.tree_map(
        lambda x: jax.random.normal(jax.random.key(7), (8,) + x.shape,
                                    x.dtype), tree)
    struct = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), stacked)
    layout = zero.build_layout(struct, 8)

    def f(x):
        local = jax.tree_util.tree_map(lambda a: a[0], x)
        chunks = zero.reduce_scatter(local, layout, DATA_AXES)
        summed = jax.tree_util.tree_map(
            lambda a: jax.lax.psum(a, DATA_AXES), local)
        return chunks, zero.all_gather_chunks(chunks, layout, DATA_AXES), \
            summed

    mapped = compat.shard_map(
        f, mesh=mesh, in_specs=(P(DATA_AXES),),
        out_specs=(P(DATA_AXES), P(), P()))
    chunks, gathered, summed = jax.jit(mapped)(stacked)

    # the concatenated global chunk array IS the padded psum'd flat leaf
    expected = zero.to_chunked(summed, layout)
    np.testing.assert_array_equal(
        np.concatenate([np.ravel(c) for c in _leaves(chunks)]),
        np.concatenate([np.ravel(e) for e in _leaves(expected)]))
    # and the gather reassembles the psum tree in original shapes
    assert _max_abs_diff(gathered, summed) == 0.0


def test_local_chunks_then_gather_is_identity(devices8):
    from jax.sharding import PartitionSpec as P
    from distributeddeeplearning_tpu import compat

    mesh = _mesh8(devices8)
    tree = _demo_tree()
    layout = zero.build_layout(tree, 8)

    def f(x):
        return zero.all_gather_chunks(
            zero.local_chunks(x, layout, DATA_AXES), layout, DATA_AXES)

    mapped = compat.shard_map(f, mesh=mesh, in_specs=(P(),), out_specs=P())
    out = jax.jit(mapped)(tree)
    assert _max_abs_diff(out, tree) == 0.0


def test_bf16_gather_moves_matrices_in_bf16_and_vectors_as_they_are(devices8):
    """zero3's gather under a bf16 policy (``out_dtype``): still one
    collective a bucket, whose payload is every matrix's chunk in bf16 and
    every vector's chunk in its master dtype; a matrix comes back as the
    bf16 its layer would cast it to, a norm scale or bias comes back in
    every bit (the layers consume those in float32)."""
    from jax.sharding import PartitionSpec as P
    from distributeddeeplearning_tpu import compat
    from distributeddeeplearning_tpu.analysis import collectives as ca

    mesh = _mesh8(devices8)
    tree = _demo_tree()
    layout = zero.build_layout(tree, 8)
    assert len(layout.plan.buckets) == 1

    def f(x):
        return zero.all_gather_chunks(
            zero.local_chunks(x, layout, DATA_AXES), layout, DATA_AXES,
            out_dtype=jnp.bfloat16)

    mapped = jax.jit(compat.shard_map(f, mesh=mesh, in_specs=(P(),),
                                      out_specs=P()))
    gathers = [op for op in ca.schedule_of(mapped, tree).ops
               if op.kind == "all_gather"]
    # 16-bit words a shard sends: one an element of a matrix's chunk, two an
    # element of a vector's.
    words = sum(c * (1 if len(shape) >= 2 else 2)
                for c, shape in zip(layout.chunk_sizes, layout.plan.shapes))
    assert [(op.dtype, op.shape) for op in gathers] == [("uint16", (words,))]
    out = mapped(tree)
    for path, master in jax.tree_util.tree_flatten_with_path(tree)[0]:
        got = out[path[0].key][path[1].key]
        want = master.astype(jnp.bfloat16) if master.ndim >= 2 else master
        assert got.dtype == want.dtype, path
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------------------------------------
# End-to-end trajectory parity on the explicit-DP path.
# --------------------------------------------------------------------------

def _sharded_opt_leaves(state):
    """(sharded, replicated) opt-state array leaves, by per-device shard."""
    sharded, replicated = [], []
    for leaf in _leaves(state.opt_state):
        if not hasattr(leaf, "addressable_shards"):
            continue
        local = leaf.addressable_shards[0].data.size
        (sharded if local < leaf.size else replicated).append(leaf)
    return sharded, replicated


@pytest.mark.parametrize("optimizer", list(zero_parity.OPTIMIZERS))
def test_zero1_matches_replicated(devices8, optimizer):
    """After each of three steps, within what tests/zero_parity.py allows
    (bit for bit where the update has no multiply feeding an add)."""
    zero_parity.assert_matches_replicated(optimizer, "zero1")
    sb = zero_parity.trajectory(optimizer, "zero1").state
    sharded, _ = _sharded_opt_leaves(sb)
    assert sharded, "no opt-state leaf is sharded under zero1"
    for leaf in sharded:
        assert leaf.addressable_shards[0].data.size == leaf.size // 8


def test_zero1_matches_replicated_lamb(devices8):
    """LAMB: norm fp order bounds one step at ~1e-6; 3 steps stay bounded
    (chaotic growth of the 1-ulp seed, see module docstring)."""
    cfg_r = _cfg(dict(name="lamb", learning_rate=1e-3, weight_decay=0.01),
                 "none")
    cfg_z = _cfg(dict(name="lamb", learning_rate=1e-3, weight_decay=0.01),
                 "zero1")
    sa, step_r, source, rng_r = _build(cfg_r, 3)
    sb, step_z, _, rng_z = _build(cfg_z, 3)
    for i in range(3):
        sa, _ = step_r(sa, source.batch(i), rng_r)
        sb, _ = step_z(sb, source.batch(i), rng_z)
        if i == 0:
            assert _max_abs_diff(jax.device_get(sa.params),
                                 jax.device_get(sb.params)) < 2e-6
    sa3, sb3 = sa, sb
    assert _max_abs_diff(jax.device_get(sa3.params),
                         jax.device_get(sb3.params)) < 5e-3
    sharded, _ = _sharded_opt_leaves(sb3)
    # Adam carries mu and nu per param leaf: both must live sharded.
    n_params = len(_leaves(sb3.params))
    assert len(sharded) == 2 * n_params
    for leaf in sharded:
        assert leaf.addressable_shards[0].data.size == leaf.size // 8


def test_zero1_rejected_on_gspmd_path(devices8):
    cfg = _cfg(dict(name="sgd", learning_rate=0.1), "zero1",
               parallel=ParallelConfig(data=4, model=2))
    with pytest.raises(ValueError, match="zero1"):
        loop.build(cfg, 2)
    with pytest.raises(ValueError, match="optimizer_sharding"):
        loop.build(_cfg(dict(name="sgd", learning_rate=0.1), "zero9"), 2)


def test_cli_flag_roundtrip():
    import train as train_cli

    cfg = train_cli.build_config(train_cli.parse_args(
        ["--optimizer-sharding", "zero1"]))
    assert cfg.optimizer_sharding == "zero1"
    assert train_cli.build_config(
        train_cli.parse_args([])).optimizer_sharding == "none"


# --------------------------------------------------------------------------
# Checkpoint: gather-on-save canonical layout, cross-degree resume.
# --------------------------------------------------------------------------

def _save_zero1_dp8(tmp_path, steps=2):
    from distributeddeeplearning_tpu.train.checkpoint import Checkpointer

    cfg = _cfg(dict(name="sgd", learning_rate=0.1, momentum=0.9), "zero1")
    state, train_step, source, rng = _build(cfg, steps + 2)
    for i in range(steps):
        state, _ = train_step(state, source.batch(i), rng)
    ckpt = Checkpointer(str(tmp_path / "ckpt"), every_steps=1,
                        converter=train_step.zero_converter)
    assert ckpt.maybe_save(int(state.step), state, force=True)
    ckpt.wait()
    ckpt.close()
    return cfg, state, train_step


def test_cross_degree_resume(devices8, tmp_path):
    """Save under zero1 on 8 shards; restore (a) replicated on dp=8 and
    (b) zero1 on dp=2. Params must be BITWISE the save's params; the
    restored optimizer states must agree in canonical form; and one
    post-resume SGD step from either restore lands on identical params."""
    from distributeddeeplearning_tpu.train.checkpoint import Checkpointer

    cfg8, saved, step8 = _save_zero1_dp8(tmp_path)
    saved_params = jax.device_get(saved.params)
    saved_canon = jax.device_get(
        step8.zero_converter.to_canonical(saved).opt_state)

    # (a) replicated restore, same degree
    cfg_r = _cfg(dict(name="sgd", learning_rate=0.1, momentum=0.9), "none")
    state_r, step_r, source, rng = _build(cfg_r, 6)
    ck_r = Checkpointer(str(tmp_path / "ckpt"), every_steps=1)
    restored_r = ck_r.restore_latest(state_r)
    ck_r.close()
    assert restored_r is not None
    assert _max_abs_diff(jax.device_get(restored_r.params),
                         saved_params) == 0.0
    assert _max_abs_diff(jax.device_get(restored_r.opt_state),
                         saved_canon) == 0.0

    # (b) zero1 restore on a DIFFERENT degree (dp=2 -> 1/2 chunks)
    cfg2 = _cfg(dict(name="sgd", learning_rate=0.1, momentum=0.9), "zero1",
                parallel=ParallelConfig(data=2), global_batch_size=16)
    state_2, step_2, _, rng2 = _build(cfg2, 6)
    ck_2 = Checkpointer(str(tmp_path / "ckpt"), every_steps=1,
                        converter=step_2.zero_converter)
    restored_2 = ck_2.restore_latest(state_2)
    ck_2.close()
    assert restored_2 is not None
    assert _max_abs_diff(jax.device_get(restored_2.params),
                         saved_params) == 0.0
    # opt state re-sharded 1/2: canonical form matches the save exactly
    assert _max_abs_diff(
        jax.device_get(step_2.zero_converter.to_canonical(
            restored_2).opt_state), saved_canon) == 0.0
    sharded, _ = _sharded_opt_leaves(restored_2)
    assert sharded
    for leaf in sharded:
        assert leaf.addressable_shards[0].data.size == leaf.size // 2

    # one post-resume step at dp=2 from each restore: identical params
    # (SGD is elementwise, so replicated and zero1 continuations agree
    # bitwise given identical restored state and batches)
    cfg_r2 = _cfg(dict(name="sgd", learning_rate=0.1, momentum=0.9), "none",
                  parallel=ParallelConfig(data=2))
    state_r2, step_r2, source2, rng_r2 = _build(cfg_r2, 6)
    ck = Checkpointer(str(tmp_path / "ckpt"), every_steps=1)
    restored_r2 = ck.restore_latest(state_r2)
    ck.close()
    # device_copy before stepping: a warm AOT cache serves deserialized
    # executables that donate their inputs unconditionally, and a donating
    # dispatch on orbax-restored buffers both corrupts the arrays this
    # test still reads AND invalidates the restored state itself
    # (train/checkpoint.py device_copy docstring).
    from distributeddeeplearning_tpu.train import checkpoint as ckptlib
    restored_r2 = ckptlib.device_copy(restored_r2)
    restored_2 = ckptlib.device_copy(restored_2)
    batch = source2.batch(2)
    next_r, _ = step_r2(restored_r2, batch, rng_r2)
    next_2, _ = step_2(restored_2, batch, rng2)
    assert int(next_r.step) == int(next_2.step)
    assert _max_abs_diff(jax.device_get(next_r.params),
                         jax.device_get(next_2.params)) == 0.0
