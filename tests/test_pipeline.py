"""Pipeline parallelism (models/pipeline.py; wires ParallelConfig.pipeline).

Checks: (a) the GPipe and interleaved-1f1b schedules compute exactly what a
sequential pass over the same stacked layer params computes, (b) layer params
actually shard over the ``pipeline`` mesh axis, (c) a pp x dp x tp train step
runs and optimizes, (d) schedule equivalence — gpipe and 1f1b reach the same
final params at identical geometry (SGD-momentum and AdamW), (e) the 1f1b
path composes with ZeRO-2, warm-boots through the AOT executable cache with
zero retraces, and resumes across schedules via the canonical (schedule-
portable) checkpoint layout (docs/pipeline.md).
"""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from distributeddeeplearning_tpu.config import (
    DataConfig, OptimizerConfig, ParallelConfig, TrainConfig)
from distributeddeeplearning_tpu.data.synthetic import SyntheticTokens
from distributeddeeplearning_tpu.models import bert, model_spec
from distributeddeeplearning_tpu.models.pipeline import (
    PipelinedEncoder, build_schedule)
from distributeddeeplearning_tpu.parallel.mesh import make_mesh
from distributeddeeplearning_tpu.train import optim, steps
import pytest


def test_pipeline_matches_sequential():
    """GPipe output == applying the same stacked layers in order."""
    cfg = bert.BertConfig(vocab_size=256, hidden_size=32, num_layers=4,
                          num_heads=2, intermediate_size=64, max_position=64,
                          dropout_rate=0.0)
    enc = PipelinedEncoder(
        layer_factory=functools.partial(bert.EncoderLayer, cfg, jnp.float32),
        num_stages=2, layers_per_stage=2, num_microbatches=4,
        dtype=jnp.float32)
    x = jax.random.normal(jax.random.key(0), (8, 16, 32), jnp.float32)
    mask = jnp.ones((8, 16), bool)
    variables = enc.init({"params": jax.random.key(1)}, x, mask,
                         deterministic=True)
    out = enc.apply(variables, x, mask, deterministic=True)

    layer_params = nn.meta.unbox(variables["params"])["stages"]["layer"]
    ref = x
    layer = bert.EncoderLayer(cfg, jnp.float32)
    for p in range(2):
        for l in range(2):  # noqa: E741
            sliced = jax.tree_util.tree_map(lambda a: a[p, l], layer_params)
            ref = layer.apply({"params": sliced}, ref, mask,
                              deterministic=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_pipeline_forward_mesh_invariant(devices8):
    """Same params + batch -> bitwise-identical logits on every (data,
    pipeline) mesh factorization. Guards the strided microbatch split: the
    old contiguous (m, mb) reshape of a data-sharded batch dim let XLA SPMD
    propagation (observed on jax 0.4.37) materialize the shard-local
    example grouping under the global grouping's name, so each mesh fed
    each microbatch a *different* set of examples — surfacing as per-step
    trajectory drift whenever the elastic controller re-formed across dp
    (tests/test_elastic_resume.py), not as any visible shape error."""
    from jax.sharding import NamedSharding
    from distributeddeeplearning_tpu.models import get_model
    from distributeddeeplearning_tpu.parallel import sharding as shardlib
    from distributeddeeplearning_tpu.parallel.mesh import use_mesh

    model = get_model("bert_tiny_pp44", vocab_size=1024, dtype=jnp.float32)
    src = SyntheticTokens(8, 16, 1024, seed=0)
    batch = src.batch(2)
    init_rules = list(shardlib.logical_rules(
        ParallelConfig(data=1, pipeline=2)))
    with nn.logical_axis_rules(init_rules):
        params = nn.meta.unbox(model.init(
            {"params": jax.random.key(0), "dropout": jax.random.key(0)},
            batch["input_ids"], train=False))["params"]
    params = jax.tree_util.tree_map(np.asarray, params)

    def logits_under(dp, pp):
        par = ParallelConfig(data=dp, pipeline=pp)
        mesh = make_mesh(par)
        bshd = shardlib.batch_sharding(mesh, seq_dim=1)
        rules = list(shardlib.logical_rules(par))

        def fwd(p, ids, am):
            with nn.logical_axis_rules(rules):
                out, _ = model.apply({"params": p}, ids, attention_mask=am,
                                     train=False, mutable=["moe_losses"])
            return out

        jitted = jax.jit(fwd,
                         in_shardings=(NamedSharding(mesh, P()), bshd, bshd),
                         out_shardings=NamedSharding(mesh, P()))
        with use_mesh(mesh):
            return np.asarray(jitted(params, batch["input_ids"],
                                     batch["attention_mask"]))

    ref = logits_under(1, 2)
    for dp, pp in ((4, 2), (2, 4), (1, 4)):
        got = logits_under(dp, pp)
        np.testing.assert_array_equal(
            got, ref, err_msg=f"pipelined forward differs on dp={dp} pp={pp}")


def _pp_cfg():
    return TrainConfig(
        model="bert_tiny_pp", global_batch_size=8, dtype="float32",
        parallel=ParallelConfig(pipeline=2, data=2, model=2),
        data=DataConfig(dataset="mlm", seq_len=32, vocab_size=1024),
        # reference_batch=8: linear-scaling identity, real learning signal.
        optimizer=OptimizerConfig(name="adamw", learning_rate=1e-3,
                                  reference_batch=8,
                                  schedule="linear", label_smoothing=0.0))


def _build():
    cfg = _pp_cfg()
    mesh = make_mesh(cfg.parallel)
    model = model_spec("bert_tiny_pp").build(vocab_size=1024,
                                             dtype=jnp.float32)
    tx, _ = optim.make_optimizer(cfg.optimizer, cfg.global_batch_size, 100)
    src = SyntheticTokens(8, 32, 1024, seed=7)
    state, shardings = steps.init_sharded_state(
        model, tx, mesh, cfg, src.batch(0), jax.random.key(0), "tokens")
    step = steps.make_gspmd_train_step(model, tx, mesh, cfg, shardings,
                                       "tokens")
    return src, state, step


def test_pp_params_shard(devices8):
    _, state, _ = _build()
    qk = (state.params["pipeline"]["stages"]["layer"]["attention"]["query"]
          ["kernel"].value)
    # (stages, layers_per_stage, embed, heads): stages over `pipeline`,
    # heads over `model`.
    assert qk.ndim == 4
    assert qk.sharding.spec == P("pipeline", None, None, "model"), qk.sharding


@pytest.mark.slow
def test_pp_step_trains(devices8):
    src, state, step = _build()
    rng = jax.random.key(42)
    fixed = src.batch(0)
    first = last = None
    for _ in range(8):
        state, metrics = step(state, fixed, rng)
        if first is None:
            first = float(metrics["loss"])
        last = float(metrics["loss"])
    assert np.isfinite(first) and np.isfinite(last)
    assert last < first, (first, last)


def test_unconsumed_axis_rejected(devices8):
    """A pipeline/expert mesh axis no model dim maps onto must error, not
    silently duplicate compute across its groups."""
    import pytest
    from distributeddeeplearning_tpu.train import loop

    cfg = _pp_cfg().replace(model="bert_tiny")  # not pipelined
    with pytest.raises(ValueError, match="pipeline_stages"):
        loop.build(cfg, total_steps=1)
    moe_less = _pp_cfg().replace(
        model="bert_tiny",
        parallel=ParallelConfig(data=4, expert=2))
    with pytest.raises(ValueError, match="num_experts"):
        loop.build(moe_less, total_steps=1)


# ---------------------------------------------------------------------------
# Schedule tables (pure Python — no devices)
# ---------------------------------------------------------------------------

@pytest.mark.pipeline
def test_schedule_bubble_matches_analytic():
    """The enumerated table's idle fraction IS the closed form
    (P-1)/(M*V+P-1) whenever P | M — for gpipe (V=1) and interleaved
    1f1b alike. 1f1b with V>1 strictly shrinks the bubble."""
    for name, p, m, v in (("gpipe", 2, 4, 1), ("gpipe", 4, 8, 1),
                          ("1f1b", 2, 4, 2), ("1f1b", 4, 8, 2),
                          ("1f1b", 2, 8, 4)):
        sched = build_schedule(name, num_stages=p, num_microbatches=m,
                               virtual_stages=v)
        assert sched.bubble_fraction() == pytest.approx(
            sched.analytic_bubble_fraction()), (name, p, m, v)
        assert sched.analytic_bubble_fraction() == pytest.approx(
            (p - 1) / (m * v + p - 1))
    gp = build_schedule("gpipe", num_stages=2, num_microbatches=4)
    il = build_schedule("1f1b", num_stages=2, num_microbatches=4,
                        virtual_stages=2)
    assert il.bubble_fraction() < gp.bubble_fraction()


@pytest.mark.pipeline
def test_schedule_conservation():
    """Every microbatch is injected exactly once, emitted exactly once, and
    each stage works each (microbatch, chunk) pair exactly once."""
    for name, p, m, v in (("gpipe", 2, 6, 1), ("1f1b", 4, 8, 2)):
        sched = build_schedule(name, num_stages=p, num_microbatches=m,
                               virtual_stages=v)
        injected = [t.inject_mb for t in sched.ticks
                    if t.inject_mb is not None]
        emitted = [t.emit_mb for t in sched.ticks if t.emit_mb is not None]
        assert sorted(injected) == list(range(m))
        assert sorted(emitted) == list(range(m))
        for k in range(p):
            work = [t.occupancy[k] for t in sched.ticks
                    if t.occupancy[k] is not None]
            assert sorted(work) == sorted(
                (mb, c) for mb in range(m) for c in range(v)), (name, k)


@pytest.mark.pipeline
def test_shift_pairs_forms():
    """The activation shift entering every tick carries the full forward
    ring k -> k+1; the wrap edge P-1 -> 0 (1f1b chunk re-entry / gpipe
    drain) appears exactly on the ticks where stage 0 takes no fresh
    microbatch — the pairing the ddl-lint rule verifies against dataflow."""
    sched = build_schedule("1f1b", num_stages=4, num_microbatches=8,
                           virtual_stages=2)
    p = sched.num_stages
    for tick in sched.ticks:
        pairs = sched.shift_pairs(tick.index)
        fwd = {(k, k + 1) for k in range(p - 1)}
        assert fwd <= set(pairs), tick
        if tick.inject_mb is None:
            assert (p - 1, 0) in pairs, tick
        else:
            assert (p - 1, 0) not in pairs, tick


@pytest.mark.pipeline
def test_build_schedule_rejects():
    with pytest.raises(ValueError, match="unknown"):
        build_schedule("zb-h1", num_stages=2, num_microbatches=4)
    with pytest.raises(ValueError, match="gpipe"):
        build_schedule("gpipe", num_stages=2, num_microbatches=4,
                       virtual_stages=2)
    with pytest.raises(ValueError, match="divisible"):
        build_schedule("1f1b", num_stages=4, num_microbatches=6,
                       virtual_stages=2)


@pytest.mark.pipeline
def test_config_fingerprint_separates_schedules():
    """perf/aot.py: gpipe, 1f1b and each virtual-stage count compile
    different programs, so their AOT/bench fingerprints must differ — two
    records with different schedules are different experiments."""
    from distributeddeeplearning_tpu.perf import aot as aotlib

    base = _pp_cfg().replace(model="bert_tiny_pp4")
    fps = {aotlib.config_fingerprint(
        base.replace(pipeline_schedule=s, pipeline_virtual_stages=v),
        total_steps=10)
        for s, v in (("gpipe", 1), ("1f1b", 1), ("1f1b", 2))}
    assert len(fps) == 3


# ---------------------------------------------------------------------------
# Model equivalence: 1f1b == gpipe == sequential
# ---------------------------------------------------------------------------

def _tiny_encoder(schedule, virtual_stages):
    # Smallest geometry that still exercises V=2 interleaving: 4 layers =
    # P*V chunks of one layer each, M=2 microbatches (1f1b needs P | M).
    # Kept tiny on purpose — three separately-compiled programs ride on it
    # in tier-1, so its compile time is paid three times per run.
    cfg = bert.BertConfig(vocab_size=64, hidden_size=16, num_layers=4,
                          num_heads=2, intermediate_size=32, max_position=16,
                          dropout_rate=0.0)
    return PipelinedEncoder(
        layer_factory=functools.partial(bert.EncoderLayer, cfg, jnp.float32),
        num_stages=2, layers_per_stage=2, num_microbatches=2,
        schedule=schedule, virtual_stages=virtual_stages, dtype=jnp.float32)


@pytest.mark.pipeline
def test_1f1b_matches_gpipe_forward():
    """Interleaved 1f1b output == gpipe output on the SAME params (the init
    tree is schedule-portable, so one init serves both applies); V=1 1f1b
    is bitwise gpipe (identical tick tables)."""
    gp = _tiny_encoder("gpipe", 1)
    x = jax.random.normal(jax.random.key(0), (4, 8, 16), jnp.float32)
    mask = jnp.ones((4, 8), bool)
    variables = gp.init({"params": jax.random.key(1)}, x, mask,
                        deterministic=True)
    out_gp = gp.apply(variables, x, mask, deterministic=True)
    out_v1 = _tiny_encoder("1f1b", 1).apply(variables, x, mask,
                                            deterministic=True)
    np.testing.assert_array_equal(np.asarray(out_v1), np.asarray(out_gp))
    out_v2 = _tiny_encoder("1f1b", 2).apply(variables, x, mask,
                                            deterministic=True)
    np.testing.assert_allclose(np.asarray(out_v2), np.asarray(out_gp),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.pipeline
@pytest.mark.slow
def test_1f1b_matches_gpipe_grads_bitwise():
    """The strong equivalence pin: on one device, the V=2 interleaved
    program backpropagates to BITWISE-identical gradients for every leaf.
    The schedules reorder *when* each (microbatch, chunk) runs, not *what*
    runs — per-leaf gradient accumulation order is fixed by the scan
    structure, so any numeric daylight between the schedules must come
    from a partitioner's resharding choices (which the multi-device parity
    test bounds), never from the schedule itself."""
    gp = _tiny_encoder("gpipe", 1)
    x = jax.random.normal(jax.random.key(0), (4, 8, 16), jnp.float32)
    mask = jnp.ones((4, 8), bool)
    variables = gp.init({"params": jax.random.key(1)}, x, mask,
                        deterministic=True)

    def loss_fn(m):
        def f(params):
            out = m.apply({"params": params}, x, mask, deterministic=True)
            return jnp.sum(out * out)
        return f

    g_gp = jax.grad(loss_fn(gp))(variables["params"])
    g_il = jax.grad(loss_fn(_tiny_encoder("1f1b", 2)))(variables["params"])
    for a, b in zip(jax.tree_util.tree_leaves(g_gp),
                    jax.tree_util.tree_leaves(g_il)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _pp4_cfg(schedule="gpipe", virtual_stages=1, optimizer="adamw",
             sharding="none"):
    return TrainConfig(
        model="bert_tiny_pp4", global_batch_size=8, dtype="float32",
        optimizer_sharding=sharding,
        pipeline_schedule=schedule, pipeline_virtual_stages=virtual_stages,
        parallel=ParallelConfig(pipeline=2, data=2, model=2),
        data=DataConfig(dataset="mlm", seq_len=32, vocab_size=1024),
        optimizer=OptimizerConfig(name=optimizer, learning_rate=1e-3,
                                  momentum=0.9, reference_batch=8,
                                  schedule="linear", label_smoothing=0.0))


def _build_pp4(cfg):
    mesh = make_mesh(cfg.parallel)
    model = model_spec(cfg.model).build(
        vocab_size=1024, dtype=jnp.float32,
        pipeline_schedule=cfg.pipeline_schedule,
        pipeline_virtual_stages=cfg.pipeline_virtual_stages)
    tx, _ = optim.make_optimizer(cfg.optimizer, cfg.global_batch_size, 100)
    src = SyntheticTokens(8, 32, 1024, seed=7)
    state, shardings = steps.init_sharded_state(
        model, tx, mesh, cfg, src.batch(0), jax.random.key(0), "tokens")
    step = steps.make_gspmd_train_step(model, tx, mesh, cfg, shardings,
                                       "tokens")
    return src, state, step, shardings


@pytest.mark.pipeline
@pytest.mark.slow
@pytest.mark.parametrize("optimizer,rtol,atol",
                         [("sgd", 1e-4, 1e-4), ("adamw", 1e-3, 5e-3)])
def test_final_params_parity_gpipe_vs_1f1b(devices8, optimizer, rtol, atol):
    """Schedule equivalence end to end: gpipe and interleaved 1f1b train to
    the SAME final params at identical geometry — SGD-momentum and AdamW.
    Same seed gives a bitwise-identical init tree (the init path is one
    schedule-independent full-stack call) and the per-leaf gradient math is
    bitwise identical (test_1f1b_matches_gpipe_grads_bitwise), so all the
    daylight here is the GSPMD partitioner resharding the two programs
    differently across the 2x2x2 mesh — ULP-level gradient reassociation,
    not schedule error. SGD integrates that noise linearly (measured
    ~1.5e-5 after 3 steps; bound 1e-4). Adam divides it by sqrt(v), so on
    near-zero-gradient elements a ULP-level sign flip becomes an O(lr)
    update difference per step — its bound is a few lr (5e-3), which still
    catches any real routing bug (wrong-chunk params diverge at the 1e-1
    param scale)."""
    finals = {}
    for schedule, v in (("gpipe", 1), ("1f1b", 2)):
        cfg = _pp4_cfg(schedule, v, optimizer=optimizer)
        src, state, step, _ = _build_pp4(cfg)
        rng = jax.random.key(42)
        fixed = src.batch(0)
        for _ in range(3):
            state, metrics = step(state, fixed, rng)
        assert np.isfinite(float(metrics["loss"]))
        finals[schedule] = jax.device_get(state.params)
    flat_gp = jax.tree_util.tree_leaves(finals["gpipe"])
    flat_il = jax.tree_util.tree_leaves(finals["1f1b"])
    for a, b in zip(flat_gp, flat_il):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=atol)


@pytest.mark.pipeline
@pytest.mark.slow
def test_zero2_composes_with_1f1b(devices8):
    """ZeRO-2 + interleaved 1f1b: optimizer-moment leaves pick up the DP
    axis on top of their stage/tp axes (the reduce-scatter layout) and the
    composed step still optimizes."""
    cfg = _pp4_cfg("1f1b", 2, sharding="zero2")
    src, state, step, shardings = _build_pp4(cfg)
    mu = shardings.opt_state[0].mu["pipeline"]["stages"]["layer"][
        "attention"]["query"]["kernel"]
    assert "data" in jax.tree_util.tree_leaves(
        [mu.spec], is_leaf=lambda x: isinstance(x, P))[0], mu
    rng = jax.random.key(42)
    fixed = src.batch(0)
    first = last = None
    for _ in range(6):
        state, metrics = step(state, fixed, rng)
        if first is None:
            first = float(metrics["loss"])
        last = float(metrics["loss"])
    assert np.isfinite(first) and np.isfinite(last)
    assert last < first, (first, last)


# ---------------------------------------------------------------------------
# AOT warm boot + cross-schedule checkpoint resume (loop.run end to end)
# ---------------------------------------------------------------------------

def _loop_cfg(tmp_path, schedule, virtual_stages, **kw):
    base = dict(
        model="bert_tiny_pp4", global_batch_size=8, dtype="float32",
        backend="cpu", log_every=10**9,
        pipeline_schedule=schedule, pipeline_virtual_stages=virtual_stages,
        parallel=ParallelConfig(pipeline=2, data=4),
        data=DataConfig(synthetic=True, dataset="mlm", seq_len=32,
                        vocab_size=512),
        optimizer=OptimizerConfig(name="adamw", learning_rate=1e-3,
                                  reference_batch=8, schedule="constant",
                                  warmup_epochs=0.0))
    base.update(kw)
    return TrainConfig(**base)


@pytest.mark.pipeline
@pytest.mark.slow
@pytest.mark.usefixtures("devices8")
@pytest.mark.parametrize("schedule,v", [("gpipe", 1), ("1f1b", 2)])
def test_aot_warm_boot_zero_retrace(tmp_path, monkeypatch, schedule, v):
    """A second boot of the identical pipelined config deserializes the
    gspmd step executable — ZERO retraces of the tick loop — and, because
    the pipeline_tick instants fire only at trace time, the warm summary
    honestly reports bubble_fraction as absent rather than 0."""
    from distributeddeeplearning_tpu.robustness import faults
    from distributeddeeplearning_tpu.train import loop

    cache = str(tmp_path / "cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
    monkeypatch.delenv(faults.ENV_PLAN, raising=False)
    monkeypatch.delenv(faults.ENV_ATTEMPT, raising=False)
    cfg = _loop_cfg(tmp_path, schedule, v)
    s1 = loop.run(cfg, total_steps=2)
    assert s1["compile_cache"]["sources"]["gspmd_train_step"] == \
        "compiled"
    before = steps.TRACE_COUNTS["gspmd_train_step"]
    s2 = loop.run(cfg, total_steps=2)
    assert steps.TRACE_COUNTS["gspmd_train_step"] == before  # ZERO
    assert s2["compile_cache"]["sources"]["gspmd_train_step"] == \
        "aot_hit"
    assert s1["final_metrics"]["loss"] == s2["final_metrics"]["loss"]
    assert s2["pipeline"]["schedule"] == schedule
    assert s2["pipeline"]["bubble_fraction"] is None  # no trace, no lie


@pytest.mark.pipeline
@pytest.mark.slow
@pytest.mark.usefixtures("devices8")
def test_cross_schedule_checkpoint_resume(tmp_path):
    """The canonical (stage-major, schedule-portable) param layout lets a
    gpipe checkpoint resume under interleaved 1f1b: run 1 trains gpipe and
    saves; run 2 restores the same tree under 1f1b and keeps training."""
    from distributeddeeplearning_tpu.train import loop

    ckpt = str(tmp_path / "ckpt")
    cfg_gp = _loop_cfg(tmp_path, "gpipe", 1, checkpoint_dir=ckpt,
                       checkpoint_every_steps=1)
    s1 = loop.run(cfg_gp, total_steps=2)
    assert s1["final_step"] == 2
    cfg_il = _loop_cfg(tmp_path, "1f1b", 2, checkpoint_dir=ckpt,
                       checkpoint_every_steps=1)
    s2 = loop.run(cfg_il, total_steps=4)
    assert s2["start_step"] == 2  # restored, not retrained
    assert s2["final_step"] == 4
    assert np.isfinite(s2["final_metrics"]["loss"])
    assert s2["pipeline"]["schedule"] == "1f1b"
