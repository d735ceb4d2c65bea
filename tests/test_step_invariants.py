"""What a CPU can say about a step, as exact counts.

Six workloads (four train steps through ``train/loop.build``, two serve
engines) and four questions whose answers are the same on every machine:
does steady state compile anything, is a planted retrace counted, what host
work does one step do (through the trainer's own loop and the engine's own
``step``), and which collectives does the step hold. No test here reads a
clock: a time on the CPU is not a speed of the system, and the check for
speed is the benchmark's (``benchmark/run.py``, parent against change on the
chip).
"""

import collections
import dataclasses
import functools
import types

import jax
import pytest

from distributeddeeplearning_tpu import data as datalib
from distributeddeeplearning_tpu.analysis import collectives as ca
from distributeddeeplearning_tpu.config import (
    AllReduceConfig, DataConfig, OptimizerConfig, ParallelConfig,
    PrecisionPolicy, TrainConfig)
from distributeddeeplearning_tpu.models import model_spec, pipeline
from distributeddeeplearning_tpu.observability import telemetry
from distributeddeeplearning_tpu.serve.engine import Engine, ServeConfig
from distributeddeeplearning_tpu.train import loop, steps

# The headline proxy: resnet18_thin, 32 px, batch 8, float32, one device.
DEFAULT = {"model": "resnet18_thin", "image_size": 32, "batch": 8,
           "dtype": "float32", "seed": 0}
TRAIN_WORKLOADS = {
    "default": DEFAULT,
    # The overlapped ZeRO-2 schedule on a dp=2 mesh. 0.5 MB buckets (six of
    # them here) so that "one reduce-scatter a bucket" has buckets to count.
    "zero2_overlap": dict(DEFAULT, dp=2, optimizer_sharding="zero2",
                          bucket_mb=0.5),
    # The large-batch recipe: 2x the batch, bf16 compute over float32
    # masters with dynamic loss scaling, LARS.
    "largebatch_bf16": dict(DEFAULT, batch=16, dtype="bfloat16",
                            precision="mixed", optimizer="lars"),
    # Interleaved 1F1B: 4 layers on 2 stages, 2 virtual chunks a stage.
    "pipeline_1f1b": {"model": "bert_tiny_pp4", "seq_len": 16,
                      "vocab_size": 256, "batch": 8, "dtype": "float32",
                      "seed": 0, "pp": 2, "pipeline_schedule": "1f1b",
                      "pipeline_virtual_stages": 2},
}
SERVE_WORKLOADS = {
    # Every slot live: a step is one decode advance.
    "serve_decode": {"model": "gpt_tiny", "vocab_size": 256,
                     "dtype": "float32", "max_slots": 4, "page_size": 4,
                     "num_pages": 32, "max_pages_per_slot": 8,
                     "prefill_buckets": (8, 16), "seed": 0},
    # The radix prefix cache primed with one shared head of four pages: an
    # admission prefills the two-token tail only.
    "serve_prefix_prefill": {"model": "gpt_tiny", "vocab_size": 256,
                             "dtype": "float32", "max_slots": 4,
                             "page_size": 4, "num_pages": 64,
                             "max_pages_per_slot": 8,
                             "prefill_buckets": (8, 16, 32), "seed": 0,
                             "prefix_cache": True,
                             "shared_prefix_len": 16, "tail_len": 2},
}
# The serve workloads warm up by their own traffic, which never needs the
# 16-token bucket: that one is the planted retrace's.
UNWARMED_BUCKET = 16
WORKLOADS = list(TRAIN_WORKLOADS) + list(SERVE_WORKLOADS)

TOTAL_STEPS = 6   # a trainer's run: the first step compiles, five follow
LOG_EVERY = 2
WARMUP = 2
K = 3             # engine steps looked at after the warm-up


def train_config(name, **over) -> TrainConfig:
    w = TRAIN_WORKLOADS[name]
    if model_spec(w["model"]).input_kind == "tokens":
        data = DataConfig(synthetic=True, seq_len=w["seq_len"],
                          vocab_size=w["vocab_size"])
    else:
        data = DataConfig(synthetic=True, image_size=w["image_size"],
                          num_classes=10)
    kw = dict(
        model=w["model"], backend="cpu", global_batch_size=w["batch"],
        dtype=w["dtype"], seed=w["seed"], log_every=10**9, data=data,
        optimizer_sharding=w.get("optimizer_sharding", "none"),
        pipeline_schedule=w.get("pipeline_schedule", "gpipe"),
        pipeline_virtual_stages=w.get("pipeline_virtual_stages", 1),
        parallel=ParallelConfig(data=w.get("dp", 1),
                                pipeline=w.get("pp", 1)),
        # A plain jit, so that a new shape is a retrace and not an error.
        compile_cache=False)
    if "bucket_mb" in w:
        kw["allreduce"] = AllReduceConfig(bucket_mb=w["bucket_mb"])
    if w.get("precision") == "mixed":
        kw["precision"] = PrecisionPolicy.mixed()
    if "optimizer" in w:
        kw["optimizer"] = OptimizerConfig(name=w["optimizer"],
                                          schedule="constant")
    kw.update(over)
    return TrainConfig(**kw)


def batch_shapes(config, batch_shd):
    """The shapes (with their sharding) of the batches ``config`` trains
    on; no batch is made."""
    spec = model_spec(config.model)
    source = datalib.make_source(config, spec.input_kind, batch_shd,
                                 objective=spec.objective)
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=batch_shd),
        jax.eval_shape(source.batch, 0))


@functools.lru_cache(maxsize=None)
def trainer_run(name, trace_dir):
    """``TOTAL_STEPS`` steps of the workload through the trainer's own loop
    (``loop.run``) with telemetry on, once a process: what the run traced,
    by ``steps.TRACE_COUNTS``, the spans it recorded, and the step it ran
    (what ``loop.build`` handed ``loop.run``, with the shapes of its
    arguments: the run donated the arrays), for reading what that step
    traces and compiles to."""
    config = train_config(name, trace_dir=trace_dir, log_every=LOG_EVERY)
    run = types.SimpleNamespace(config=config)
    build = loop.build

    def build_and_keep(*a, **kw):
        built = build(*a, **kw)
        _mesh, _model, run.batch_shd, state, run.train_step, _sched, rng = (
            built)
        run.state, run.rng = _shapes(state), _shapes(rng)
        return built

    before = collections.Counter(steps.TRACE_COUNTS)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(loop, "build", build_and_keep)
        try:
            loop.run(config, total_steps=TOTAL_STEPS)
        finally:
            telemetry.reset()
    run.traced = collections.Counter(steps.TRACE_COUNTS) - before
    events = telemetry.load_events(
        telemetry.trace_path(trace_dir, jax.process_index()))
    run.spans = [e for e in events if e.get("ph") == "X"]
    run.batch = batch_shapes(config, run.batch_shd)
    return run


def _shapes(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        tree)


@pytest.fixture
def trained(request, tmp_path_factory):
    name = request.getfixturevalue("workload")
    return trainer_run(name, str(tmp_path_factory.getbasetemp() / name))


@functools.lru_cache(maxsize=None)
def engine(name, cache_dir):
    """The workload's engine with its traffic's programs compiled, once a
    process. The compile cache is on and placed at ``cache_dir`` so that
    ``aot_stats()`` counts every program the engine has to build."""
    w = dict(SERVE_WORKLOADS[name])
    head_len, tail_len = w.pop("shared_prefix_len", 0), w.pop("tail_len", 0)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("JAX_COMPILATION_CACHE_DIR", cache_dir)
        eng = Engine(ServeConfig(compile_cache=True, **w))
    eng.head = [1 + i % (w["vocab_size"] - 2) for i in range(head_len)]
    eng.tail_len = tail_len
    if eng.prefix is not None:
        # Prime the tree (one full prefill), then one admission that hits.
        eng.submit(eng.head + [2] * tail_len, max_new_tokens=1)
        eng.run_until_idle()
        admit_on_the_head(eng, 0)
    else:
        fill_every_slot(eng, steps=WARMUP)
    return eng


@pytest.fixture
def eng(request, tmp_path_factory):
    name = request.getfixturevalue("workload")
    return engine(name, str(tmp_path_factory.getbasetemp() / name))


def fill_every_slot(eng, *, steps):
    """One request a slot, long enough to stay live for ``steps`` steps."""
    eng.run_until_idle()
    for s in range(eng.config.max_slots):
        eng.submit([1 + s] * 4, max_new_tokens=steps + 4)
    while eng.waiting:
        eng.step()  # admits and prefills
    assert eng.num_live == eng.config.max_slots


def admit_on_the_head(eng, k):
    """Submit one request that shares the primed head, and step once: it is
    admitted, prefilled and (one new token) retired within the step."""
    tail = [2 + (k + j) % (eng.config.vocab_size - 3)
            for j in range(eng.tail_len)]
    eng.submit(eng.head + tail, max_new_tokens=1)
    eng.step()
    assert eng.idle


def count_calls(monkeypatch, obj, *names):
    """Count the calls of ``obj``'s named methods, and beside each name the
    width of a first array argument (a prefill's bucket)."""
    calls = collections.Counter()
    for name in names:
        real = getattr(obj, name)

        def spy(*a, _real=real, _name=name, **kw):
            calls[_name] += 1
            if a and hasattr(a[0], "shape"):
                calls[f"{_name}:{a[0].shape[-1]}"] += 1
            return _real(*a, **kw)
        monkeypatch.setattr(obj, name, spy)
    return calls


# -- 1. steady state compiles nothing ---------------------------------------

@pytest.mark.parametrize("workload", WORKLOADS)
def test_steady_state_compiles_nothing(workload, request):
    if workload in TRAIN_WORKLOADS:
        # One trace of the train step for the whole run: the five steps
        # after the first compiled nothing.
        run = request.getfixturevalue("trained")
        step_name = ("gspmd_train_step"
                     if "pp" in TRAIN_WORKLOADS[workload] else "dp_train_step")
        assert run.traced == {step_name: 1}
        return
    eng = request.getfixturevalue("eng")
    before = eng.aot_stats()
    assert before["aot_misses"] == before["aot_saves"] >= 2
    if eng.prefix is not None:
        for k in range(K):
            admit_on_the_head(eng, 1 + k)
    else:
        fill_every_slot(eng, steps=K)
        for _ in range(K):
            eng.step()
    assert eng.aot_stats() == before


# -- 2. a planted retrace is counted ----------------------------------------

@pytest.mark.parametrize("workload", WORKLOADS)
def test_planted_retrace_is_counted(workload, request):
    """The self-test: a counter that cannot move is decoration."""
    if workload in TRAIN_WORKLOADS:
        # On the step the trainer's run left behind. Lowering is the
        # tracing half of a call with these arguments (the half the counter
        # counts), without the compile: the run's own shapes trace nothing,
        # a batch of another shape traces once.
        run = request.getfixturevalue("trained")
        twice = dataclasses.replace(
            run.config, global_batch_size=2 * run.config.global_batch_size)
        other = batch_shapes(twice, run.batch_shd)
        before = sum(steps.TRACE_COUNTS.values())
        run.train_step.lower(run.state, run.batch, run.rng)
        assert sum(steps.TRACE_COUNTS.values()) == before
        run.train_step.lower(run.state, other, run.rng)
        assert sum(steps.TRACE_COUNTS.values()) == before + 1
        return
    eng = request.getfixturevalue("eng")
    before = eng.aot_stats()["aot_misses"]
    # A prompt (or, behind the shared head, a tail) of 9 to 16 tokens.
    eng.run_until_idle()
    eng.submit(eng.head + [3] * (UNWARMED_BUCKET - 4), max_new_tokens=1)
    eng.run_until_idle()
    assert eng.aot_stats()["aot_misses"] == before + 1


# -- 3. host work a step -----------------------------------------------------

@pytest.mark.parametrize("workload", WORKLOADS)
def test_host_work_per_step(workload, request, monkeypatch):
    if workload in TRAIN_WORKLOADS:
        # Through ``loop.run``: under its phase clock ``_run_inner`` records
        # one ``data_wait`` and one ``dispatch`` a step, and a
        # ``fetch_barrier`` on the log cadence only.
        spans = request.getfixturevalue("trained").spans
        names = collections.Counter(e["name"] for e in spans)
        assert names["data_wait"] == names["dispatch"] == TOTAL_STEPS
        assert names["fetch_barrier"] == TOTAL_STEPS // LOG_EVERY
        for phase in ("data_wait", "dispatch"):
            assert sorted(e["args"]["step"] for e in spans
                          if e["name"] == phase) == list(range(TOTAL_STEPS))
        return
    eng = request.getfixturevalue("eng")
    if eng.prefix is None:
        # A step that admits nothing: one decode dispatch, no prefill.
        fill_every_slot(eng, steps=K)
        calls = count_calls(monkeypatch, eng, "_decode_step", "_run_prefill",
                            "_run_block_prefill")
        for _ in range(K):
            eng.step()
        assert calls == {"_decode_step": K}
        return
    # An admission that hits the primed head: the tree hands over the
    # head's pages, the two-token tail alone is prefilled (its bucket: 8),
    # at most one page is cloned, and every page comes back on retire.
    hits, misses = eng.prefix_hits, eng.prefix_misses
    reused, copies = eng.prefix_tokens_reused, eng.cow_copies
    in_use = eng.allocator.pages_in_use
    calls = count_calls(monkeypatch, eng, "_run_block_prefill",
                        "_run_prefill", "_decode_step")
    for k in range(K):
        admit_on_the_head(eng, 10 + k)
    assert (eng.prefix_hits, eng.prefix_misses) == (hits + K, misses)
    assert calls == {"_run_block_prefill": K, "_run_block_prefill:8": K}
    assert eng.prefix_tokens_reused - reused == K * len(eng.head)
    assert eng.cow_copies - copies <= K
    assert eng.allocator.pages_in_use == in_use


# -- 4. the collectives a step holds ----------------------------------------

@pytest.mark.parametrize("workload", ["zero2_overlap", "pipeline_1f1b"])
def test_collectives_per_step(workload, trained):
    {"zero2_overlap": _zero2_overlap_collectives,
     "pipeline_1f1b": _pipeline_1f1b_collectives}[workload](trained)


def _zero2_overlap_collectives(b):
    """The traced step holds the bucket planner's promise: one
    reduce-scatter a bucket, issued in backward (so bucket order reversed
    is fine, the count is not), and one all-gather a bucket after the
    update; nothing else but the ``psum`` of metrics and BatchNorm
    statistics in between."""
    buckets = len(b.train_step.zero_layout.plan.buckets)
    assert buckets > 1
    sched = ca.schedule_of(b.train_step.raw_step, b.state, b.batch, b.rng)
    assert sched.errors == ()
    kinds = [op.kind for op in sched.ops if op.kind != "psum"]
    assert kinds == (["reduce_scatter"] * buckets
                     + ["all_gather"] * buckets), sched.describe()


def _pipeline_1f1b_collectives(b):
    """The compiled step moves activations between stages along the
    schedule table's pairs and no others. GSPMD's partitioner makes the
    collective-permutes and XLA drops the edges nothing reads, so the
    count a tick is the compiler's; the forms are the table's: every form
    ``shift_pairs`` has, or its transpose (the backward pass), appears
    among the activation-shaped permutes, and nothing else does. The only
    other collectives are the all-gathers and the one-way permutes that
    lay the stage-stacked parameters out by chunk."""
    w = TRAIN_WORKLOADS["pipeline_1f1b"]
    microbatches = model_spec(w["model"]).build(
        vocab_size=w["vocab_size"]).cfg.pipeline_microbatches
    table = pipeline.build_schedule(
        w["pipeline_schedule"], num_stages=w["pp"],
        num_microbatches=microbatches,
        virtual_stages=w["pipeline_virtual_stages"])
    forms = {tuple(sorted(table.shift_pairs(t.index))) for t in table.ticks}
    forms |= {tuple(sorted((d, s) for s, d in f)) for f in forms}
    sched = ca.extract_from_hlo_text(
        b.train_step.lower(b.state, b.batch, b.rng).compile().as_text())
    assert sched.errors == ()
    assert {op.kind for op in sched.ops} == {"collective-permute",
                                             "all-gather"}
    # One stage's slice of the (stages, microbatch, seq, hidden) state.
    activation = (1, w["batch"] // microbatches, w["seq_len"])
    seen = set()
    for op in sched.ops:
        if op.kind != "collective-permute":
            continue
        pairs = tuple(sorted(tuple(p) for p in op.pairs))
        if op.shape[:3] == activation:
            seen.add(pairs)
        else:
            assert pairs == ((0, 1),), op.describe()
    assert seen == forms, (seen, forms)
