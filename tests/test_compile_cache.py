"""Persistent compile cache + AOT step executables (docs/compile_cache.md).

Three layers under test:

- policy (perf/compile_cache.py): the cache is placed from outside
  ($JAX_COMPILATION_CACHE_DIR, else the repo default) and the variable is
  never rewritten; off switch, stats sidecar, age-based prune;
- fingerprint (perf/aot.py): equal configs -> equal keys, volatile host
  knobs never perturb the key, program-shaping fields and jax upgrades
  always do, and attempt-scoped faults expire out of the hash;
- warm restart: a second attempt through ``launch.run_with_restarts``
  loads the serialized executable and performs ZERO retraces of the train
  step (probed via ``steps.TRACE_COUNTS``), end-to-end through
  ``loop.run`` with the summary/logger cold-start fields.
"""

from __future__ import annotations

import io
import json
import os
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest

from distributeddeeplearning_tpu import launch
from distributeddeeplearning_tpu.config import (
    DataConfig, OptimizerConfig, ParallelConfig, TrainConfig)
from distributeddeeplearning_tpu.perf import aot, compile_cache
from distributeddeeplearning_tpu.robustness import faults


def _cfg(**kw):
    base = dict(
        model="resnet18_thin", global_batch_size=16, dtype="float32",
        log_every=10**9,
        parallel=ParallelConfig(data=8),
        data=DataConfig(synthetic=True, image_size=8, num_classes=10),
        optimizer=OptimizerConfig(name="sgd", learning_rate=0.1,
                                  reference_batch=16, momentum=0.9,
                                  schedule="constant", warmup_epochs=0.0))
    base.update(kw)
    return TrainConfig(**base)


# ---------------------------------------------------------------------------
# Cache-dir policy
# ---------------------------------------------------------------------------

@pytest.mark.core
def test_cache_dir_is_placed_from_outside(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.cache_dir() == compile_cache.default_dir()
    assert compile_cache.default_dir().endswith(
        os.path.join(".cache", "jax_compile"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    assert compile_cache.cache_dir() == str(tmp_path / "env")
    # the one switch a run has: off
    assert compile_cache.cache_dir(enabled=False) is None


@pytest.mark.core
@pytest.mark.parametrize("placed", [True, False])
def test_activate_never_touches_the_variable(monkeypatch, tmp_path, placed):
    """Set => jax's cache and aot/ land there and the variable is left as
    it was; unset => the repo default, and the variable stays unset."""
    want = str(tmp_path / "placed")
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = compile_cache.default_dir()
    before = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.activate() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert jax.config.jax_enable_compilation_cache
    handle = aot.StepExecutableCache.for_config(_cfg(), jax.devices(),
                                                total_steps=4)
    assert handle.dir == os.path.join(want, compile_cache.AOT_SUBDIR)
    assert os.environ.get("JAX_COMPILATION_CACHE_DIR") == before
    assert "DDL_COMPILE_CACHE" not in os.environ


@pytest.mark.core
def test_activate_off_disables_both_layers(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.activate(False) is None
    assert not jax.config.jax_enable_compilation_cache
    handle = aot.StepExecutableCache.for_config(
        _cfg(compile_cache=False), jax.devices(), total_steps=4)
    assert not handle.enabled
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)


@pytest.mark.core
def test_stats_sidecar_and_prune(tmp_path):
    cache = str(tmp_path)
    compile_cache.write_stats(cache, {"aot_hits": 3, "aot_misses": 1})
    stats = compile_cache.read_stats(cache)
    assert stats["aot_hits"] == 3 and "updated_at" in stats

    old = tmp_path / "stale.bin"
    new = tmp_path / "aot" / "fresh.aotx"
    new.parent.mkdir()
    old.write_bytes(b"x" * 10)
    new.write_bytes(b"y" * 20)
    past = time.time() - 40 * 86400
    os.utime(old, (past, past))
    removed, kept = compile_cache.prune(cache, max_age_days=30.0)
    assert (removed, kept) == (1, 1)
    assert not old.exists() and new.exists()
    # the stats sidecar is bookkeeping, never a prunable entry
    assert compile_cache.read_stats(cache)["aot_hits"] == 3
    info = compile_cache.summarize(cache)
    assert info["entries"] == 0 and info["aot_entries"] == 1
    assert info["total_bytes"] == 20


# ---------------------------------------------------------------------------
# Config fingerprint stability
# ---------------------------------------------------------------------------

@pytest.mark.core
def test_equal_configs_equal_keys(monkeypatch):
    monkeypatch.delenv(faults.ENV_PLAN, raising=False)
    monkeypatch.delenv(faults.ENV_ATTEMPT, raising=False)
    a = aot.config_fingerprint(_cfg(), total_steps=10)
    b = aot.config_fingerprint(_cfg(), total_steps=10)
    assert a == b


@pytest.mark.core
def test_volatile_fields_do_not_change_key(monkeypatch, tmp_path):
    monkeypatch.delenv(faults.ENV_PLAN, raising=False)
    monkeypatch.delenv(faults.ENV_ATTEMPT, raising=False)
    base = aot.config_fingerprint(_cfg(), total_steps=10)
    for kw in (dict(trace_dir=str(tmp_path / "tr")),
               dict(checkpoint_dir=str(tmp_path / "ck"),
                    checkpoint_every_steps=2),
               dict(log_every=1),
               dict(straggler_threshold=9.9),
               dict(compile_cache=False),
               # host-side process faults (crash/sigterm) never reach the
               # compiled program — only nan_grads does (tested below)
               dict(fault_plan="crash@3,sigterm@5")):
        assert aot.config_fingerprint(_cfg(**kw), total_steps=10) == base, kw
    # host data-pipeline knobs leave batch shapes alone
    wide = _cfg(data=DataConfig(synthetic=True, image_size=8, num_classes=10,
                                prefetch_depth=7))
    assert aot.config_fingerprint(wide, total_steps=10) == base


@pytest.mark.core
def test_program_shaping_fields_change_key(monkeypatch):
    monkeypatch.delenv(faults.ENV_PLAN, raising=False)
    monkeypatch.delenv(faults.ENV_ATTEMPT, raising=False)
    base = aot.config_fingerprint(_cfg(), total_steps=10)
    assert aot.config_fingerprint(_cfg(model="resnet18"),
                                  total_steps=10) != base
    assert aot.config_fingerprint(_cfg(global_batch_size=32),
                                  total_steps=10) != base
    assert aot.config_fingerprint(_cfg(dtype="bfloat16"),
                                  total_steps=10) != base
    # the LR schedule bakes the horizon into the update computation
    assert aot.config_fingerprint(_cfg(), total_steps=20) != base


@pytest.mark.core
def test_nan_grad_plan_shapes_program_but_expires_per_attempt(monkeypatch):
    """nan_grads compiles injection ops + the bad-step guard into the step,
    so it must change the key — but only on the attempt it fires on. The
    default scope is attempt 0, so the restart attempt's fingerprint equals
    a clean run's and reuses its executable (the warm-restart fast path)."""
    monkeypatch.delenv(faults.ENV_PLAN, raising=False)
    monkeypatch.delenv(faults.ENV_ATTEMPT, raising=False)
    clean = aot.config_fingerprint(_cfg(), total_steps=10)
    faulted = _cfg(fault_plan="nan_grads@3")
    assert aot.config_fingerprint(faulted, total_steps=10) != clean
    monkeypatch.setenv(faults.ENV_ATTEMPT, "1")  # fault expired
    assert aot.config_fingerprint(faulted, total_steps=10) == clean


@pytest.mark.core
def test_jax_version_changes_key(monkeypatch):
    monkeypatch.delenv(faults.ENV_PLAN, raising=False)
    monkeypatch.delenv(faults.ENV_ATTEMPT, raising=False)
    base = aot.config_fingerprint(_cfg(), total_steps=10)
    monkeypatch.setattr(jax, "__version__", "99.0.0")
    assert aot.config_fingerprint(_cfg(), total_steps=10) != base


@pytest.mark.core
def test_source_digest_is_part_of_the_key(monkeypatch):
    """The config says which program was asked for, the package's source
    which program that is: the same tree gives the same key twice, another
    tree's digest another key (train and serve), and its payload is refused
    at load even under an equal key."""
    from distributeddeeplearning_tpu.serve.engine import (ServeConfig,
                                                          serve_fingerprint)
    monkeypatch.delenv(faults.ENV_PLAN, raising=False)
    monkeypatch.delenv(faults.ENV_ATTEMPT, raising=False)
    base = aot.config_fingerprint(_cfg(), total_steps=10)
    serve = serve_fingerprint(ServeConfig(model="gpt_tiny"))
    assert aot.config_fingerprint(_cfg(), total_steps=10) == base
    assert aot.source_digest() == aot.source_digest()
    assert aot.versions()["source"] == aot.source_digest()
    monkeypatch.setattr(aot, "source_digest", lambda: "another-tree")
    assert aot.config_fingerprint(_cfg(), total_steps=10) != base
    assert serve_fingerprint(ServeConfig(model="gpt_tiny")) != serve


def test_source_digest_reads_the_package_files(tmp_path, monkeypatch):
    """The digest covers every .py file under the package by path and
    content, and nothing else."""
    pkg = tmp_path / "pkg"
    (pkg / "perf").mkdir(parents=True)
    (pkg / "perf" / "aot.py").write_text("a = 1\n")
    (pkg / "steps.py").write_text("b = 2\n")
    (pkg / "notes.txt").write_text("not source\n")
    monkeypatch.setattr(aot, "__file__", str(pkg / "perf" / "aot.py"))

    def digest():
        aot.source_digest.cache_clear()
        return aot.source_digest()

    try:
        first = digest()
        assert digest() == first
        (pkg / "notes.txt").write_text("changed\n")
        assert digest() == first
        (pkg / "steps.py").write_text("b = 3\n")
        changed = digest()
        assert changed != first
        (pkg / "steps.py").rename(pkg / "steps2.py")
        assert digest() not in (first, changed)
    finally:
        monkeypatch.undo()
        aot.source_digest.cache_clear()


@pytest.mark.usefixtures("devices8")
def test_entry_saved_by_another_tree_is_not_loaded(tmp_path, monkeypatch):
    """Even handed the same key, a payload whose `versions` name another
    source digest is a miss, deleted and recompiled cold; and `save` leaves
    the step's anatomy table beside the entry."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    one = jax.devices()[:1]
    x = jnp.arange(4.0)

    def scaled(x):
        with jax.named_scope("optimizer"):
            return x * 2.0

    compiled = jax.jit(scaled).lower(x).compile()
    cache = aot.StepExecutableCache.for_config(_cfg(), one, total_steps=4)
    key = cache.key("double", (x,))
    assert cache.save("double", key, compiled)
    table = aot.anatomy("double")
    assert table and all("optimizer" in v for v in table.values())
    with open(os.path.join(str(tmp_path), compile_cache.AOT_SUBDIR,
                           f"{key}.anatomy.json")) as fh:
        assert json.load(fh) == table
    again = aot.StepExecutableCache.for_config(_cfg(), one, total_steps=4)
    assert again.key("double", (x,)) == key
    assert again.load("double", key) is not None
    monkeypatch.setattr(aot, "source_digest", lambda: "another-tree")
    other = aot.StepExecutableCache.for_config(_cfg(), one, total_steps=4)
    assert other.key("double", (x,)) != key
    assert other.load("double", key) is None and other.failures == 1


def test_an_entrys_compile_never_gets_another_trees_names(
        tmp_path, monkeypatch):
    """Two programs that differ only in a scope name are one program to
    JAX's persistent cache (metadata is not in its key), so the second
    would come back with the first's names in it. `aot.compile_lowered`
    keys that one compile on metadata, and puts the flag back."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    compile_cache.activate()
    flag = "jax_compilation_cache_include_metadata_in_key"
    assert getattr(jax.config, flag) is False

    def program(scope):
        def fn(x):
            with jax.named_scope(scope):
                return jnp.tanh(x @ x).sum()
        return jax.jit(fn).lower(jnp.ones((64, 64)))

    assert "/lossA/" in program("lossA").compile().as_text()
    stale = program("lossB").compile().as_text()
    assert "/lossA/" in stale and "/lossB/" not in stale  # the hazard
    assert "/lossB/" in aot.compile_lowered(program("lossB")).as_text()
    assert "/lossC/" in aot.compile_lowered(program("lossC")).as_text()
    assert getattr(jax.config, flag) is False


# ---------------------------------------------------------------------------
# Warm restart: zero retraces through run_with_restarts
# ---------------------------------------------------------------------------

class _TinyNet(nn.Module):
    @nn.compact
    def __call__(self, x, train=False):
        x = x.reshape((x.shape[0], -1))
        return nn.Dense(10)(nn.relu(nn.Dense(16)(x)))


@pytest.mark.usefixtures("devices8")
@pytest.mark.core
def test_restart_attempt_hits_aot_cache_zero_retraces(tmp_path, monkeypatch):
    """Attempt 0 cold-compiles the DP train step and serializes it; the
    restarted attempt (same config, fresh jit function) must load that
    executable without tracing at all — the TRACE_COUNTS probe increments
    only while jax runs the step's Python body, i.e. per (re)trace."""
    from distributeddeeplearning_tpu.parallel import mesh as meshlib
    from distributeddeeplearning_tpu.train import optim, steps
    from distributeddeeplearning_tpu.train.state import TrainState

    monkeypatch.delenv(faults.ENV_PLAN, raising=False)
    monkeypatch.delenv(faults.ENV_ATTEMPT, raising=False)
    cfg = _cfg()
    cache = str(tmp_path / "cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
    batch = {
        "image": jax.random.normal(jax.random.key(2), (16, 8, 8, 3)),
        "label": jax.random.randint(jax.random.key(3), (16,), 0, 10),
    }
    rng = jax.random.key(1)
    traces, sources = [], []

    def run_once():
        # Fresh build per attempt, exactly like a relaunched process: new
        # jit function, new cache handle — only the disk entry is shared.
        mesh = meshlib.make_mesh(cfg.parallel)
        cache_handle = aot.StepExecutableCache.for_config(
            cfg, mesh.devices.flat, total_steps=4)
        model = _TinyNet()
        tx, _ = optim.make_optimizer(cfg.optimizer, cfg.global_batch_size,
                                     4, None)
        variables = model.init({"params": jax.random.key(0)},
                               jnp.zeros((1, 8, 8, 3)), train=False)
        state = TrainState.create(params=variables["params"],
                                  opt_state=tx.init(variables["params"]),
                                  batch_stats=None)
        step = steps.make_dp_train_step(model, tx, mesh, cfg,
                                        aot=cache_handle)
        before = steps.TRACE_COUNTS["dp_train_step"]
        _, metrics = step(state, batch, rng)
        jax.device_get(metrics)  # execution barrier
        traces.append(steps.TRACE_COUNTS["dp_train_step"] - before)
        sources.append(cache_handle.sources["dp_train_step"])
        cache_handle.flush_stats()
        return 1 if len(traces) == 1 else 0  # attempt 0 "crashes"

    rc = launch.run_with_restarts(run_once, 1, sleep=lambda s: None)
    assert rc == 0
    assert traces == [1, 0]  # cold trace once, warm restart retraces NEVER
    assert sources == ["compiled", "aot_hit"]
    # the stats sidecar (last writer = the warm attempt) records the hit
    stats = compile_cache.read_stats(cache)
    assert stats["aot_hits"] == 1 and stats["aot_saves"] == 0


@pytest.mark.usefixtures("devices8")
def test_loop_warm_start_summary_and_zero_retrace(tmp_path, monkeypatch):
    """End-to-end through loop.run: run 1 cold-compiles (summary +
    MetricLogger carry compile_time_s / time_to_first_step_s, the AOT
    entry is saved, the eval step warm-compiles on a thread); run 2 of the
    identical config loads the executable — zero retraces of the train
    step and sources=aot_hit in the summary."""
    from distributeddeeplearning_tpu.train import loop, steps
    from distributeddeeplearning_tpu.utils.logging import MetricLogger

    cache = str(tmp_path / "cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
    monkeypatch.delenv(faults.ENV_PLAN, raising=False)
    monkeypatch.delenv(faults.ENV_ATTEMPT, raising=False)
    cfg = _cfg(log_every=1)
    stream = io.StringIO()
    s1 = loop.run(cfg, total_steps=2, eval_batches=1,
                  logger=MetricLogger(stream=stream, enabled=True))
    assert s1["compile_time_s"] > 0
    assert s1["time_to_first_step_s"] >= s1["compile_time_s"]
    cc = s1["compile_cache"]
    assert cc["sources"]["dp_train_step"] == "compiled"
    assert cc["aot_saves"] >= 1
    first = json.loads(stream.getvalue().splitlines()[0])
    assert first["compile_time_s"] > 0
    assert first["time_to_first_step_s"] > 0

    before = steps.TRACE_COUNTS["dp_train_step"]
    s2 = loop.run(cfg, total_steps=2, eval_batches=1,
                  logger=MetricLogger(enabled=False))
    assert steps.TRACE_COUNTS["dp_train_step"] == before  # ZERO retraces
    assert s2["compile_cache"]["sources"]["dp_train_step"] == "aot_hit"
    assert s2["compile_cache"]["aot_hits"] >= 1
    assert s2["compile_time_s"] < s1["compile_time_s"]
    # both runs trained the same program: identical final loss
    assert s1["final_metrics"]["loss"] == s2["final_metrics"]["loss"]


@pytest.mark.usefixtures("devices8")
def test_warm_resume_with_checkpointing_is_donation_safe(tmp_path, monkeypatch):
    """The warm-RESTART path with checkpointing live — the one combination
    that corrupted the heap before loop.run learned to device-copy restored
    state: orbax-restored arrays can alias host memory the restore machinery
    owns (zero-copy device_put on CPU), and a directly-called deserialized
    executable donates its inputs unconditionally, where jit would refuse.
    Attempt 0 cold-compiles, saves every step, and crashes mid-run; the
    resumed attempt restores the checkpoint, loads the serialized executable
    (zero retraces), checkpoint-saves while donating, and must land on the
    EXACT final loss of an uninterrupted run. A regression here tends to die
    of SIGSEGV/SIGABRT rather than assert — that is the bug."""
    from distributeddeeplearning_tpu.train import loop, steps
    from distributeddeeplearning_tpu.utils.logging import MetricLogger

    cache = str(tmp_path / "cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
    monkeypatch.delenv(faults.ENV_PLAN, raising=False)
    monkeypatch.delenv(faults.ENV_ATTEMPT, raising=False)
    kw = dict(checkpoint_every_steps=1)
    # Uninterrupted reference: cold-compiles and populates the cache.
    ref = loop.run(_cfg(checkpoint_dir=str(tmp_path / "ck_ref"), **kw),
                   total_steps=4, eval_batches=0,
                   logger=MetricLogger(enabled=False))
    assert ref["compile_cache"]["sources"]["dp_train_step"] == "compiled"

    # Attempt 0: warm, saves at 1 and 2, then the injected crash.
    faulted = _cfg(checkpoint_dir=str(tmp_path / "ck"),
                   fault_plan="crash@2", **kw)
    with pytest.raises(SystemExit):
        loop.run(faulted, total_steps=4, eval_batches=0,
                 logger=MetricLogger(enabled=False))

    # The restart: crash@2 is attempt-0-scoped, so the fingerprint
    # matches the clean one and the serialized executable is reused on
    # the restored state — restore, AOT-hit donating dispatches, and
    # async saves all interleaved.
    monkeypatch.setenv(faults.ENV_ATTEMPT, "1")
    before = steps.TRACE_COUNTS["dp_train_step"]
    s = loop.run(faulted, total_steps=4, eval_batches=0,
                 logger=MetricLogger(enabled=False))
    assert steps.TRACE_COUNTS["dp_train_step"] == before
    assert s["compile_cache"]["sources"]["dp_train_step"] == "aot_hit"
    assert s["start_step"] == 2 and s["final_step"] == 4
    # Recovery is bitwise: kill + restore + warm executable fully erased.
    assert s["final_metrics"]["loss"] == ref["final_metrics"]["loss"]


# ---------------------------------------------------------------------------
# Executables load onto the devices they were compiled for
# ---------------------------------------------------------------------------

@pytest.mark.usefixtures("devices8")
def test_aot_entry_is_keyed_by_and_loaded_onto_its_devices(tmp_path,
                                                           monkeypatch):
    """On a host with several devices a one-device program must come back
    as a one-device program ON ITS device: the key separates device 0's
    entry from device 2's, and the warm executable dispatches (it used to
    load onto all 8 and die at the first call)."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    devs = jax.devices()
    fn = jax.jit(lambda x: x * 2.0)
    keys = {}
    for d in (devs[0], devs[2]):
        x = jax.device_put(jnp.arange(4.0), d)
        cold = aot.StepExecutableCache.for_config(_cfg(), [d], total_steps=4)
        keys[d.id] = key = cold.key("double", (x,))
        assert cold.load("double", key) is None
        assert cold.save("double", key, fn.lower(x).compile())
        warm = aot.StepExecutableCache.for_config(_cfg(), [d], total_steps=4)
        loaded = warm.load("double", key)
        assert loaded is not None and warm.hits == 1
        out = loaded(x)  # dispatches — not merely deserializes
        assert out.devices() == {d}
        assert out.tolist() == [0.0, 2.0, 4.0, 6.0]
    assert keys[devs[0].id] != keys[devs[2].id]


# ---------------------------------------------------------------------------
# Donation backstop (the runtime form of analysis/donation.py's invariant)
# ---------------------------------------------------------------------------

def test_donation_signature_parses_alias_header():
    class Fake:
        def as_text(self):
            return ("HloModule m, input_output_alias={ {0}: (0, {}, "
                    "may-alias) }\n\nENTRY %main () -> f32[] {\n}\n")

    assert aot.donation_signature(Fake()) == "{{0}:(0,{},may-alias)}"

    class NoAlias:
        def as_text(self):
            return "HloModule m\n"

    assert aot.donation_signature(NoAlias()) is None

    class Broken:
        def as_text(self):
            raise RuntimeError("boom")

    assert aot.donation_signature(Broken()) is None


@pytest.mark.usefixtures("devices8")
def test_aot_load_rejects_drifted_donation_set(tmp_path, monkeypatch):
    """A cached executable whose input_output_alias no longer matches the
    one recorded at save time could donate buffers the caller still
    aliases (the PR 5 bug class, through the cache): the entry must be
    deleted and recompiled cold, never dispatched. CPU executables carry
    no alias header, so the signature probe is patched to simulate the
    TPU donation sets."""
    cache = str(tmp_path / "cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
    one = jax.devices()[:1]
    handle = aot.StepExecutableCache.for_config(_cfg(), one, total_steps=4)
    args = (jnp.ones((4,)), jnp.ones((4,)))
    compiled = jax.jit(lambda x, y: x + y).lower(*args).compile()
    key = handle.key("step", args)

    monkeypatch.setattr(aot, "donation_signature", lambda _: "{{0}:(0,{})}")
    assert handle.save("step", key, compiled)

    # Unchanged donation set: a hit.
    warm = aot.StepExecutableCache.for_config(_cfg(), one, total_steps=4)
    assert warm.load("step", key) is not None
    assert warm.hits == 1 and warm.failures == 0

    # Drifted donation set: deleted + cold fallback.
    monkeypatch.setattr(aot, "donation_signature", lambda _: "{{1}:(0,{})}")
    drifted = aot.StepExecutableCache.for_config(_cfg(), one, total_steps=4)
    assert drifted.load("step", key) is None
    assert drifted.failures == 1 and drifted.hits == 0
    assert not os.path.exists(os.path.join(
        cache, compile_cache.AOT_SUBDIR, f"{key}.aotx"))

    # Payloads with no recorded signature (pre-backstop entries, or a
    # backend whose text lacks the header) are tolerated: absence of
    # evidence is not a mismatch.
    monkeypatch.setattr(aot, "donation_signature", lambda _: None)
    assert handle.save("step", key, compiled)
    monkeypatch.setattr(aot, "donation_signature", lambda _: "{{0}:(0,{})}")
    legacy = aot.StepExecutableCache.for_config(_cfg(), one, total_steps=4)
    assert legacy.load("step", key) is not None
    assert legacy.failures == 0
