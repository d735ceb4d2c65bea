"""The phase log (observability/telemetry.py): one-off set-up phases and
JAX's own compile stages, recorded always, on the clock the benchmark's host
spans read, and by the program sites that replaced their ad-hoc timings."""

import collections
import time

import jax
import jax.numpy as jnp
import pytest

from distributeddeeplearning_tpu.config import TrainConfig
from distributeddeeplearning_tpu.observability import telemetry
from distributeddeeplearning_tpu.perf import aot, compile_cache
from distributeddeeplearning_tpu.train import steps


@pytest.fixture(autouse=True)
def _empty_log():
    telemetry.clear_phases()
    yield
    telemetry.reset()


def named(name, fun=None):
    return [r for r in telemetry.phases()
            if r.name == name and (fun is None or fun in r.args.get("fun"))]


def test_phase_records_with_telemetry_off_and_emits_with_it_on():
    telemetry.reset()
    with telemetry.phase("aot_save", program="p"):
        pass
    (rec,) = telemetry.phases()
    assert rec.name == "aot_save" and rec.end_s >= rec.start_s
    assert rec.args == {"program": "p"}
    assert telemetry.get().snapshot() == []

    tele = telemetry.configure(enabled=True)
    with telemetry.phase("compile", program="q"):
        pass
    (event,) = tele.snapshot()
    assert (event["name"], event["ph"]) == ("compile", "X")
    assert event["args"] == {"program": "q"}
    last = telemetry.phases()[-1]
    assert event["ts"] == int(last.start_s * 1e6)


def test_phase_as_decorator_and_on_error():
    @telemetry.phase("build")
    def build(x):
        return x + 1

    assert build(1) == 2 and build(2) == 3
    assert [r.name for r in telemetry.phases()] == ["build", "build"]
    with pytest.raises(ValueError):
        with telemetry.phase("aot_load"):
            raise ValueError("corrupt")
    assert telemetry.phases()[-1].args == {"error": "ValueError"}


def test_now_s_is_the_clock_of_perf_counter():
    """Phases read now_s(); the benchmark's host spans read perf_counter.
    On Linux both are CLOCK_MONOTONIC, so the two lie on one axis."""
    gaps = []
    for _ in range(5):
        a = telemetry.now_s()
        b = time.perf_counter()
        c = telemetry.now_s()
        gaps.append(abs(b - (a + c) / 2))
    assert min(gaps) < 1e-3


def test_a_log_that_dropped_records_reads_none(monkeypatch):
    monkeypatch.setattr(telemetry, "_phase_log",
                        collections.deque(maxlen=2))
    for name in ("a", "b"):
        with telemetry.phase(name):
            pass
    assert [r.name for r in telemetry.phases()] == ["a", "b"]
    with telemetry.phase("c"):
        pass
    assert telemetry.phases() is None
    telemetry.clear_phases()
    assert telemetry.phases() == []


def test_jit_records_trace_lower_and_one_compile_once():
    compile_cache.activate(False)
    telemetry.watch_compiles()
    telemetry.watch_compiles()
    from jax._src import monitoring
    assert monitoring.get_event_duration_listeners().count(
        telemetry._on_jax_duration) == 1
    assert telemetry.watching_compiles()

    def fresh_program_a(x):
        return jnp.sin(x) * 3.0

    f = jax.jit(fresh_program_a)
    f(jnp.ones(7)).block_until_ready()
    assert len(named("trace", "fresh_program_a")) == 1
    assert len(named("lower", "fresh_program_a")) == 1
    assert len(named("xla_compile", "fresh_program_a")) == 1
    assert not named("cache_load", "fresh_program_a")
    for rec in telemetry.phases():
        assert rec.end_s >= rec.start_s
    before = len(telemetry.phases())
    f(jnp.ones(7)).block_until_ready()
    assert len(telemetry.phases()) == before


def test_a_warm_persistent_cache_gives_a_cache_load(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    compile_cache.activate()

    def fresh_program_b(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((16, 16))
    jax.jit(fresh_program_b).lower(x).compile()
    assert len(named("xla_compile", "fresh_program_b")) == 1
    jax.clear_caches()  # as a new process starts: no program in memory
    jax.jit(fresh_program_b).lower(x).compile()
    assert len(named("xla_compile", "fresh_program_b")) == 1
    (load,) = named("cache_load", "fresh_program_b")
    assert 0 <= load.end_s - load.start_s


def test_aot_acquire_miss_and_hit(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    compile_cache.activate(False)  # the AOT layer alone
    one = jax.devices()[:1]
    x = jnp.arange(4.0)

    def doubled(x):
        return x * 2.0

    cfg = TrainConfig(model="gpt_tiny")
    cold = aot.StepExecutableCache.for_config(cfg, one, total_steps=4)
    steps._aot_acquire(cold, "double", jax.jit(doubled), (x,))
    assert [r.name for r in telemetry.phases()
            if r.name in ("aot_load", "compile", "aot_save")] == \
        ["compile", "aot_save"]
    assert named("compile")[0].args == {"program": "double"}
    assert len(named("xla_compile", "doubled")) == 1

    telemetry.clear_phases()
    warm = aot.StepExecutableCache.for_config(cfg, one, total_steps=4)
    fn = steps._aot_acquire(warm, "double", jax.jit(doubled), (x,))
    assert [r.name for r in telemetry.phases()] == ["aot_load"]
    assert named("aot_load")[0].args == {"program": "double"}
    assert fn(x).tolist() == [0.0, 2.0, 4.0, 6.0]


def test_the_disabled_span_is_still_the_shared_noop():
    telemetry.reset()
    assert telemetry.get().span("x") is telemetry._NULL_SPAN
    with telemetry.phase("build"):
        pass
    assert telemetry.get().snapshot() == []
