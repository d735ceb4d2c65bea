"""Chaos harness (robustness/faults.py + hardened recovery, SURVEY.md §5.3).

Fast tier: the fault-plan grammar, the fail_at_step shim, checkpoint
corruption mechanics, the loader watchdog, and the launcher's backoff /
restart-budget / attribution logic — all unit-level, no XLA compiles.

Slow tier: the compiled bad-step guard (NaN grads skip the update), the
consecutive-bad-step abort, corrupt-checkpoint quarantine + fallback, the
forced preemption save on an already-saved step, and the capstone chaos
soak — kill + corrupted checkpoint + NaN step through ``run_with_restarts``
ending BITWISE-identical to a fault-free run.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from distributeddeeplearning_tpu import launch
from distributeddeeplearning_tpu.observability import health
from distributeddeeplearning_tpu.robustness import faults


# ---------------------------------------------------------------------------
# Plan grammar + resolution
# ---------------------------------------------------------------------------

@pytest.mark.core
def test_parse_plan_grammar():
    plan = faults.parse_plan(
        "sigkill@6, corrupt_latest_ckpt@6,nan_grads@5,"
        "loader_stall@4:2.5s,crash@3:always,sigterm@7:a1")
    kinds = [(f.kind, f.step) for f in plan]
    assert kinds == [("sigkill", 6), ("corrupt_latest_ckpt", 6),
                     ("nan_grads", 5), ("loader_stall", 4),
                     ("crash", 3), ("sigterm", 7)]
    assert plan[3].seconds == 2.5
    assert plan[4].attempt == faults.ALWAYS
    assert plan[5].attempt == 1
    assert plan[0].attempt == 0  # default: first attempt only


@pytest.mark.core
@pytest.mark.parametrize("bad", [
    "explode@3",          # unknown kind
    "sigkill",            # no @step
    "sigkill@x",          # non-integer step
    "sigkill@0",          # non-positive step
    "sigkill@3:b2",       # unknown qualifier
    "loader_stall@3:-1s",  # negative stall
])
def test_parse_plan_rejects(bad):
    with pytest.raises(ValueError):
        faults.parse_plan(bad)


class _Cfg:
    """Duck-typed config stub for resolve()."""

    def __init__(self, fault_plan=None, fail_at_step=None):
        self.fault_plan = fault_plan
        self.fail_at_step = fail_at_step


@pytest.mark.core
def test_resolve_merges_and_scopes_by_attempt(monkeypatch):
    monkeypatch.delenv(faults.ENV_PLAN, raising=False)
    monkeypatch.delenv(faults.ENV_ATTEMPT, raising=False)
    plan = faults.resolve(_Cfg(fault_plan="nan_grads@5,sigterm@7:a1",
                               fail_at_step=3))
    kinds = {(f.kind, f.step) for f in plan.faults}
    # attempt-0 process: the a1 sigterm is filtered out; the fail_at_step
    # shim (crash@3:always) is in.
    assert kinds == {("nan_grads", 5), ("crash", 3)}
    assert plan.nan_grad_steps() == (4,)  # state.step space: N-1

    monkeypatch.setenv(faults.ENV_ATTEMPT, "1")
    plan1 = faults.resolve(_Cfg(fault_plan="nan_grads@5,sigterm@7:a1",
                                fail_at_step=3))
    kinds1 = {(f.kind, f.step) for f in plan1.faults}
    assert kinds1 == {("sigterm", 7), ("crash", 3)}  # shim is ALWAYS

    # Per-child env plan (launcher --child-fault-plan) merges in too.
    monkeypatch.setenv(faults.ENV_ATTEMPT, "0")
    monkeypatch.setenv(faults.ENV_PLAN, "sigkill@9")
    planv = faults.resolve(_Cfg())
    assert [(f.kind, f.step) for f in planv.faults] == [("sigkill", 9)]


@pytest.mark.core
def test_plan_validate(monkeypatch):
    monkeypatch.delenv(faults.ENV_PLAN, raising=False)
    monkeypatch.delenv(faults.ENV_ATTEMPT, raising=False)
    plan = faults.resolve(_Cfg(fault_plan="sigkill@20"))
    with pytest.raises(ValueError, match="would never fire"):
        plan.validate(10)
    plan.validate(20)
    plan2 = faults.resolve(_Cfg(fault_plan="corrupt_latest_ckpt@2"))
    with pytest.raises(ValueError, match="checkpoint_dir"):
        plan2.validate(10, checkpoint_dir=None)
    plan2.validate(10, checkpoint_dir="/tmp/x")


@pytest.mark.core
def test_corrupt_latest_checkpoint(tmp_path):
    # Fake orbax layout: steps 2 and 4, commit markers + payload files.
    for step in (2, 4):
        d = tmp_path / str(step) / "default"
        d.mkdir(parents=True)
        (d / "array.bin").write_bytes(b"A" * 64)
        (tmp_path / str(step) / "_CHECKPOINT_METADATA").write_bytes(b"meta")
    hit = faults.corrupt_latest_checkpoint(str(tmp_path))
    assert hit == 4
    assert (tmp_path / "4" / "default" / "array.bin").read_bytes() == \
        b"\x00DDL_FAULT_CORRUPTED\x00"
    # Commit marker intact: the step still LOOKS restorable (that's the
    # point — restore must discover the damage, not the step listing).
    assert (tmp_path / "4" / "_CHECKPOINT_METADATA").read_bytes() == b"meta"
    # Older step untouched.
    assert (tmp_path / "2" / "default" / "array.bin").read_bytes() == b"A" * 64
    assert faults.corrupt_latest_checkpoint(str(tmp_path / "nope")) is None


# ---------------------------------------------------------------------------
# Loader watchdog (StreamSource)
# ---------------------------------------------------------------------------

def _sharding1():
    import jax

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    return jax.sharding.NamedSharding(mesh,
                                      jax.sharding.PartitionSpec("data"))


def test_watchdog_delivers_then_catches_stall(capsys):
    from distributeddeeplearning_tpu.data.imagenet import StreamSource

    def it():
        yield {"x": np.ones((2, 3), np.float32)}
        time.sleep(60)  # a wedged pipeline

    src = StreamSource(it(), _sharding1(), lookahead=False,
                       timeout_s=0.2, max_retries=1)
    b0 = src.batch(0)
    assert np.asarray(b0["x"]).shape == (2, 3)
    with pytest.raises(RuntimeError, match="data loader stalled"):
        src.batch(1)
    err = capsys.readouterr().err
    assert "data watchdog" in err  # per-timeout warning before the raise


def test_watchdog_propagates_producer_error_and_exhaustion():
    from distributeddeeplearning_tpu.data.imagenet import StreamSource

    def boom():
        yield {"x": np.zeros((1, 2), np.float32)}
        raise ValueError("decode failed")

    src = StreamSource(boom(), _sharding1(), lookahead=False,
                       timeout_s=5.0, max_retries=0)
    src.batch(0)
    with pytest.raises(ValueError, match="decode failed"):
        src.batch(1)

    def finite():
        yield {"x": np.zeros((1, 2), np.float32)}

    src2 = StreamSource(finite(), _sharding1(), lookahead=False,
                        timeout_s=5.0, max_retries=0)
    src2.batch(0)
    with pytest.raises(StopIteration):
        src2.batch(1)


def test_loader_stall_injection_delays_target_batch():
    from distributeddeeplearning_tpu.data.imagenet import StreamSource

    def it():
        while True:
            yield {"x": np.zeros((1, 2), np.float32)}

    src = StreamSource(it(), _sharding1(), lookahead=False,
                       stall_steps={1: 0.3})
    t0 = time.monotonic()
    src.batch(0)
    fast = time.monotonic() - t0
    t1 = time.monotonic()
    src.batch(1)  # the stalled one
    stalled = time.monotonic() - t1
    assert stalled >= 0.3 > fast


@pytest.mark.core
def test_stream_guard_kwargs_default_empty(monkeypatch):
    """No watchdog config + no plan => StreamSource gets ZERO extra kwargs
    (the hot path carries no fault machinery)."""
    from distributeddeeplearning_tpu.config import TrainConfig

    monkeypatch.delenv(faults.ENV_PLAN, raising=False)
    monkeypatch.delenv(faults.ENV_ATTEMPT, raising=False)

    assert faults.stream_guard_kwargs(TrainConfig()) == {}
    cfg = TrainConfig(fault_plan="loader_stall@3:0.1s")
    kw = faults.stream_guard_kwargs(cfg, train=True)
    assert kw == {"stall_steps": {3: 0.1}}
    # Eval sources never get train-stream stall injection.
    assert faults.stream_guard_kwargs(cfg, train=False) == {}


# ---------------------------------------------------------------------------
# Launcher hardening
# ---------------------------------------------------------------------------

@pytest.mark.core
def test_backoff_delay_deterministic_and_capped():
    a = launch._backoff_delay(1, 3.0, 60.0)
    assert a == launch._backoff_delay(1, 3.0, 60.0)  # deterministic
    b = launch._backoff_delay(2, 3.0, 60.0)
    assert 3.0 <= a <= 3.75 and b > a
    assert launch._backoff_delay(10, 3.0, 60.0) == 60.0  # capped


@pytest.mark.core
def test_run_with_restarts_exports_attempt_and_backs_off(monkeypatch):
    monkeypatch.delenv(faults.ENV_ATTEMPT, raising=False)
    sleeps, attempts = [], []

    def run_once():
        attempts.append(os.environ[faults.ENV_ATTEMPT])
        return 1 if len(attempts) < 3 else 0

    rc = launch.run_with_restarts(run_once, 5, backoff_s=1.0,
                                  backoff_cap_s=10.0, sleep=sleeps.append)
    assert rc == 0
    assert attempts == ["0", "1", "2"]
    assert sleeps == [launch._backoff_delay(1, 1.0, 10.0),
                      launch._backoff_delay(2, 1.0, 10.0)]
    assert faults.ENV_ATTEMPT not in os.environ  # restored on exit


@pytest.mark.core
@pytest.mark.parametrize("stop_rc", [130, 143, -15])
def test_run_with_restarts_operator_stop_never_retries(stop_rc, capsys):
    calls = []

    def run_once():
        calls.append(1)
        return stop_rc

    rc = launch.run_with_restarts(run_once, 5, sleep=lambda s: None)
    assert rc == stop_rc
    assert len(calls) == 1
    assert "operator stop" in capsys.readouterr().err


@pytest.mark.core
def test_restart_budget_refills_on_progress_and_stops_crash_loops(capsys):
    # Progressing job: budget 1, but every failure lands AFTER a new
    # checkpoint step — the budget refills and the job eventually finishes.
    state = {"calls": 0}

    def run_once():
        state["calls"] += 1
        return 1 if state["calls"] < 6 else 0

    rc = launch.run_with_restarts(run_once, 1,
                                  progress_fn=lambda: state["calls"],
                                  sleep=lambda s: None)
    assert rc == 0 and state["calls"] == 6
    assert "restart budget refilled" in capsys.readouterr().err

    # Crash loop: no progress ever — budget 1 allows exactly one restart.
    loops = []

    def crash_loop():
        loops.append(1)
        return 1

    rc = launch.run_with_restarts(crash_loop, 1, progress_fn=lambda: None,
                                  sleep=lambda s: None)
    assert rc == 1 and len(loops) == 2
    assert "crash loop, giving up" in capsys.readouterr().err


def _spawn_py(code: str) -> subprocess.Popen:
    return subprocess.Popen([sys.executable, "-c", code])


@pytest.mark.core
def test_monitor_attributes_failed_child(capsys):
    slow = _spawn_py("import time; time.sleep(60)")
    bad = _spawn_py("import sys; sys.exit(7)")
    rc = launch.monitor([slow, bad], poll_interval_s=0.05, grace_s=5.0)
    assert rc == 7
    err = capsys.readouterr().err
    assert "child 1 exited rc=7" in err
    assert "terminating 1 surviving" in err


@pytest.mark.core
def test_monitor_attributes_signal_death(capsys):
    victim = _spawn_py("import os, signal; os.kill(os.getpid(), "
                       "signal.SIGKILL)")
    rc = launch.monitor([victim], poll_interval_s=0.05, grace_s=5.0)
    assert rc == -9
    assert "child 0 exited rc=-9 (killed by signal 9)" in \
        capsys.readouterr().err


@pytest.mark.core
def test_checkpoint_dir_from_command():
    f = launch._checkpoint_dir_from_command
    assert f(["train.py", "--checkpoint-dir", "/tmp/c"]) == "/tmp/c"
    assert f(["train.py", "--checkpoint-dir=/tmp/c"]) == "/tmp/c"
    assert f(["train.py", "--steps", "5"]) is None


@pytest.mark.core
def test_latest_ckpt_step(tmp_path):
    assert launch._latest_ckpt_step(str(tmp_path)) is None
    (tmp_path / "2").mkdir()
    (tmp_path / "10").mkdir()
    (tmp_path / "corrupt.12").mkdir()  # quarantined: not progress
    (tmp_path / "stream_meta.json").write_text("{}")
    assert launch._latest_ckpt_step(str(tmp_path)) == 10
    assert launch._latest_ckpt_step(str(tmp_path / "missing")) is None


@pytest.mark.core
def test_bench_chaos_rejects_bad_fail_step(capsys):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    import bench

    rc = bench.main(["--chaos", "--chaos-steps", "8",
                     "--chaos-fail-at", "8"])
    assert rc == 1  # harness contract: parseable record, nothing measured
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["metric"] == "chaos_recovery_overhead"
    assert rec["value"] is None and "chaos-fail-at" in rec["error"]


# ---------------------------------------------------------------------------
# Elastic membership (launch.py --elastic)
# ---------------------------------------------------------------------------

@pytest.mark.core
def test_parse_plan_elastic_kinds():
    plan = faults.parse_plan("host_lost@4,host_rejoin@8:a1,host_lost@2:always")
    assert [(f.kind, f.step) for f in plan] == [
        ("host_lost", 4), ("host_rejoin", 8), ("host_lost", 2)]
    assert plan[0].attempt == 0          # default: first attempt only
    assert plan[1].attempt == 1          # fires on the SHRUNKEN attempt
    assert plan[2].attempt == faults.ALWAYS
    with pytest.raises(ValueError):
        faults.parse_plan("host_lost@0")
    # Neither kind needs a checkpoint dir to validate.
    faults.FaultPlan(tuple(plan)).validate(10, checkpoint_dir=None)


@pytest.mark.core
def test_attribute_failure_partition(tmp_path):
    hb = str(tmp_path)
    # Watchdog verdict dominates: the process was killed WHILE alive.
    assert launch.attribute_failure(hb, 0, hung=True, ever_beat=True) == \
        "hung"
    # Beat once, file gone with the process: the host took its filesystem
    # presence with it.
    assert launch.attribute_failure(hb, 0, ever_beat=True) == "host_lost"
    # Beat once, file still there: transient crash, host is fine.
    (tmp_path / "heartbeat.1").write_text("{}")
    assert launch.attribute_failure(hb, 1, ever_beat=True) == "crash"
    # Never armed / never beat: no evidence, default to crash.
    assert launch.attribute_failure(hb, 2, ever_beat=False) == "crash"
    assert launch.attribute_failure(None, 0, ever_beat=True) == "crash"


@pytest.mark.core
def test_with_flag_value():
    f = launch._with_flag_value
    assert f(["train.py", "--dp", "4", "--steps", "8"], "--dp", "2") == \
        ["train.py", "--dp", "2", "--steps", "8"]
    assert f(["train.py", "--dp=4"], "--dp", "2") == ["train.py", "--dp=2"]
    assert f(["train.py", "--steps", "8"], "--dp", "2") == \
        ["train.py", "--steps", "8", "--dp", "2"]


@pytest.mark.core
def test_elastic_controller_shrink_remaps_slots(tmp_path):
    hb = str(tmp_path / "hb")
    os.makedirs(hb)
    with pytest.raises(ValueError, match="divide evenly"):
        launch.ElasticController(3, hb, base_dp=8)
    ctl = launch.ElasticController(4, hb, base_dp=8)
    assert (ctl.num_processes, ctl.degree) == (4, 8)
    base = {0: {"X": "h0"}, 2: {"X": "h2"}}
    env0 = ctl.child_env(base)
    assert env0[2]["X"] == "h2" and health.ENV_ELASTIC_EVENT not in env0[0]

    # Host 2 dies taking its heartbeat with it (slot 2 == host 2 here).
    assert ctl.note_failure(2, -9, ever_beat=True) == "host_lost"
    assert ctl.live == [0, 1, 3] and ctl.degree == 6
    event = ctl.take_reconfiguration()
    assert (event["trigger"], event["degree_before"],
            event["degree_after"]) == ("host_lost", 8, 6)
    assert ctl.take_reconfiguration() is None  # consumed

    # Re-formed attempt: --dp rewritten, fault plans follow the ORIGINAL
    # host id (host 2's env died with it; host 3 now sits in slot 2), and
    # every slot carries the membership event — exactly once.
    assert ctl.command(["train.py", "--dp", "8"]) == ["train.py", "--dp", "6"]
    env1 = ctl.child_env(base)
    assert set(env1) == {0, 1, 2}
    assert env1[0]["X"] == "h0" and "X" not in env1[2]
    for slot in env1:
        evt = json.loads(env1[slot][health.ENV_ELASTIC_EVENT])
        assert evt["trigger"] == "host_lost" and "detect_t" in evt
    env2 = ctl.child_env(base)
    assert health.ENV_ELASTIC_EVENT not in env2[0]


@pytest.mark.core
def test_elastic_controller_rejoin_grows_back(tmp_path):
    hb = str(tmp_path / "hb")
    os.makedirs(hb)
    ctl = launch.ElasticController(2, hb, base_dp=4)
    # Rejoin marker with nobody missing: consumed, ignored.
    health.announce_rejoin(hb)
    assert ctl.poll_rejoin() is False
    assert ctl.poll_rejoin() is False  # marker actually consumed

    ctl.note_failure(1, -9, ever_beat=True)
    assert ctl.degree == 2
    assert ctl.take_reconfiguration()["trigger"] == "host_lost"
    health.announce_rejoin(hb)
    assert ctl.poll_rejoin() is True
    assert ctl.live == [0, 1] and ctl.degree == 4
    event = ctl.take_reconfiguration()
    assert (event["trigger"], event["degree_before"],
            event["degree_after"]) == ("host_rejoin", 2, 4)
    assert ctl.events and len(ctl.events) == 2


@pytest.mark.core
def test_elastic_controller_min_hosts_gives_up(tmp_path, capsys):
    hb = str(tmp_path / "hb")
    os.makedirs(hb)
    ctl = launch.ElasticController(2, hb, base_dp=4, min_hosts=2)
    ctl.note_failure(0, -9, ever_beat=True)
    assert ctl.take_reconfiguration() is None
    assert "cannot re-form, giving up" in capsys.readouterr().err


@pytest.mark.core
def test_run_with_restarts_reconfiguration_skips_backoff(capsys):
    """The satellite contract, pinned on the delay schedule: a planned
    re-formation relaunches with NO backoff sleep and NO restart-budget
    charge, while an ordinary crash in the same job still backs off."""
    class _Stub:
        def __init__(self):
            self.queue = [
                {"trigger": "host_lost", "degree_before": 4,
                 "degree_after": 2}, None, None]

        def take_reconfiguration(self):
            return self.queue.pop(0)

    sleeps, calls = [], []

    def run_once():
        calls.append(1)
        # attempt 0: host loss; attempt 1: plain crash; attempt 2: done.
        return 1 if len(calls) < 3 else 0

    rc = launch.run_with_restarts(run_once, 1, backoff_s=1.0,
                                  backoff_cap_s=10.0, sleep=sleeps.append,
                                  elastic=_Stub())
    assert rc == 0 and len(calls) == 3
    # Exactly ONE backoff (the crash); the re-formation slept zero. And the
    # budget of 1 survived because the re-formation never charged it.
    assert sleeps == [launch._backoff_delay(1, 1.0, 10.0)]
    err = capsys.readouterr().err
    assert "elastic re-formation (host_lost): degree 4 -> 2" in err
    assert "no backoff, budget untouched" in err


@pytest.mark.core
def test_run_with_restarts_ctrl_c_beats_reconfiguration():
    """^C stops the job even with a re-formation pending — the operator
    always outranks the controller."""
    class _Stub:
        def take_reconfiguration(self):  # pragma: no cover - must not run
            raise AssertionError("consulted elastic controller on rc=130")

    rc = launch.run_with_restarts(lambda: 130, 5, sleep=lambda s: None,
                                  elastic=_Stub())
    assert rc == 130


# ---------------------------------------------------------------------------
# Compiled bad-step guard + recovery (slow tier: XLA compiles, subprocesses)
# ---------------------------------------------------------------------------

def _cfg(**kw):
    from distributeddeeplearning_tpu.config import (
        DataConfig, OptimizerConfig, ParallelConfig, TrainConfig)

    base = dict(
        model="resnet18_thin", global_batch_size=16, dtype="float32",
        log_every=10**9,
        parallel=ParallelConfig(data=8),
        data=DataConfig(synthetic=True, image_size=32, num_classes=10),
        optimizer=OptimizerConfig(schedule="constant"))
    base.update(kw)
    return TrainConfig(**base)


def _assert_trees_equal(a, b):
    import jax

    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                   np.asarray(y)), a, b)


@pytest.mark.slow
@pytest.mark.parametrize("sharding", ["none", "zero1"])
def test_nan_step_skips_update_exactly(sharding):
    """nan_grads@2 poisons the update 1->2: the step must apply NOTHING
    (params/opt_state bitwise unchanged), flag bad_step=1, and keep the
    step counter advancing. zero1 exercises the cross-shard psum of the
    bad flag (shard-local grad chunks must agree on skipping)."""
    import jax

    from distributeddeeplearning_tpu import data as datalib
    from distributeddeeplearning_tpu.models import model_spec
    from distributeddeeplearning_tpu.train import loop

    cfg = _cfg(fault_plan="nan_grads@2", optimizer_sharding=sharding)
    spec = model_spec(cfg.model)
    mesh, model, batch_shd, state0, train_step, sched, rng = loop.build(
        cfg, 3)
    source = datalib.make_source(cfg, spec.input_kind, batch_shd,
                                 objective=spec.objective)

    def snap(state):  # state buffers are DONATED into the next step
        return jax.tree_util.tree_map(np.asarray,
                                      (state.params, state.opt_state))

    p0, _ = snap(state0)
    state1, m1 = train_step(state0, source.batch(0), rng)
    assert float(m1["bad_step"]) == 0.0
    p1, o1 = snap(state1)
    assert not np.array_equal(jax.tree_util.tree_leaves(p1)[0],
                              jax.tree_util.tree_leaves(p0)[0])
    step1 = int(state1.step)
    state2, m2 = train_step(state1, source.batch(1), rng)  # poisoned update
    assert float(m2["bad_step"]) == 1.0
    _assert_trees_equal(state2.params, p1)
    _assert_trees_equal(state2.opt_state, o1)
    assert int(state2.step) == step1 + 1  # counter still advances
    state3, m3 = train_step(state2, source.batch(2), rng)  # recovers
    assert float(m3["bad_step"]) == 0.0
    assert np.isfinite(float(m3["loss"]))


@pytest.mark.slow
def test_consecutive_bad_steps_abort():
    from distributeddeeplearning_tpu.train import loop

    cfg = _cfg(fault_plan="nan_grads@2,nan_grads@3", bad_step_limit=2)
    with pytest.raises(RuntimeError, match="consecutive non-finite"):
        loop.run(cfg, total_steps=6)


@pytest.mark.slow
def test_bad_steps_counted_in_summary():
    from distributeddeeplearning_tpu.train import loop

    summary = loop.run(_cfg(fault_plan="nan_grads@3"), total_steps=5)
    assert summary["bad_steps"] == 1
    assert np.isfinite(summary["final_metrics"]["loss"])


@pytest.mark.slow
def test_corrupt_checkpoint_quarantined_then_fallback(tmp_path):
    """Restore hits a damaged latest step: quarantine (rename to
    corrupt.<step>), fall back to the previous good step, resume there."""
    from distributeddeeplearning_tpu.train import loop

    ckpt = str(tmp_path / "ckpt")
    cfg = _cfg(checkpoint_dir=ckpt, checkpoint_every_steps=2)
    s1 = loop.run(cfg, total_steps=4)
    assert s1["final_step"] == 4
    assert faults.corrupt_latest_checkpoint(ckpt) == 4

    with pytest.warns(UserWarning, match="quarantin"):
        s2 = loop.run(cfg, total_steps=6)
    assert s2["start_step"] == 2, s2  # fell back past the damaged step 4
    assert s2["final_step"] == 6
    assert (tmp_path / "ckpt" / "corrupt.4").exists()


def _train_cmd(ckpt: str, steps: int, extra=()):
    return [sys.executable, "train.py", "--backend", "cpu", "--model",
            "resnet18_thin", "--image-size", "32", "--batch-size", "8",
            "--dp", "1", "--synthetic", "--dtype", "float32", "--steps",
            str(steps), "--checkpoint-dir", ckpt, "--checkpoint-every", "2",
            "--log-every", "1000000", *extra]


def _clean_env():
    return {k: v for k, v in os.environ.items()
            if k not in (faults.ENV_PLAN,
                         faults.ENV_ATTEMPT)}


def _summary_of(proc):
    lines = [ln for ln in proc.stdout.splitlines() if "summary" in ln]
    assert lines, (proc.returncode, proc.stderr[-2000:])
    return json.loads(lines[-1])["summary"]


@pytest.mark.slow
def test_sigterm_on_cadence_step_saves_and_resumes(tmp_path):
    """sigterm@4 lands right after the CADENCE save of step 4 already
    launched: the preemption path's forced save must short-circuit on the
    already-saved step (no duplicate-save crash), exit reporting a usable
    checkpoint, and the resume must land on exactly step 4."""
    ckpt = str(tmp_path / "ckpt")
    env = _clean_env()
    crash = subprocess.run(
        _train_cmd(ckpt, 8, ("--fault-plan", "sigterm@4")),
        capture_output=True, text=True, timeout=600, env=env)
    assert crash.returncode != 0
    assert "fault injection: SIGTERM" in crash.stderr
    assert "preempted (signal 15): checkpoint saved at step 4" in crash.stderr

    resume = subprocess.run(_train_cmd(ckpt, 8), capture_output=True,
                            text=True, timeout=600, env=env)
    assert resume.returncode == 0, resume.stderr[-2000:]
    s = _summary_of(resume)
    assert s["start_step"] == 4 and s["final_step"] == 8


@pytest.mark.slow
def test_chaos_soak_bitwise_identical_recovery(tmp_path):
    """The capstone: NaN step + corrupted checkpoint + SIGKILL in ONE run
    under run_with_restarts. Attempt 0 skips poisoned step 5, saves a
    diverged step-6 checkpoint, has it corrupted, dies by SIGKILL; the
    restart quarantines corrupt step 6, falls back to the clean step-4
    save, and replays 5..10 fault-free (attempt scoping) — so the final
    step-10 params must be BITWISE identical to a never-faulted run's."""
    ref_ckpt = str(tmp_path / "ref")
    chaos_ckpt = str(tmp_path / "chaos")
    env = _clean_env()

    ref = subprocess.run(_train_cmd(ref_ckpt, 10), capture_output=True,
                         text=True, timeout=600, env=env)
    assert ref.returncode == 0, ref.stderr[-2000:]

    plan = "nan_grads@5,corrupt_latest_ckpt@6,sigkill@6"
    proc = subprocess.run(
        [sys.executable, "launch.py", "--num-processes", "1",
         "--max-restarts", "2", "--backoff", "0.2", "--"]
        + _train_cmd(chaos_ckpt, 10, ("--fault-plan", plan)),
        capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    # Attempt 0's faults all fired and were attributed...
    assert "fault injection: corrupted checkpoint step 6" in proc.stderr
    assert "child 0 exited rc=-9 (killed by signal 9)" in proc.stderr
    assert "restart 1/2" in proc.stderr
    # ...and the restart quarantined the damaged step and fell back.
    assert (tmp_path / "chaos" / "corrupt.6").exists()
    s = _summary_of(proc)
    assert s["start_step"] == 4, s  # clean step-4 save, not corrupt 6
    assert s["final_step"] == 10

    # Bitwise identity of the final step-10 params: recovery fully erased
    # the kill, the corruption, AND the NaN step (its divergence lived only
    # in the quarantined checkpoint).
    import orbax.checkpoint as ocp

    def params_at(directory, step):
        with ocp.CheckpointManager(directory) as mgr:
            tree = mgr.restore(step, args=ocp.args.StandardRestore())
        return tree["params"]

    _assert_trees_equal(params_at(ref_ckpt, 10), params_at(chaos_ckpt, 10))
