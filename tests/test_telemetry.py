"""Observability contracts: telemetry ring buffer + Chrome-trace export,
heartbeat health, the launcher's hang watchdog, the straggler aggregation,
and the MetricLogger hardening that rides this PR.

The fast tests exercise the stdlib layer directly (no jax backend); the
@slow tests run the real acceptance scenarios through launch.py + train.py
subprocesses (the same harness test_launch.py uses)."""

import json
import os
import subprocess
import sys
import time

import pytest

from distributeddeeplearning_tpu.observability import health, telemetry


# --- telemetry core --------------------------------------------------------


def test_span_nesting_and_ring_bound():
    tele = telemetry.Telemetry(enabled=True, max_events=8)
    with tele.span("outer", step=1):
        with tele.span("inner", step=1):
            pass
    events = tele.snapshot()
    # Inner exits (and records) first; both carry the step arg.
    assert [e["name"] for e in events] == ["inner", "outer"]
    assert all(e["args"]["step"] == 1 for e in events)
    for k in range(100):
        tele.instant(f"i{k}")
    events = tele.snapshot()
    assert len(events) == 8  # ring bound holds
    assert events[-1]["name"] == "i99"  # ...and keeps the newest events


def test_chrome_trace_schema(tmp_path):
    tele = telemetry.Telemetry(enabled=True, trace_dir=str(tmp_path),
                               process_index=3, process_name="t")
    with tele.span("phase_a", step=0, detail="x"):
        pass
    tele.record_span("phase_b", telemetry.now_s() - 0.5, telemetry.now_s())
    tele.instant("fault:crash", step=2)
    tele.gauge("hbm/d0", 123.0, step=0)
    tele.counter("bad_steps")
    path = tele.export()
    assert path == telemetry.trace_path(str(tmp_path), 3)
    obj = json.load(open(path))  # must be VALID json, loadable in one shot
    assert obj["displayTimeUnit"] == "ms"
    events = obj["traceEvents"]
    by_name = {e["name"]: e for e in events}
    for e in events:
        assert {"name", "ph", "ts", "pid"} <= set(e), e
    for name in ("phase_a", "phase_b"):
        assert by_name[name]["ph"] == "X"
        assert by_name[name]["dur"] >= 0
    assert by_name["fault:crash"]["ph"] == "i"
    assert by_name["fault:crash"]["s"] == "p"
    assert by_name["hbm/d0"]["ph"] == "C"
    assert by_name["hbm/d0"]["args"]["value"] == 123.0
    assert by_name["process_name"]["ph"] == "M"
    assert by_name["process_name"]["args"]["name"] == "t p3"
    assert by_name["phase_b"]["dur"] == pytest.approx(500_000, rel=0.05)


def test_export_drains_and_merges(tmp_path):
    """Two exports to the same path accumulate WITHOUT duplicating: the
    restart-recovered chaos run and the launcher both fold into one file."""
    path = str(tmp_path / "trace.json")
    tele = telemetry.Telemetry(enabled=True)
    tele.instant("first")
    assert tele.export(path) == path
    assert tele.export(path) is None  # buffer drained: nothing to write
    tele.instant("second")
    tele.export(path)
    other = telemetry.Telemetry(enabled=True, process_index=7)
    other.instant("launcher:restart")
    other.export(path)
    names = [e["name"] for e in telemetry.load_events(path)]
    assert names.count("first") == 1
    assert names.count("second") == 1
    assert "launcher:restart" in names
    # one process_name meta per pid
    metas = [e for e in telemetry.load_events(path) if e["ph"] == "M"]
    assert len(metas) == 2


def test_disabled_path_is_noop():
    tele = telemetry.Telemetry(enabled=False)
    assert tele.span("x") is telemetry._NULL_SPAN  # shared, no allocation
    tele.record_span("x", 0.0, 1.0)
    tele.instant("x")
    tele.gauge("x", 1.0)
    tele.counter("x")
    assert tele.snapshot() == []
    assert tele.export("/nonexistent/should/never/be/written") is None
    # Overhead bound: the disabled hot path is one attribute check; 50k
    # calls must land far under a single training step even on a loaded
    # CI box (generous 0.5 s bound for a ~5 ms expected cost).
    t0 = time.perf_counter()
    for _ in range(50_000):
        with tele.span("step"):
            pass
    assert time.perf_counter() - t0 < 0.5


def test_trace_steps_window():
    tele = telemetry.Telemetry(enabled=True, trace_steps=(10, 20))
    with tele.span("in", step=10):
        pass
    assert tele.span("out", step=20) is telemetry._NULL_SPAN  # half-open
    tele.record_span("out", 0.0, 1.0, step=9)
    tele.gauge("out", 1.0, step=25)
    with tele.span("stepless"):  # step-less events are always kept
        pass
    names = [e["name"] for e in tele.snapshot()]
    assert names == ["in", "stepless"]


def test_phase_totals():
    events = [
        {"name": "a", "ph": "X", "ts": 0, "dur": 1000},
        {"name": "a", "ph": "X", "ts": 0, "dur": 3000},
        {"name": "b", "ph": "X", "ts": 0, "dur": 10_000},
        {"name": "skip", "ph": "i", "ts": 0},
    ]
    totals = telemetry.phase_totals(events)
    assert list(totals) == ["b", "a"]  # largest total first
    assert totals["a"] == {"count": 2, "total_ms": 4.0, "mean_ms": 2.0}
    assert totals["b"]["count"] == 1


def test_configure_singleton_roundtrip():
    try:
        tele = telemetry.configure(trace_dir="/tmp/x")
        assert tele.enabled  # enabled defaults to "destination given"
        assert telemetry.get() is tele
        assert not telemetry.configure().enabled
    finally:
        telemetry.reset()
    assert not telemetry.get().enabled


def test_summarize_trace_cli(tmp_path, capsys):
    tele = telemetry.Telemetry(enabled=True, trace_dir=str(tmp_path))
    with tele.span("dispatch", step=1):
        pass
    tele.instant("fault:crash", step=1)
    tele.gauge("hbm/d0", 42.0)
    path = tele.export()

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from tools import summarize_trace
    assert summarize_trace.main([path, "--json"]) == 0
    rec = json.loads(capsys.readouterr().out)
    assert "dispatch" in rec["phases"]
    assert [e["name"] for e in rec["instants"]] == ["fault:crash"]
    assert rec["counters"]["hbm/d0"]["last"] == 42.0
    assert summarize_trace.main([path]) == 0  # table mode renders too
    out = capsys.readouterr().out
    assert "dispatch" in out and "fault:crash" in out
    with pytest.raises(SystemExit):
        summarize_trace.main([str(tmp_path / "missing.json")])


# --- heartbeat health ------------------------------------------------------


def test_heartbeat_writer_and_staleness(tmp_path, monkeypatch):
    d = str(tmp_path)
    w = health.HeartbeatWriter(d, process_id=1)
    w.beat(5)
    crumb = json.load(open(health.heartbeat_path(d, 1)))
    assert crumb["step"] == 5
    now = time.time()
    # Fresh beat: not stale. Child 0 never beat: never reported (the
    # watchdog arms per child on its first beat — no startup grace logic).
    assert health.check_stale(d, 2, timeout_s=1.0, now=now) == []
    os.utime(w.path, (now - 30, now - 30))  # fake clock via mtime
    stale = health.check_stale(d, 2, timeout_s=1.0, now=now)
    assert [pid for pid, _age in stale] == [1]
    assert stale[0][1] == pytest.approx(30, abs=1)
    w.beat(6)  # beating again un-stales
    assert health.check_stale(d, 2, timeout_s=1.0) == []


def test_heartbeat_from_env(tmp_path, monkeypatch):
    monkeypatch.delenv(health.ENV_HEARTBEAT_DIR, raising=False)
    assert health.HeartbeatWriter.from_env() is None
    monkeypatch.setenv(health.ENV_HEARTBEAT_DIR, str(tmp_path))
    monkeypatch.setenv("DDL_PROCESS_ID", "2")
    w = health.HeartbeatWriter.from_env()
    assert w is not None and w.process_id == 2
    w.beat(0)
    assert os.path.exists(health.heartbeat_path(str(tmp_path), 2))


def test_monitor_kills_stale_heartbeat(tmp_path):
    """The hang watchdog end-to-end at unit scale: a child that sleeps
    forever but whose heartbeat has gone stale is killed by monitor() and
    attributed through the existing fail-whole path (nonzero rc)."""
    from distributeddeeplearning_tpu import launch

    d = str(tmp_path)
    specs = launch.plan_local(1, port=9481)
    child = launch.spawn(
        specs[0], [sys.executable, "-c", "import time; time.sleep(120)"])
    # The child "beat once" long ago: write its heartbeat pre-staled.
    health.HeartbeatWriter(d, 0).beat(0)
    old = time.time() - 60
    os.utime(health.heartbeat_path(d, 0), (old, old))
    t0 = time.monotonic()
    rc = launch.monitor([child], poll_interval_s=0.05, grace_s=2.0,
                        heartbeat_dir=d, heartbeat_timeout_s=0.5)
    assert rc != 0  # hung child was killed and attributed, not waited on
    assert time.monotonic() - t0 < 30
    assert child.poll() is not None


# --- MetricLogger hardening (satellite) ------------------------------------


def test_metric_logger_context_manager_and_idempotent_close(tmp_path):
    from distributeddeeplearning_tpu.utils.logging import MetricLogger

    path = str(tmp_path / "metrics.jsonl")
    with pytest.raises(RuntimeError):
        with MetricLogger(file_path=path, enabled=True) as logger:
            logger.log(1, {"loss": 1.0})
            raise RuntimeError("boom")  # close() must still run
    assert logger.file is None  # released despite the exception
    logger.close()  # double-close is a no-op, not an error
    rec = json.loads(open(path).read().strip())
    assert rec == {"step": 1, "loss": 1.0}


def test_metric_logger_nonmonotonic_step_resets_throughput(tmp_path):
    from distributeddeeplearning_tpu.utils.logging import MetricLogger

    logger = MetricLogger(stream=open(os.devnull, "w"), enabled=True)
    logger.log(10, {}, examples_per_step=8)
    r = logger.log(20, {}, examples_per_step=8)
    assert "step_time_s" in r  # monotonic: throughput accounted normally
    # Restart resumed from an earlier checkpoint: step goes BACKWARD.
    # The elapsed wall time is restore/compile downtime, not step time —
    # no garbage sample now, and none at the next log either.
    r = logger.log(5, {}, examples_per_step=8)
    assert "step_time_s" not in r
    r = logger.log(15, {}, examples_per_step=8)
    assert "step_time_s" in r  # baseline re-armed from the step-5 log
    logger.close()


# --- end-to-end acceptance (slow: real subprocess training runs) -----------


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return env


@pytest.mark.slow
def test_chaos_run_produces_single_merged_trace(tmp_path):
    """ISSUE acceptance: a --fault-plan chaos run under launch.py
    --max-restarts with --trace-dir yields ONE valid Chrome-trace JSON
    holding step phase spans, per-bucket collective spans, the fault
    instant, and the launcher's restart instant."""
    trace = str(tmp_path / "trace")
    ckpt = str(tmp_path / "ckpt")
    cmd = [sys.executable, "launch.py", "--num-processes", "1",
           "--max-restarts", "1", "--backoff", "0.2", "--",
           sys.executable, "train.py", "--backend", "cpu", "--model",
           "resnet18", "--batch-size", "8", "--dp", "1", "--synthetic",
           "--dtype", "float32", "--steps", "5", "--log-every", "2",
           "--checkpoint-dir", ckpt, "--checkpoint-every", "2",
           "--fault-plan", "crash@3", "--trace-dir", trace]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                          env=_env())
    assert proc.returncode == 0, proc.stderr[-2000:]
    files = os.listdir(trace)
    assert files == ["trace.p0.json"]  # ONE merged file
    events = telemetry.load_events(os.path.join(trace, files[0]))
    names = {e["name"] for e in events}
    assert {"data_wait", "dispatch", "fetch_barrier"} <= names
    assert any(n.startswith("collective:allreduce/bucket") for n in names)
    assert "fault:crash" in names
    assert "launcher:restart" in names
    # Both attempts landed: the dispatch spans cover pre- and post-crash
    # steps (crash@3 kills after step 3; resume covers 3..5).
    steps = {e["args"].get("step") for e in events
             if e["name"] == "dispatch"}
    assert steps & {1, 2, 3} and steps & {4, 5}


# --- straggler aggregation -------------------------------------------------
# Unit-level with the allgather stubbed: this box's jax CPU backend cannot
# run multiprocess computations (the pre-existing 2-process dp=2 training
# test in test_launch.py hits the same wall), so the collective itself is
# exercised on real multi-host hardware while the skew math, warning, and
# telemetry instant are pinned here.


def _collect_with(monkeypatch, per_host, threshold=1.5):
    import numpy as np
    from jax.experimental import multihost_utils

    from distributeddeeplearning_tpu.observability import straggler

    monkeypatch.setattr(
        multihost_utils, "process_allgather",
        lambda x: np.concatenate([np.asarray(h, np.float64)
                                  for h in per_host]))
    mon = straggler.StragglerMonitor(threshold, len(per_host))
    return mon.collect(10, *per_host[0])


def test_straggler_skew_fields_no_straggler(monkeypatch, capsys):
    rec = _collect_with(monkeypatch, [(0.10, 0.01), (0.12, 0.02)])
    assert rec["host_count"] == 2
    assert rec["host_step_time_min"] == 0.10
    assert rec["host_step_time_max"] == 0.12
    assert rec["host_step_time_mean"] == pytest.approx(0.11)
    assert rec["host_data_wait_max"] == 0.02
    assert "straggler_host" not in rec  # 0.12 < 1.5 * 0.11
    assert "straggler" not in capsys.readouterr().err


def test_straggler_warning_and_instant(monkeypatch, capsys):
    telemetry.configure(enabled=True)
    try:
        rec = _collect_with(monkeypatch,
                            [(0.10, 0.01), (0.10, 0.01), (0.40, 0.30)])
        assert rec["straggler_host"] == 2
        err = capsys.readouterr().err
        assert "# straggler: host 2" in err
        assert "data_wait 0.3000s" in err  # names the likely cause
        inst = [e for e in telemetry.get().snapshot()
                if e["name"] == "straggler"]
        assert len(inst) == 1 and inst[0]["args"]["host"] == 2
    finally:
        telemetry.reset()


def test_make_monitor_gating():
    from distributeddeeplearning_tpu.config import TrainConfig
    from distributeddeeplearning_tpu.observability import straggler

    # Single-process (this test env): no monitor, regardless of threshold.
    assert straggler.make_monitor(TrainConfig(model="resnet18")) is None
    mon = straggler.StragglerMonitor(1.5, 2)  # what multi-process builds
    assert mon.threshold == 1.5 and mon.num_processes == 2


# --- MetricLogger <-> telemetry single emit path (ISSUE 6 satellite) --------


def test_metric_logger_uses_caller_clock_and_mirrors_gauges():
    """One clock, one emit: the step-time window is computed from the
    ``now_s`` reading the caller already took for the straggler monitor
    (not a second internal clock that can disagree by the cost of the
    straggler allgather), and every numeric field of the record is
    mirrored into the active telemetry registry so trace and JSONL can
    never diverge."""
    from distributeddeeplearning_tpu.utils.logging import MetricLogger

    try:
        telemetry.configure(enabled=True)
        logger = MetricLogger(stream=open(os.devnull, "w"), enabled=True)
        logger.log(1, {"loss": 2.0}, examples_per_step=8, now_s=100.0)
        rec = logger.log(2, {"loss": 1.5}, examples_per_step=8,
                         now_s=100.5, lr=0.1)
        # Exactly the caller's readings: 0.5 s apart — impossible to get
        # from an internal wall clock in a microsecond-fast test.
        assert rec["step_time_s"] == 0.5
        assert rec["examples_per_sec"] == 16.0
        gauges = {}
        for e in telemetry.get().snapshot():
            if e.get("ph") == "C":
                gauges.setdefault(e["name"], []).append(
                    e["args"]["value"])
        for key in ("loss", "step_time_s", "examples_per_sec", "lr"):
            assert key in gauges, f"{key} not mirrored into telemetry"
        assert gauges["loss"] == [2.0, 1.5]
        assert gauges["examples_per_sec"][-1] == 16.0
        logger.close()
    finally:
        telemetry.reset()


def test_metric_logger_no_mirroring_when_telemetry_disabled():
    from distributeddeeplearning_tpu.utils.logging import MetricLogger

    telemetry.reset()  # the disabled singleton
    logger = MetricLogger(stream=open(os.devnull, "w"), enabled=True)
    logger.log(1, {"loss": 2.0}, now_s=1.0)
    assert telemetry.get().snapshot() == []
    logger.close()


def test_metric_logger_roofline_pct_of_peak():
    """set_roofline turns every throughput record into a roofline record:
    tflops_per_sec always, pct_of_peak when the peak is known — the
    log-cadence %-of-peak line ISSUE 6's tentpole requires."""
    from distributeddeeplearning_tpu.utils.logging import MetricLogger

    logger = MetricLogger(stream=open(os.devnull, "w"), enabled=True)
    logger.set_roofline(1e9, 1e12)  # 1 GFLOP/example, 1 TFLOP/s peak
    logger.log(1, {}, examples_per_step=100, now_s=10.0)
    rec = logger.log(2, {}, examples_per_step=100, now_s=11.0)
    assert rec["examples_per_sec"] == 100.0
    assert rec["tflops_per_sec"] == 0.1
    assert rec["pct_of_peak"] == 10.0
    # Unknown peak (CPU): tflops still reported, pct honestly absent.
    logger.set_roofline(1e9, None)
    logger.log(3, {}, examples_per_step=100, now_s=12.0)
    rec = logger.log(4, {}, examples_per_step=100, now_s=13.0)
    assert rec["tflops_per_sec"] == 0.1 and "pct_of_peak" not in rec
    logger.close()
