"""Flight recorder + metrics registry + anomaly detector + post-mortem.

Unit tier: recorder identity/durability semantics, sidecar helper,
metrics aggregation/export, anomaly detection bounds (flag an injected
spike fast, zero false positives on a clean soak), incident chains on a
synthetic record.

Acceptance tier (tier-1, ``flight``-marked): SIGKILL a child mid-run via
the chaos harness and assert the flight record survives complete and
parseable, with ``tools/postmortem.py`` producing a correctly-attributed
incident timeline.

Integration tier (slow): in-process loop runs with fault plans writing
real flight records.
"""

import io
import json
import os
import subprocess
import sys
import time
from types import SimpleNamespace

import pytest

from distributeddeeplearning_tpu.observability import anomaly, flight
from distributeddeeplearning_tpu.observability import metrics as metricslib
from distributeddeeplearning_tpu.observability import sidecars

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools import postmortem  # noqa: E402


# ---------------------------------------------------------------------------
# Recorder
# ---------------------------------------------------------------------------

def test_recorder_identity_and_sequence(tmp_path):
    rec = flight.FlightRecorder(str(tmp_path), run_id="run-x", host=1,
                                attempt=2)
    rec.record("run_start", step=0, degree=4)
    rec.record("step", step=10, loss=2.5)
    rec.close()
    events, err = flight.read_file(flight.flight_path(str(tmp_path), 1))
    assert err is None
    assert [(e["ev"], e["run"], e["attempt"], e["host"], e["seq"])
            for e in events] == [("run_start", "run-x", 2, 1, 1),
                                 ("step", "run-x", 2, 1, 2)]
    assert events[1]["loss"] == 2.5
    assert events[0]["t"] > 0 and events[0]["mono"] > 0


def test_disabled_recorder_is_noop(tmp_path):
    rec = flight.FlightRecorder(None)
    assert not rec.enabled
    rec.record("anything", step=1)  # must not raise or write
    rec.close()


def test_from_env(tmp_path, monkeypatch):
    monkeypatch.setenv(flight.ENV_FLIGHT_DIR, str(tmp_path))
    monkeypatch.setenv(flight.ENV_RUN_ID, "run-env")
    monkeypatch.setenv("DDL_PROCESS_ID", "3")
    monkeypatch.setenv("DDL_RESTART_ATTEMPT", "2")
    rec = flight.FlightRecorder.from_env()
    assert rec.enabled and rec.run_id == "run-env"
    assert rec.host == 3 and rec.attempt == 2
    assert rec.path.endswith("flight.p3.jsonl")
    rec.close()


def test_torn_tail_is_salvaged_and_reported(tmp_path):
    rec = flight.FlightRecorder(str(tmp_path), run_id="r", host=0)
    rec.record("run_start", step=0)
    rec.record("step", step=1)
    rec.close()
    path = flight.flight_path(str(tmp_path), 0)
    with open(path, "a") as fh:  # a writer killed mid-line
        fh.write('{"ev": "step", "t": 123.0, "loss')
    events, errors = flight.read_all(str(tmp_path))
    assert [e["ev"] for e in events] == ["run_start", "step"]
    assert len(errors) == 1 and "unparseable" in errors[0]


def test_rotation_bounds_the_file_and_keeps_recent_window(tmp_path):
    rec = flight.FlightRecorder(str(tmp_path), run_id="r", host=0,
                                max_bytes=400, fsync=False)
    for i in range(20):
        rec.record("step", step=i)
    rec.close()
    assert os.path.exists(rec.path + ".1")
    # the live segment re-opens lazily after a rotation; whatever exists
    # stays bounded near max_bytes
    if os.path.exists(rec.path):
        assert os.path.getsize(rec.path) < 800
    assert os.path.getsize(rec.path + ".1") < 800
    events, errors = flight.read_all(str(tmp_path))
    assert errors == []
    # the most recent window is intact even though old lines rolled off
    assert events[-1]["step"] == 19


def test_singleton_configure_and_reset(tmp_path):
    try:
        rec = flight.configure(str(tmp_path), run_id="r", host=0)
        assert flight.get() is rec
        flight.get().record("launch", num_processes=2)
        events, _ = flight.read_all(str(tmp_path))
        assert events[0]["ev"] == "launch"
    finally:
        flight.reset()
    assert not flight.get().enabled


def test_mint_run_id_is_sortable_and_distinct():
    a, b = flight.mint_run_id(1000.0), flight.mint_run_id(1000.0)
    assert a.startswith("run-") and a != b


def test_describe_is_one_human_line():
    line = flight.describe({"ev": "fault", "t": 0.0, "host": 2,
                            "attempt": 1, "kind": "sigkill", "step": 4})
    assert "[a1 h2] fault" in line
    assert "kind=sigkill" in line and "step=4" in line


def test_last_incident_is_scoped_to_the_latest_run(tmp_path):
    rec = flight.FlightRecorder(str(tmp_path), run_id="run-old", host=0)
    rec.record("fault", kind="sigkill", step=4)
    rec.close()
    assert flight.last_incident(str(tmp_path))["kind"] == "sigkill"
    time.sleep(0.01)
    rec = flight.FlightRecorder(str(tmp_path), run_id="run-new", host=0)
    rec.record("run_start", step=0)
    rec.record("run_end", step=6)
    rec.close()
    # the clean newest run has no incident; the old run's fault must not
    # leak into "what killed the LAST run?"
    assert flight.last_incident(str(tmp_path)) is None


# ---------------------------------------------------------------------------
# Sidecar helper
# ---------------------------------------------------------------------------

def test_sidecar_roundtrip_with_envelope(tmp_path):
    path = str(tmp_path / "side.json")
    assert sidecars.write(path, {"trigger": "host_lost", "resume_step": 4}) \
        == path
    rec = sidecars.read(path)
    assert rec["trigger"] == "host_lost" and rec["resume_step"] == 4
    assert rec["schema"] == sidecars.SCHEMA_VERSION
    assert isinstance(rec["written_at"], float)
    assert sidecars.age_s(rec, now=rec["written_at"] + 7.5) == 7.5


def test_sidecar_read_tolerates_absent_and_malformed(tmp_path):
    assert sidecars.read(str(tmp_path / "missing.json")) is None
    bad = tmp_path / "bad.json"
    bad.write_text("{nope")
    assert sidecars.read(str(bad)) is None
    notdict = tmp_path / "list.json"
    notdict.write_text("[1, 2]")
    assert sidecars.read(str(notdict)) is None
    assert sidecars.age_s(None) is None
    assert sidecars.age_s({"written_at": "yesterday"}) is None


def test_sidecar_bare_names_resolve_into_repo_cache():
    path = sidecars.path_for("last_elastic_event")
    assert path.endswith(os.path.join(".cache", "last_elastic_event.json"))
    # explicit paths pass through untouched
    assert sidecars.path_for("/x/y.json") == "/x/y.json"


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------

def test_metrics_aggregate_across_hosts():
    reg = metricslib.MetricsRegistry(run_id="r")
    reg.observe("step_time_ms", 100.0, step=1, host=0)
    reg.observe("step_time_ms", 140.0, step=1, host=1)
    reg.observe("step_time_ms", float("nan"), step=1, host=2)  # dropped
    agg = reg.aggregate()
    m = agg["metrics"]["step_time_ms"]
    assert m["min"] == 100.0 and m["max"] == 140.0 and m["mean"] == 120.0
    assert m["per_host"] == {"0": 100.0, "1": 140.0}
    assert reg.hosts() == [0, 1]


def test_metrics_observe_many_skips_step_key():
    reg = metricslib.MetricsRegistry()
    reg.observe_many({"step": 5, "loss": 2.0, "note": "text"}, host=0)
    agg = reg.aggregate()["metrics"]
    assert set(agg) == {"loss"}
    assert agg["loss"]["series_tail"] == [[5, 2.0]] or \
        agg["loss"]["series_tail"] == [(5, 2.0)]


def test_metrics_prometheus_text_format(tmp_path):
    reg = metricslib.MetricsRegistry(run_id="run-p")
    reg.observe("examples/sec", 1234.5, step=2, host=0)
    text = reg.prometheus_text()
    assert "# TYPE ddl_examples_sec gauge" in text
    assert 'ddl_examples_sec{run="run-p",host="0"} 1234.5' in text
    out = reg.write_prometheus(str(tmp_path / "m.prom"))
    assert out and open(out).read() == text
    snap_path = reg.write_snapshot(str(tmp_path / "snap.json"))
    snap = json.load(open(snap_path))
    assert snap["run"] == "run-p" and "examples/sec" in snap["metrics"]


# ---------------------------------------------------------------------------
# Anomaly detector: flag fast, stay quiet on clean runs
# ---------------------------------------------------------------------------

def _clean_signal(i):
    """A deterministic healthy run: drifting loss with noise, ~5% jitter
    on throughput and grad norms, mild straggler skew."""
    wobble = 0.1 * ((i * 2654435761) % 97 / 97.0 - 0.5)
    return dict(loss=2.5 - 0.01 * i + wobble,
                grad_norm=1.0 + 0.5 * wobble,
                examples_per_sec=1000.0 * (1 + 0.5 * wobble),
                data_wait_frac=0.05,
                straggler_ratio=1.05,
                bad_step=0.0)


def test_clean_soak_produces_zero_anomalies():
    det = anomaly.AnomalyDetector()
    flagged = []
    for i in range(200):
        flagged += det.update(i, **_clean_signal(i))
    assert flagged == []


def test_loss_spike_flagged_within_five_cadences():
    det = anomaly.AnomalyDetector()
    for i in range(10):
        det.update(i, loss=2.0 + 0.01 * (i % 3))
    sig = _clean_signal(10)
    sig["loss"] = 9.0  # diverged
    cadences = 0
    flagged = []
    while not flagged and cadences < 5:
        cadences += 1
        flagged = det.update(10 + cadences, **sig)
    assert cadences <= 5 and flagged
    assert flagged[0]["kind"] == "loss_spike"
    assert flagged[0]["step"] == 10 + cadences


def test_nonfinite_loss_and_grad_flag_immediately():
    det = anomaly.AnomalyDetector()
    out = det.update(1, loss=float("nan"), grad_norm=float("inf"))
    assert sorted(a["kind"] for a in out) == ["grad_norm_nonfinite",
                                             "loss_nonfinite"]


def test_grad_norm_drift_both_directions():
    det = anomaly.AnomalyDetector()
    for i in range(6):
        det.update(i, grad_norm=1.0)
    up = det.update(6, grad_norm=50.0)
    assert [a["kind"] for a in up] == ["grad_norm_drift"]
    det2 = anomaly.AnomalyDetector()
    for i in range(6):
        det2.update(i, grad_norm=1.0)
    down = det2.update(6, grad_norm=0.001)
    assert [a["kind"] for a in down] == ["grad_norm_drift"]


def test_throughput_collapse_vs_loader_stall():
    det = anomaly.AnomalyDetector()
    for i in range(6):
        det.update(i, examples_per_sec=1000.0, data_wait_frac=0.05)
    out = det.update(6, examples_per_sec=100.0, data_wait_frac=0.1)
    assert [a["kind"] for a in out] == ["throughput_collapse"]
    det2 = anomaly.AnomalyDetector()
    for i in range(6):
        det2.update(i, examples_per_sec=1000.0, data_wait_frac=0.05)
    out = det2.update(6, examples_per_sec=100.0, data_wait_frac=0.9)
    assert [a["kind"] for a in out] == ["loader_stall"]
    assert "waiting on data" in out[0]["detail"]


def test_straggler_needs_patience_then_resets():
    det = anomaly.AnomalyDetector(straggler_patience=3)
    assert det.update(1, straggler_ratio=2.0) == []
    assert det.update(2, straggler_ratio=2.0) == []
    out = det.update(3, straggler_ratio=2.0)
    assert [a["kind"] for a in out] == ["straggler_trending"]
    # streak resets after the emit AND on a healthy interval
    assert det.update(4, straggler_ratio=2.0) == []
    assert det.update(5, straggler_ratio=1.0) == []
    assert det.update(6, straggler_ratio=2.0) == []


def test_report_fans_out_to_all_consumers(tmp_path):
    rec = flight.FlightRecorder(str(tmp_path), run_id="r", host=0)
    tele_calls, guard_feeds = [], []
    tele = SimpleNamespace(instant=lambda name, **kw:
                           tele_calls.append(name))
    tracker = SimpleNamespace(note_anomaly=lambda:
                              guard_feeds.append(1))
    out = io.StringIO()
    anomaly.report(
        [{"kind": "loss_nonfinite", "step": 7, "value": None,
          "baseline": None, "detail": "loss=nan"},
         {"kind": "bad_step", "step": 7, "value": 1.0, "baseline": 0.0,
          "detail": "guard tripped"}],
        flight_rec=rec, tele=tele, bad_tracker=tracker, stream=out)
    rec.close()
    events, _ = flight.read_all(str(tmp_path))
    assert [e["kind"] for e in events] == ["loss_nonfinite", "bad_step"]
    assert tele_calls == ["anomaly:loss_nonfinite", "anomaly:bad_step"]
    # bad_step must NOT feed the guard: push() already counted the
    # compiled flag; only the non-finite kinds count extra.
    assert len(guard_feeds) == 1
    assert "# anomaly: loss_nonfinite at step 7" in out.getvalue()


def test_injected_loader_stall_flags_through_production_injection(
        tmp_path, monkeypatch):
    """Satellite: a ``loader_stall`` fault plan, injected through the SAME
    wrapper production host-streaming loaders use (_stalling_iterator via
    the resolved plan), must surface as a flagged flight-recorder event."""
    from distributeddeeplearning_tpu.data import imagenet
    from distributeddeeplearning_tpu.robustness import faults

    monkeypatch.delenv(faults.ENV_PLAN, raising=False)
    monkeypatch.delenv(faults.ENV_ATTEMPT, raising=False)
    plan = faults.resolve(SimpleNamespace(fault_plan="loader_stall@6:0.3s",
                                          fail_at_step=None))
    stalls = plan.loader_stalls()
    assert stalls == {6: 0.3}
    it = imagenet._stalling_iterator(iter([{"x": i} for i in range(10)]),
                                     stalls, 1)
    rec = flight.FlightRecorder(str(tmp_path), run_id="r", host=0)
    det = anomaly.AnomalyDetector()
    flagged = []
    for step in range(1, 9):
        t0 = time.perf_counter()
        next(it)
        wait = time.perf_counter() - t0
        interval = wait + 0.01  # 10 ms of simulated compute per step
        out = det.update(step, examples_per_sec=8.0 / interval,
                         data_wait_frac=wait / interval)
        anomaly.report(out, flight_rec=rec, stream=io.StringIO())
        flagged += out
    rec.close()
    assert [a["kind"] for a in flagged] == ["loader_stall"]
    assert flagged[0]["step"] == 6
    events, _ = flight.read_all(str(tmp_path))
    assert [e["ev"] for e in events] == ["anomaly"]
    assert events[0]["kind"] == "loader_stall" and events[0]["step"] == 6


# ---------------------------------------------------------------------------
# Post-mortem on a synthetic record
# ---------------------------------------------------------------------------

def test_postmortem_attributes_a_synthetic_elastic_incident(tmp_path):
    d = str(tmp_path)
    launcher = flight.FlightRecorder(d, run_id="run-s", host="launcher")
    h0 = flight.FlightRecorder(d, run_id="run-s", host=0)
    launcher.record("launch", num_processes=4, elastic=True)
    h0.record("run_start", step=0, degree=4)
    h0.record("step", step=400, loss=2.1)
    launcher.record("fault", kind="host_lost", step=412)
    launcher.record("child_exit", child=2, rc=1, attribution="host_lost")
    launcher.record("reconfiguration_planned", trigger="host_lost",
                    degree_before=4, degree_after=2)
    launcher.record("restart", attempt=1, restart=1, backoff_s=0.2)
    h1 = flight.FlightRecorder(d, run_id="run-s", host=0, attempt=1)
    h1.record("restore", step=400)
    h1.record("reconfiguration", step=400, trigger="host_lost",
              degree_before=4, degree_after=2,
              reconfiguration_time_s=15.0, resume_step=400)
    h1.record("run_end", step=500, bad_steps=0)
    launcher.record("job_end", rc=0)
    for r in (launcher, h0, h1):
        r.close()

    report = postmortem.build_report(d)
    assert report["complete"] and report["run"] == "run-s"
    chain = " → ".join(report["incident"])
    assert "host_lost" in chain
    assert "attributed as host_lost" in chain
    assert "re-formed 4→2 in 15.0 s" in chain
    assert "resumed from step 400" in chain
    assert "run completed at step 500" in chain
    # dense step events stay out of the timeline except as milestones
    assert any(e["ev"] == "step" for e in report["timeline"])
    assert report["last_step"] == 400


def test_postmortem_exits_cleanly_without_a_record(tmp_path):
    rc = postmortem.main([str(tmp_path / "nothing")])
    assert rc == 1


# ---------------------------------------------------------------------------
# ACCEPTANCE (tier-1): SIGKILL mid-run -> complete record + attribution
# ---------------------------------------------------------------------------

def _train_cmd(ckpt, steps, extra=()):
    return [sys.executable, "train.py", "--backend", "cpu", "--model",
            "resnet18_thin", "--image-size", "32", "--batch-size", "8",
            "--dp", "1", "--synthetic", "--dtype", "float32", "--steps",
            str(steps), "--checkpoint-dir", ckpt, "--checkpoint-every", "2",
            "--log-every", "1000000", *extra]


def _clean_env():
    drop = ("DDL_FAULT_PLAN", "DDL_RESTART_ATTEMPT",
            flight.ENV_FLIGHT_DIR, flight.ENV_RUN_ID)
    return {k: v for k, v in os.environ.items() if k not in drop}


@pytest.mark.flight
def test_sigkill_leaves_complete_record_and_attributed_postmortem(tmp_path):
    """The PR's acceptance bar: SIGKILL a child mid-run (chaos harness),
    then assert (a) the flight record parses whole — the fsync'd fault
    event written moments before the kill survived — and (b) one command
    turns it into a correctly-attributed incident timeline."""
    ckpt = str(tmp_path / "ckpt")
    fdir = str(tmp_path / "flight")
    env = _clean_env()
    proc = subprocess.run(
        [sys.executable, "launch.py", "--num-processes", "1",
         "--max-restarts", "2", "--backoff", "0.2",
         "--heartbeat-timeout", "120", "--flight-dir", fdir, "--"]
        + _train_cmd(ckpt, 6, ("--fault-plan", "sigkill@4")),
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stderr[-2000:]

    events, errors = flight.read_all(fdir)
    assert errors == [], errors  # complete + parseable despite SIGKILL
    assert len({e["run"] for e in events}) == 1  # one identity end to end
    fault = next(e for e in events if e["ev"] == "fault")
    assert fault["kind"] == "sigkill" and fault["step"] == 4
    exit_ = next(e for e in events if e["ev"] == "child_exit")
    assert exit_["rc"] == -9 and exit_["attribution"] == "crash"
    assert any(e["ev"] == "restart" for e in events)
    restore = next(e for e in events if e["ev"] == "restore")
    assert restore["step"] >= 2 and restore["attempt"] == 1
    assert next(e for e in events if e["ev"] == "run_end")["step"] == 6
    assert next(e for e in events if e["ev"] == "job_end")["rc"] == 0
    # the metrics pipeline exported its aggregate next to the record
    assert os.path.exists(os.path.join(fdir, "metrics_snapshot.json"))

    pm = subprocess.run(
        [sys.executable, "tools/postmortem.py", fdir,
         "--checkpoint-dir", ckpt, "--json"],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert pm.returncode == 0, pm.stderr[-2000:]
    report = json.loads(pm.stdout)
    assert report["complete"] is True
    chain = " → ".join(report["incident"])
    assert "sigkill" in chain and "step 4" in chain
    assert "attributed as crash" in chain
    assert "resumed from step" in chain
    assert "run completed at step 6" in chain


# ---------------------------------------------------------------------------
# Integration (slow): in-process loop runs writing real flight records
# ---------------------------------------------------------------------------

def _loop_cfg(**kw):
    from distributeddeeplearning_tpu.config import (
        DataConfig, OptimizerConfig, ParallelConfig, TrainConfig)

    base = dict(
        model="resnet18_thin", global_batch_size=16, dtype="float32",
        log_every=1,
        parallel=ParallelConfig(data=8),
        data=DataConfig(synthetic=True, image_size=32, num_classes=10),
        optimizer=OptimizerConfig(schedule="constant"))
    base.update(kw)
    return TrainConfig(**base)


@pytest.mark.slow
@pytest.mark.flight
def test_nan_grads_run_writes_flagged_flight_event(tmp_path):
    """Satellite: an injected ``nan_grads`` plan must leave a flagged
    anomaly event in the flight record (the compiled guard's bad_step
    flag, observed on the log cadence, reported through anomaly.report)."""
    from distributeddeeplearning_tpu.train import loop

    fdir = str(tmp_path / "flight")
    try:
        summary = loop.run(_loop_cfg(fault_plan="nan_grads@3",
                                     flight_dir=fdir), total_steps=5)
    finally:
        flight.reset()
    assert summary["bad_steps"] == 1
    events, errors = flight.read_all(fdir)
    assert errors == []
    flagged = [e for e in events if e["ev"] == "anomaly"]
    assert any(e["kind"] == "bad_step" and e["step"] == 3 for e in flagged)
    assert next(e for e in events if e["ev"] == "run_end")["step"] == 5


@pytest.mark.slow
@pytest.mark.flight
def test_fault_free_run_writes_zero_anomaly_events(tmp_path):
    """Satellite: the detector's zero-false-positive bar, end to end — a
    clean soak on the real loop (log cadence 1, detector on) must leave
    no anomaly events in the flight record."""
    from distributeddeeplearning_tpu.train import loop

    fdir = str(tmp_path / "flight")
    try:
        summary = loop.run(_loop_cfg(flight_dir=fdir), total_steps=8)
    finally:
        flight.reset()
    assert summary["final_step"] == 8
    events, errors = flight.read_all(fdir)
    assert errors == []
    assert [e for e in events if e["ev"] == "anomaly"] == []
    assert [e["ev"] for e in events if e["ev"] in
            ("run_start", "run_end")] == ["run_start", "run_end"]
