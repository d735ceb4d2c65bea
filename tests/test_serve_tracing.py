"""Per-request serve tracing + TTFT attribution (ISSUE 19).

The load-bearing pins, in order:

* **Exact-sum attribution** — every finished request's component
  decomposition (queue / admission_stall / prefill / interference /
  decode) sums to its measured total latency, and the TTFT snapshot sums
  to its measured TTFT, to float precision. The protocol (one moving
  mark per request, every interval charged to exactly one component)
  makes "the components don't add up" a structural impossibility, and
  these tests keep it that way.
* **Zero overhead off** — with tracing disabled the engine holds no
  tracer, requests carry no trace state, and a decode step allocates
  NOTHING in tracing.py/telemetry.py (tracemalloc-pinned), so serving
  throughput is untouched.
* **Crash-safe multi-writer traces** — concurrent exports to one path
  lose nothing (flock-serialized read-modify-write), a SIGKILL-truncated
  replica trace is salvaged by the merge, and a request re-dispatched
  across replica processes appears as ONE flow id spanning both pids in
  the merged trace.

Around the pins: the span-name registry schema (every emitted ``serve:*``
name is registered; ddl-lint enforces the same at the AST level),
scheduler skip-reason classification, the attribution-fed anomaly kinds,
metrics percentile summaries, and the tools/trace_report.py CLI.
"""

import json
import os
import sys
import threading
import tracemalloc

import pytest

from distributeddeeplearning_tpu.observability import (anomaly, metrics,
                                                       telemetry)
from distributeddeeplearning_tpu.serve import tracing
from distributeddeeplearning_tpu.serve.engine import Engine, ServeConfig
from distributeddeeplearning_tpu.serve.scheduler import (SloScheduler,
                                                         TenantPolicy)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from tools import summarize_trace  # noqa: E402
from tools import trace_report  # noqa: E402

pytestmark = pytest.mark.serve

VOCAB = 97


def _engine(model="gpt_tiny", **kw):
    kw.setdefault("vocab_size", VOCAB)
    kw.setdefault("max_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 32)
    kw.setdefault("max_pages_per_slot", 8)
    kw.setdefault("prefill_buckets", (8,))
    kw.setdefault("compile_cache", False)
    t = [0.0]

    def clock():
        t[0] += 0.001  # strictly increasing: every read a distinct time
        return t[0]

    return Engine(ServeConfig(model=model, **kw), clock=clock)


def _drain(eng):
    while not eng.idle:
        eng.step()


@pytest.fixture
def traced():
    """Enabled telemetry singleton (no trace dir — events inspected via
    snapshot); engines built inside resolve a live tracer."""
    tele = telemetry.configure(enabled=True)
    metrics.reset()
    yield tele
    telemetry.reset()
    metrics.reset()


class _TracedRun:
    """One traced max_slots=1 engine run, shared (read-only) by every
    test that only inspects its artifacts — the engine compile is the
    expensive part, so it is paid once for the module."""

    def __init__(self, trace_dir):
        self.trace_dir = trace_dir
        telemetry.configure(enabled=True, trace_dir=trace_dir)
        metrics.reset()
        try:
            eng = _engine(max_slots=1)
            eng.warmup()
            for i in range(4):
                eng.submit([(7 * i + j) % VOCAB + 1 for j in range(6)],
                           max_new_tokens=4)
            _drain(eng)
            self.finished = list(eng.finished)
            self.events = telemetry.get().snapshot()
            telemetry.get().export()  # export drains the buffer: snapshot first
        finally:
            telemetry.reset()
            metrics.reset()


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory):
    return _TracedRun(str(tmp_path_factory.mktemp("traced_run")))


# --- exact-sum attribution --------------------------------------------------

def test_attribution_sums_exactly_under_queueing(traced_run):
    """max_slots=1 forces real queueing/interference; every component
    decomposition still sums to the measured latency to float precision
    — far inside the 1 ms acceptance bound."""
    finished = traced_run.finished
    assert len(finished) == 4
    for req in finished:
        rt = req.trace
        assert rt is not None and rt.done
        assert set(rt.comp) == set(tracing.COMPONENTS)
        assert all(v >= 0.0 for v in rt.comp.values())
        total = req.finished_s - req.arrival_s
        assert sum(rt.comp.values()) == pytest.approx(total, abs=1e-9)
        assert sum(rt.ttft_comp.values()) == pytest.approx(req.ttft_s,
                                                           abs=1e-9)
    # With one slot, the later arrivals waited: their non-service time
    # is attributed, not lost.
    waited = [r for r in finished
              if r.trace.comp["queue"] + r.trace.comp["interference"] > 0]
    assert len(waited) >= 2

    atts = [e for e in traced_run.events
            if e.get("ph") == "i" and e["name"] == "serve:attribution"]
    assert len(atts) == 4
    for e in atts:
        assert set(e["args"]["components"]) == set(tracing.COMPONENTS)
        assert abs(e["args"]["sum_err_s"]) < 1e-9
        assert abs(e["args"]["ttft_sum_err_s"]) < 1e-9


def test_every_emitted_serve_name_is_registered(traced_run):
    emitted = {e["name"] for e in traced_run.events
               if str(e.get("name", "")).startswith("serve:")}
    assert emitted, "a traced run must emit serve spans"
    assert emitted <= set(tracing.REGISTERED_PHASES)
    for must in ("serve:submit", "serve:scheduler_plan", "serve:page_alloc",
                 "serve:prefill", "serve:decode", "serve:decode_tick",
                 "serve:attribution", "serve:request",
                 "serve:request_flow"):
        assert must in emitted, f"core span {must} missing from a run"


def test_components_schema_is_exhaustive():
    """The component set is the closed vocabulary every consumer (bench
    record, trace_report tables, docs) keys on."""
    assert tracing.COMPONENTS == ("queue", "admission_stall", "prefill",
                                  "interference", "decode")
    rt = tracing.RequestTrace(1, 0.0)
    assert set(rt.comp) == set(tracing.COMPONENTS)
    for reason in tracing.STALL_REASONS:
        assert tracing.component_for_reason(reason) == "admission_stall"
    for reason in ("priority", "no_slot", "no_pages", "backoff",
                   "tenant_cap", "anything-else"):
        assert tracing.component_for_reason(reason) in tracing.COMPONENTS


def test_resumed_submit_continues_the_flow(traced):
    """A re-dispatched victim (supervisor retry after replica loss)
    CONTINUES its flow under the supervisor's global id — phase "t", not
    a fresh "s" — and the finish closes the same id."""
    eng = _engine()
    eng.warmup()
    eng.submit([3, 1, 4, 1, 5, 9], max_new_tokens=3, trace_id=424242,
               resumed=True)
    _drain(eng)
    flows = [e for e in traced.snapshot()
             if e["name"] == "serve:request_flow"]
    assert [e["ph"] for e in flows] == ["t", "f"]
    assert all(e["id"] == 424242 for e in flows)


# --- disabled path: a TRUE no-op -------------------------------------------

def test_disabled_tracing_is_zero_allocation():
    """Tracing off: no tracer object, no per-request trace state, and a
    decode step allocates zero objects in tracing.py/telemetry.py — the
    'tracing off leaves serve throughput unchanged' acceptance pin."""
    telemetry.reset()  # the disabled singleton
    eng = _engine()
    eng.warmup()
    assert eng._tracer is None and eng.tracer is None
    req = eng.submit([2, 7, 1, 8, 2, 8], max_new_tokens=6)
    assert req.trace is None
    eng.step()  # admission + prefill before the pinned window

    filters = [tracemalloc.Filter(True, "*serve/tracing.py"),
               tracemalloc.Filter(True, "*observability/telemetry.py")]
    tracemalloc.start()
    try:
        before = tracemalloc.take_snapshot().filter_traces(filters)
        for _ in range(3):
            eng.step()  # pure decode ticks
        after = tracemalloc.take_snapshot().filter_traces(filters)
    finally:
        tracemalloc.stop()
    diff = [d for d in after.compare_to(before, "filename")
            if d.size_diff > 0 or d.count_diff > 0]
    assert diff == [], (
        f"tracing-disabled decode allocated in the tracing stack: {diff}")
    assert telemetry.get().snapshot() == []


# --- scheduler skip reasons -------------------------------------------------

def _req(uid, tenant="default", arrival=0.0, total=8, not_before=0.0):
    class R:
        pass
    r = R()
    r.uid, r.tenant, r.arrival_s, r.total_tokens = uid, tenant, arrival, total
    r.not_before_s = not_before
    return r


def test_plan_reasons_classify_every_skipped_request():
    sched = SloScheduler()
    # No free slots and nothing preemptible: everyone skipped as no_slot.
    plan = sched.plan(now=1.0, waiting=[_req(0), _req(1)], live=[],
                      free_slots=0, free_pages=100, page_size=4)
    assert plan.reasons == {0: "no_slot", 1: "no_slot"}
    # Slots free but pages exhausted: no_pages — an admission stall, not
    # scheduler interference (the attribution layer splits on this).
    plan = sched.plan(now=1.0, waiting=[_req(0), _req(1)], live=[],
                      free_slots=2, free_pages=0, page_size=4)
    assert plan.reasons == {0: "no_pages", 1: "no_pages"}
    assert tracing.component_for_reason("no_pages") == "admission_stall"
    assert tracing.component_for_reason("no_slot") == "interference"
    # A backoff hold is named even when capacity exists.
    plan = sched.plan(now=1.0, waiting=[_req(5, not_before=9.0)], live=[],
                      free_slots=2, free_pages=100, page_size=4)
    assert plan.reasons == {5: "backoff"} and not plan.admit
    # Admitted requests carry no reason.
    plan = sched.plan(now=1.0, waiting=[_req(7)], live=[],
                      free_slots=2, free_pages=100, page_size=4)
    assert [r.uid for r in plan.admit] == [7] and plan.reasons == {}


# --- concurrent export, truncation salvage, cross-process flows -------------

def test_concurrent_exports_to_one_path_lose_nothing(tmp_path):
    """N registries flushing to the same trace file concurrently (the
    supervisor + a dying replica's final export): the flock-serialized
    read-modify-write keeps every event exactly once."""
    path = str(tmp_path / "trace.p0.json")
    errs = []

    def writer(i):
        try:
            tele = telemetry.Telemetry(enabled=True)
            for j in range(25):
                tele.instant(f"w{i}.e{j}", writer=i)
                if j % 10 == 9:
                    tele.export(path)
            tele.export(path)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errs == []
    events = telemetry.load_events(path)
    names = [e["name"] for e in events if e["ph"] == "i"]
    assert sorted(names) == sorted(f"w{i}.e{j}"
                                   for i in range(4) for j in range(25))


def test_truncated_replica_trace_salvaged_by_merge(tmp_path):
    t0 = telemetry.Telemetry(enabled=True, trace_dir=str(tmp_path),
                             process_index=0, process_name="replica-0")
    for i in range(3):
        t0.instant(f"ok{i}")
    t0.export()
    t1 = telemetry.Telemetry(enabled=True, trace_dir=str(tmp_path),
                             process_index=1, process_name="replica-1")
    for i in range(3):
        t1.instant(f"cut{i}")
    p1 = t1.export()
    text = open(p1).read()
    with open(p1, "w") as fh:  # SIGKILL mid-copy: cut inside the 3rd event
        fh.write(text[:text.rindex('"cut2"') + 3])
    merged, errors = telemetry.merge_trace_dir(str(tmp_path))
    assert merged and errors and "truncated" in errors[0]
    names = {e["name"] for e in telemetry.load_events(merged)}
    assert {"ok0", "ok1", "ok2", "cut0", "cut1"} <= names
    assert "cut2" not in names  # the lost tail is reported, not invented
    # Directory mode never double-counts the merged file...
    assert merged not in summarize_trace.expand_traces([str(tmp_path)])
    # ...but a dir holding ONLY the merged artifact falls back to it.
    only = tmp_path / "pulled"
    only.mkdir()
    os.rename(merged, only / "trace.merged.json")
    assert summarize_trace.expand_traces([str(only)]) == [
        str(only / "trace.merged.json")]


def test_cross_process_flow_links_in_merged_trace(tmp_path):
    """A request whose first life was on replica 0 and whose re-dispatch
    landed on replica 1: one flow id, two pids, reported by both
    summarize_trace and trace_report."""
    t0 = telemetry.Telemetry(enabled=True, trace_dir=str(tmp_path),
                             process_index=0, process_name="replica-0")
    t0.record_span("serve:prefill", 1.0, 1.2, request=0, trace=77)
    t0.flow("serve:request_flow", 77, "s", ts_s=1.1, request=0)
    t0.export()
    t1 = telemetry.Telemetry(enabled=True, trace_dir=str(tmp_path),
                             process_index=1, process_name="replica-1")
    t1.record_span("serve:prefill", 2.0, 2.3, request=0, trace=77,
                   resumed=True)
    t1.flow("serve:request_flow", 77, "t", ts_s=2.15, request=0)
    t1.flow("serve:request_flow", 77, "f", ts_s=2.5, request=0)
    t1.export()
    merged, errors = telemetry.merge_trace_dir(str(tmp_path))
    assert merged and not errors
    events = telemetry.load_events(merged)

    fl = summarize_trace.flow_summary(events)
    assert fl["chains"] == 1
    assert fl["cross_process"] == [
        {"id": 77, "name": "serve:request_flow", "pids": [0, 1],
         "events": 3}]

    rep = trace_report.serve_report(events)
    assert rep["cross_process_flows"] == [{"id": 77, "pids": [0, 1]}]


def test_async_track_pairing_flags_unretired_requests():
    t = telemetry.Telemetry(enabled=True)
    t.async_begin("serve:request", 1, ts_s=0.0)
    t.async_end("serve:request", 1, ts_s=1.0)
    t.async_begin("serve:request", 2, ts_s=0.5)  # never retires
    fl = summarize_trace.flow_summary(t.snapshot())
    assert fl["async_unclosed"] == ["2"]
    assert fl["async_unmatched_ends"] == 0


# --- attribution-fed anomaly kinds -----------------------------------------

def test_serve_attribution_anomaly_kinds_fire_and_stay_quiet():
    det = anomaly.AnomalyDetector()
    for step in range(6):  # a healthy baseline: no flags, ever
        assert det.update_serve(step, queue_wait_s=0.010 + step * 1e-4,
                                alloc_stall_s=0.002,
                                decode_tick_s=0.004) == []
    flags = det.update_serve(10, queue_wait_s=1.0, alloc_stall_s=0.8,
                             decode_tick_s=0.5)
    kinds = {f["kind"] for f in flags}
    assert kinds == {"queue_wait_regression", "allocation_stall",
                     "decode_stall"}
    # An untraced engine supplies None: those detectors stay silent.
    det2 = anomaly.AnomalyDetector()
    for step in range(8):
        assert det2.update_serve(step) == []


# --- metrics percentiles ----------------------------------------------------

def test_percentile_linear_interpolation():
    assert metrics.percentile([], 50) is None
    assert metrics.percentile([5.0], 99) == 5.0
    assert metrics.percentile([1, 2, 3, 4], 50) == 2.5
    assert metrics.percentile([4, 1, 3, 2], 50) == 2.5  # order-free
    assert metrics.percentile(range(1, 101), 99) == pytest.approx(99.01)
    assert metrics.percentile([1, 2, float("nan"), 3, 4], 50) == 2.5


def test_registry_percentiles_in_aggregate_and_prometheus():
    reg = metrics.MetricsRegistry(run_id="r1")
    for i in range(1, 101):
        reg.observe("serve_ttft_s", i / 100.0, step=i)
    m = reg.aggregate()["metrics"]["serve_ttft_s"]
    assert m["percentiles"]["p50"] == pytest.approx(0.505)
    assert m["percentiles"]["p90"] == pytest.approx(0.901)
    assert m["percentiles"]["p99"] == pytest.approx(0.9901)
    text = reg.prometheus_text()
    assert '# TYPE ddl_serve_ttft_s_p99 gauge' in text
    assert 'ddl_serve_ttft_s_p99{run="r1"} 0.9901' in text
    # A single sample gets no quantile lines (they would all be the
    # sample itself — noise, not signal).
    reg2 = metrics.MetricsRegistry(run_id="r2")
    reg2.observe("x", 1.0)
    assert "_p99" not in reg2.prometheus_text()


# --- straggler warnings on the shared warn path -----------------------------

def test_straggler_warn_path_emits_ratio_gauge_and_data_wait(monkeypatch,
                                                            capsys):
    import numpy as np
    from jax.experimental import multihost_utils

    from distributeddeeplearning_tpu.observability import straggler

    per_host = [(0.10, 0.01), (0.10, 0.01), (0.40, 0.30)]
    monkeypatch.setattr(
        multihost_utils, "process_allgather",
        lambda x: np.concatenate([np.asarray(h, np.float64)
                                  for h in per_host]))
    telemetry.configure(enabled=True)
    metrics.reset()
    try:
        mon = straggler.StragglerMonitor(1.5, len(per_host))
        rec = mon.collect(10, *per_host[0])
        assert rec["straggler_host"] == 2
        inst = [e for e in telemetry.get().snapshot()
                if e["name"] == "straggler"]
        assert len(inst) == 1
        assert inst[0]["args"]["data_wait_s"] == pytest.approx(0.30)
        ratio = metrics.get().aggregate()["metrics"][
            "straggler_step_time_ratio"]
        assert ratio["last"] == pytest.approx(0.40 / 0.20)
        assert "# straggler: host 2" in capsys.readouterr().err
    finally:
        telemetry.reset()
        metrics.reset()


# --- trace_report CLI -------------------------------------------------------

def test_trace_report_serve_cli(traced_run, capsys):
    assert trace_report.main(
        ["--serve", traced_run.trace_dir, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["aggregate"]["requests"] == 4
    assert rep["max_sum_err_s"] < 1e-6
    cp = rep["p99_critical_path"]
    assert cp["dominant"] in tracing.COMPONENTS
    shares = cp["shares"]
    assert set(shares) == set(tracing.COMPONENTS)
    for scope in ("all", "p99_tail"):
        assert sum(shares[c][scope] for c in tracing.COMPONENTS) == \
            pytest.approx(1.0, abs=0.01)
    # Human mode renders the same report without error.
    assert trace_report.main(
        ["--serve", traced_run.trace_dir, "--top", "2"]) == 0
    out = capsys.readouterr().out
    assert "p99 critical path" in out and "dominant" in out
