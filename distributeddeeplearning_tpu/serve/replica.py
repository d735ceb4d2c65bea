"""One supervised serve replica: an Engine wrapped in the file protocol
``launch.run_serve`` speaks.

The supervisor (launch.py serve mode) owns the request trace and the
frontend view of every stream; replicas own a model and a paged KV pool.
The wire protocol is deliberately plain files, chosen for the same reason
the flight recorder is fsync'd JSONL — every piece must survive a replica
dying at ANY instruction with no cleanup:

- ``<workdir>/config.json``        — ServeConfig fields (shared by all
  replicas; same fingerprint -> shared AOT executable cache -> a restarted
  replica warm-boots with zero retraces).
- ``<workdir>/inbox/r<I>.a<A>/*.json`` — one file per dispatched request:
  ``{uid, tenant, prompt, max_new_tokens, prefix}``. ``prefix`` is the
  token stream the supervisor already received for a re-dispatched victim;
  the replica folds it into the prompt (``Engine`` prefix-folding), so the
  continuation is token-identical to the uninterrupted run.
- ``<workdir>/events/r<I>.jsonl``  — append-only stream back: ``ready``
  (the device this replica holds + its AOT warm-boot stats) / ``accepted``
  / ``token`` / ``finished`` / ``failed`` / ``drained``. Flushed per step:
  an OS-buffered line survives SIGKILL of the writer, so the supervisor's
  view after a replica loss is exactly "everything up to the last completed
  step" — tokens emitted by the dying step were never reported and are
  regenerated identically on replay.
- ``<workdir>/stop.r<I>``          — drain sentinel: finish live work, run
  the shutdown leak gate, exit 0. A replica that leaked pages exits
  nonzero here — leaks are loud, not logged.

Heartbeats (``DDL_HEARTBEAT_DIR``/``DDL_PROCESS_ID``) and the flight
recorder (``DDL_FLIGHT_DIR``) arm exactly as training children do, so the
supervisor reuses the launcher's staleness clock and attribution. Fault
plans arrive via ``DDL_FAULT_PLAN`` (the supervisor's per-replica
injection), resolved attempt-scoped inside the Engine.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _emit(fh, obj: dict) -> None:
    fh.write(json.dumps(obj) + "\n")
    fh.flush()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="supervised serve replica (spawned by launch.py serve "
                    "mode; not a user entry point)")
    parser.add_argument("--workdir", required=True)
    parser.add_argument("--replica", type=int, required=True)
    parser.add_argument("--poll-s", type=float, default=0.02,
                        help="idle inbox poll interval")
    args = parser.parse_args(argv)
    wd, rid = args.workdir, args.replica

    from distributeddeeplearning_tpu.observability import (anomaly, flight,
                                                           health, telemetry)
    from distributeddeeplearning_tpu.robustness import faults
    from distributeddeeplearning_tpu.serve import engine as enginelib

    with open(os.path.join(wd, "config.json"), encoding="utf-8") as f:
        d = json.load(f)
    # JSON turned the bucket tuple into a list; normalize it back so the
    # serve fingerprint (and with it the shared AOT executable cache key)
    # is byte-identical to an in-process Engine built from the same
    # ServeConfig — warm restarts depend on that exact match.
    if "prefill_buckets" in d:
        d["prefill_buckets"] = tuple(d["prefill_buckets"])
    cfg = enginelib.ServeConfig(**d)

    flight.configure_from_env(host=rid)
    # Tracing destination rides DDL_TRACE_DIR from the supervisor; the
    # replica id is the Chrome pid, so every replica gets its own named
    # process track in the merged trace. Must happen BEFORE the engine
    # is built — the engine resolves its tracer at construction.
    tele = telemetry.configure_from_env(
        process_index=rid, process_name=f"serve-replica-{rid}")
    attempt = faults.current_attempt()
    flight.get().record("serve_replica_start", replica=rid, attempt=attempt)
    hb = health.HeartbeatWriter.from_env()

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "id": devices[0].id,
              "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS")}
    if device["platform"] == "tpu" and len(devices) != 1:
        # One process per chip (launch._spawn_replica): a replica that sees
        # the whole host would take chips its siblings need.
        raise RuntimeError(
            f"serve replica {rid} sees {len(devices)} TPU devices; it must "
            f"be given exactly one (TPU_VISIBLE_CHIPS="
            f"{device['visible_chips']!r})")

    eng = enginelib.Engine(cfg)
    aot = eng.warmup()
    if hb is not None:
        hb.beat(0)
    # Per-attempt inbox: a restarted replica must NOT replay its
    # predecessor's inbox — the supervisor already re-dispatched those
    # victims (possibly to this very replica, into the new inbox).
    inbox = os.path.join(wd, "inbox", f"r{rid}.a{attempt}")
    events_path = os.path.join(wd, "events", f"r{rid}.jsonl")
    stop_path = os.path.join(wd, f"stop.r{rid}")
    os.makedirs(inbox, exist_ok=True)
    os.makedirs(os.path.dirname(events_path), exist_ok=True)
    ev = open(events_path, "a", encoding="utf-8")
    _emit(ev, {"ev": "ready", "replica": rid, "attempt": attempt,
               "aot": aot, "device": device})

    seen: set = set()
    reqs: dict = {}    # supervisor uid -> engine Request
    sent: dict = {}    # supervisor uid -> tokens already reported
    closed: set = set()

    def pull_inbox() -> None:
        try:
            names = sorted(os.listdir(inbox))
        except OSError:
            return
        for name in names:
            if not name.endswith(".json") or name in seen:
                continue
            seen.add(name)
            with open(os.path.join(inbox, name), encoding="utf-8") as f:
                d = json.load(f)
            uid = int(d["uid"])
            prefix = [int(t) for t in (d.get("prefix") or [])]
            # The supervisor's GLOBAL uid is the trace/flow id (engine
            # uids are replica-local): a re-dispatched victim keeps ONE
            # flow id across both replica processes, which is what links
            # its spans in the merged trace.
            req = eng.submit(
                [int(t) for t in d["prompt"]] + prefix,
                max_new_tokens=int(d["max_new_tokens"]) - len(prefix),
                tenant=d.get("tenant", "default"),
                trace_id=int(d.get("trace", uid)),
                resumed=bool(d.get("redispatch")) or bool(prefix))
            reqs[uid], sent[uid] = req, 0
            _emit(ev, {"ev": "accepted", "uid": uid, "replica": rid,
                       "resumed_from": len(prefix)})

    def report_progress() -> None:
        for uid, req in reqs.items():
            n = len(req.tokens)
            if n > sent[uid]:
                _emit(ev, {"ev": "token", "uid": uid, "step": eng.steps,
                           "tokens": [int(t)
                                      for t in req.tokens[sent[uid]:n]]})
                sent[uid] = n
            if uid in closed:
                continue
            if req.failed is not None:
                closed.add(uid)
                _emit(ev, {"ev": "failed", "uid": uid, "step": eng.steps,
                           "reason": req.failed})
            elif req.finished_s is not None:
                closed.add(uid)
                _emit(ev, {"ev": "finished", "uid": uid, "step": eng.steps,
                           "tokens": n})

    # Attribution-fed anomaly watch (queue-wait regression, allocation
    # stall, decode stall) rides the same cadence as the trace export;
    # both exist only when the supervisor asked for tracing, so an
    # untraced replica's step loop is unchanged.
    det = anomaly.AnomalyDetector() if tele is not None else None
    det_last = (0, 0, 0, 0, 0)

    while True:
        pull_inbox()
        if eng.idle:
            if os.path.exists(stop_path):
                break
            if hb is not None:
                hb.beat(eng.steps)
            time.sleep(args.poll_s)
            continue
        eng.step()
        if hb is not None:
            hb.beat(eng.steps)
        report_progress()
        if tele is not None:
            # Export every step: the merge in telemetry.export is what
            # makes a SIGKILL'd replica lose at most the dying step's
            # spans — the pre-kill life of a later re-dispatched request
            # survives into the merged trace.
            tele.export()
            if det is not None and eng.steps % 16 == 0:
                cur = (eng.sheds, eng.deadline_misses, len(eng.finished),
                       eng.spec_proposed, eng.spec_accepted)
                diff = [c - p for c, p in zip(cur, det_last)]
                det_last = cur
                sig = (eng.tracer.interval_signals()
                       if eng.tracer is not None else {})
                anomaly.report(
                    det.update_serve(
                        eng.steps, queue_depth=len(eng.waiting),
                        sheds=diff[0], deadline_misses=diff[1],
                        finished=diff[2], spec_proposed=diff[3],
                        spec_accepted=diff[4],
                        queue_wait_s=sig.get("queue_wait_s"),
                        alloc_stall_s=sig.get("alloc_stall_s"),
                        decode_tick_s=sig.get("decode_tick_s")),
                    flight_rec=flight.get(), tele=tele)

    try:
        eng.shutdown()  # raises on a page leak -> nonzero exit, by design
    finally:
        if tele is not None:
            tele.export()
    # Fast-path counters ride the drain event so the supervisor (and
    # doctor's serve report) can aggregate prefix reuse and speculative
    # acceptance across replicas without scraping flight logs.
    _emit(ev, {"ev": "drained", "replica": rid, "steps": eng.steps,
               "finished": len(eng.finished), "failed": len(eng.failed),
               "prefix_hits": eng.prefix_hits,
               "prefix_misses": eng.prefix_misses,
               "prefix_tokens_reused": eng.prefix_tokens_reused,
               "prefix_evictions": (eng.prefix.evictions
                                    if eng.prefix is not None else 0),
               "cow_copies": eng.cow_copies,
               "spec_rounds": eng.spec_rounds,
               "spec_proposed": eng.spec_proposed,
               "spec_accepted": eng.spec_accepted})
    return 0


if __name__ == "__main__":
    sys.exit(main())
