#!/usr/bin/env python3
"""Find the highest request rate a serving cell's engine sustains: the same
traffic mix at each of `--rates`, one engine after another in one process.
Run once, when a cell is defined; the cell's traffic file then fixes a rate
(about four fifths of the knee for a cell below capacity).

    python3 benchmark/sweep.py --workload <cell> --rates 10,20,30 --seconds 10

A rate is sustained when the backlog does not grow: what is waiting when the
window closes is no more than arrives in a second or so, and the tokens
emitted keep up with the tokens asked for. One JSON line per rate.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--out", default=None)
    p.add_argument("--rehearsal", action="store_true")
    args = p.parse_args(argv)

    from benchmark import harness
    import distributeddeeplearning_tpu  # noqa: F401

    cell = harness.load_cell(args.workload)
    harness.devices_for(cell, args.rehearsal)
    runner = harness.load_module("runners", cell["traffic_file"]["runner"])
    for rate in [float(x) for x in args.rates.split(",") if x]:
        tr = dict(cell["traffic_file"], rate_rps=rate)
        got = runner.offer(cell, args, args.seconds, traffic=tr)
        engine, res, e2e, spans = (got["engine"], got["res"], got["e2e"],
                                   got["spans"])
        t_open, t_close = res["t_open"], res["t_close"]
        asked = sum(item["max_new_tokens"] for item, _, due in res["sent"]
                    if t_open <= due < t_close) / args.seconds
        drain_s = max([r.finished_s for _, r, due in res["sent"]
                       if r.finished_s and due < t_close]
                      + [t_close]) - t_close
        # due before the close and still without a first token at the close
        backlog = sum(1 for _, r, due in res["sent"] if due < t_close
                      and (r.ttft_s is None or due + r.ttft_s > t_close))
        rec = {"rate_rps": rate, "attempted": e2e["attempted"],
               "backlog_at_close": backlog,
               "failed": e2e["failed"],
               "tokens_per_s": e2e["serve_tokens_per_s"],
               "tokens_asked_per_s": asked,
               "ttft_p95_ms": e2e["ttft_p95_ms"],
               "itl_p95_ms": e2e["itl_p95_ms"],
               "decode_step_ms": 1e3 * statistics.median(
                   spans.durations("decode_step") or [0.0]),
               "prefill_step_ms": 1e3 * statistics.median(
                   spans.durations("prefill_step") or [0.0]),
               "steps": engine.steps, "preemptions": engine.preemptions,
               "drain_after_close_s": drain_s}
        line = json.dumps(rec)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as fh:
                fh.write(line + "\n")
        engine.shutdown()
        del engine, res, e2e, got
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
