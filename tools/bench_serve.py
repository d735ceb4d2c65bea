#!/usr/bin/env python
"""Continuous-batching serve bench: seeded Poisson open-loop load.

    python tools/bench_serve.py [--model gpt2_small] [--requests 16]
        [--rate 80] [--max-new 16] [--platform cpu]

At the default load (80 req/s against a batch-1 capacity of a few
requests/sec) both arms are saturated, so tokens/sec/chip measures
engine capacity, not the arrival rate. At low rates both arms are
arrival-limited and the speedup tends to 1 by construction.

One requester process submits requests at exponential inter-arrival times
(open loop: arrivals do not wait for completions — the honest serving
load model) against two arms over the SAME request trace:

- **continuous** — serve/engine.py: slots admitted/retired every step,
  paged KV cache, prefill/decode split;
- **sequential baseline** — models/generate.py ``use_cache=True``, one
  request at a time in arrival order (what the repo could do before this
  engine existed). Its TTFT is the full generation latency: the
  ``generate()`` API yields nothing until the scan finishes, which is
  precisely the serving gap the engine closes. Its inter-token latency is
  the per-call average (scan internals are not observable).

Both arms run greedy, so outputs are token-identical — the bench asserts
it request-by-request (``token_identity_checked``) before reporting any
number. ``--chaos`` adds a third, supervised arm (launch.run_serve, two
replicas): the same trace fault-free and then under ``sigkill`` +
``decode_stall`` injection, reporting p50/p99 TTFT, tokens/sec/chip and
``recovery_overhead_frac`` — after asserting the recovered streams are
token-identical to the fault-free run and the page-leak check held.
Records are provenance-stamped via observability/perf_report.py;
the summary lands in the ``last_serve`` sidecar
(observability/sidecars.py) for tools/doctor.py.

Serve fast path (docs/serving.md "Prefix reuse" / "Speculative
decoding"): ``--prefix-cache`` / ``--spec-draft-model``+``--spec-k``
turn the engine features on; ``--shared-prefix-len N`` makes the trace
realistic for them — every tenant gets its own seeded N-token "system
prompt" and each request is that shared head plus a unique tail, so the
radix cache has real reuse to find. Prefix hit rate, tokens reused, COW
copies, evictions and speculative acceptance are stamped into the
record.

``--fixed-slo S`` switches to the capacity-at-SLO protocol the fast
path is judged by: sweep offered load (``--slo-rates``), run the
configured engine AND a features-off baseline (the PR-12 engine) over
the SAME trace at each rate, assert token identity between them, and
report each arm's best tokens/sec/chip among rates whose p99 TTFT still
meets the SLO — raw throughput at blown latency does not count.
``speedup_at_slo`` is the fast/baseline ratio of those numbers.

``--trace-dir DIR`` turns on per-request tracing (docs/serve_tracing.md):
the continuous arm writes a Chrome trace to ``DIR/trace.p0.json`` and the
record gains ``continuous.ttft_attribution`` — p50/p99/mean of each TTFT
component (queue / admission_stall / prefill / interference / decode),
reported only after every request's components are verified to sum back
to its measured TTFT within 1 ms. With ``--chaos`` the supervised arm
writes per-replica traces under ``DIR/chaos/`` and the bench asserts the
re-dispatched requests' spans are flow-linked across both replica pids
in the merged trace. Read the breakdown with
``python tools/trace_report.py --serve DIR``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _pct(values, q):
    if not values:
        return None
    import numpy as np
    return round(float(np.percentile(np.asarray(values, float), q)), 6)


def _latency_block(ttfts, itls):
    return {"ttft_s": {"p50": _pct(ttfts, 50), "p99": _pct(ttfts, 99)},
            "itl_s": {"p50": _pct(itls, 50), "p99": _pct(itls, 99)}}


def _ttft_attribution(requests) -> dict:
    """Aggregate the tracer's per-request TTFT decomposition (queue /
    admission_stall / prefill / interference / decode) into p50/p99/mean
    per component, after asserting each request's components sum back to
    its measured TTFT within 1 ms — an attribution that does not add up
    is not reported."""
    from distributeddeeplearning_tpu.serve import tracing

    per_comp = {c: [] for c in tracing.COMPONENTS}
    ttft_errs, total_errs = [], []
    for r in requests:
        rt = getattr(r, "trace", None)
        if rt is None or rt.ttft_comp is None or r.ttft_s is None:
            continue
        ttft_errs.append(abs(sum(rt.ttft_comp.values()) - r.ttft_s))
        if r.finished_s is not None:
            total_errs.append(abs(sum(rt.comp.values())
                                  - (r.finished_s - r.arrival_s)))
        for c in tracing.COMPONENTS:
            per_comp[c].append(rt.ttft_comp.get(c, 0.0))
    if ttft_errs and max(ttft_errs) >= 1e-3:
        raise AssertionError(
            f"TTFT attribution components sum {max(ttft_errs) * 1e3:.3f} ms "
            f"away from the measured TTFT — the exact-sum protocol is "
            f"broken; do not trust the breakdown")
    out = {c: {"p50": _pct(v, 50), "p99": _pct(v, 99),
               "mean": round(sum(v) / len(v), 6) if v else None}
           for c, v in per_comp.items()}
    out["requests"] = len(ttft_errs)
    out["max_ttft_sum_err_ms"] = (round(max(ttft_errs) * 1e3, 6)
                                  if ttft_errs else None)
    out["max_total_sum_err_ms"] = (round(max(total_errs) * 1e3, 6)
                                   if total_errs else None)
    return out


def run_continuous(engine, trace, clock):
    """Drive the engine under the arrival trace (real sleeps in the idle
    gaps — open loop, submission never waits for completions)."""
    t0 = clock()
    pending = list(trace)
    while pending or not engine.idle:
        now = clock() - t0
        while pending and pending[0]["arrival_s"] <= now:
            item = pending.pop(0)
            engine.submit(item["prompt"],
                          max_new_tokens=item["max_new_tokens"],
                          tenant=item["tenant"],
                          arrival_s=t0 + item["arrival_s"])
        if engine.idle and pending:
            time.sleep(max(0.0, pending[0]["arrival_s"] - (clock() - t0)))
            continue
        engine.step()
    done = {r.uid: r for r in engine.finished}
    end = max(r.finished_s for r in done.values())
    total_tokens = sum(len(r.tokens) for r in done.values())
    return {
        "requests": [done[uid] for uid in sorted(done)],
        "tokens": total_tokens,
        "window_s": end - (t0 + trace[0]["arrival_s"]),
        "steps": engine.steps,
        "preemptions": engine.preemptions,
    }


def run_sequential(model, variables, trace, clock):
    """FIFO batch-1 ``generate(use_cache=True)`` over the same trace —
    the strongest form of the old API: each distinct
    (prompt_len, max_new) shape is jit-wrapped and warmed before timing
    (bare ``generate`` re-traces its scan per call; charging the baseline
    for that would inflate the speedup with Python overhead instead of
    measuring batching)."""
    import jax
    import jax.numpy as jnp

    from distributeddeeplearning_tpu.models.generate import generate

    compiled = {}
    for plen in sorted({len(t["prompt"]) for t in trace}):
        for mnew in sorted({t["max_new_tokens"] for t in trace
                            if len(t["prompt"]) == plen}):
            fn = jax.jit(lambda v, ids, m=mnew: generate(
                model, v, ids, max_new_tokens=m, use_cache=True))
            jax.block_until_ready(
                fn(variables, jnp.ones((1, plen), jnp.int32)))
            compiled[(plen, mnew)] = fn

    t0 = clock()
    results = []
    total_tokens = 0
    for item in trace:
        wait = item["arrival_s"] - (clock() - t0)
        if wait > 0:
            time.sleep(wait)
        fn = compiled[(len(item["prompt"]), item["max_new_tokens"])]
        out = fn(variables, jnp.asarray([item["prompt"]], jnp.int32))
        jax.block_until_ready(out)
        done_s = clock() - t0
        toks = [int(x) for x in
                list(jax.device_get(out)[0][len(item["prompt"]):])]
        total_tokens += len(toks)
        results.append({
            "tokens": toks,
            "ttft_s": done_s - item["arrival_s"],
            "itl_s": ((done_s - item["arrival_s"]) / len(toks)
                      if toks else None),
        })
    end = clock() - t0
    return {"results": results, "tokens": total_tokens,
            "window_s": end - trace[0]["arrival_s"]}


def _run_fixed_slo(args, cfg, base, make_trace, fast_path_counters) -> int:
    """Capacity at a fixed p99 TTFT SLO: sweep offered load, run the
    configured (fast) engine and a features-off baseline over the same
    trace at each rate, keep each arm's best tokens/sec/chip among rates
    that still meet the SLO. Token identity between arms is asserted at
    every rate before anything is reported."""
    import dataclasses as dcl
    import json as jsonlib

    import jax

    from distributeddeeplearning_tpu.observability import perf_report
    from distributeddeeplearning_tpu.observability import sidecars
    from distributeddeeplearning_tpu.serve.engine import Engine

    clock = time.monotonic
    n_chips = jax.device_count()
    base_cfg = dcl.replace(cfg, prefix_cache=False, spec_draft_model=None,
                           spec_k=0)
    rates = [float(x) for x in args.slo_rates.split(",") if x]
    rec = dict(base)
    rec["mode"] = "fixed_slo"
    rec["slo_p99_ttft_s"] = args.fixed_slo
    sweep = []
    best = {"fast": None, "baseline": None}
    for rate in rates:
        trace = make_trace(rate)
        point = {"rate_rps": rate}
        arm_tokens = {}
        for arm, acfg in (("fast", cfg), ("baseline", base_cfg)):
            engine = Engine(acfg, clock=clock)
            engine.warmup()
            res = run_continuous(engine, trace, clock)
            tps = res["tokens"] / res["window_s"] / n_chips
            p99 = _pct([r.ttft_s for r in res["requests"]], 99)
            arm_tokens[arm] = [r.tokens for r in res["requests"]]
            point[arm] = {
                "tokens_per_sec_per_chip": round(tps, 1),
                "p99_ttft_s": p99,
                "meets_slo": bool(p99 <= args.fixed_slo),
                **fast_path_counters(engine),
            }
            if p99 <= args.fixed_slo and (
                    best[arm] is None
                    or tps > best[arm]["tokens_per_sec_per_chip"]):
                best[arm] = {"rate_rps": rate,
                             "tokens_per_sec_per_chip": round(tps, 1),
                             "p99_ttft_s": p99}
        if arm_tokens["fast"] != arm_tokens["baseline"]:
            mism = [i for i, (a, b) in enumerate(
                zip(arm_tokens["fast"], arm_tokens["baseline"]))
                if a != b]
            raise AssertionError(
                f"fast vs baseline token mismatch at rate {rate} for "
                f"requests {mism[:5]} — the fast path must be "
                f"token-identical; do not trust either number")
        point["token_identity_checked"] = True
        sweep.append(point)
    rec["sweep"] = sweep
    rec["fast_at_slo"] = best["fast"]
    rec["baseline_at_slo"] = best["baseline"]
    rec["token_identity_checked"] = True
    rec["value"] = (best["fast"]["tokens_per_sec_per_chip"]
                    if best["fast"] else None)
    if best["fast"] and best["baseline"]:
        rec["speedup_at_slo"] = round(
            best["fast"]["tokens_per_sec_per_chip"]
            / best["baseline"]["tokens_per_sec_per_chip"], 2)
    perf_report.annotate(rec, provenance="fresh")
    print(jsonlib.dumps(rec), flush=True)
    sidecars.write("last_serve", {"record": rec})
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="gpt2_small")
    p.add_argument("--vocab-size", type=int, default=1024,
                   help="shrunk head keeps the CPU default tractable; "
                        "weight traffic (the thing batching amortizes) "
                        "is still dominated by the 12 real layers")
    p.add_argument("--dtype", default="float32")
    p.add_argument("--requests", type=int, default=16)
    p.add_argument("--rate", type=float, default=80.0,
                   help="mean arrival rate, requests/sec (Poisson)")
    p.add_argument("--prompt-lens", default="6,10,14",
                   help="comma list; each request draws one uniformly")
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--tenants", default="default",
                   help="comma list; requests round-robin across them")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--max-slots", type=int, default=8)
    p.add_argument("--page-size", type=int, default=8)
    p.add_argument("--num-pages", type=int, default=128)
    p.add_argument("--max-pages-per-slot", type=int, default=4)
    p.add_argument("--prefill-buckets", default="16,32")
    p.add_argument("--platform", default="tpu", choices=["tpu", "cpu"],
                   help="tpu (default) needs a TPU and fails without one; "
                        "cpu is the explicit way to smoke-test")
    p.add_argument("--no-compile-cache", action="store_true")
    p.add_argument("--prefix-cache", action="store_true",
                   help="radix prefix cache on (serve fast path)")
    p.add_argument("--spec-draft-model", default=None,
                   help="drafter model name: speculative decoding on")
    p.add_argument("--spec-k", type=int, default=0,
                   help="drafted tokens per speculative round")
    p.add_argument("--shared-prefix-len", type=int, default=0,
                   help="per-tenant shared system-prompt length; each "
                        "request is that head + a unique tail drawn "
                        "from --prompt-lens")
    p.add_argument("--fixed-slo", type=float, default=None,
                   help="p99 TTFT SLO in seconds: sweep --slo-rates and "
                        "report capacity at the SLO, fast vs features-off "
                        "baseline")
    p.add_argument("--slo-rates", default="20,40,80,160",
                   help="offered loads (req/s) the --fixed-slo sweep "
                        "visits")
    p.add_argument("--skip-baseline", action="store_true",
                   help="continuous arm only (no speedup field)")
    p.add_argument("--trace-dir", default=None,
                   help="enable per-request tracing + TTFT attribution; "
                        "the continuous arm's Chrome trace lands at "
                        "<dir>/trace.p0.json and the record gains a "
                        "ttft_attribution block (p50/p99/mean per "
                        "component, exact-sum checked); with --chaos the "
                        "supervised arm writes a merged multi-replica "
                        "trace under <dir>/chaos/")
    p.add_argument("--chaos", action="store_true",
                   help="add a supervised chaos arm: the same trace "
                        "through launch.run_serve twice (2 replicas) — "
                        "fault-free, then with replica 0 SIGKILLed "
                        "mid-decode and replica 1 decode-stalled — and "
                        "report p50/p99 TTFT, tokens/sec/chip and the "
                        "recovery overhead vs the supervised fault-free "
                        "window, asserting recovery is token-identical "
                        "and the page-leak check holds")
    args = p.parse_args(argv)
    if args.chaos and args.platform != "cpu":
        # One process per chip: the in-process arms below hold the chip the
        # supervised replica children would need.
        p.error("--chaos is a CPU harness (its in-process arm and its "
                "replica children cannot share a chip); pass "
                "--platform cpu")
    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"

    import numpy as np

    import jax

    from distributeddeeplearning_tpu.models import flops as flopslib
    from distributeddeeplearning_tpu.observability import perf_report
    from distributeddeeplearning_tpu.observability import sidecars
    from distributeddeeplearning_tpu.observability import telemetry
    from distributeddeeplearning_tpu.parallel import mesh as meshlib
    from distributeddeeplearning_tpu.serve.engine import Engine, ServeConfig

    meshlib.backend_devices(args.platform)  # no TPU, no measurement

    if args.trace_dir:
        # Must precede Engine construction: the engine resolves its
        # tracer once, at build time (the zero-overhead-off contract).
        telemetry.configure(enabled=True, trace_dir=args.trace_dir,
                            process_index=0, process_name="bench-serve")

    prompt_lens = [int(x) for x in args.prompt_lens.split(",") if x]
    tenants = [t for t in args.tenants.split(",") if t]
    cfg = ServeConfig(
        model=args.model, vocab_size=args.vocab_size, dtype=args.dtype,
        max_slots=args.max_slots, page_size=args.page_size,
        num_pages=args.num_pages,
        max_pages_per_slot=args.max_pages_per_slot,
        prefill_buckets=tuple(int(x) for x in
                              args.prefill_buckets.split(",") if x),
        seed=args.seed, prefix_cache=args.prefix_cache,
        spec_draft_model=args.spec_draft_model, spec_k=args.spec_k,
        compile_cache=not args.no_compile_cache)

    # Per-tenant shared system prompts, fixed across every arm and every
    # sweep rate: real multi-tenant traffic repeats the instruction head,
    # which is exactly the structure the radix prefix cache exploits.
    srng = np.random.default_rng(args.seed + 7)
    shared_heads = {
        t: [int(x) for x in
            srng.integers(1, args.vocab_size, args.shared_prefix_len)]
        for t in tenants}

    def make_trace(rate: float) -> list:
        """Seeded trace: Poisson arrivals (exponential gaps), uniform
        tail lengths, random token ids — identical request contents at
        every rate (only the arrival gaps scale), identical for every
        arm."""
        rng = np.random.default_rng(args.seed)
        gaps = rng.exponential(1.0 / rate, args.requests)
        arrivals = np.cumsum(gaps) - gaps[0]  # first request at t=0
        trace = []
        for i in range(args.requests):
            plen = int(rng.choice(prompt_lens))
            tenant = tenants[i % len(tenants)]
            trace.append({
                "arrival_s": float(arrivals[i]),
                "prompt": shared_heads[tenant] + [
                    int(x) for x in rng.integers(1, args.vocab_size, plen)],
                "max_new_tokens": args.max_new,
                "tenant": tenant,
            })
        return trace

    trace = make_trace(args.rate)

    clock = time.monotonic
    base = {
        "metric": "serve_tokens_per_sec_per_chip",
        "unit": "tokens/sec/chip",
        "model": args.model, "requests": args.requests,
        "rate_rps": args.rate, "max_new_tokens": args.max_new,
        "prompt_lens": prompt_lens, "seed": args.seed,
        "shared_prefix_len": args.shared_prefix_len,
        "tenants": len(tenants),
        "serve_config": {
            "max_slots": cfg.max_slots, "page_size": cfg.page_size,
            "num_pages": cfg.num_pages,
            "max_pages_per_slot": cfg.max_pages_per_slot,
            "prefill_buckets": list(cfg.prefill_buckets),
            "prefix_cache": cfg.prefix_cache,
            "spec_draft_model": cfg.spec_draft_model,
            "spec_k": cfg.spec_k},
    }

    def fast_path_counters(engine) -> dict:
        """Prefix-reuse and speculative-acceptance counters for the
        record — the in-record evidence the capacity claim rides on."""
        out = {}
        if engine.prefix is not None:
            admits = engine.prefix_hits + engine.prefix_misses
            out["prefix_hit_rate"] = round(
                engine.prefix_hits / admits, 4) if admits else None
            out["prefix_tokens_reused"] = engine.prefix_tokens_reused
            out["prefix_evictions"] = engine.prefix.evictions
            out["cow_copies"] = engine.cow_copies
        if engine._draft_model is not None:
            out["spec_rounds"] = engine.spec_rounds
            out["spec_acceptance_rate"] = round(
                engine.spec_accepted / engine.spec_proposed, 4) \
                if engine.spec_proposed else None
        return out

    try:
        if args.fixed_slo is not None:
            return _run_fixed_slo(args, cfg, base, make_trace,
                                  fast_path_counters)
        engine = Engine(cfg, clock=clock)
        engine.warmup()
        n_chips = jax.device_count()
        cont = run_continuous(engine, trace, clock)
        cont_tps = cont["tokens"] / cont["window_s"] / n_chips

        rec = dict(base)
        rec["value"] = round(cont_tps, 1)
        rec["continuous"] = {
            "tokens_per_sec_per_chip": round(cont_tps, 1),
            **_latency_block(
                [r.ttft_s for r in cont["requests"]],
                [s for r in cont["requests"] for s in r.itl_s]),
            "steps": cont["steps"], "preemptions": cont["preemptions"],
            "finished": len(cont["requests"]),
            # Degradation counters for tools/doctor.py serve health: a
            # fault-free bench run must show zeros here.
            "sheds": engine.sheds,
            "deadline_misses": engine.deadline_misses,
            "retries": engine.retries,
            **fast_path_counters(engine),
        }
        rec["aot"] = engine.aot_stats()
        if args.trace_dir:
            rec["continuous"]["ttft_attribution"] = _ttft_attribution(
                cont["requests"])
            rec["trace"] = telemetry.get().export()

        if not args.skip_baseline:
            seq = run_sequential(engine.model, {**engine._fresh}, trace,
                                 clock)
            seq_tps = seq["tokens"] / seq["window_s"] / n_chips
            mism = [i for i, (r, s) in
                    enumerate(zip(cont["requests"], seq["results"]))
                    if r.tokens != s["tokens"]]
            if mism:
                raise AssertionError(
                    f"continuous vs sequential token mismatch for "
                    f"requests {mism[:5]} — greedy serving must be "
                    f"token-identical; do not trust either number")
            rec["token_identity_checked"] = True
            rec["sequential_baseline"] = {
                "tokens_per_sec_per_chip": round(seq_tps, 1),
                **_latency_block(
                    [r["ttft_s"] for r in seq["results"]],
                    [r["itl_s"] for r in seq["results"]
                     if r["itl_s"] is not None]),
            }
            rec["speedup_vs_sequential"] = round(cont_tps / seq_tps, 2)

        if args.chaos:
            import tempfile

            from distributeddeeplearning_tpu import launch as launchlib

            kill_step = max(2, args.max_new // 2)
            stall_step = max(1, kill_step - 1)
            plans = {0: f"sigkill@{kill_step}",
                     1: f"decode_stall@{stall_step}:0.05s"}
            cfg_dict = dataclasses.asdict(cfg)
            reqs = [{"prompt": t["prompt"],
                     "max_new_tokens": t["max_new_tokens"],
                     "tenant": t["tenant"], "arrival_s": t["arrival_s"]}
                    for t in trace]
            # Two supervised runs over the same trace: the fault-free one
            # is the honest reference (same spawn + warm-boot cost), so
            # recovery_overhead_frac isolates what the faults cost, not
            # what process supervision costs. Both warm-boot from the AOT
            # cache the in-process arm above already populated.
            ok_run = launchlib.run_serve(
                2, reqs, cfg_dict,
                workdir=tempfile.mkdtemp(prefix="ddl-bserve-ok-"),
                heartbeat_dir=tempfile.mkdtemp(prefix="ddl-bserve-okhb-"),
                timeout_s=300.0)
            chaos_trace_dir = (os.path.join(args.trace_dir, "chaos")
                               if args.trace_dir else None)
            chaos_run = launchlib.run_serve(
                2, reqs, cfg_dict,
                workdir=tempfile.mkdtemp(prefix="ddl-bserve-chaos-"),
                heartbeat_dir=tempfile.mkdtemp(prefix="ddl-bserve-chb-"),
                child_fault_plans=plans, max_restarts=1, timeout_s=300.0,
                trace_dir=chaos_trace_dir)
            mism = [uid for uid, r in chaos_run["results"].items()
                    if r["tokens"] != cont["requests"][int(uid)].tokens]
            if mism:
                raise AssertionError(
                    f"chaos-arm tokens diverge from the fault-free run "
                    f"for requests {sorted(mism)[:5]} — recovery must be "
                    f"token-identical; do not trust these numbers")
            if not chaos_run["leak_check_ok"]:
                raise AssertionError(
                    "page-leak check failed at replica drain after the "
                    "chaos soak — the allocator lost accounting")
            ttfts = [r["ttft_s"] for r in chaos_run["results"].values()
                     if r["ttft_s"] is not None]
            chaos_tokens = sum(len(r["tokens"]) for r in
                               chaos_run["results"].values())
            rec["chaos"] = {
                "replicas": 2, "fault_plans": plans,
                "token_identity_checked": True,
                "leak_check_ok": True,
                "redispatched": chaos_run["redispatched"],
                "restarts": chaos_run["restarts"],
                "tokens_per_sec_per_chip": round(
                    chaos_tokens / chaos_run["window_s"] / n_chips, 1),
                "ttft_s": {"p50": _pct(ttfts, 50), "p99": _pct(ttfts, 99)},
                "fault_free_window_s": round(ok_run["window_s"], 3),
                "chaos_window_s": round(chaos_run["window_s"], 3),
                "recovery_overhead_frac": round(
                    chaos_run["window_s"] / ok_run["window_s"] - 1, 3),
            }
            if chaos_trace_dir and chaos_run.get("merged_trace"):
                # The chaos arm's whole point under tracing: a request
                # whose first replica was SIGKILLed must appear as ONE
                # flow chain spanning two Chrome pids in the merged
                # trace. Verify from the artifact, not from intent.
                evs, _ = telemetry.load_events_tolerant(
                    chaos_run["merged_trace"])
                flow_pids: dict = {}
                for e in evs:
                    if (e.get("ph") in ("s", "t", "f")
                            and e.get("cat") == "serve"):
                        flow_pids.setdefault(e.get("id"),
                                             set()).add(e.get("pid"))
                cross = [fid for fid, pids in flow_pids.items()
                         if len(pids) > 1]
                rec["chaos"]["merged_trace"] = chaos_run["merged_trace"]
                rec["chaos"]["flow_linked_requests"] = len(cross)
                if chaos_run["redispatched"] and not cross:
                    raise AssertionError(
                        "replica death re-dispatched "
                        f"{chaos_run['redispatched']} request(s) but "
                        "the merged trace has no flow chain spanning two "
                        "replica pids — cross-process trace linking is "
                        "broken")

        mid_context = int(np.mean(prompt_lens)) + args.max_new // 2
        roof = flopslib.decode_roofline(
            args.model, context_len=mid_context,
            tokens_per_sec=cont_tps,
            device_kind=getattr(jax.devices()[0], "device_kind", ""),
            dtype_bytes=2 if args.dtype == "bfloat16" else 4,
            batch=cfg.max_slots)
        if roof:
            rec["decode_roofline"] = roof
        perf_report.annotate(rec, provenance="fresh")
        print(json.dumps(rec), flush=True)
        sidecars.write("last_serve", {"record": rec})
        return 0
    except Exception as exc:  # noqa: BLE001 — emit an honest error record
        rec = dict(base)
        rec["value"] = None
        rec["error"] = f"{type(exc).__name__}: {exc}"
        perf_report.annotate(rec, provenance="error")
        print(json.dumps(rec), flush=True)
        return 1


if __name__ == "__main__":
    sys.exit(main())
