"""The serving runner: drives `serve/engine.Engine` under an open-loop arrival
schedule, as `tools/bench_serve.run_continuous` does (submit what is due,
sleep in idle gaps, `engine.step()`; copied in outline, the rest of that tool
is not used).

Set-up makes the weights from the seed (the configuration's reference file),
builds the engine round them, warms up its programs, and runs the traffic's
generator for `warm_s` seconds so that the window opens at steady occupancy.
The window then lasts `--seconds`; requests due in it are the window's
requests, and the tails are taken over them. The generator's arrivals go on
after the window has closed, submitted and not counted, until the window's
requests have finished (an answer can take a minute at this engine's pace, and
one that comes late is late, not wrong): so every gap of theirs is measured
under the cell's load, not on an engine that nothing more arrives at. Then the
engine is freed and the plain float32 reference reads a sample of the
finished streams.
"""

from __future__ import annotations

import collections
import gc
import random
import sys
import time

from benchmark import generators, harness

WAIT_AFTER_CLOSE_S = 150.0
TRACE_LEAD_S = 8.0  # the profiler starts this long before the traced stretch


def _unflatten(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        parts = path.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = leaf
    return tree


def build_engine(cell: dict, args, wrap_engine=None):
    import jax

    from distributeddeeplearning_tpu.serve.engine import Engine, ServeConfig

    cfgfile = cell["config_file"]
    ref = harness.load_module("references", cfgfile["reference"])
    sz = ref.sizes(cfgfile)
    prog = dict(cfgfile["serve"])
    prog["prefill_buckets"] = tuple(prog["prefill_buckets"])
    scfg = ServeConfig(vocab_size=cfgfile["vocab_size"], seed=0, **prog)
    seed_key = jax.random.key(args.seed)
    params = jax.jit(lambda k: ref.init_params(sz, k))(seed_key)
    engine = Engine(scfg, variables={"params": _unflatten(params)},
                    clock=time.monotonic)
    del params
    engine.warmup()
    if wrap_engine is not None:  # tests plant faults here
        wrap_engine(engine)
    return engine, ref, sz, seed_key


def drive(engine, requests, *, warm_s, seconds, spans, tracer, record_steps):
    """Submit what is due, step the engine, until the window's requests are
    done. Returns the submitted (item, Request) pairs and the window's edges
    on the engine's clock."""
    clock = time.monotonic
    t0 = clock()
    t_open, t_close = t0 + warm_s, t0 + warm_s + seconds
    pending = collections.deque(requests)
    sent = []
    steps = []  # traced stretch only: what each engine step worked on
    open_reqs = []  # submitted and not finished, kept for the traced run
    late = []
    opened_wall = None
    while True:
        now = clock()
        if opened_wall is None and now >= t_open:
            opened_wall = time.time()
        while pending and t0 + pending[0]["arrival_s"] <= now:
            item = pending.popleft()
            due = t0 + item["arrival_s"]
            late.append(now - due)
            req = engine.submit(item["prompt"],
                                max_new_tokens=item["max_new_tokens"],
                                tenant=item["tenant"], arrival_s=due)
            sent.append((item, req, due))
            if record_steps:
                open_reqs.append(req)
        if now >= t_close:
            tracer.finish(lambda: None)
            todo = [r for _, r, due in sent if t_open <= due < t_close
                    and r.finished_s is None and r.failed is None]
            if not todo or now >= t_close + WAIT_AFTER_CLOSE_S:
                break
        else:
            tracer.poll(now - t_open, lambda: None)
        if engine.idle:
            # nothing to step: sleep to the next arrival, or to the close
            wake = t0 + pending[0]["arrival_s"] if pending else t_close
            if now < t_close:
                wake = min(wake, t_close)
            with spans("sleep"):
                time.sleep(max(0.0, wake - clock()))
            continue
        waiting_before = len(engine.waiting)
        ta = clock()
        with spans("step"):
            engine.step()
        tb = clock()
        admitted = waiting_before - len(engine.waiting)
        name = "prefill_step" if admitted > 0 else "decode_step"
        spans.spans.setdefault(name, []).append((ta, tb))
        if record_steps:
            # rows the decode advanced in this step (a row admitted in it has
            # its prefill's token and this decode's) with the context each
            # read, and the prompts the step prefilled
            rows = [r for r in open_reqs if r.ttft_s is not None]
            fresh = [len(r.prompt) for r in rows if len(r.tokens) == 2]
            if tracer.state == "tracing":
                steps.append(dict(
                    seconds=tb - ta, rows=len(rows), prefilled=fresh,
                    context=sum(len(r.prompt) + len(r.tokens) - 2
                                for r in rows)))
            open_reqs[:] = [r for r in open_reqs
                            if r.finished_s is None and r.failed is None]
    return dict(sent=sent, t_open=t_open, t_close=t_close, steps=steps,
                late=late, opened_wall=opened_wall)


def emission_times(req) -> list:
    """When each of a request's tokens was emitted, on the engine's clock."""
    if req.ttft_s is None:
        return []
    t = req.arrival_s + req.ttft_s
    out = [t]
    for gap in req.itl_s:
        t += gap
        out.append(t)
    return out


def end_to_end(run: dict, seconds: float) -> dict:
    t_open, t_close = run["t_open"], run["t_close"]
    inside = [r for _, r, due in run["sent"] if t_open <= due < t_close]
    emitted = sum(1 for _, r, _ in run["sent"] for t in emission_times(r)
                  if t_open <= t < t_close)
    done = [r for r in inside if r.finished_s is not None and not r.failed]
    ttft = [r.ttft_s for r in inside if r.ttft_s is not None]
    itl = [g for r in inside for g in r.itl_s]
    return dict(
        attempted=len(inside), failed=len(inside) - len(done), done=done,
        serve_tokens_per_s=emitted / seconds,
        ttft_p95_ms=1e3 * harness.quantile(ttft, 0.95) if ttft else None,
        itl_p95_ms=1e3 * harness.quantile(itl, 0.95) if itl else None)


def sample_streams(done: list, seed: int, count: int) -> list:
    """The longest finished stream and `count - 1` others drawn from the
    seed."""
    if not done:
        return []
    ordered = sorted(done, key=lambda r: (-(len(r.prompt) + len(r.tokens)),
                                          r.uid))
    rest = ordered[1:]
    random.Random(seed).shuffle(rest)
    return [ordered[0]] + rest[:max(0, count - 1)]


def logit_gaps(ref, sz, seed_key, streams, quant=None) -> dict:
    """Float32 reference logits over each sampled prompt with its served
    tokens. `served`: the widest gap by which a served token's logit lies
    below the reference's best at its position. With `quant`, `control`: the
    same for the token a lower-precision forward puts first there."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    params = jax.jit(lambda k: ref.init_params(sz, k))(seed_key)
    length = sz["positions"]

    @jax.jit
    def gaps(params, ids, first, last, other):
        with jax.default_matmul_precision("highest"):
            logits = ref.forward(sz, params, ids[None])[0]
        pos = jnp.arange(length)
        nxt = jnp.roll(ids, -1)
        best = logits.max(-1)
        gap = best - jnp.take_along_axis(logits, nxt[:, None], -1)[:, 0]
        ogap = best - jnp.take_along_axis(logits, other[:, None], -1)[:, 0]
        mask = (pos >= first) & (pos <= last)
        return (jnp.where(mask, gap, 0.0).max(),
                jnp.where(mask, ogap, 0.0).max())

    @jax.jit
    def low_choice(params, ids):
        with jax.default_matmul_precision("highest"):
            return ref.forward(sz, params, ids[None],
                               quant=quant)[0].argmax(-1)

    served, control, tokens = 0.0, 0.0, 0
    for r in streams:
        seq = list(r.prompt) + list(r.tokens)
        ids = np.zeros((length,), np.int32)
        ids[:len(seq)] = seq
        ids = jnp.asarray(ids)
        first, last = len(r.prompt) - 1, len(seq) - 2
        other = (low_choice(params, ids).astype(jnp.int32)
                 if quant is not None else jnp.roll(ids, -1))
        g, og = gaps(params, ids, first, last, other)
        served, control = max(served, float(g)), max(control, float(og))
        tokens += len(r.tokens)
    return {"served": served, "control": control, "tokens": tokens}


def offer(cell: dict, args, seconds: float, *, traffic=None, trace=False,
          wrap_engine=None) -> dict:
    """One engine from the seed under the cell's mix (or `traffic`, the mix
    with a parameter changed): warm-up, a window of `seconds`, the wait for
    its requests. What a run, a calibration reading and a sweep's rate share."""
    tr = traffic or cell["traffic_file"]
    spans = harness.Spans()
    tracer = harness.Tracer(spans, trace, lead_s=TRACE_LEAD_S, hold=True)
    engine, ref, sz, seed_key = build_engine(cell, args, wrap_engine)
    requests = generators.make_requests(
        tr, cell["config_file"]["vocab_size"], args.seed,
        tr["warm_s"] + seconds + WAIT_AFTER_CLOSE_S)
    res = drive(engine, requests, warm_s=tr["warm_s"], seconds=seconds,
                spans=spans, tracer=tracer, record_steps=trace)
    return dict(engine=engine, ref=ref, sz=sz, seed_key=seed_key, res=res,
                spans=spans, tracer=tracer, e2e=end_to_end(res, seconds))


def run(cell: dict, args, devices, t_start: float, wrap_engine=None):
    tr = cell["traffic_file"]
    got = offer(cell, args, args.seconds, trace=bool(args.trace),
                wrap_engine=wrap_engine)
    engine, res, e2e = got["engine"], got["res"], got["e2e"]
    spans, tracer = got["spans"], got["tracer"]
    setup_s = res["opened_wall"] - t_start
    memory_peak = harness.memory_peak_bytes(devices)
    late = sorted(res["late"])
    print(f"phases: setup {setup_s:.1f}s requests in window "
          f"{e2e['attempted']} failed {e2e['failed']} ttft p95 "
          f"{e2e['ttft_p95_ms']} ms engine steps "
          f"{engine.steps} preemptions {engine.preemptions} generator late "
          f"p50 {1e3 * harness.quantile(late, 0.5):.2f} ms p99 "
          f"{1e3 * harness.quantile(late, 0.99):.2f} ms memory_stats "
          f"{devices[0].memory_stats()}", file=sys.stderr)
    reduced = tracer.reduced()
    ctx = dict(cell=cell, config=cell["config_file"], traffic=tr, spans=spans,
               trace=reduced, chips=len(devices),
               peaks=(harness.peaks_for(devices[0].device_kind)
                      if devices[0].platform == "tpu" else None),
               traced_s=tracer.stretch_s, steps=res["steps"])
    streams = sample_streams(e2e["done"], args.seed, tr["sample"])
    engine.shutdown()
    del engine, got["engine"]
    gc.collect()
    t_ref = time.time()
    got = logit_gaps(got["ref"], got["sz"], got["seed_key"], streams)
    print(f"phases: reference over {len(streams)} streams, {got['tokens']} "
          f"served tokens, {time.time() - t_ref:.1f}s", file=sys.stderr)
    limit = tr["limits"]["logit_gap"]
    checks = [["logit_gap", got["served"], limit]]
    ok = bool(streams) and got["served"] <= limit and e2e["failed"] == 0
    values = {k: e2e[k] for k in ("serve_tokens_per_s", "ttft_p95_ms",
                                  "itl_p95_ms") if e2e[k] is not None}
    values["setup_s"] = setup_s
    return harness.emit(cell, trace_on=bool(args.trace), e2e=values, ctx=ctx,
                        attempted=e2e["attempted"], failed=e2e["failed"],
                        devices=devices, memory_peak=memory_peak,
                        checks=checks, correct=ok, reduced=reduced)
