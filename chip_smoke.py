#!/usr/bin/env python
"""Smoke test of the whole program on the chip: the trainer and the server,
through the entry points a user calls, at published width.

    python chip_smoke.py                 # one TPU chip; what the driver runs
    python chip_smoke.py --four-chips    # one four-chip host: mesh + replicas
    python chip_smoke.py --rehearse [--four-chips]   # tiny sizes on the CPU

Every phase is a child process, one after another, each gone before the next
starts: a chip belongs to one process at a time, so this script itself never
initialises a JAX backend (``launch.run_serve``, which it calls, starts the
replica children and stays off the chip too). Each child fails at once when
its devices are not TPUs; any failed phase makes the script print
``"ok": false`` and exit non-zero. The last line of stdout is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as the children reported it; everything worth reading about
the phases (steps, losses, tokens, compile seconds, kernel counts, cache
entries, peak HBM) is on earlier lines, and appended to
``chiprun_out/chip_smoke.jsonl``. A second run in the same chip call finds
the first run's compile cache; it prints cold against warm compile seconds.

``--rehearse`` shrinks sizes only (tiny models, CPU devices — four virtual
ones with ``--four-chips``); the phases, entry points and checks are the same.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out")
RECORD = os.path.join(OUT_DIR, "chip_smoke.jsonl")
CHILD_TIMEOUT_S = 900


class PhaseFailed(Exception):
    pass


def say(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def require(cond, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


# ---------------------------------------------------------------------------
# Sizes: the real ones, and the rehearsal's (sizes only — same phases)
# ---------------------------------------------------------------------------

def sizes(rehearse: bool) -> dict:
    if not rehearse:
        return {
            "backend": "tpu",
            "xla": ["--model", "resnet50", "--batch-size", "256",
                    "--precision", "mixed", "--steps", "20"],
            "flash": ["--model", "gpt2_small", "--seq-len", "1024",
                      "--batch-size", "16", "--attn", "flash",
                      "--steps", "10"],
            # as many steps as train_xla: the warmup schedule depends on
            # the step count, and the two runs are compared step for step
            "fused": ["--model", "resnet50", "--batch-size", "256",
                      "--precision", "mixed", "--fused-block",
                      "--steps", "20"],
            "dp": ["--model", "resnet50", "--batch-size", "256",
                   "--sync-bn", "--steps", "5"],
            "flash_shape": (16, 1024, 12, 64),
            "bn_shape": (256 * 56 * 56, 64),
            "linear_bn_shapes": [(256 * 56 * 56, 64, 256),
                                 (50176, 1024, 256)],
            "serve": dict(model="gpt2_small", vocab_size=50257,
                          dtype="bfloat16", max_slots=8, page_size=16,
                          num_pages=512, max_pages_per_slot=64,
                          prefill_buckets=(128, 256, 512),
                          prefix_cache=True),
            "prompt_lens": (100, 400), "shared_head": 128, "max_new": 32,
        }
    return {
        "backend": "cpu",
        "xla": ["--model", "resnet26_thin", "--batch-size", "8",
                "--image-size", "32", "--precision", "mixed",
                "--steps", "4"],
        "flash": ["--model", "gpt_tiny", "--seq-len", "128",
                  "--batch-size", "4", "--attn", "flash", "--steps", "4"],
        "fused": ["--model", "resnet26_thin", "--batch-size", "8",
                  "--image-size", "32", "--precision", "mixed",
                  "--fused-block", "--steps", "4"],
        "dp": ["--model", "resnet18_thin", "--batch-size", "16",
               "--image-size", "32", "--sync-bn", "--steps", "4"],
        "flash_shape": (2, 128, 2, 16),
        "bn_shape": (256, 64),
        "linear_bn_shapes": [(256, 64, 128)],
        "serve": dict(model="gpt_tiny", vocab_size=1024, dtype="bfloat16",
                      max_slots=4, page_size=4, num_pages=64,
                      max_pages_per_slot=16, prefill_buckets=(8, 16, 32),
                      prefix_cache=True),
        "prompt_lens": (6, 24), "shared_head": 8, "max_new": 6,
    }


def child_env(rehearse: bool, devices: int) -> dict:
    env = dict(os.environ)
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices}")
    return env


# ---------------------------------------------------------------------------
# Running children
# ---------------------------------------------------------------------------

def run_child(cmd: list, env: dict, *, fail_phase: str | None = None,
              phase: str) -> list:
    """Run one child to its end, passing its stderr through; returns the
    JSON objects it printed on stdout. A non-zero exit fails the phase."""
    if fail_phase == phase:
        cmd = [sys.executable, "-c", "import sys; sys.exit(3)"]
    t0 = time.monotonic()
    proc = subprocess.run(cmd, env=env, cwd=REPO, stdout=subprocess.PIPE,
                          text=True, timeout=CHILD_TIMEOUT_S)
    recs = []
    for line in proc.stdout.splitlines():
        if line.startswith("{"):
            try:
                recs.append(json.loads(line))
            except ValueError:
                pass
    require(proc.returncode == 0,
            f"{phase}: child exited {proc.returncode} after "
            f"{time.monotonic() - t0:.0f}s: {' '.join(cmd[:6])} ...; last "
            f"stdout: {proc.stdout[-600:]!r}")
    return recs


def cache_dir() -> str:
    from distributeddeeplearning_tpu.perf import compile_cache
    return compile_cache.cache_dir()


def cache_entries() -> int:
    return sum(len(files) for _r, _d, files in os.walk(cache_dir()))


def train_phase(phase: str, flags: list, cfg: dict, env: dict,
                fail_phase, extra: tuple = ()) -> dict:
    """One ``train.py`` run through the CLI; checks the loss at every logged
    step and the run summary's device fields.

    What the loss can show in a few steps: it is finite, the optimizer moves
    it, and it stays near where a fresh net starts — a step that is wrong
    blows up or freezes. It cannot show learning: the synthetic stream
    draws a new random batch every step (1000-way labels; uniform random
    tokens), and 120 resnet50 steps on the chip drift 6.96 -> 7.1 as the
    warmup raises the learning rate (PERF.md, PR 21). Agreement with a
    reference is checked where there is one: fused block against the
    unfused run step for step, dp4 against dp1, kernels against dense
    math."""
    before = cache_entries()
    cmd = [sys.executable, os.path.join(REPO, "train.py"),
           "--backend", cfg["backend"], "--synthetic", "--log-every", "1",
           *flags, *extra]
    recs = run_child(cmd, env, fail_phase=fail_phase, phase=phase)
    steps = [r for r in recs if "step" in r and "loss" in r]
    summaries = [r["summary"] for r in recs if "summary" in r]
    require(summaries, f"{phase}: train.py printed no summary")
    s = summaries[-1]
    losses = [float(r["loss"]) for r in steps]
    want_steps = int(flags[flags.index("--steps") + 1])
    require(len(losses) == want_steps,
            f"{phase}: {len(losses)} logged steps, wanted {want_steps}")
    require(all(math.isfinite(x) for x in losses),
            f"{phase}: non-finite loss in {losses}")
    require(len(set(losses)) > 1, f"{phase}: the loss never moved: {losses}")
    require(all(abs(x - losses[0]) < 0.1 * abs(losses[0]) for x in losses),
            f"{phase}: loss left the band around its start: {losses}")
    dev = s["backend"]
    require(dev["platform"] == cfg["backend"],
            f"{phase}: ran on {dev['platform']}, wanted {cfg['backend']}")
    cc = s.get("compile_cache", {})
    rec = {
        "phase": phase, "ok": True, "cmd": " ".join(cmd[1:]),
        "steps": len(losses), "loss_first": losses[0],
        "loss_last": losses[-1], "losses": losses,
        "compile_time_s": s.get("compile_time_s"),
        "time_to_first_step_s": s.get("time_to_first_step_s"),
        "step_source": cc.get("sources"),
        "aot_hits": cc.get("aot_hits"), "aot_saves": cc.get("aot_saves"),
        "cache_entries_before": before, "cache_entries_after":
            cache_entries(),
        "peak_hbm_bytes": s.get("memory", {}).get("peak_bytes_in_use"),
        "device": {"platform": dev["platform"], "kind": dev["device_kind"],
                   "count": dev["device_count"]},
    }
    say({k: v for k, v in rec.items() if k != "losses"})
    return rec


def losses_agree(run: dict, ref: dict, tol: float) -> None:
    """Same seed, same synthetic batches: ``run``'s losses must equal
    ``ref``'s step for step, within ``tol`` relative (bf16 compute)."""
    pairs = list(zip(run["losses"], ref["losses"]))
    worst = max(abs(a - b) / max(abs(b), 1e-6) for a, b in pairs)
    require(worst < tol,
            f"{run['phase']}: losses {run['losses']} differ from "
            f"{ref['phase']}'s {ref['losses'][:len(pairs)]} by {worst:.3g}")
    say({"phase": f"{run['phase']}_vs_{ref['phase']}", "ok": True,
         "steps_compared": len(pairs), "max_rel_loss_diff": worst})


def self_child(phase: str, args, env: dict, *extra: str) -> dict:
    cmd = [sys.executable, os.path.abspath(__file__), "--child", phase,
           "--seed", str(args.seed), *extra]
    if args.rehearse:
        cmd.append("--rehearse")
    recs = run_child(cmd, env, fail_phase=args.fail_phase, phase=phase)
    done = [r for r in recs if r.get("phase") == phase]
    require(done and done[-1].get("ok"),
            f"{phase}: child printed no ok result")
    for r in recs:
        say(r)
    return done[-1]


# ---------------------------------------------------------------------------
# Serve: requests from the seed, the supervised run, and what it must show
# ---------------------------------------------------------------------------

def compare_streams(args, env: dict, under_test: dict, against,
                    uids: list) -> dict:
    """Hold ``under_test`` streams to ``against`` (another run's), or, when
    that is None, to sequential ``generate(use_cache=True)`` on the same
    weights — in a child, since it needs the chip.

    Greedy decoding is exact only in exact arithmetic. In bfloat16 two
    correct programs (paged batch-of-slots decode vs contiguous batch-1
    decode; prefill with or without reused prefix pages) round differently,
    and with random weights the top two logits are sometimes closer than
    that rounding. So: streams must be token-identical up to the first
    position where they part, and there the dense float32-logits forward of
    the shared context must rate the two tokens as a tie (within TIE_TOL of
    the logits' scale, both at the top). Anything else — a stream that
    parts where the reference sees no tie — fails."""
    with tempfile.NamedTemporaryFile("w", suffix=".json", delete=False) as f:
        json.dump({"under_test": {str(u): under_test[u] for u in uids},
                   "against": against and {str(u): against[u] for u in uids}},
                  f)
    try:
        return self_child("serve_reference", args, env,
                          "--streams-file", f.name)
    finally:
        os.unlink(f.name)


def make_requests(cfg: dict, seed: int, n: int) -> list:
    """``n`` seeded requests, prompts of prompt_lens[0]..[1] tokens; the
    first two share a ``shared_head``-token head (prefix-cache reuse)."""
    import random

    rng = random.Random(seed)
    vocab = cfg["serve"]["vocab_size"]
    lo, hi = cfg["prompt_lens"]
    head = [rng.randrange(1, vocab) for _ in range(cfg["shared_head"])]
    reqs = []
    for i in range(n):
        plen = rng.randint(lo, hi)
        if i < 2:
            plen = max(plen, cfg["shared_head"] + 4)
            prompt = head + [rng.randrange(1, vocab)
                             for _ in range(plen - len(head))]
        else:
            prompt = [rng.randrange(1, vocab) for _ in range(plen)]
        reqs.append({"uid": i, "prompt": prompt,
                     "max_new_tokens": cfg["max_new"]})
    return reqs


def serve_run(phase: str, replicas: int, reqs: list, cfg: dict, env: dict,
              fail_phase) -> dict:
    """One supervised run through ``launch.run_serve`` (the supervisor runs
    here, in the parent, and never touches jax; replicas are children)."""
    from distributeddeeplearning_tpu import launch

    require(fail_phase != phase, f"{phase}: forced failure")
    work = tempfile.mkdtemp(prefix=f"ddl-smoke-{phase}-")
    saved = dict(os.environ)
    os.environ.clear()
    os.environ.update(env)  # replicas inherit the supervisor's environ
    try:
        out = launch.run_serve(
            replicas, reqs, cfg["serve"], workdir=os.path.join(work, "w"),
            heartbeat_dir=os.path.join(work, "hb"), max_restarts=0,
            timeout_s=CHILD_TIMEOUT_S)
    finally:
        os.environ.clear()
        os.environ.update(saved)
    res = out["results"]
    finished = sum(1 for r in res.values() if r["finished"])
    require(finished == len(reqs),
            f"{phase}: {finished}/{len(reqs)} requests finished; rcs "
            f"{out['replica_rcs']}")
    require(all(len(r["tokens"]) == cfg["max_new"] for r in res.values()),
            f"{phase}: a stream is not {cfg['max_new']} tokens long")
    require(out["leak_check_ok"], f"{phase}: page-leak check failed")
    boots = {int(i): b for i, b in out["replica_boots"].items()}
    require(all(len(b) == 1 for b in boots.values()),
            f"{phase}: expected one boot per replica, got {boots}")
    devices = [b[0]["device"] for b in boots.values()]
    require(all(d["platform"] == cfg["backend"] for d in devices),
            f"{phase}: replica devices {devices}")
    if cfg["backend"] == "tpu":
        require(all(d["count"] == 1 for d in devices),
                f"{phase}: a replica saw more than one chip: {devices}")
        require(len({d["visible_chips"] for d in devices}) == replicas,
                f"{phase}: replicas were not given distinct chips: "
                f"{devices}")
    aot = [b[0]["aot"] for b in boots.values()]
    rec = {"phase": phase, "ok": True, "replicas": replicas,
           "requests": len(reqs), "finished": finished,
           "tokens": sum(len(r["tokens"]) for r in res.values()),
           "leak_check_ok": True, "window_s": round(out["window_s"], 1),
           "replica_devices": devices,
           "aot": [{k: a[k] for k in ("aot_hits", "aot_misses",
                                      "aot_saves")} for a in aot],
           "device": {"platform": devices[0]["platform"],
                      "kind": devices[0]["kind"], "count": replicas}}
    say(rec)
    rec["streams"] = {int(u): r["tokens"] for u, r in res.items()}
    return rec


# ---------------------------------------------------------------------------
# The two paths
# ---------------------------------------------------------------------------

def one_chip(args) -> list:
    cfg = sizes(args.rehearse)
    env = child_env(args.rehearse, devices=1)
    fail = args.fail_phase
    xla = train_phase("train_xla", cfg["xla"], cfg, env, fail)
    flash = train_phase("train_flash", cfg["flash"], cfg, env, fail)
    fused = train_phase("train_fused_block", cfg["fused"], cfg, env, fail)
    # Same model, seed and batches as train_xla, the 1x1 convs on the
    # Pallas path: the two steps must agree end to end.
    losses_agree(fused, xla, tol=1e-2)
    recs = [xla, flash, fused, self_child("kernels", args, env)]
    reqs = make_requests(cfg, args.seed, 8)
    cold = serve_run("serve", 1, reqs, cfg, env, fail)
    # one stream with the shared head (prefix-cache hit), one without
    ref = compare_streams(args, env, cold["streams"], None, uids=[1, 5])
    warm = serve_run("serve_warm_boot", 1, reqs, cfg, env, fail)
    require(warm["aot"][0]["aot_hits"] > 0
            and warm["aot"][0]["aot_misses"] == 0,
            f"serve_warm_boot: replica did not boot from the AOT cache: "
            f"{warm['aot']}")
    require(warm["streams"] == cold["streams"],
            "serve_warm_boot: tokens differ from the cold run")
    say({"phase": "serve_checks", "ok": True,
         "streams_checked_against_generate": ref["streams"],
         "warm_boot": warm["aot"][0], "warm_tokens_identical": True})
    return recs + [cold, ref, warm]


def four_chips(args) -> list:
    cfg = sizes(args.rehearse)
    env = child_env(args.rehearse, devices=4)
    fail = args.fail_phase
    dp1 = train_phase("train_dp1", cfg["dp"], cfg, env, fail,
                      extra=("--dp", "1"))
    dp4 = train_phase("train_dp4", cfg["dp"], cfg, env, fail,
                      extra=("--dp", "4"))
    z1 = train_phase("train_dp4_zero1", cfg["dp"], cfg, env, fail,
                     extra=("--dp", "4", "--optimizer-sharding", "zero1"))
    for run in (dp4, z1):
        require(run["device"]["count"] == 4,
                f"{run['phase']}: saw {run['device']['count']} devices")
        # bf16 compute: the reduction order differs across shards, the
        # statistics (sync-bn) and the global batch do not.
        losses_agree(run, dp1, tol=2e-2)
    layout = self_child("dp_layout", args, env)
    reqs = make_requests(cfg, args.seed, 16)
    one = serve_run("serve_1_replica", 1, reqs, cfg, env, fail)
    four = serve_run("serve_4_replicas", 4, reqs, cfg, env, fail)
    # Not the same computation: behind four replicas the two prompts that
    # share a head land on different chips and neither reuses the other's
    # prefix pages, so a stream may part at a rounding tie (see
    # compare_streams); anything more than that fails.
    ref = compare_streams(args, env, four["streams"], one["streams"],
                          uids=sorted(one["streams"]))
    say({"phase": "serve_checks", "ok": True,
         "streams_4_replicas_vs_1": ref["streams"]})
    return [dp1, dp4, z1, layout, one, four, ref]


# ---------------------------------------------------------------------------
# Children that need the chip (this file re-invoked with --child)
# ---------------------------------------------------------------------------

def _device_or_die(cfg: dict) -> dict:
    import jax
    dev = jax.devices()[0]
    if dev.platform != cfg["backend"]:
        raise SystemExit(f"need {cfg['backend']} devices, got "
                         f"{dev.platform}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _train_config(flags: list, cfg: dict, extra: tuple = ()):
    """The TrainConfig and step count ``train.py`` builds for these flags —
    through its own parser, so the programs match the train phases'."""
    import train as train_cli

    args = train_cli.parse_args(["--backend", cfg["backend"], "--synthetic",
                                 "--log-every", "1", *flags, *extra])
    return train_cli.build_config(args), args.steps


def _built_step(flags: list, cfg: dict, extra: tuple = ()):
    """The train step exactly as ``train.py`` builds it (train/loop.build),
    with one batch — for reading the compiled program."""
    from distributeddeeplearning_tpu import data as datalib
    from distributeddeeplearning_tpu.models import model_spec
    from distributeddeeplearning_tpu.perf import compile_cache
    from distributeddeeplearning_tpu.train import loop

    config, steps = _train_config(flags, cfg, extra)
    compile_cache.activate(config.compile_cache)
    mesh, _model, batch_shd, state, step, _sched, rng = loop.build(
        config, steps)
    spec = model_spec(config.model)
    batch = datalib.make_source(config, spec.input_kind, batch_shd,
                                objective=spec.objective).batch(0)
    return mesh, state, batch, step.lower(state, batch, rng)


def child_kernels(args) -> None:
    """Compiled kernels against their plain references at the train phases'
    shapes, then the Pallas train steps as train.py builds them: the kernel
    must be IN the compiled step (a twin or an interpreted kernel leaves no
    ``tpu_custom_call``)."""
    cfg = sizes(args.rehearse)
    device = _device_or_die(cfg)
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import validate_flash_tpu
    import validate_fused_bn_tpu

    ok = validate_flash_tpu.check_correctness(cfg["flash_shape"],
                                              causal=True)
    ok &= validate_fused_bn_tpu.check_correctness(*cfg["bn_shape"])
    for shape in cfg["linear_bn_shapes"]:
        ok &= validate_fused_bn_tpu.check_linear_bn(*shape)
    require(ok, "kernels: a compiled kernel disagrees with its reference")
    calls = {}
    for name in ("flash", "fused"):
        text = _built_step(cfg[name], cfg)[-1].compile().as_text()
        calls[name] = text.count("tpu_custom_call")
        if cfg["backend"] == "tpu":
            require(calls[name] > 0,
                    f"kernels: no tpu_custom_call in the {name} train step")
    say({"phase": "kernels", "ok": True, "device": device,
         "tpu_custom_calls_in_step": calls})


TIE_TOL = 2.0 ** -6  # of max|logit|: a few bfloat16 ulps of the scale


def child_serve_reference(args) -> None:
    """The reference side of :func:`compare_streams`: the model and weights
    the serve replicas built (same ServeConfig seed -> the Engine's own
    init), sequential generate, and the tie test at a parting."""
    cfg = sizes(args.rehearse)
    device = _device_or_die(cfg)
    import jax
    import jax.numpy as jnp

    from distributeddeeplearning_tpu.models.generate import generate
    from distributeddeeplearning_tpu.serve.engine import Engine, ServeConfig

    with open(args.streams_file) as f:
        job = json.load(f)
    eng = Engine(ServeConfig(**cfg["serve"]))
    variables = {**eng._fresh}
    prompts = {r["uid"]: r["prompt"]
               for r in make_requests(cfg, args.seed, 16)}
    gen = jax.jit(lambda v, ids: generate(
        eng.model, v, ids, max_new_tokens=cfg["max_new"], use_cache=True))
    logits_of = jax.jit(lambda v, ids: eng.model.apply(
        v, ids, train=False)[0, -1].astype(jnp.float32))
    out = {}
    for uid, got in job["under_test"].items():
        prompt = prompts[int(uid)]
        if job["against"] is None:
            full = jax.device_get(gen(variables,
                                      jnp.asarray([prompt], jnp.int32)))
            want = [int(t) for t in full[0][len(prompt):]]
        else:
            want = job["against"][uid]
        k = next((i for i, (a, b) in enumerate(zip(got, want)) if a != b),
                 None)
        rec = {"identical_tokens": len(want) if k is None else k,
               "of": len(want)}
        if k is not None:
            ctx = jnp.asarray([prompt + want[:k]], jnp.int32)
            logits = jax.device_get(logits_of(variables, ctx))
            scale = float(abs(logits).max())
            a, b, top = (float(logits[got[k]]), float(logits[want[k]]),
                         float(logits.max()))
            rec.update(parted_at=k, tokens=[got[k], want[k]],
                       logits=[a, b], top_logit=top, logit_scale=scale,
                       tie_tol=TIE_TOL * scale)
            require(top - min(a, b) <= TIE_TOL * scale,
                    f"serve: stream {uid} parts from its reference at token "
                    f"{k} where the reference sees no tie: {rec}")
        out[uid] = rec
    say({"phase": "serve_reference", "ok": True, "device": device,
         "against": "generate" if job["against"] is None else "other run",
         "streams": out})


def child_dp_layout(args) -> None:
    """Where the dp4 programs and their state really live: collectives in
    the compiled text, shards on four distinct devices, bytes per device."""
    cfg = sizes(args.rehearse)
    device = _device_or_die(cfg)
    import jax

    out = {}
    for name, extra in (("dp4", ("--dp", "4")),
                        ("dp4_zero1", ("--dp", "4", "--optimizer-sharding",
                                       "zero1"))):
        mesh, state, batch, lowered = _built_step(cfg["dp"], cfg, extra)
        compiled = lowered.compile()
        text = compiled.as_text()
        counts = {op: text.count(f"{op}(") + text.count(f"{op}-start(")
                  for op in ("all-reduce", "reduce-scatter", "all-gather")}
        # What the program asks for, before the compiler has its say (the
        # TPU compiler may rewrite a reduce-scatter as all-reduce + slice).
        counts["reduce_scatter_as_lowered"] = lowered.as_text().count(
            "reduce_scatter")
        require(counts["all-reduce"] > 0,
                f"dp_layout: no all-reduce in the {name} step")
        batch_devs = {s.device.id for s in
                      batch["image"].addressable_shards}
        require(len(batch_devs) == 4,
                f"dp_layout: {name} batch lives on devices {batch_devs}")
        rec = {"collectives": counts, "batch_devices": sorted(batch_devs),
               "mesh_devices": [d.id for d in mesh.devices.flat]}
        if name == "dp4_zero1":
            require(counts["reduce_scatter_as_lowered"] > 0
                    and counts["all-gather"] > 0,
                    f"dp_layout: zero1 step lacks reduce-scatter/"
                    f"all-gather: {counts}")
            big = max(jax.tree_util.tree_leaves(state.opt_state),
                      key=lambda x: x.size)
            shards = big.addressable_shards
            opt_devs = {s.device.id for s in shards}
            require(len(opt_devs) == 4 and
                    all(s.data.size * 4 == big.size for s in shards),
                    f"dp_layout: zero1 optimizer state is not spread 1/4 "
                    f"per device: {opt_devs}")
            rec["opt_state_devices"] = sorted(opt_devs)
            rec["opt_state_leaf"] = {"global": big.size,
                                     "per_device": shards[0].data.size}
        ma = compiled.memory_analysis()
        rec["bytes_per_device"] = {
            "arguments": ma.argument_size_in_bytes,
            "outputs": ma.output_size_in_bytes,
            "temps": ma.temp_size_in_bytes}
        out[name] = rec
    require(out["dp4_zero1"]["bytes_per_device"]["arguments"]
            < out["dp4"]["bytes_per_device"]["arguments"],
            "dp_layout: zero1 did not shrink the per-device state")
    say({"phase": "dp_layout", "ok": True, "device": device, **out})


CHILDREN = {"kernels": child_kernels,
            "serve_reference": child_serve_reference,
            "dp_layout": child_dp_layout}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="the four-chip path only (mesh + replicas) and "
                        "what it is compared with")
    p.add_argument("--rehearse", action="store_true",
                   help="tiny sizes on CPU devices: rehearses the control "
                        "flow, proves nothing about the chip")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--fail-phase", default=None, help=argparse.SUPPRESS)
    p.add_argument("--streams-file", default=None, help=argparse.SUPPRESS)
    p.add_argument("--child", default=None, choices=sorted(CHILDREN),
                   help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    sys.path.insert(0, REPO)

    if args.child:
        try:
            CHILDREN[args.child](args)
        except PhaseFailed as e:
            say({"phase": args.child, "ok": False, "error": str(e)})
            return 1
        return 0

    t0 = time.monotonic()
    try:
        recs = (four_chips if args.four_chips else one_chip)(args)
    except (PhaseFailed, subprocess.TimeoutExpired) as e:
        say({"ok": False, "error": str(e)})
        return 1
    devices = [r["device"] for r in recs]
    want = 4 if args.four_chips else 1
    device = next(d for d in devices if d["count"] == want)
    if not all((d["platform"], d["kind"]) == (device["platform"],
                                              device["kind"])
               for d in devices):
        say({"ok": False, "error": f"phases disagree on the device: "
                                   f"{devices}"})
        return 1

    # The record of this run, next to the last one's: cold against warm.
    compiles = {r["phase"]: {k: r.get(k) for k in
                             ("compile_time_s", "step_source",
                              "cache_entries_before", "cache_entries_after")}
                for r in recs if "compile_time_s" in r}
    os.makedirs(OUT_DIR, exist_ok=True)
    previous = None
    if os.path.exists(RECORD):
        with open(RECORD) as f:
            lines = [json.loads(ln) for ln in f if ln.strip()]
        same = [ln for ln in lines
                if ln["mode"] == [args.four_chips, args.rehearse]]
        previous = same[-1] if same else None
    say({"phase": "compile_cache", "dir": cache_dir(),
         "entries": cache_entries(), "this_run": compiles,
         "previous_run": previous and previous["compiles"]})
    with open(RECORD, "a") as f:
        f.write(json.dumps({
            "mode": [args.four_chips, args.rehearse], "device": device,
            "wall_s": round(time.monotonic() - t0, 1), "compiles": compiles,
            "phases": [{k: v for k, v in r.items() if k != "streams"}
                       for r in recs]}) + "\n")
    say({"phase": "done", "wall_s": round(time.monotonic() - t0, 1)})
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
