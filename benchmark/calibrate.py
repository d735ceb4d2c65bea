#!/usr/bin/env python3
"""Readings that the limits of a cell's `correct` are set from, all in one
process on the chip at the cell's own size, each judged by the comparison a
run makes (`correct` in every record):

    python3 benchmark/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--control 1,2,3] [--faults 1,2,3] [--out chiprun_out/x.jsonl]

A training cell needs no measured window. `--seeds`: the program's first
three steps against the float32 reference (the lower readings). `--control`:
the reference in 8-bit floating point against the float32 reference (the upper
readings). `--faults`: the program with each fault of `faults.py` planted.

A serving cell runs a short window at the cell's own load (`--seconds`) for
each seed. `--seeds`: the served tokens' widest logit gap, and beside it the
control's: the gap of the token that the reference in 8-bit floating point
puts first, at the same positions of the same streams. `--faults`: the served
gap with the fault planted. One JSON line per reading. Exits 0 when every
sound run came out correct and every control and fault not correct.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _ints(text):
    return [int(x) for x in text.split(",") if x]


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=_ints, default=[])
    p.add_argument("--control", type=_ints, default=[])
    p.add_argument("--faults", type=_ints, default=[])
    p.add_argument("--out", default=None)
    p.add_argument("--seconds", type=float, default=None,
                   help="serving cells: the short window's length")
    p.add_argument("--rehearsal", action="store_true")
    args = p.parse_args(argv)

    import jax

    from benchmark import faults, harness, train_check
    from benchmark.references import lowprec
    import distributeddeeplearning_tpu  # noqa: F401

    cell = harness.load_cell(args.workload)
    devices = harness.devices_for(cell, args.rehearsal)
    runner = harness.load_module("runners", cell["traffic_file"]["runner"])
    limits = cell["traffic_file"]["limits"]
    surprises = []  # a sound run not correct, or a control or fault correct

    def emit(kind, seed, t0, correct, **readings):
        if correct != (kind == "program"):
            surprises.append(f"{kind} seed {seed}")
        line = json.dumps({"kind": kind, "seed": seed, "correct": correct,
                           "seconds": time.time() - t0, **readings,
                           "limits": limits})
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as fh:
                fh.write(line + "\n")

    def train(seed, wrap=None, kind="program"):
        t0 = time.time()
        run_args = argparse.Namespace(seed=seed, rehearsal=args.rehearsal)
        prep = runner.prepare(cell, run_args, devices, wrap)
        ok, _, numbers = runner.check(prep, limits)
        numbers.pop("leaves_left_out")
        emit(kind, seed, t0, ok, **numbers)

    def train_control(seed):
        t0 = time.time()
        cfgfile = cell["config_file"]
        ref = harness.load_module("references", cfgfile["reference"])
        sz = ref.sizes(cfgfile)
        tr = dict(cell["traffic_file"])
        tr["batch"] *= len(devices)
        opt = cfgfile["train"]["optimizer"]
        key = jax.random.key(seed)
        rng = jax.random.fold_in(key, 0x5EED)
        want = train_check.reference_readings(ref, sz, tr, opt, key, rng)
        got = train_check.reference_readings(ref, sz, tr, opt, key, rng,
                                             quant=lowprec.fp8)
        numbers = train_check.compare(got, want)
        numbers.pop("leaves_left_out")
        ok, _ = train_check.verdict(numbers, limits)
        emit("control:fp8", seed, t0, ok, **numbers)

    def serve(seed, wrap=None, kind="program"):
        t0 = time.time()
        tr = cell["traffic_file"]
        seconds = args.seconds or float(cell["spec"]["run_seconds"])
        run_args = argparse.Namespace(seed=seed, rehearsal=args.rehearsal)
        got = runner.offer(cell, run_args, seconds, wrap_engine=wrap)
        e2e = got["e2e"]
        streams = runner.sample_streams(e2e["done"], seed, tr["sample"])
        got.pop("engine").shutdown()
        gc.collect()
        gaps = runner.logit_gaps(got["ref"], got["sz"], got["seed_key"],
                                 streams,
                                 quant=None if wrap else lowprec.fp8)
        limit = limits["logit_gap"]
        emit(kind, seed, t0,
             bool(streams) and gaps["served"] <= limit and not e2e["failed"],
             attempted=e2e["attempted"], failed=e2e["failed"],
             streams=len(streams), served_tokens=gaps["tokens"],
             logit_gap=gaps["served"])
        if wrap is None:  # the control, at the same positions of the streams
            emit("control:fp8", seed, t0, gaps["control"] <= limit,
                 logit_gap=gaps["control"])

    serving = cell["traffic_file"]["runner"] == "serve"
    program = serve if serving else train
    for seed in args.seeds:
        program(seed)
    for seed in args.faults:
        for name, wrap in (faults.SERVE if serving else faults.TRAIN).items():
            program(seed, wrap, "fault:" + name)
    if not serving:
        for seed in args.control:
            train_control(seed)
    print(f"calibrate: {len(surprises)} readings came out the wrong way "
          f"{surprises}", file=sys.stderr)
    return 1 if surprises else 0


if __name__ == "__main__":
    sys.exit(main())
