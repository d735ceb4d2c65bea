#!/usr/bin/env python3
"""A fault for training cells whose batch is ONE sequence, where
`faults.py::half_batch` has no row to leave out (half of a batch of one is no
rows): `half_sequence` leaves out the second half of every sequence instead.
It is read as `calibrate.py --faults` reads the faults of `faults.py`, in one
process at the cell's own size, with the run's own verdict:

    python3 benchmark/faults_one_sequence.py --workload <cell> --seeds 1,2 \\
        [--out chiprun_out/x.jsonl] [--rehearsal]

One JSON line a seed. Exits 0 when the fault came out not correct every time.
`faults.py::unchanged_state` copies the whole state beside the one the step
holds, which a cell whose state fills half the chip cannot do; what it shows
there is `change_gap` = 1, the reading a state left unchanged has by the
definition of that number.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def half_sequence(step):
    """The second half of every sequence is left out: its tokens are
    replaced by the first half's, so the step learns from half the text."""
    import jax
    import jax.numpy as jnp

    def faulty(state, batch, rng):
        ids = batch["input_ids"]
        n = ids.shape[1] // 2
        halved = jnp.concatenate([ids[:, :n], ids[:, :n]], axis=1)
        halved = jax.device_put(halved, ids.sharding)
        return step(state, dict(batch, input_ids=halved), rng)
    return faulty


TRAIN = {"half_sequence": half_sequence}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True,
                   type=lambda t: [int(x) for x in t.split(",") if x])
    p.add_argument("--out", default=None)
    p.add_argument("--rehearsal", action="store_true")
    args = p.parse_args(argv)

    from benchmark import harness
    import distributeddeeplearning_tpu  # noqa: F401

    cell = harness.load_cell(args.workload)
    devices = harness.devices_for(cell, args.rehearsal)
    runner = harness.load_module("runners", cell["traffic_file"]["runner"])
    limits = cell["traffic_file"]["limits"]
    passed = []
    for seed in args.seeds:
        for name, wrap in TRAIN.items():
            t0 = time.time()
            run_args = argparse.Namespace(seed=seed, rehearsal=args.rehearsal)
            prep = runner.prepare(cell, run_args, devices, wrap)
            ok, _, numbers = runner.check(prep, limits)
            numbers.pop("leaves_left_out")
            if ok:
                passed.append(f"{name} seed {seed}")
            line = json.dumps({"kind": "fault:" + name, "seed": seed,
                               "correct": ok, "seconds": time.time() - t0,
                               **numbers, "limits": limits})
            print(line, flush=True)
            if args.out:
                with open(args.out, "a") as fh:
                    fh.write(line + "\n")
    print(f"faults_one_sequence: {len(passed)} faults came out correct "
          f"{passed}", file=sys.stderr)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
