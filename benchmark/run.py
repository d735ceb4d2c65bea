#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip. The cell's configuration, traffic and
per-layer metrics are files found by the names in `BENCHMARK.json`; the core
knows the runners (`benchmark/runners/<runner>.py`, named by the traffic
file) and nothing else. The last line of standard output is the result.
"""

from __future__ import annotations

import time

T_START = time.time()  # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearsal", action="store_true",
                   help="tests only: run on whatever devices JAX has; the "
                        "numbers of such a run are no device numbers")
    args = p.parse_args(argv)

    from benchmark import harness

    try:
        cell = harness.load_cell(args.workload)
        if args.seconds is None:
            args.seconds = float(cell["spec"]["run_seconds"])
        # The program under test; a tree without it cannot run a cell.
        import distributeddeeplearning_tpu  # noqa: F401
        devices = harness.devices_for(cell, args.rehearsal)
        runner = harness.load_module("runners", cell["traffic_file"]["runner"])
        runner.run(cell, args, devices, T_START)
    except harness.CellError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
