"""Run one cell of the checkout in the working directory with a fault of
`benchmark/faults.py` planted under the timed path. Skips the harness's look
for a chip (this is the tests' driver) and drives the rest of a run.

    python plant.py <workload> <fault> <seed> <seconds>
"""

import argparse
import os
import sys
import time

T0 = time.time()
sys.path.insert(0, os.getcwd())


def main() -> int:
    workload, fault, seed, seconds = sys.argv[1:5]
    from benchmark import faults, harness
    import distributeddeeplearning_tpu  # noqa: F401

    cell = harness.load_cell(workload)
    devices = harness.devices_for(cell, rehearsal=True)
    runner = harness.load_module("runners", cell["traffic_file"]["runner"])
    args = argparse.Namespace(seed=int(seed), seconds=float(seconds),
                              trace=0, rehearsal=True)
    runner.run(cell, args, devices, T0, faults.ALL[fault])
    return 0


if __name__ == "__main__":
    sys.exit(main())
