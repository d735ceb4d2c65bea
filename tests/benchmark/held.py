"""Run the set-up of one training cell of the checkout in the working
directory and print, as one JSON line, what the process held in live arrays
each time the runner called the step for a checked step: in all, and of that
the state, the batch and the largest leaf of the parameters. Skips the
harness's look for a chip (this is the tests' driver).

    python held.py <workload> <seed>
"""

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.getcwd())


def main() -> int:
    workload, seed = sys.argv[1:3]
    import jax

    from benchmark import harness
    import distributeddeeplearning_tpu  # noqa: F401

    def nbytes(tree):
        return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))

    calls = []

    def recording(step):
        def called(state, batch, rng):
            gc.collect()
            calls.append({
                "live": sum(a.nbytes for a in jax.live_arrays()),
                "state": nbytes(state), "batch": nbytes(batch),
                "params": nbytes(state.params),
                "largest_leaf": max(map(
                    nbytes, jax.tree_util.tree_leaves(state.params)))})
            return step(state, batch, rng)
        return called

    cell = harness.load_cell(workload)
    devices = harness.devices_for(cell, rehearsal=True)
    runner = harness.load_module("runners", cell["traffic_file"]["runner"])
    args = argparse.Namespace(seed=int(seed), rehearsal=True)
    runner.prepare(cell, args, devices, recording)
    print(json.dumps(calls))
    return 0


if __name__ == "__main__":
    sys.exit(main())
