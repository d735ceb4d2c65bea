#!/usr/bin/env python
"""CPU-proxy perf gate CLI: check the current build, or recalibrate.

Default mode measures the fixed proxy workload and compares it against the
checked-in ``perf_baselines.json`` (exit 1 on violation — the same check
the tier-1 ``perf_gate``-marked test runs). ``--recalibrate`` re-measures
and rewrites the baseline; commit the resulting ``perf_baselines.json``
diff in the PR that intentionally changed performance.

    python tools/perf_gate.py                 # gate the current build
    python tools/perf_gate.py --json          # machine-readable result
    python tools/perf_gate.py --recalibrate   # rewrite perf_baselines.json
    python tools/perf_gate.py --inject-sleep 0.3   # prove the gate fires
    python tools/perf_gate.py --workload zero2_overlap   # gate the sharded
                                              # schedule (extras baseline)

Always runs on CPU (JAX_PLATFORMS=cpu is forced before jax loads): the
gate must never depend on — or hold — a chip.
"""

import argparse
import json
import os
import sys

# Force the CPU backend before any jax import: an attached TPU must not
# turn the gate into a chip job.
os.environ["JAX_PLATFORMS"] = "cpu"
# Multi-device CPU mesh, same forcing as tests/conftest.py: the sharded
# gate workloads (e.g. zero2_overlap, dp=2) need more than one device.
_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributeddeeplearning_tpu.observability import perf_gate  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--recalibrate", action="store_true",
                   help="re-measure and rewrite the baseline file")
    p.add_argument("--baseline", default=None,
                   help=f"baseline path (default {perf_gate.BASELINE_PATH})")
    p.add_argument("--inject-sleep", type=float, default=0.0,
                   metavar="SECONDS",
                   help="plant a sleep in the data_wait phase (self-test: "
                        "the gate must fail)")
    p.add_argument("--passes", type=int, default=3,
                   help="recalibration passes; fastest wins (default 3)")
    p.add_argument("--workload", default="default",
                   choices=sorted(perf_gate.WORKLOADS),
                   help="named gate workload: 'default' is the headline "
                        "proxy (top level of perf_baselines.json); others "
                        "live under its 'extras' key (e.g. zero2_overlap "
                        "gates the overlapped ZeRO-2 schedule)")
    p.add_argument("--json", action="store_true",
                   help="emit the full result as JSON on stdout")
    args = p.parse_args(argv)

    if args.recalibrate:
        baseline = perf_gate.recalibrate(args.baseline, passes=args.passes,
                                         workload=args.workload)
        path = args.baseline or perf_gate.BASELINE_PATH
        if args.json:
            print(json.dumps(baseline, indent=2, sort_keys=True))
        else:
            print(f"wrote {path}")
            print(f"  normalized_step {baseline['normalized_step']} "
                  f"(step {baseline['step_time_ms']} ms / calib "
                  f"{baseline['calib_unit_ms']} ms)")
            print(f"  phase_share {baseline['phase_share']}")
            print(f"  tolerance {baseline['tolerance']}")
        return 0

    result = perf_gate.check(args.baseline,
                             inject_sleep_s=args.inject_sleep,
                             workload=args.workload)
    if args.json:
        print(json.dumps(result, indent=2, sort_keys=True))
    else:
        cur = result["current"]
        print(f"perf gate [{args.workload}]: "
              f"{'PASS' if result['ok'] else 'FAIL'}")
        print(f"  normalized_step {cur['normalized_step']} vs baseline "
              f"{result['baseline_normalized_step']} "
              f"(step {cur['step_time_ms']} ms / calib "
              f"{cur['calib_unit_ms']} ms)")
        print(f"  phase_share {cur['phase_share']}")
        for v in result["violations"]:
            print(f"  VIOLATION: {v}")
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
