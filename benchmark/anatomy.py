"""Device time of a traced train step by part of the program.

The trace names a device operation by its HLO line; the leading `%name` is
the instruction. The program knows what each instruction of its compiled step
belongs to (`distributeddeeplearning_tpu/analysis/anatomy.py`: the table of
`op_name`s it saved beside the step's executable, the rule from an `op_name`
to a phase and a part, and the join of a trace's operations to both). This
file hands it `ctx["trace"]["per_op"]` and gives device milliseconds by
(phase, part) over the traced steps, where the trace can bear it. The
`device_ms.*` readers in `metrics/` each pick their parts from it.

A program that saved no such table (the parent of the PR that brought this
file, a run without the compile cache) gives nothing to read, and every
reader returns None.
"""

from __future__ import annotations

import sys

# Names under which `train/steps.py` resolves its step executables.
STEP_NAMES = ("gspmd_train_step", "dp_train_step")
OTHER_MODULES_MAX = 0.01  # of busy time; see `by_part`


def _seconds_by_part(per_op: dict) -> dict | None:
    """{(phase, part): seconds} of `per_op` by the table of the step this
    process ran and the program's own rule; None where it has neither."""
    try:
        from distributeddeeplearning_tpu.analysis import anatomy
        from distributeddeeplearning_tpu.perf import aot
    except ImportError:
        return None
    for name in STEP_NAMES:
        table = aot.anatomy(name)
        if table:
            return anatomy.by_part(per_op, table)
    return None


def by_part(ctx) -> dict | None:
    """{(phase, part): device milliseconds per traced step}, or None where
    there is no trace, no table, or no way to tell the step's operations
    from other programs'.

    Instructions of other programs in the window (the batch generator's
    `%fusion.28`) can share a name with the step's, and the trace's
    operations do not say which program they belong to. Its modules do:
    where programs other than the largest hold more than 1 % of the device's
    busy time, the join would book too much of their time under the step's
    names, and nothing is returned."""
    if "anatomy_ms" in ctx:
        return ctx["anatomy_ms"]
    ctx["anatomy_ms"] = None
    reduced = ctx.get("trace")
    if not reduced or not reduced.get("per_op") or not ctx["traced_units"]:
        return None
    modules = sorted(reduced.get("per_module", {}).values())
    if not modules:
        return None
    others = sum(modules[:-1]) / reduced["chips"]
    if others > OTHER_MODULES_MAX * reduced["busy_s"]:
        print(f"anatomy: programs other than the step hold {others:.4f}s of "
              f"{reduced['busy_s']:.4f}s busy; not read", file=sys.stderr)
        return None
    seconds = _seconds_by_part(reduced["per_op"])
    if seconds is None:
        return None
    steps = ctx["traced_units"] / (ctx["traffic"]["batch"] * ctx["chips"])
    out = {key: 1e3 * s / reduced["chips"] / steps
           for key, s in seconds.items()}
    _print(out, steps)
    ctx["anatomy_ms"] = out
    return out


def device_ms(ctx, parts) -> float | None:
    """Device milliseconds per step of `parts`, all phases; None where
    nothing of them ran (or nothing can be read)."""
    table = by_part(ctx)
    if table is None:
        return None
    found = [ms for (_, part), ms in table.items() if part in parts]
    return sum(found) if found else None


def _print(table: dict, steps: float) -> None:
    """The whole part x phase table, on standard error."""
    phases = sorted({phase for phase, _ in table})
    parts = sorted({part for _, part in table},
                   key=lambda p: -sum(ms for (_, q), ms in table.items()
                                      if q == p))
    total = sum(table.values())
    print(f"anatomy: device ms per step over {steps:g} traced steps "
          f"(total {total:.3f})", file=sys.stderr)
    print("anatomy: " + f"{'part':<16}" + "".join(f"{p:>10}" for p in phases)
          + f"{'all':>10}{'share':>8}", file=sys.stderr)
    for part in parts:
        row = [table.get((phase, part), 0.0) for phase in phases]
        print("anatomy: " + f"{part:<16}"
              + "".join(f"{ms:>10.3f}" for ms in row)
              + f"{sum(row):>10.3f}{100 * sum(row) / total:>7.1f}%",
              file=sys.stderr)
