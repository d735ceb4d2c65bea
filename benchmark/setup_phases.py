"""The program's phase log (`observability/telemetry.py::phases`) laid
against the runner's window, for the set-up readers in `benchmark/metrics/`.

The window runs from the earliest start to the latest end of the runner's own
spans (`ctx["spans"]`, on `perf_counter`, which is the log's clock on Linux).
A record belongs to set-up when it ended before the window opened, and to the
window when it ended inside it. Seconds are the union of the records'
intervals, counts are records. Nothing is read, and None returned, where the
program keeps no phase log (an older tree), where the log dropped records,
where the run recorded no span, or, for JAX's own stage records (`trace`,
`lower`, `xla_compile`, `cache_load`), where nothing listened for them.
"""

from __future__ import annotations

JAX_STAGES = {"trace", "lower", "xla_compile", "cache_load"}


def _window(ctx):
    spans = getattr(ctx.get("spans"), "spans", None) or {}
    ivs = [iv for held in spans.values() for iv in held]
    if not ivs:
        return None
    return min(a for a, _ in ivs), max(b for _, b in ivs)


def records(ctx, names, where: str):
    """The log's records named in `names` that ended before the window
    (`where="setup"`) or inside it (`where="window"`); None where there is
    nothing to read."""
    try:
        from distributeddeeplearning_tpu.observability import telemetry
    except ImportError:
        return None
    read = getattr(telemetry, "phases", None)
    if read is None:
        return None
    if JAX_STAGES & set(names) and not telemetry.watching_compiles():
        return None
    window, log = _window(ctx), read()
    if window is None or log is None:
        return None
    lo, hi = window
    if where == "setup":
        return [r for r in log if r.name in names and r.end_s <= lo]
    return [r for r in log if r.name in names and lo <= r.end_s <= hi]


def seconds(ctx, names, where: str = "setup"):
    """Seconds during which at least one such record was open: JAX traces
    a program inside the trace of the one that calls it, so a plain sum
    would count the inner one twice."""
    recs = records(ctx, names, where)
    if recs is None:
        return None
    total, end = 0.0, None
    for r in sorted(recs, key=lambda r: r.start_s):
        if end is None or r.start_s > end:
            total += r.end_s - r.start_s
            end = r.end_s
        elif r.end_s > end:
            total += r.end_s - end
            end = r.end_s
    return total


def count(ctx, names, where: str = "setup"):
    recs = records(ctx, names, where)
    return None if recs is None else len(recs)
