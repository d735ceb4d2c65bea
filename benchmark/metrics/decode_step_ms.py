"""Median host time of an `engine.step()` that admitted nothing, so ran the
decode program and no prefill (span `decode_step`, recorded by the runner
round the call)."""

import statistics


def read(ctx):
    d = ctx["spans"].durations("decode_step")
    return statistics.median(d) * 1e3 if d else None
