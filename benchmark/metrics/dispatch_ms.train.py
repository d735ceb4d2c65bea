"""Median host time of one call of the compiled train step (span `dispatch`,
recorded by the runner round the call): what the host pays to enqueue a step."""

import statistics


def read(ctx):
    d = ctx["spans"].durations("dispatch")
    return statistics.median(d) * 1e3 if d else None
