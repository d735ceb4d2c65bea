"""Seconds JAX spent tracing and lowering programs before the window opened
(records `trace` and `lower`), every program of the process: the step, the
init program, and the runner's own (`<lambda>`)."""

from benchmark import setup_phases


def read(ctx):
    return setup_phases.seconds(ctx, {"trace", "lower"})
