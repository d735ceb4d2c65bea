"""Seconds of `loop.build` before the window opened (phase `build`): the
model, the optimizer, and the state's init program loaded or compiled and
run."""

from benchmark import setup_phases


def read(ctx):
    return setup_phases.seconds(ctx, {"build"})
