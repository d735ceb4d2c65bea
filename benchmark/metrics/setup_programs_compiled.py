"""Programs XLA compiled before the window opened (records `xla_compile`):
0 when every program came from a cache."""

from benchmark import setup_phases


def read(ctx):
    return setup_phases.count(ctx, {"xla_compile"})
