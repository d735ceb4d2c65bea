"""Seconds of loading compiled programs before the window opened: from JAX's
persistent cache (records `cache_load`) and from the AOT entries (phases
`aot_load`)."""

from benchmark import setup_phases


def read(ctx):
    return setup_phases.seconds(ctx, {"cache_load", "aot_load"})
