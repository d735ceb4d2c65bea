"""Programs compiled or taken from the persistent cache inside the window
(records `xla_compile` and `cache_load` that ended between the first and the
last of the runner's spans): 0 when set-up built everything the window ran."""

from benchmark import setup_phases


def read(ctx):
    return setup_phases.count(ctx, {"xla_compile", "cache_load"}, "window")
