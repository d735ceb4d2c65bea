"""Seconds of compiling before the window opened: XLA compiles that no cache
held (records `xla_compile`) and the AOT entries written after them (phases
`aot_save`). What a cold start pays that a warm one does not."""

from benchmark import setup_phases


def read(ctx):
    return setup_phases.seconds(ctx, {"xla_compile", "aot_save"})
