"""Share of the traced window in which no operation ran on the device
(1 - union of the device's operation intervals over the window, mean over
chips)."""

from benchmark import trace


def read(ctx):
    return trace.idle_share(ctx["trace"])
