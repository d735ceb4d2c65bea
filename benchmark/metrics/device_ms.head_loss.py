"""Device milliseconds per training step of the tied head and the loss,
forward and backward: the scopes `head` (`models/gpt.py`) and `loss`
(`train/steps.py`), summed from the trace by `benchmark/anatomy.py`."""

from benchmark import anatomy


def read(ctx):
    return anatomy.device_ms(ctx, ("head", "loss"))
