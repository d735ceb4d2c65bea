"""Device milliseconds per training step of a decoder's head and loss, forward
and backward: the scopes `head` (`models/afmoe.py`, `models/kimi_linear.py`:
the untied head over this chip's slice of the vocabulary) and `loss`
(`train/steps.py`), summed from the trace by `benchmark/anatomy.py`. One name
for these parts in every cell that lists it; `device_ms.head_loss` is the same
reading, and lists only the cell it was added with."""

from benchmark import anatomy


def read(ctx):
    return anatomy.device_ms(ctx, ("head", "loss"))
