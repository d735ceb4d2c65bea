"""Device milliseconds per training step that the rule of
`distributeddeeplearning_tpu/analysis/anatomy.py` placed in no part: the
compiler's own instructions without a name, scopes the rule does not know.
Summed from the trace by `benchmark/anatomy.py`."""

from benchmark import anatomy


def read(ctx):
    return anatomy.device_ms(ctx, ("unattributed",))
