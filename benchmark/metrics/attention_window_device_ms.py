"""Device milliseconds per training step of attention on the sliding-window
layers: the three flash kernels and the relayouts round them (scope
`attn_window` in `models/afmoe.py`; the part `attention_window` of
`analysis/anatomy.py`), summed from the trace by `benchmark/anatomy.py`."""

from benchmark import anatomy


def read(ctx):
    return anatomy.device_ms(ctx, ("attention_window",))
