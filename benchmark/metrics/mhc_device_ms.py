"""Device milliseconds per training step of the hyper-connections, forward and
backward, over every sub-layer: the norm over the residual streams, the
product that makes the coefficients, Sinkhorn's iterations and the three
mixes, with the copy of the embedding to the streams and their sum after the
last layer (scope `mhc` in `models/hyper_connections.py`; the part
`residual_mhc` of `analysis/anatomy.py`), summed from the trace by
`benchmark/anatomy.py`. A program whose rule knows no such part books
nothing under it, and nothing is read."""

from benchmark import anatomy


def read(ctx):
    return anatomy.device_ms(ctx, ("residual_mhc",))
