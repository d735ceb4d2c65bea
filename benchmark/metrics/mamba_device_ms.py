"""Device milliseconds per training step of the Mamba-2 mixers, forward and
backward, over the mixer layers: the causal convolution with its SiLU, the
step sizes, the chunked state-space recurrence with its D skip, and the
gated group norm (scope `mamba` in `models/nemotron_h.py`; the part
`mixer_mamba` of `analysis/anatomy.py`), summed from the trace by
`benchmark/anatomy.py`. The projections in and out of a mixer are
`attention_other`. A program without that part gives nothing to read."""

from benchmark import anatomy


def read(ctx):
    return anatomy.device_ms(ctx, ("mixer_mamba",))
