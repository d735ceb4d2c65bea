"""Device milliseconds per training step of Kimi Delta Attention, forward and
backward, over the KDA layers: the short convolutions, the L2 norms, the
gates, the chunked delta-rule operator and the gated norm (scope `attn_kda`
in `models/kimi_linear.py`; the part `attention_kda` of
`analysis/anatomy.py`), summed from the trace by `benchmark/anatomy.py`. The
projections in and out of the layer are `attention_other`."""

from benchmark import anatomy


def read(ctx):
    return anatomy.device_ms(ctx, ("attention_kda",))
