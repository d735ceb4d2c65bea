"""Device milliseconds per training step of latent attention's kernels,
forward and backward, over the latent layers: the three flash kernels at
queries and keys of qk_nope + qk_rope and values of v_head, with the relayouts
round them (scope `attn_mla` in `models/kimi_linear.py`; the part
`attention_mla` of `analysis/anatomy.py`), summed from the trace by
`benchmark/anatomy.py`. The projections down to and up from the latent are
`attention_other`."""

from benchmark import anatomy


def read(ctx):
    return anatomy.device_ms(ctx, ("attention_mla",))
