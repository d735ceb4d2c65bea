"""Device milliseconds per training step of attention on the full (causal, no
window) layers of a model that scopes its attention by layer kind: the three
flash kernels and the relayouts round them (scope `attn_full` in
`models/afmoe.py`; the part `attention_full` of `analysis/anatomy.py`), summed
from the trace by `benchmark/anatomy.py`."""

from benchmark import anatomy


def read(ctx):
    return anatomy.device_ms(ctx, ("attention_full",))
