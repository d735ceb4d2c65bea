"""Device milliseconds per training step of the decoder blocks and the
embedding outside the flash kernels, forward and backward: attention
projections and reshapes, MLP, LayerNorm, embedding (the flax module path and
the scopes `mlp` and `embed` in `models/gpt.py`), summed from the trace by
`benchmark/anatomy.py`."""

from benchmark import anatomy


def read(ctx):
    return anatomy.device_ms(
        ctx, ("attention_other", "mlp", "layernorm", "embed"))
