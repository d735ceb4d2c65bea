"""Device milliseconds per training step of a decoder's blocks and embedding
outside its attention operators and its routed experts, forward and backward:
the attention projections with what sits round them (the flax module path
`layerN/attention/...`: QK-norm, rotary positions, the output gate and the
residual in `models/afmoe.py`; the projections into and out of the KDA and
latent layers in `models/kimi_linear.py`), the dense FFN and the shared
experts (scope `mlp`), the RMSNorms, the embedding (scope `embed`), and what
only a recomputed block's boundary names (the part `remat` of
`analysis/anatomy.py`); summed from the trace by `benchmark/anatomy.py`. One
name for these parts in every cell that lists it; `device_ms.blocks_other` is
the same reading without `remat`, and lists only the cell it was added
with."""

from benchmark import anatomy


def read(ctx):
    return anatomy.device_ms(
        ctx, ("attention_other", "mlp", "layernorm", "embed", "remat"))
