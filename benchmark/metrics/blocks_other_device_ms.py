"""Device milliseconds per training step of an AFMoE decoder's blocks and
embedding outside the flash kernels and the routed experts, forward and
backward: the attention projections with QK-norm, rotary positions, the output
gate and the residual (the flax module path `layerN/attention/...`), the dense
FFN and the shared experts (scope `mlp` in `models/afmoe.py` and
`models/moe.py`), the RMSNorms, the embedding (scope `embed`), and what only a
recomputed block's boundary names (the part `remat` of
`analysis/anatomy.py`); summed from the trace by `benchmark/anatomy.py`.
`device_ms.blocks_other` is the same reading without `remat`, and lists only
the cell it was added with."""

from benchmark import anatomy


def read(ctx):
    return anatomy.device_ms(
        ctx, ("attention_other", "mlp", "layernorm", "embed", "remat"))
