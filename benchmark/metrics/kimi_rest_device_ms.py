"""Device milliseconds per training step of the Kimi Linear cell outside its
two attention operators and its routed experts, forward and backward: the
projections into and out of the KDA and latent layers (`attention_other`), the
dense FFN and the shared experts (`mlp`), the RMSNorms, the embedding, the
head over this chip's slice of the vocabulary, the loss, and what only a
recomputed block's boundary names (`remat`); parts of `analysis/anatomy.py`,
summed from the trace by `benchmark/anatomy.py`. The sum of what
`blocks_other_device_ms` and `head_loss_device_ms` read in the cell they
list."""

from benchmark import anatomy


def read(ctx):
    return anatomy.device_ms(
        ctx, ("attention_other", "mlp", "layernorm", "embed", "head", "loss",
              "remat"))
