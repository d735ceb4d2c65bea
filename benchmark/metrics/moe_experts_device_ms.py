"""Device milliseconds per training step of the routed experts' grouped
products over the held experts' stacked kernels, forward and backward (scope
`moe_experts` in `models/moe.py`, and the kernels the compiler makes of
`jax.lax.ragged_dot`; the part `moe_experts` of `analysis/anatomy.py`), summed
from the trace by `benchmark/anatomy.py`."""

from benchmark import anatomy


def read(ctx):
    return anatomy.device_ms(ctx, ("moe_experts",))
