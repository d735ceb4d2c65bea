"""Device milliseconds per training step of the routed experts in the Kimi
Linear cell, forward and backward: routing (the router's product, scores and
top-k, the sort of the assignments, the gathers into and out of the row buffer
and the weighted sum) and the held experts' own products (the parts
`moe_routing` and `moe_experts` of `analysis/anatomy.py`, scopes of
`models/moe.py`), summed from the trace by `benchmark/anatomy.py`. The sum of
what `moe_routing_device_ms` and `moe_experts_device_ms` read in the cell they
list."""

from benchmark import anatomy


def read(ctx):
    return anatomy.device_ms(ctx, ("moe_routing", "moe_experts"))
