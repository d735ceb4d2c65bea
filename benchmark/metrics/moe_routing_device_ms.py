"""Device milliseconds per training step of the routed experts' routing,
forward and backward: the router's product, scores and top-k, the sort of the
assignments, the gathers into and out of the row buffer and the weighted sum
(scopes `moe_router`, `moe_dispatch`, `moe_combine` in `models/moe.py`; the
part `moe_routing` of `analysis/anatomy.py`), summed from the trace by
`benchmark/anatomy.py`."""

from benchmark import anatomy


def read(ctx):
    return anatomy.device_ms(ctx, ("moe_routing",))
