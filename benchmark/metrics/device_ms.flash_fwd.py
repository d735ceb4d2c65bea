"""Device milliseconds per training step of the flash-attention forward
kernel: the Pallas kernel named `flash_fwd` in `ops/flash_attention.py`,
summed from the trace by `benchmark/anatomy.py`."""

from benchmark import anatomy


def read(ctx):
    return anatomy.device_ms(ctx, ("flash_fwd",))
