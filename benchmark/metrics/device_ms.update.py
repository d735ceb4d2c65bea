"""Device milliseconds per training step of what follows the gradients:
loss-scale check and skip, optimizer, EMA and bad-step guard, gradient
collectives (`STEP_SCOPES` in `train/steps.py`), summed from the trace by
`benchmark/anatomy.py`."""

from benchmark import anatomy


def read(ctx):
    return anatomy.device_ms(
        ctx, ("loss_scale", "optimizer", "ema_guard", "grad_reduce"))
