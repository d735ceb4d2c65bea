"""Share of their roofline that the flash kernels reach on the full (causal,
no window) layers of a model whose layers differ in attention kind: as
`flash_window_roofline.py`, over the causal pairs and the device time of the
part `attention_full` (scope `attn_full`)."""

from benchmark import harness


def read(ctx):
    window = harness.load_module("metrics", "flash_window_roofline")
    return window.read(ctx, "full_attention", "attention_full")
