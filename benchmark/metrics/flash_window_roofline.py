"""Share of their roofline that the flash kernels reach on the
sliding-window layers in training: the least time the chip could take for the
pairs inside the window at the model's grouped heads
(`counts/flash_attention_gqa.py`), times the window layers and the examples of
a step, over the device time of the part `attention_window` (the three
kernels under the scope `attn_window`, with the relayouts round them)."""

from benchmark import anatomy, harness

KIND, PART = "sliding_attention", "attention_window"


def read(ctx, kind=KIND, part=PART):
    ms = anatomy.device_ms(ctx, (part,))
    cfg = ctx["config"]
    if not ms or not ctx["peaks"] or "layer_types" not in cfg:
        return None
    counts = harness.load_module("counts", "flash_attention_gqa")
    window = cfg["sliding_window"] if kind == KIND else None
    layers = sum(k == kind for k in cfg["layer_types"])
    least = counts.least_seconds(cfg, ctx["traffic"]["seq_len"], window,
                                 ctx["peaks"])
    return 100.0 * layers * ctx["traffic"]["batch"] * least / (ms / 1e3)
