"""Share of their roofline that the flash kernels reach on the latent layers
in training: the least time the chip could take for the causal pairs at
queries and keys of qk_nope + qk_rope and values of v_head
(`counts/kimi_linear.py::mla_least_seconds`), times the latent layers and the
examples of a step, over the device time of the part `attention_mla` (the
three kernels under the scope `attn_mla`, with the relayouts round them)."""

from benchmark import anatomy, harness


def read(ctx):
    ms = anatomy.device_ms(ctx, ("attention_mla",))
    cfg = ctx["config"]
    if not ms or not ctx["peaks"] or "linear_attn_config" not in cfg:
        return None
    counts = harness.load_module("counts", "kimi_linear")
    layers = len(cfg["linear_attn_config"]["full_attn_layers"])
    least = counts.mla_least_seconds(cfg, ctx["traffic"]["seq_len"],
                                     ctx["peaks"])
    return 100.0 * layers * ctx["traffic"]["batch"] * least / (ms / 1e3)
