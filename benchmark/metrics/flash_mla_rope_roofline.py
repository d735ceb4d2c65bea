"""Share of their roofline that the flash kernels reach on latent attention
with rotated keys, in training: the least time the chip could take for the
causal pairs at queries and keys of qk_nope + qk_rope and values of v_head
(`counts/xing4.py::mla_least_seconds`), times the layers (every layer's
attention is latent) and the examples of a step, over the device time of the
part `attention_mla` (the three kernels under the scope `attn_mla`, with the
relayouts round them). `flash_mla_roofline` is the same share for a
configuration whose latent layers are listed in a `linear_attn_config`."""

from benchmark import anatomy, harness


def read(ctx):
    ms = anatomy.device_ms(ctx, ("attention_mla",))
    cfg = ctx["config"]
    if not ms or not ctx["peaks"] or "hc_mult" not in cfg:
        return None
    counts = harness.load_module("counts", "xing4")
    least = counts.mla_least_seconds(cfg, ctx["traffic"]["seq_len"],
                                     ctx["peaks"])
    return (100.0 * cfg["num_hidden_layers"] * ctx["traffic"]["batch"]
            * least / (ms / 1e3))
