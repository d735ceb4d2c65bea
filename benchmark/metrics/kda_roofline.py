"""Share of its roofline that Kimi Delta Attention reaches in training: the
least time the chip could take for the recurrence of one layer
(`counts/kimi_linear.py::kda_least_seconds`: the token-by-token operations
against the bf16 peak, or three passes over q, k, v, o and the gates against
the HBM bandwidth, whichever is larger), times the KDA layers and the
examples of a step, over the device time of the part `attention_kda`. The
required work is the recurrence's, whatever form computes it: a chunked
form's extra products and what remat computes again are not required."""

from benchmark import anatomy, harness


def read(ctx):
    ms = anatomy.device_ms(ctx, ("attention_kda",))
    cfg = ctx["config"]
    if not ms or not ctx["peaks"] or "linear_attn_config" not in cfg:
        return None
    counts = harness.load_module("counts", "kimi_linear")
    layers = len(cfg["linear_attn_config"]["kda_layers"])
    least = counts.kda_least_seconds(cfg, ctx["traffic"]["seq_len"],
                                     ctx["peaks"])
    return 100.0 * layers * ctx["traffic"]["batch"] * least / (ms / 1e3)
