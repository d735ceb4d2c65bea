"""Share of its roofline that the Mamba-2 state-space recurrence reaches in
training: the least time the chip could take for the recurrence of one mixer
(`counts/nemotron_h.py::ssd_least_seconds`: the token-by-token operations
against the bf16 peak, or three passes over x, B, C, dt and y against the
HBM bandwidth, whichever is larger), times the mixer layers and the examples
of a step, over the device time of the part `mixer_mamba`. The required work
is the recurrence's, whatever form computes it: a chunked form's extra
products and what remat computes again are not required."""

from benchmark import anatomy, harness


def read(ctx):
    ms = anatomy.device_ms(ctx, ("mixer_mamba",))
    cfg = ctx["config"]
    if not ms or not ctx["peaks"] or "hybrid_override_pattern" not in cfg:
        return None
    counts = harness.load_module("counts", "nemotron_h")
    layers = cfg["hybrid_override_pattern"].count("M")
    least = counts.ssd_least_seconds(cfg, ctx["traffic"]["seq_len"],
                                     ctx["peaks"])
    return 100.0 * layers * ctx["traffic"]["batch"] * least / (ms / 1e3)
