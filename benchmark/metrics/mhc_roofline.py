"""Share of their roofline that the hyper-connections reach in training: the
least time the chip could take for one hyper-connection round a sub-layer
(`counts/xing4.py::mhc_least_seconds`: three passes' worth of reading the
residual streams twice and writing them once, with the sub-layer's input and
result, against the HBM bandwidth, or the mixes' operations against the bf16
peak, whichever is larger), times two a layer, the layers and the examples of
a step, over the device time of the part `residual_mhc`. What remat computes
again is not required, nor is any pass over the streams beyond those three."""

from benchmark import anatomy, harness


def read(ctx):
    ms = anatomy.device_ms(ctx, ("residual_mhc",))
    cfg = ctx["config"]
    if not ms or not ctx["peaks"] or "hc_mult" not in cfg:
        return None
    counts = harness.load_module("counts", "xing4")
    rounds = 2 * cfg["num_hidden_layers"]
    least = counts.mhc_least_seconds(cfg, ctx["traffic"]["seq_len"],
                                     ctx["peaks"])
    return 100.0 * rounds * ctx["traffic"]["batch"] * least / (ms / 1e3)
