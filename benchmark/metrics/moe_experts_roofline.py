"""Share of their roofline that the routed experts' grouped products reach in
training: the least time the chip could take for the three products, forward
and the two backward products of each, over the EXPECTED rows of a layer and
the held experts' kernels once a pass (`counts/afmoe.py`; the expectation,
because the train runner keeps no counter of the rows that landed), times the
expert layers and the examples of a step, over the device time of the part
`moe_experts`. What remat computes again is not required work."""

from benchmark import anatomy, harness


def read(ctx):
    ms = anatomy.device_ms(ctx, ("moe_experts",))
    cfg = ctx["config"]
    if not ms or not ctx["peaks"] or "moe_intermediate_size" not in cfg:
        return None
    counts = harness.load_module("counts", "afmoe")
    s = ctx["traffic"]["seq_len"]
    least = max(
        counts.experts_train_ops(cfg, s) / ctx["peaks"]["bf16_flops_per_s"],
        counts.experts_train_bytes(cfg, s) / ctx["peaks"]["hbm_bytes_per_s"])
    layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    return 100.0 * layers * ctx["traffic"]["batch"] * least / (ms / 1e3)
