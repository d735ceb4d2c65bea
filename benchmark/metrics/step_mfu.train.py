"""The whole training step's share of the chip's peak: operations the forward
and backward passes require per example (the configuration's `counts` file)
times the examples per second of the traced stretch, over chips times the
bf16 peak."""

from benchmark import harness


def read(ctx):
    if not ctx["peaks"] or not ctx["traced_s"] or not ctx["traced_units"]:
        return None
    counts = harness.load_module("counts", ctx["config"]["counts"])
    ops = counts.train_ops_per_example(ctx["config"], ctx["traffic"])
    rate = ctx["traced_units"] / ctx["traced_s"]
    return 100.0 * ops * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
