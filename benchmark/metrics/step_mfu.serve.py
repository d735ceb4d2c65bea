"""The serving steps' share of the chip's peak: operations that the prompt
tokens prefilled and the output tokens decoded in the traced stretch require
(`counts/transformer_serve.py`), over the stretch's length times the bf16
peak."""

from benchmark import harness


def read(ctx):
    steps = ctx.get("steps") or []
    if not (steps and ctx["peaks"] and ctx["traced_s"]):
        return None
    counts = harness.load_module("counts", "transformer_serve")
    cfg = ctx["config"]
    ops = sum(counts.decode_ops(cfg, s["rows"], s["context"])
              + sum(counts.prefill_ops(cfg, n) for n in s["prefilled"])
              for s in steps)
    return 100.0 * ops / (ctx["traced_s"] * ctx["chips"]
                          * ctx["peaks"]["bf16_flops_per_s"])
