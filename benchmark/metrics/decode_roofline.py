"""Share of its roofline that the decode program reaches: for each engine
step of the traced stretch, the least time the chip could take for that step's
rows at their live contexts (the larger of operations over peak and bytes over
bandwidth, `counts/transformer_serve.py`), summed, over the device time of the
decode program in the trace (`kernels.decode_program` in the configuration's
file names its events among the trace's programs)."""

from benchmark import harness, trace


def read(ctx):
    pattern = ctx["config"].get("kernels", {}).get("decode_program")
    steps = [s for s in ctx.get("steps", []) if s["rows"]]
    if not (pattern and ctx["trace"] and ctx["peaks"] and steps):
        return None
    seconds = trace.op_seconds(ctx["trace"], pattern, "per_module")
    if seconds <= 0:
        return None
    counts = harness.load_module("counts", "transformer_serve")
    cfg, pk = ctx["config"], ctx["peaks"]
    least = sum(max(
        counts.decode_ops(cfg, s["rows"], s["context"])
        / pk["bf16_flops_per_s"],
        counts.decode_bytes(cfg, s["rows"], s["context"])
        / pk["hbm_bytes_per_s"]) for s in steps)
    return 100.0 * least / seconds
