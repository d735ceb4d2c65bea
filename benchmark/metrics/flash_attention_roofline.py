"""Share of their roofline that the flash-attention kernels (forward, dQ,
dK/dV) reach in training: the least time the chip could take for what the
traced examples require of them, the larger of operations over peak and bytes
over bandwidth (`counts/flash_attention.py`), over the device time of the
kernels' events in the trace. The configuration's file names the kernels'
events (`kernels.flash_attention`, a regular expression)."""

from benchmark import harness, trace


def read(ctx):
    pattern = ctx["config"].get("kernels", {}).get("flash_attention")
    if not (pattern and ctx["trace"] and ctx["peaks"] and ctx["traced_units"]):
        return None
    seconds = trace.op_seconds(ctx["trace"], pattern)
    if seconds <= 0:
        return None
    counts = harness.load_module("counts", "flash_attention")
    cfg, s = ctx["config"], ctx["traffic"]["seq_len"]
    calls = ctx["traced_units"] * cfg["n_layer"]
    least = max(counts.train_ops(cfg, s) / ctx["peaks"]["bf16_flops_per_s"],
                counts.train_bytes(cfg, s) / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * calls * least / seconds
