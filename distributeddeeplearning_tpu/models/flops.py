"""Analytic training FLOPs per example, for MFU reporting (bench.py).

Convention: a multiply-accumulate counts as 2 FLOPs — the same convention
as both XLA's cost analysis and published chip peaks, so
``mfu = rate * train_flops_per_example / peak`` is dimensionally honest.
``train ≈ 3 x forward`` (backward is two matmuls per forward matmul); the
optimizer update is O(params) — three orders of magnitude below the matmul
term at batch>=1 — and is deliberately not counted, matching the standard
MFU definition (model FLOPs, not executed FLOPs: remat recompute is also
NOT counted, so a remat run's MFU honestly reports the efficiency loss).

CNN entries are the conv-sum constants at 224x224 (the literature MAC
counts x2); transformer FLOPs are enumerated exactly from each model's
config dataclass (qkv/out/ffn matmuls + the two S^2 attention matmuls +
the LM/MLM head). Both are validated against XLA lowered-HLO cost
analysis on CPU by tests/test_flops.py (tools/calibrate_flops.py is the
standalone calibration harness).
"""

from __future__ import annotations

# Forward FLOPs per image at 224x224, 2 x the canonical conv+fc MAC sums
# (torchvision geometry — enforced by the param-count tests in
# tests/test_models.py).
_CNN_FWD_FLOPS_224 = {
    "resnet18": 3.64e9,
    "resnet34": 7.34e9,
    "resnet50": 8.18e9,
    "resnet101": 15.6e9,
    "resnet152": 23.0e9,
    "densenet121": 5.74e9,
    "densenet169": 6.81e9,
}

# bf16 systolic-array peak FLOP/s per chip, keyed by substrings of
# ``jax.devices()[0].device_kind`` (lowercased). Sources: published TPU
# spec sheets; v5e ("TPU v5 lite") = 197 TFLOP/s bf16.
_BF16_PEAK_BY_KIND = (
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v6 lite", 918e12),
    ("v6e", 918e12),
    ("trillium", 918e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 46e12),
)

# HBM bandwidth bytes/s per chip, same key scheme. Decode at small batch
# is memory-bound (every token re-reads the weights and the KV cache), so
# this is the roof serving numbers are scored against. Sources: published
# TPU spec sheets (v5e 819 GB/s, v5p 2765, v4 1228, v3 900, v2 700,
# Trillium ~1640).
_HBM_BW_BY_KIND = (
    ("v5 lite", 819e9),
    ("v5e", 819e9),
    ("v5p", 2765e9),
    ("v6 lite", 1640e9),
    ("v6e", 1640e9),
    ("trillium", 1640e9),
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
)


def _by_kind(table, device_kind: str) -> float | None:
    """The table's value for a TPU ``device_kind``; None for a device that
    is not a TPU (roofline fields are then absent, never computed from an
    assumed peak). A TPU the table does not know is an error, not a
    silently missing field: add its row, with its source."""
    kind = device_kind.lower()
    for sub, value in table:
        if sub in kind:
            return value
    if "tpu" in kind:
        raise ValueError(
            f"no published peak for TPU device_kind {device_kind!r} in "
            f"models/flops.py — add it to the tables with its source")
    return None


def bf16_peak_flops(device_kind: str) -> float | None:
    """Per-chip bf16 peak for a jax device_kind; None off TPU."""
    return _by_kind(_BF16_PEAK_BY_KIND, device_kind)


# TPU MXUs natively multiply bf16; XLA executes a true-f32 matmul as a
# 6-pass bf16x6 decomposition (each operand split into three bf16 terms),
# so the sustainable f32 matmul peak is the bf16 peak / 6 across
# generations. Published spec sheets quote bf16 only, which is why the
# ratio is a convention here rather than a per-chip table.
_F32_PEAK_DIVISOR = 6.0


def peak_flops(device_kind: str, dtype: str = "bfloat16") -> float | None:
    """Per-chip matmul peak for a compute dtype; None off TPU, an error
    for a TPU kind the table does not list.

    The dtype-aware roofline denominator (docs/perf_measurement.md): an
    fp32 arm is scored against the fp32 roof and a mixed/bf16 arm against
    the bf16 roof, so ``pct_of_peak`` measures distance from what the
    chip could do AT THAT PRECISION — while raw ``examples_per_sec``
    still shows the mixed arm's absolute win.
    """
    peak = _by_kind(_BF16_PEAK_BY_KIND, device_kind)
    if peak is None:
        return None
    if dtype in ("float32", "f32"):
        return peak / _F32_PEAK_DIVISOR
    if dtype in ("bfloat16", "bf16", "float16", "f16"):
        return peak
    raise ValueError(f"unknown compute dtype {dtype!r} for peak_flops "
                     f"(expected float32 or bfloat16)")


def hbm_bw_bytes(device_kind: str) -> float | None:
    """Per-chip HBM bandwidth (bytes/s); None off TPU."""
    return _by_kind(_HBM_BW_BY_KIND, device_kind)


def _transformer_fwd_flops(*, num_layers: int, hidden: int, ffn: int,
                           seq_len: int, vocab: int, head_positions: int,
                           kv_heads_frac: float = 1.0,
                           ffn_matmuls: int = 2,
                           mlm_transform: bool = False,
                           patch_embed_in: int = 0,
                           num_classes: int = 0) -> float:
    """Exact matmul enumeration for one example (2 x MAC).

    ``head_positions``: rows hitting the vocab projection (S for causal /
    dense MLM, the gather width for gather-mode MLM, 0 for classifiers).
    ``kv_heads_frac``: num_kv_heads / num_heads (GQA shrinks the KV proj).
    ``ffn_matmuls``: 2 for GELU MLPs, 3 for SwiGLU.
    ``patch_embed_in``: ViT patch-embedding input dim (P*P*3), else 0.
    """
    s, d = seq_len, hidden
    per_layer = (
        2 * s * d * d            # Q proj
        + 2 * 2 * s * d * (d * kv_heads_frac)  # K and V proj
        + 2 * s * s * d          # scores Q @ K^T (all heads)
        + 2 * s * s * d          # probs @ V
        + 2 * s * d * d          # output proj
        + ffn_matmuls * 2 * s * d * ffn)
    head = 2 * head_positions * d * vocab
    if mlm_transform:
        head += 2 * head_positions * d * d
    if num_classes:
        head += 2 * d * num_classes
    embed = 2 * s * patch_embed_in * d if patch_embed_in else 0.0
    return num_layers * per_layer + head + embed


# Causal-LM geometries, shared by the training-FLOPs path (below) and the
# decode FLOPs/bytes model: one source of truth so a serving roofline and
# a training MFU for the same model can never disagree on shapes. Tiny
# test models are deliberately absent, like everywhere else in this file.
_CAUSAL_GEOM = {
    "gpt2_small": dict(num_layers=12, hidden=768, ffn=3072, vocab=50257),
    "gpt2_medium": dict(num_layers=24, hidden=1024, ffn=4096, vocab=50257),
    "llama2_7b": dict(num_layers=32, hidden=4096, ffn=11008, vocab=32000,
                      ffn_matmuls=3),
    "tinyllama_1b": dict(num_layers=22, hidden=2048, ffn=5632, vocab=32000,
                         ffn_matmuls=3, kv_heads_frac=4 / 32),
}


def fwd_flops_per_example(model: str, *, seq_len: int | None = None,
                          mlm_positions: int = 0) -> float | None:
    """Analytic forward FLOPs for one example, or None if the model has no
    entry (tiny/test models are deliberately absent). ``mlm_positions`` is
    the gather-head width (0 = dense full-sequence logits)."""
    if model in _CNN_FWD_FLOPS_224:
        return _CNN_FWD_FLOPS_224[model]
    if model == "vit_b16":
        return _transformer_fwd_flops(
            num_layers=12, hidden=768, ffn=3072, seq_len=197, vocab=0,
            head_positions=0, patch_embed_in=16 * 16 * 3, num_classes=1000)
    if model == "vit_l16":
        return _transformer_fwd_flops(
            num_layers=24, hidden=1024, ffn=4096, seq_len=197, vocab=0,
            head_positions=0, patch_embed_in=16 * 16 * 3, num_classes=1000)
    if seq_len is None:
        return None
    if model in ("bert_base", "bert_large"):
        large = model == "bert_large"
        return _transformer_fwd_flops(
            num_layers=24 if large else 12, hidden=1024 if large else 768,
            ffn=4096 if large else 3072, seq_len=seq_len, vocab=30522,
            head_positions=mlm_positions or seq_len, mlm_transform=True)
    geom = _CAUSAL_GEOM.get(model)
    if geom is not None:
        return _transformer_fwd_flops(seq_len=seq_len,
                                      head_positions=seq_len, **geom)
    return None


def train_flops_per_example(model: str, *, seq_len: int | None = None,
                            mlm_positions: int = 0) -> float | None:
    """fwd+bwd model FLOPs per example (3 x forward), or None."""
    fwd = fwd_flops_per_example(model, seq_len=seq_len,
                                mlm_positions=mlm_positions)
    return None if fwd is None else 3.0 * fwd


def decode_flops_per_token(model: str, *,
                           context_len: int) -> float | None:
    """Model FLOPs to emit ONE token at batch 1 with a KV cache holding
    ``context_len`` positions: every weight matmul at seq=1 plus the two
    attention products against the cached context. None for models with
    no causal geometry entry."""
    g = _CAUSAL_GEOM.get(model)
    if g is None:
        return None
    d, ffn = g["hidden"], g["ffn"]
    kv = g.get("kv_heads_frac", 1.0)
    per_layer = (
        2 * d * d                      # Q proj
        + 2 * 2 * d * (d * kv)         # K and V proj
        + 2 * context_len * d          # q @ K^T over the cache (all heads)
        + 2 * context_len * d          # probs @ V
        + 2 * d * d                    # output proj
        + g.get("ffn_matmuls", 2) * 2 * d * ffn)
    return g["num_layers"] * per_layer + 2 * d * g["vocab"]


def _decode_weight_and_kv_bytes(model: str, *, context_len: int,
                                dtype_bytes: int = 2):
    """(weight_bytes, kv_bytes) per decode step row: the full weight set
    and one row's KV-cache read (+ its one-position write). Split out
    because batching amortizes the first and multiplies the second."""
    g = _CAUSAL_GEOM.get(model)
    if g is None:
        return None
    d, ffn = g["hidden"], g["ffn"]
    kv = g.get("kv_heads_frac", 1.0)
    weight_params = g["num_layers"] * (
        d * d * 2                      # Q + output proj
        + 2 * d * (d * kv)             # K and V proj
        + g.get("ffn_matmuls", 2) * d * ffn) + d * g["vocab"]  # LM head
    kv_traffic = g["num_layers"] * 2 * (context_len + 1) * (d * kv)
    return (weight_params * float(dtype_bytes),
            kv_traffic * float(dtype_bytes))


def decode_bytes_per_token(model: str, *, context_len: int,
                           dtype_bytes: int = 2) -> float | None:
    """HBM bytes moved to emit ONE token at batch 1: the full weight set
    (read once per token — nothing amortizes it at batch 1) plus the KV
    cache read (2 x context x kv-width per layer) and the one-position
    write. This is why small-batch decode is memory-bound: FLOPs shrink
    with seq=1 but the weight traffic does not."""
    traffic = _decode_weight_and_kv_bytes(model, context_len=context_len,
                                          dtype_bytes=dtype_bytes)
    return None if traffic is None else traffic[0] + traffic[1]


def decode_roofline(model: str, *, context_len: int,
                    tokens_per_sec: float | None,
                    device_kind: str | None,
                    dtype_bytes: int = 2, batch: int = 1) -> dict:
    """Roofline fields for a decode token rate (tokens/sec/chip).

    Per decode step at batch B the chip moves ``weights + B x kv`` bytes
    and does ``B x flops_per_token`` FLOPs, so the attainable rate is
    ``B / max(B*flops/peak, (weights + B*kv)/bw)`` — at batch 1 the
    weight traffic dominates (``bound == "memory"``), and growing B
    amortizes exactly that term, which is the whole motivation for
    continuous batching. Unknown model/chip omits the respective fields;
    never raises."""
    flops = decode_flops_per_token(model, context_len=context_len)
    traffic = _decode_weight_and_kv_bytes(model, context_len=context_len,
                                          dtype_bytes=dtype_bytes)
    if flops is None or traffic is None or tokens_per_sec is None:
        return {}
    weight_bytes, kv_bytes = traffic
    batch = max(1, int(batch))
    out = {"decode_flops_per_token": flops,
           "decode_bytes_per_token": weight_bytes + kv_bytes,
           "context_len": int(context_len), "batch": batch,
           "gflops_per_sec": round(tokens_per_sec * flops / 1e9, 2)}
    if not device_kind:
        return out
    peak, bw = bf16_peak_flops(device_kind), hbm_bw_bytes(device_kind)
    if not peak or not bw:
        return out
    compute_s = batch * flops / peak
    memory_s = (weight_bytes + batch * kv_bytes) / bw
    out["bound"] = "memory" if memory_s >= compute_s else "compute"
    attainable = batch / max(compute_s, memory_s)
    out["attainable_tokens_per_sec"] = round(attainable, 1)
    out["pct_of_peak"] = round(100.0 * tokens_per_sec / attainable, 1)
    return out
