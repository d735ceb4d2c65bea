"""What serving a decoder-only transformer requires of the chip.

`decode_ops` / `decode_bytes` are copied from `distributeddeeplearning_tpu/
models/flops.py` (`decode_flops_per_token`, `_decode_weight_and_kv_bytes`,
`decode_roofline`): one decode step for a batch of rows reads every weight
once and each row's LIVE key/value context, and does each row's products
against that context. The bytes are those of the live context, which is what
the algorithm needs; a program that reads each slot's whole capacity wastes
the difference, and that waste must not enter the count. `prefill_ops` is the
causal forward over a prompt, with the head applied to its last position only.
A multiply-accumulate is 2 operations.
"""

from __future__ import annotations


def _dims(config: dict):
    d = config["n_embd"]
    return d, config.get("n_inner") or 4 * d, config["n_layer"]


def weight_params(config: dict) -> float:
    d, f, layers = _dims(config)
    return layers * (4 * d * d + 2 * d * f) + d * config["vocab_size"]


def decode_ops(config: dict, rows: int, context_tokens: int) -> float:
    """One decode step: `rows` rows whose live contexts sum to
    `context_tokens`."""
    d, f, layers = _dims(config)
    per_row = layers * (4 * 2 * d * d + 2 * 2 * d * f) \
        + 2 * d * config["vocab_size"]
    return rows * per_row + layers * 2 * 2 * d * context_tokens


def decode_bytes(config: dict, rows: int, context_tokens: int,
                 width: int = 2) -> float:
    """Weights once, each row's live keys and values read, one position a
    row written."""
    d, _, layers = _dims(config)
    kv = layers * 2 * d * (context_tokens + rows)
    return width * (weight_params(config) + kv)


def prefill_ops(config: dict, prompt_len: int) -> float:
    d, f, layers = _dims(config)
    s = prompt_len
    per_layer = 4 * 2 * s * d * d + 2 * 2 * s * d * f \
        + 2 * 2 * d * s * (s + 1) / 2
    return layers * per_layer + 2 * d * config["vocab_size"]
