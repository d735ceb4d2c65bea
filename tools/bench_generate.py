#!/usr/bin/env python
"""Decode-throughput benchmark: emitted tokens/sec, KV-cache vs full-refeed.

    python tools/bench_generate.py [--model gpt2_small] [--batch 8]
        [--prompt-len 128] [--new-tokens 128] [--platform cpu]

Random weights (throughput is weight-independent), greedy decode, one
warmup generation (compile) then a timed one. Prints one JSON line per
mode; the KV-cache line is the serving number (O(S) per token), the
refeed line is the context the speedup is measured against.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="gpt2_small")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--prompt-len", type=int, default=128)
    p.add_argument("--new-tokens", type=int, default=128)
    p.add_argument("--vocab-size", type=int, default=None)
    p.add_argument("--platform", default="tpu", choices=["tpu", "cpu"],
                   help="tpu (default) needs a TPU and fails without one")
    p.add_argument("--skip-refeed", action="store_true",
                   help="cache-only (the refeed arm is O(S^2) and slow at "
                        "long prompts)")
    p.add_argument("--speculative", action="store_true",
                   help="add a self-draft speculative arm (batch 1): the "
                        "all-accepted upper bound on spec-decode speedup")
    p.add_argument("--draft-len", type=int, default=4)
    args = p.parse_args(argv)
    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributeddeeplearning_tpu.parallel import mesh as meshlib
    meshlib.backend_devices(args.platform)  # no TPU, no measurement

    from distributeddeeplearning_tpu.models import flops as flopslib
    from distributeddeeplearning_tpu.models import model_spec
    from distributeddeeplearning_tpu.models.generate import generate
    from distributeddeeplearning_tpu.observability import perf_report

    total = args.prompt_len + args.new_tokens
    spec = model_spec(args.model)
    kw = dict(dtype=jnp.bfloat16, seq_len=total)
    if args.vocab_size:
        kw["vocab_size"] = args.vocab_size
    model = spec.build(**kw)
    rng = np.random.default_rng(0)
    vocab = model.cfg.vocab_size
    prompt = jnp.asarray(
        rng.integers(1, vocab, (args.batch, args.prompt_len)), jnp.int32)
    variables = model.init({"params": jax.random.key(0)}, prompt[:, :8],
                           train=False)

    # Roofline context: decode sweeps positions prompt..prompt+new, so the
    # mid-decode context is the representative KV-read size for the row.
    mid_context = args.prompt_len + args.new_tokens // 2
    device_kind = getattr(jax.devices()[0], "device_kind", "")

    def timed(use_cache: bool) -> None:
        t_c = time.perf_counter()
        out = generate(model, variables, prompt,
                       max_new_tokens=args.new_tokens, use_cache=use_cache)
        jax.block_until_ready(out)
        compile_s = time.perf_counter() - t_c
        t0 = time.perf_counter()
        out = generate(model, variables, prompt,
                       max_new_tokens=args.new_tokens, use_cache=use_cache)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        value = round(args.batch * args.new_tokens / dt, 1)
        rec = {
            "metric": f"{args.model}_decode_tokens_per_sec",
            "mode": "kv_cache" if use_cache else "full_refeed",
            "value": value,
            "unit": "tokens/sec",
            "batch": args.batch, "prompt_len": args.prompt_len,
            "new_tokens": args.new_tokens,
            "wall_s": round(dt, 2), "compile_s": round(compile_s, 1),
        }
        roof = flopslib.decode_roofline(
            args.model, context_len=mid_context,
            tokens_per_sec=value / jax.device_count(),
            device_kind=device_kind, batch=args.batch)
        if roof:
            rec["decode_roofline"] = roof
        print(json.dumps(perf_report.annotate(rec, provenance="fresh")),
              flush=True)

    timed(True)
    if not args.skip_refeed:
        timed(False)
    if args.speculative:
        from distributeddeeplearning_tpu.models.generate import (
            generate_speculative)

        prompt1 = prompt[:1]

        def spec():
            return generate_speculative(
                model, variables, model, variables, prompt1,
                max_new_tokens=args.new_tokens, draft_len=args.draft_len)

        t_c = time.perf_counter()
        jax.block_until_ready(spec())
        compile_s = time.perf_counter() - t_c
        t0 = time.perf_counter()
        jax.block_until_ready(spec())
        dt = time.perf_counter() - t0
        rec = {
            "metric": f"{args.model}_decode_tokens_per_sec",
            "mode": f"speculative_selfdraft_k{args.draft_len}",
            "value": round(args.new_tokens / dt, 1),
            "unit": "tokens/sec", "batch": 1,
            "prompt_len": args.prompt_len, "new_tokens": args.new_tokens,
            "wall_s": round(dt, 2), "compile_s": round(compile_s, 1),
        }
        roof = flopslib.decode_roofline(
            args.model, context_len=mid_context,
            tokens_per_sec=rec["value"] / jax.device_count(),
            device_kind=device_kind, batch=1)
        if roof:
            rec["decode_roofline"] = roof
        print(json.dumps(perf_report.annotate(rec, provenance="fresh")),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
