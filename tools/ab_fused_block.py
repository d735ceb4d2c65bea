#!/usr/bin/env python
"""Step-time A/B: --fused-block (conv-epilogue fusion) vs the unfused path.

    python tools/ab_fused_block.py [--batches 256,512] [--steps 20]
        [--model resnet50] [--platform cpu]

One JSON line per batch size: unfused and fused img/s/chip and the
speedup. Needs a TPU; --platform cpu exists for smoke-testing the harness
itself.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def step_rate(model: str, batch: int, steps: int, backend: str,
              **flags) -> float:
    import jax

    from distributeddeeplearning_tpu import data as datalib
    from distributeddeeplearning_tpu.config import (
        DataConfig, ParallelConfig, TrainConfig)
    from distributeddeeplearning_tpu.train import loop

    cfg = TrainConfig(model=model, global_batch_size=batch,
                      dtype="bfloat16", log_every=10**9,
                      parallel=ParallelConfig(data=1), backend=backend,
                      data=DataConfig(synthetic=True), **flags)
    mesh, m, shd, state, train_step, _, rng = loop.build(cfg, 64)
    src = datalib.make_source(cfg, "image", shd)
    i, metrics = 0, None
    for _ in range(5):
        state, metrics = train_step(state, src.batch(i), rng)
        i += 1
    jax.block_until_ready(metrics)
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = train_step(state, src.batch(i), rng)
        i += 1
    jax.block_until_ready(metrics)
    return batch * steps / (time.perf_counter() - t0)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--model", default="resnet50")
    p.add_argument("--batches", default="256,512")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--platform", default="tpu", choices=["tpu", "cpu"])
    args = p.parse_args(argv)
    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"

    variants = [("unfused", {}), ("fused", {"fused_block": True})]
    for batch in (int(b) for b in args.batches.split(",")):
        rates = {}
        for name, flags in variants:
            try:
                rates[name] = round(
                    step_rate(args.model, batch, args.steps, args.platform,
                              **flags), 1)
            except Exception as e:  # one failure must not sink the rest
                rates[name] = None
                rates[f"{name}_error"] = f"{type(e).__name__}: {e}"[:300]
        rec = {"check": "fused_block_ab", "model": args.model,
               "batch": batch, **rates}
        base = rates.get("unfused")
        if base:
            for name, _ in variants[1:]:
                if rates.get(name):
                    rec[f"speedup_{name}"] = round(rates[name] / base, 3)
        print(json.dumps(rec), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
