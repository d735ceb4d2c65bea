#!/usr/bin/env python
"""One-off calibration: XLA cost-analysis FLOPs per example for the
benchable models, used to pin/validate the analytic tables in
``models/flops.py`` (MFU reporting).

Prints one JSON line per config: lowered (pre-optimization) HLO flops for
the FULL train step (fwd+bwd+optimizer), per example. XLA counts a MAC as
2 flops — the same convention as MFU peak numbers.

Run CPU-only:
  env JAX_PLATFORMS=cpu python tools/calibrate_flops.py
"""
import json
import sys

sys.path.insert(0, ".")

CONFIGS = [
    ("resnet50", {"batch": 8}),
    ("resnet152", {"batch": 4}),
    ("densenet121", {"batch": 4}),
    ("vit_b16", {"batch": 4}),
    ("bert_base", {"batch": 2, "seq_len": 512}),
    ("bert_base", {"batch": 2, "seq_len": 512, "mlm_dense": True}),
    ("gpt2_small", {"batch": 1, "seq_len": 1024}),
]


def main() -> int:
    from distributeddeeplearning_tpu import data as datalib
    from distributeddeeplearning_tpu.config import (
        DataConfig, ParallelConfig, TrainConfig, resolve_mlm_max_predictions)
    from distributeddeeplearning_tpu.models import model_spec
    from distributeddeeplearning_tpu.train import loop

    for model, o in CONFIGS:
        spec = model_spec(model)
        tokens = spec.input_kind == "tokens"
        seq_len = o.get("seq_len", 512)
        mlm_pred = (0 if o.get("mlm_dense")
                    else resolve_mlm_max_predictions(-1, seq_len,
                                                     spec.objective))
        data = (DataConfig(synthetic=True, dataset="mlm", seq_len=seq_len,
                           mlm_max_predictions=mlm_pred)
                if tokens else DataConfig(synthetic=True))
        batch = o["batch"]
        cfg = TrainConfig(model=model, global_batch_size=batch,
                          dtype="bfloat16", log_every=10**9,
                          parallel=ParallelConfig(data=1), data=data)
        mesh, _m, batch_shd, state, train_step, _s, rng = loop.build(cfg, 100)
        source = datalib.make_source(cfg, spec.input_kind, batch_shd,
                                     objective=spec.objective)
        import jax
        raw = getattr(train_step, "raw_step", None)
        step = jax.jit(raw) if raw is not None else train_step
        lowered = step.lower(state, source.batch(0), rng)
        cost = lowered.cost_analysis()
        if isinstance(cost, list):  # older jax returns [dict]
            cost = cost[0]
        flops = float(cost["flops"])
        print(json.dumps({
            "model": model, "seq_len": seq_len if tokens else None,
            "mlm_pred": mlm_pred if tokens else None, "batch": batch,
            "step_flops_per_example": round(flops / batch / 1e9, 3),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
