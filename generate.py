#!/usr/bin/env python
"""Sampling CLI for the causal LM families — restore a checkpoint, extend
prompts.

    python generate.py --model gpt2_small --checkpoint-dir /ckpts/run1 \
        --prompt-ids 464,3290,318 --max-new-tokens 32 --temperature 0.8

Prompts are raw token ids (comma-separated; `--prompt-ids` repeatable for a
batch) — tokenization is corpus-specific and lives with the data tooling
(tools/tokenize_corpus.py), not the sampler.
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default="gpt2_small")
    p.add_argument("--checkpoint-dir", required=True)
    p.add_argument("--prompt-ids", action="append", required=True,
                   help="comma-separated token ids; repeat for a batch "
                        "(rows must share a length)")
    p.add_argument("--max-new-tokens", type=int, default=32)
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy")
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seq-len", type=int, default=None,
                   help="model context length (defaults to prompt+new)")
    p.add_argument("--vocab-size", type=int, default=None)
    p.add_argument("--backend", default="tpu", choices=["tpu", "cpu"])
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel ways (Megatron-style kernel "
                        "sharding over the model mesh axis) — serves a "
                        "model too big for one chip; composes with "
                        "sampling, beam search, and --use-cache (the KV "
                        "caches shard over heads)")
    p.add_argument("--num-beams", type=int, default=0,
                   help="beam-search decoding with this many beams "
                        "(deterministic; overrides temperature/top-k; "
                        "composes with --use-cache for O(S)/token beams)")
    p.add_argument("--length-penalty", type=float, default=1.0,
                   help="beam scores divide by length**alpha (>1 favors "
                        "longer hypotheses); only with --num-beams")
    p.add_argument("--eos-id", type=int, default=None,
                   help="end-of-sequence token id for beam search "
                        "(finished beams freeze and pad)")
    p.add_argument("--use-cache", action="store_true",
                   help="KV-cache incremental decoding (GPT and Llama "
                        "families): O(S) per token instead of full-refeed "
                        "O(S^2); output is identical at the same seed")
    p.add_argument("--draft-model", default=None,
                   help="speculative decoding: draft-model name (same "
                        "vocabulary); emits the EXACT target greedy "
                        "continuation with fewer target forwards. "
                        "Batch-1, greedy only")
    p.add_argument("--draft-checkpoint-dir", default=None,
                   help="checkpoint for --draft-model")
    p.add_argument("--draft-len", type=int, default=4,
                   help="draft tokens proposed per verify round")
    args = p.parse_args(argv)

    import os
    if args.backend == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"

    import contextlib

    import flax.linen as nn
    import jax

    from distributeddeeplearning_tpu.parallel import sharding as shardlib
    from distributeddeeplearning_tpu.parallel.mesh import use_mesh
    from distributeddeeplearning_tpu.config import (
        DataConfig, ParallelConfig, TrainConfig)
    from distributeddeeplearning_tpu.models import model_spec
    from distributeddeeplearning_tpu.models.generate import (
        generate, generate_beam)
    from distributeddeeplearning_tpu.train import checkpoint as ckptlib
    from distributeddeeplearning_tpu.train import loop

    prompts = [[int(t) for t in row.split(",")] for row in args.prompt_ids]
    if len({len(r) for r in prompts}) != 1:
        raise SystemExit("all --prompt-ids rows must share a length")
    total = len(prompts[0]) + args.max_new_tokens

    spec = model_spec(args.model)
    if spec.objective != "causal":
        raise SystemExit(f"{args.model!r} is not a causal LM")
    # The speculative path writes up to draft_len cache slots past `total`
    # before each rewind, so both models get that much position/cache slack.
    slack = args.draft_len if args.draft_model else 0
    data_kw = dict(synthetic=True, seq_len=(args.seq_len or total) + slack)
    if args.vocab_size:
        data_kw["vocab_size"] = args.vocab_size
    if args.tp < 1:
        raise SystemExit(f"--tp {args.tp}: need a positive ways count")
    cfg = TrainConfig(model=args.model, global_batch_size=len(prompts),
                      dtype="float32", checkpoint_dir=args.checkpoint_dir,
                      backend=args.backend, data=DataConfig(**data_kw),
                      parallel=ParallelConfig(model=args.tp))

    mesh, model, _, state, _, _, _ = loop.build(cfg, total_steps=1)
    ckpt = ckptlib.Checkpointer.create(cfg)
    try:
        # Params-only partial restore: the sampler must not need to know
        # which optimizer the training run used.
        params = ckpt.restore_latest_params(state.params)
    finally:
        ckpt.close()
    if ((args.use_cache or args.draft_model) and hasattr(model, "cfg")
            and hasattr(model.cfg, "decode_cache_len")):
        # Right-size the Llama KV cache to this request: a fixed default
        # buffer would make every decode step attend over unused slots.
        import dataclasses
        model = model.clone(cfg=dataclasses.replace(
            model.cfg, decode_cache_len=total + slack))
    if params is None:
        raise SystemExit(
            f"no checkpoint in {args.checkpoint_dir!r}; refusing to sample "
            "from randomly initialized weights")

    # Under TP the model's logical-axis constraints must resolve against
    # the mesh while the generation scan traces — same rules as training;
    # the restored params already carry their NamedShardings (loop.build +
    # the partial restore place them), so GSPMD propagates the kernel
    # sharding through every decode forward.
    ctx = contextlib.ExitStack()
    if args.tp > 1:
        ctx.enter_context(use_mesh(mesh))
        ctx.enter_context(nn.logical_axis_rules(
            list(shardlib.logical_rules(cfg.parallel))))
    draft = None
    if args.draft_model:
        if args.num_beams or args.temperature > 0 or args.tp > 1:
            raise SystemExit("--draft-model (speculative) is greedy, "
                             "single-stream, untensored; drop "
                             "--num-beams/--temperature/--tp")
        if args.use_cache:
            raise SystemExit("--draft-model decodes through KV caches "
                             "already; drop --use-cache")
        if args.draft_len < 1:
            raise SystemExit(f"--draft-len {args.draft_len}: need >= 1")
        if not args.draft_checkpoint_dir:
            raise SystemExit("--draft-model needs --draft-checkpoint-dir")
        dcfg = cfg.replace(model=args.draft_model,
                           checkpoint_dir=args.draft_checkpoint_dir)
        _, draft_model, _, dstate, _, _, _ = loop.build(dcfg, total_steps=1)
        if hasattr(draft_model, "cfg") and hasattr(draft_model.cfg,
                                                   "decode_cache_len"):
            import dataclasses
            draft_model = draft_model.clone(cfg=dataclasses.replace(
                draft_model.cfg, decode_cache_len=total + slack))
        dckpt = ckptlib.Checkpointer.create(dcfg)
        try:
            draft_params = dckpt.restore_latest_params(dstate.params)
        finally:
            dckpt.close()
        if draft_params is None:
            raise SystemExit(
                f"no draft checkpoint in {args.draft_checkpoint_dir!r}")
        draft = (draft_model, draft_params)

    with ctx:
        if draft is not None:
            from distributeddeeplearning_tpu.models.generate import (
                generate_speculative)
            draft_model, draft_params = draft
            out = generate_speculative(
                model, {"params": params}, draft_model,
                {"params": draft_params}, prompts,
                max_new_tokens=args.max_new_tokens,
                draft_len=args.draft_len)
        elif args.num_beams > 0:
            out = generate_beam(model, {"params": params}, prompts,
                                max_new_tokens=args.max_new_tokens,
                                num_beams=args.num_beams,
                                length_penalty=args.length_penalty,
                                eos_id=args.eos_id,
                                use_cache=args.use_cache)
        else:
            out = generate(model, {"params": params}, prompts,
                           max_new_tokens=args.max_new_tokens,
                           temperature=args.temperature, top_k=args.top_k,
                           rng=jax.random.key(args.seed),
                           use_cache=args.use_cache)
    for row in jax.device_get(out).tolist():
        print(json.dumps({"tokens": row}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
