"""Model zoo: the architectures named by the acceptance configs
(BASELINE.json:6-12): ResNet-50/152, DenseNet-121, BERT-base MLM.

``get_model`` is the single registry the trainer/CLI uses; every entry is a
Flax module plus metadata about its input signature so the trainer stays
model-agnostic (one trainer, many models — SURVEY.md §2 #1/#2).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    """Registry entry: module factory + input kind ('image' or 'tokens')."""

    name: str
    build: Callable[..., Any]          # (num_classes/vocab, dtype) -> nn.Module
    input_kind: str                    # "image" | "tokens"
    param_count: int                   # known-good total, used by tests
    objective: str = "classify"        # classify | mlm | causal — selects the
                                       # loss (train/steps.py) and, for token
                                       # pipelines, masking vs plain ids


def _registry() -> dict[str, ModelSpec]:
    from distributeddeeplearning_tpu.models import (afmoe, bert, densenet,
                                                    gpt, kimi_linear, llama,
                                                    nemotron_h, resnet, vit,
                                                    xing4)

    def img(build, name, params):
        return ModelSpec(name=name, build=build, input_kind="image",
                         param_count=params)

    return {
        "resnet18": img(resnet.resnet18, "resnet18", 11_689_512),
        "resnet18_thin": img(resnet.resnet18_thin, "resnet18_thin", 831_096),
        "resnet26_thin": img(resnet.resnet26_thin, "resnet26_thin",
                             1_392_184),
        "resnet34": img(resnet.resnet34, "resnet34", 21_797_672),
        "resnet50": img(resnet.resnet50, "resnet50", 25_557_032),
        "resnet101": img(resnet.resnet101, "resnet101", 44_549_160),
        "resnet152": img(resnet.resnet152, "resnet152", 60_192_808),
        "densenet121": img(densenet.densenet121, "densenet121", 7_978_856),
        "densenet169": img(densenet.densenet169, "densenet169", 14_149_480),
        # Vision transformers (beyond reference scope): the MXU-friendliest
        # image models — all matmuls, no BatchNorm bandwidth tax. Param
        # counts match timm vit_{base,large}_patch16_224 at 224px init.
        "vit_b16": img(vit.vit_b16, "vit_b16", 86_567_656),
        "vit_l16": img(vit.vit_l16, "vit_l16", 304_326_632),
        "vit_tiny": img(vit.tiny_vit, "vit_tiny", 0),
        "bert_base": ModelSpec(
            name="bert_base", build=bert.bert_base_mlm, input_kind="tokens",
            param_count=109_514_298, objective="mlm"),
        "bert_large": ModelSpec(
            name="bert_large", build=bert.bert_large_mlm, input_kind="tokens",
            param_count=335_174_458, objective="mlm"),
        # Decoder-only causal LMs (beyond reference scope): GPT-2 geometry,
        # same trainer/sharding rules, causal Pallas flash kernel available.
        "gpt2_small": ModelSpec(
            name="gpt2_small", build=gpt.gpt2_small, input_kind="tokens",
            param_count=124_439_808, objective="causal"),
        "gpt2_medium": ModelSpec(
            name="gpt2_medium", build=gpt.gpt2_medium, input_kind="tokens",
            param_count=354_823_168, objective="causal"),
        "gpt_tiny": ModelSpec(
            name="gpt_tiny", build=gpt.tiny_gpt, input_kind="tokens",
            param_count=0, objective="causal"),
        # Llama family (RMSNorm/RoPE/SwiGLU/GQA) — the modern-LM shapes;
        # llama2_7b's count matches the canonical checkpoint exactly.
        "llama2_7b": ModelSpec(
            name="llama2_7b", build=llama.llama2_7b, input_kind="tokens",
            param_count=6_738_415_616, objective="causal"),
        "tinyllama_1b": ModelSpec(
            name="tinyllama_1b", build=llama.tinyllama_1b,
            input_kind="tokens", param_count=1_100_048_384,
            objective="causal"),
        "llama_tiny": ModelSpec(
            name="llama_tiny", build=llama.tiny_llama, input_kind="tokens",
            param_count=0, objective="causal"),
        # Trinity-Mini (AFMoE) as published — 26B parameters, for shape
        # tests — and one chip's share of it when eight chips share each
        # layer (models/afmoe.py; the benchmark's trinity_mini cell).
        "trinity_mini": ModelSpec(
            name="trinity_mini", build=afmoe.trinity_mini,
            input_kind="tokens", param_count=26_123_970_560,
            objective="causal"),
        "trinity_mini_ep8": ModelSpec(
            name="trinity_mini_ep8", build=afmoe.trinity_mini_ep8,
            input_kind="tokens", param_count=705_473_792,
            objective="causal"),
        "afmoe_tiny": ModelSpec(
            name="afmoe_tiny", build=afmoe.tiny_afmoe, input_kind="tokens",
            param_count=0, objective="causal"),
        # Kimi-Linear-48B-A3B as published, for shape tests, and one chip's
        # share of it when 32 chips share each layer (models/kimi_linear.py;
        # the benchmark's kimi_linear cell).
        "kimi_linear_48b": ModelSpec(
            name="kimi_linear_48b", build=kimi_linear.kimi_linear_48b,
            input_kind="tokens", param_count=0, objective="causal"),
        "kimi_linear_ep32": ModelSpec(
            name="kimi_linear_ep32", build=kimi_linear.kimi_linear_ep32,
            input_kind="tokens", param_count=0, objective="causal"),
        "kimi_linear_tiny": ModelSpec(
            name="kimi_linear_tiny", build=kimi_linear.kimi_linear_tiny,
            input_kind="tokens", param_count=0, objective="causal"),
        # Xing4.0-29B-A4B as published (without its MTP module), for shape
        # tests, and one chip's share of it when 8 chips share each layer
        # (models/xing4.py; the benchmark's xing4 cell).
        "xing4_29b": ModelSpec(
            name="xing4_29b", build=xing4.xing4_29b, input_kind="tokens",
            param_count=29_506_649_712, objective="causal"),
        "xing4_ep8": ModelSpec(
            name="xing4_ep8", build=xing4.xing4_ep8, input_kind="tokens",
            param_count=759_489_550, objective="causal"),
        "xing4_tiny": ModelSpec(
            name="xing4_tiny", build=xing4.xing4_tiny, input_kind="tokens",
            param_count=0, objective="causal"),
        # Nemotron 3 Nano 30B-A3B as published, for shape tests, and one
        # chip's share of it when 16 chips share each layer
        # (models/nemotron_h.py; the benchmark's nemotron3_nano cell).
        "nemotron3_nano_30b": ModelSpec(
            name="nemotron3_nano_30b", build=nemotron_h.nemotron3_nano_30b,
            input_kind="tokens", param_count=31_577_937_344,
            objective="causal"),
        "nemotron3_nano_ep16": ModelSpec(
            name="nemotron3_nano_ep16",
            build=nemotron_h.nemotron3_nano_ep16, input_kind="tokens",
            param_count=666_962_944, objective="causal"),
        "nemotron3_nano_tiny": ModelSpec(
            name="nemotron3_nano_tiny",
            build=nemotron_h.nemotron3_nano_tiny, input_kind="tokens",
            param_count=0, objective="causal"),
        # Nano drafters for speculative decoding (serve/engine.py): a
        # shrunk config of the same family — cheap to step, same
        # tokenizer/vocab, verified by the full target model so output
        # stays token-identical regardless of drafter quality.
        "gpt_nano": ModelSpec(
            name="gpt_nano", objective="causal",
            build=lambda **kw: gpt.tiny_gpt(
                **{"hidden_size": 32, "num_layers": 1, "num_heads": 2,
                   **kw}),
            input_kind="tokens", param_count=0),
        "llama_nano": ModelSpec(
            name="llama_nano", objective="causal",
            build=lambda **kw: llama.tiny_llama(
                **{"hidden_size": 32, "num_layers": 1, "num_heads": 2,
                   "num_kv_heads": 1, "intermediate_size": 64, **kw}),
            input_kind="tokens", param_count=0),
        # GPT-2 124M as a 4-stage GPipe pipeline over the `pipeline` axis.
        "gpt2_small_pp": ModelSpec(
            name="gpt2_small_pp", objective="causal",
            build=lambda **kw: gpt.gpt2_small(
                **{"pipeline_stages": 4,
                   "pipeline_microbatches": 8, **kw}),
            input_kind="tokens", param_count=0),
        "gpt_tiny_pp": ModelSpec(
            name="gpt_tiny_pp", objective="causal",
            build=lambda **kw: gpt.tiny_gpt(
                **{"pipeline_stages": 2,
                   "pipeline_microbatches": 4, **kw}),
            input_kind="tokens", param_count=0),
        # BERT-base with a top-1-routed 8-expert MoE FFN every other layer
        # (models/moe.py), expert-parallel over the `expert` mesh axis.
        "bert_base_moe": ModelSpec(
            name="bert_base_moe", objective="mlm",
            build=lambda **kw: bert.bert_base_mlm(num_experts=8, **kw),
            input_kind="tokens", param_count=0),
        # Test/dry-run sized transformer; param_count=0 means "unchecked".
        "bert_tiny": ModelSpec(
            name="bert_tiny", build=bert.tiny_bert_mlm, input_kind="tokens",
            param_count=0, objective="mlm"),
        "bert_tiny_moe": ModelSpec(
            name="bert_tiny_moe", objective="mlm",
            build=lambda **kw: bert.tiny_bert_mlm(num_experts=4, **kw),
            input_kind="tokens", param_count=0),
        "bert_tiny_moe2": ModelSpec(
            name="bert_tiny_moe2", objective="mlm",
            build=lambda **kw: bert.tiny_bert_mlm(num_experts=4,
                                                  moe_top_k=2, **kw),
            input_kind="tokens", param_count=0),
        # BERT-base as a 4-stage GPipe pipeline over the `pipeline` axis.
        "bert_base_pp": ModelSpec(
            name="bert_base_pp", objective="mlm",
            build=lambda **kw: bert.bert_base_mlm(
                **{"pipeline_stages": 4,
                   "pipeline_microbatches": 8, **kw}),
            input_kind="tokens", param_count=0),
        "bert_tiny_pp": ModelSpec(
            name="bert_tiny_pp", objective="mlm",
            build=lambda **kw: bert.tiny_bert_mlm(
                **{"pipeline_stages": 2,
                   "pipeline_microbatches": 4, **kw}),
            input_kind="tokens", param_count=0),
        # 4-layer variant: layers_per_stage=2 admits interleaved 1f1b with
        # pipeline_virtual_stages=2 — the schedule A/B geometry used by
        # tests/test_pipeline.py, bench.py and the pipeline_1f1b workload of
        # tests/test_step_invariants.py.
        "bert_tiny_pp4": ModelSpec(
            name="bert_tiny_pp4", objective="mlm",
            build=lambda **kw: bert.tiny_bert_mlm(
                **{"num_layers": 4, "pipeline_stages": 2,
                   "pipeline_microbatches": 4, **kw}),
            input_kind="tokens", param_count=0),
        # 4 layers over 4 stages (1 layer/stage): stage count divisible by
        # pipeline mesh axes 1/2/4, so one model can re-form across
        # pipeline degrees — the cross-axis elastic soak geometry
        # (tests/test_elastic_resume.py, launch.py --elastic-geometry).
        # 2 microbatches keeps the tick count (M+P-1) minimal: the soak
        # measures re-formation outage, and the first post-resume step is
        # on that clock. Dropout off: flax derives dropout masks from the
        # module tree, and re-grouping layers into stages changes that
        # tree — so across a pipeline-degree change the masks are
        # legitimately different random draws. Zeroing dropout makes the
        # uninterrupted run a valid parity reference; everything else
        # about the cross-axis path is mask-independent.
        "bert_tiny_pp44": ModelSpec(
            name="bert_tiny_pp44", objective="mlm",
            build=lambda **kw: bert.tiny_bert_mlm(
                **{"num_layers": 4, "pipeline_stages": 4,
                   "pipeline_microbatches": 2, "dropout_rate": 0.0, **kw}),
            input_kind="tokens", param_count=0),
    }


def get_model(name: str, *, dtype: Any = jnp.bfloat16, **kw: Any):
    """Build a model module by registry name."""
    spec = model_spec(name)
    return spec.build(dtype=dtype, **kw)


def model_spec(name: str) -> ModelSpec:
    reg = _registry()
    if name not in reg:
        # the name comes last: what is kept of a failed child's error is
        # its tail (bench.py), and the registry's list outgrew it
        raise KeyError(f"the registry has {sorted(reg)}: unknown model "
                       f"{name!r}")
    return reg[name]


def available_models() -> tuple[str, ...]:
    return tuple(sorted(_registry()))
