"""Typed configuration for trainers, data, optimizers, and parallelism.

Replaces the reference's dotenv + Makefile variables + per-script argparse
flags (SURVEY.md §2 #12) with one dataclass tree; ``train.py`` exposes the
same CLI surface (``--backend``, model/batch/epoch flags) per BASELINE.json:5
("train.py entrypoints ... run unchanged from the CLI with --backend=tpu").
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional


@dataclasses.dataclass
class ParallelConfig:
    """Device-mesh layout.

    Axis sizes multiply to the total device count. ``data`` is the
    Horovod-equivalent allreduce axis (BASELINE.json:5: "jax.pmap/pjit
    emitting XLA psum over ICI"); ``model``/``seq`` enable tensor and
    sequence/context parallelism for transformer workloads.
    """

    data: int = 1       # dp: batch sharding, grad psum
    fsdp: int = 1       # parameter sharding along the data axis family
    model: int = 1      # tp: weight-column/row sharding
    seq: int = 1        # sp/cp: sequence-dim sharding (ring attention)
    expert: int = 1     # ep: MoE expert sharding (models/moe.py)
    pipeline: int = 1   # pp: GPipe pipeline stages (models/pipeline.py)
    # Validation-only: emulate an N-slice pod's hybrid ICI/DCN device layout
    # on non-TPU platforms (tests / dryrun_multichip), exercising the same
    # _hybrid_shapes axis split a real multi-slice mesh gets. 0/1 = off.
    # On real TPU the slice count is auto-detected and this knob is ignored.
    emulate_slices: int = 0

    @property
    def num_devices(self) -> int:
        return (self.data * self.fsdp * self.model * self.seq
                * self.expert * self.pipeline)

    def axis_sizes(self) -> dict[str, int]:
        return {
            "data": self.data,
            "fsdp": self.fsdp,
            "model": self.model,
            "seq": self.seq,
            "expert": self.expert,
            "pipeline": self.pipeline,
        }


@dataclasses.dataclass
class AllReduceConfig:
    """Gradient all-reduce policy for the explicit-DP path (shard_map).

    Horovod-style tensor fusion (parallel/collectives.py): gradient leaves
    are packed into size-targeted buckets and reduced with ONE collective
    per bucket instead of one per parameter tensor, so XLA can overlap the
    early buckets' reductions with the tail of the backward pass.
    """

    bucket_mb: float = 4.0        # fusion-buffer target size; 0 = per-leaf
                                  # reduction (the unfused A/B baseline)
    dtype: str = "float32"        # reduction payload: float32 (grads' own
                                  # dtype) | bfloat16 (half the wire bytes;
                                  # fp32 masters restored after the reduce)
    algorithm: str = "psum"       # psum (one all-reduce) | ring
                                  # (psum_scatter + all_gather, the
                                  # bandwidth-optimal two-phase form)

    def describe(self) -> str:
        mode = (f"fused bucket_mb={self.bucket_mb:g}" if self.bucket_mb > 0
                else "per-leaf")
        return f"{mode} dtype={self.dtype} algo={self.algorithm}"


@dataclasses.dataclass
class DataConfig:
    """Input pipeline settings (SURVEY.md §2 #5/#6)."""

    dataset: str = "imagenet"
    data_dir: Optional[str] = None
    synthetic: bool = True        # config 1: "synthetic data" BASELINE.json:7
    synthetic_learnable: bool = False  # embed a class signal in synthetic
                                  # images (top-1 becomes meaningful)
    loader: str = "auto"          # auto | tf | native (csrc/ C++ loader) |
                                  # grain (data/grain_pipeline.py)
    image_size: int = 224
    num_classes: int = 1000
    shuffle_buffer: int = 16384
    prefetch_depth: int = 2       # StreamSource lookahead batches (host->HBM
                                  # pipelining; also the native loader's
                                  # batch-slot ring depth - 1)
    # Per-batch loader watchdog for host-streaming sources (tf/native/
    # grain/tokens): a pull that exceeds the timeout is retried (with a
    # loud warning) up to loader_retries times, then the run dies with a
    # clear "loader stalled" error instead of hanging the collective step
    # on every host. 0 = watchdog off (docs/fault_tolerance.md).
    loader_timeout_s: float = 0.0
    loader_retries: int = 2
    # BERT-style sequence workloads:
    seq_len: int = 128
    vocab_size: int = 30522
    mlm_mask_prob: float = 0.15
    mlm_max_predictions: int = 0  # >0: gather-mode MLM — batches carry fixed-
                                  # width (masked_positions, masked_labels)
                                  # and the model projects ONLY those
                                  # positions to vocab (the canonical BERT /
                                  # MLPerf head: ~6.7x less head compute +
                                  # logits memory at 15% masking); 0 = dense
                                  # (B, S) labels


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """End-to-end mixed-precision policy (docs/mixed_precision.md).

    One object names every dtype decision the large-batch recipes care
    about (PAPERS.md: arXiv 1711.04325 trains ResNet-50 at 32k in mixed
    precision), instead of the three half-coordinated knobs the legacy
    path spreads across ``TrainConfig.dtype`` / ``AllReduceConfig.dtype``:

    - ``compute_dtype`` — forward/backward activation (and zero3 gathered-
      parameter) dtype;
    - ``param_dtype`` — the persistent master weights + optimizer state.
      MUST stay ``float32``: the update ``p - lr*g`` at bf16 resolution
      silently loses every increment below ~2^-8 of the weight magnitude
      (the silent-precision-loss bug class ddl-lint's
      ``master-weight-cast`` rule exists for);
    - ``reduce_dtype`` — gradient all-reduce / reduce-scatter wire payload
      (bfloat16 halves wire bytes; fp32 masters are restored after);
    - ``loss_scale`` — initial DYNAMIC loss scale (0 = off). The loss is
      multiplied by the scale before backward and gradients divided after;
      a non-finite scaled gradient skips the update and halves the scale,
      ``loss_scale_growth_interval`` consecutive good steps double it
      (bounded to [``loss_scale_min``, ``loss_scale_max``]). A scale
      backoff is a *controlled* event — it reports under its own
      ``loss_scale_skip`` metric and never increments the bad-step
      anomaly counter (train/loop.py ``_BadStepTracker``).

    The policy is part of the AOT ``config_fingerprint`` (perf/aot.py
    hashes the whole config dataclass), so fp32 and mixed arms key
    separate executables and separate perf baselines by construction.
    """

    compute_dtype: str = "bfloat16"
    param_dtype: str = "float32"
    reduce_dtype: str = "bfloat16"
    loss_scale: float = 0.0
    loss_scale_growth_interval: int = 200
    loss_scale_min: float = 1.0
    loss_scale_max: float = 65536.0

    @classmethod
    def mixed(cls) -> "PrecisionPolicy":
        """The large-batch mixed arm: bf16 compute + wire, fp32 masters,
        dynamic loss scaling armed (bf16 shares fp32's exponent range, so
        the scale rarely moves — it exists to catch the overflow tail)."""
        return cls(compute_dtype="bfloat16", reduce_dtype="bfloat16",
                   loss_scale=32768.0)

    @classmethod
    def fp32(cls) -> "PrecisionPolicy":
        """The A/B reference arm: everything float32, no scaling."""
        return cls(compute_dtype="float32", reduce_dtype="float32",
                   loss_scale=0.0)

    def describe(self) -> str:
        """Compact provenance tag, e.g. ``bf16/f32/bf16+dls32768``."""
        short = {"float32": "f32", "bfloat16": "bf16"}
        tag = (f"{short.get(self.compute_dtype, self.compute_dtype)}/"
               f"{short.get(self.param_dtype, self.param_dtype)}/"
               f"{short.get(self.reduce_dtype, self.reduce_dtype)}")
        if self.loss_scale > 0:
            tag += f"+dls{self.loss_scale:g}"
        return tag


def resolve_precision(config: "TrainConfig") -> PrecisionPolicy:
    """The run's effective precision policy. ``config.precision=None``
    (default) derives the legacy behavior — compute at ``config.dtype``,
    fp32 params, reduction payload per ``config.allreduce`` — so every
    existing config compiles the exact same program as before the policy
    existed. An explicit policy is validated here, once, on the way in."""
    policy = getattr(config, "precision", None)
    if policy is None:
        return PrecisionPolicy(
            compute_dtype=config.dtype, param_dtype="float32",
            reduce_dtype=getattr(config.allreduce, "dtype", "float32"),
            loss_scale=0.0)
    for field, value in (("compute_dtype", policy.compute_dtype),
                         ("reduce_dtype", policy.reduce_dtype)):
        if value not in ("float32", "bfloat16"):
            raise ValueError(
                f"PrecisionPolicy.{field}={value!r}: use 'float32' or "
                f"'bfloat16'")
    if policy.param_dtype != "float32":
        raise ValueError(
            f"PrecisionPolicy.param_dtype={policy.param_dtype!r}: master "
            f"weights must stay float32 — a bf16 master silently drops "
            f"every update below ~2^-8 of the weight magnitude "
            f"(docs/mixed_precision.md)")
    if policy.loss_scale < 0:
        raise ValueError(f"loss_scale must be >= 0 "
                         f"(got {policy.loss_scale})")
    if policy.loss_scale > 0:
        if policy.loss_scale_growth_interval < 1:
            raise ValueError("loss_scale_growth_interval must be >= 1")
        if not (0 < policy.loss_scale_min <= policy.loss_scale
                <= policy.loss_scale_max):
            raise ValueError(
                f"need 0 < loss_scale_min <= loss_scale <= loss_scale_max "
                f"(got {policy.loss_scale_min} / {policy.loss_scale} / "
                f"{policy.loss_scale_max})")
    return policy


@dataclasses.dataclass
class OptimizerConfig:
    """Optimizer + schedule (SGD-momentum default; LARS for config 5)."""

    name: str = "sgd"             # sgd | lars | adamw | lamb
    learning_rate: float = 0.1    # for the reference batch size (256)
    reference_batch: int = 256    # linear-scaling rule base
    momentum: float = 0.9
    weight_decay: float = 1e-4
    warmup_epochs: float = 5.0
    schedule: str = "warmup_cosine"  # warmup_cosine | constant | linear
    label_smoothing: float = 0.1
    grad_clip_norm: Optional[float] = None
    # Exponential moving average of params (0 = off). When on, every
    # update folds new params in at (1 - decay) and ALL held-out evals
    # (periodic, final, --eval-only) score the EMA weights — the classic
    # ImageNet/BERT eval-smoothing recipe.
    ema_decay: float = 0.0
    # LARS (config 5, BASELINE.json:11):
    trust_coefficient: float = 0.001
    # AdamW (BERT):
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8


@dataclasses.dataclass
class TrainConfig:
    """Top-level run description — one per acceptance config."""

    model: str = "resnet50"
    backend: Optional[str] = None  # "tpu": TPUs or an error, never a
                                  # quiet CPU run (the CLIs' default);
                                  # "cpu": the host's CPU devices; None:
                                  # JAX's devices as the process was
                                  # started (parallel/mesh.backend_devices)
    global_batch_size: int = 32   # config 1 default (BASELINE.json:7)
    num_epochs: float = 90.0
    steps_per_epoch: Optional[int] = None  # derived from dataset if None
    total_steps: Optional[int] = None      # overrides epochs when set
    dtype: str = "bfloat16"       # compute dtype; params stay f32. Subsumed
                                  # by ``precision`` when that is set — kept
                                  # as the legacy knob so every existing
                                  # config compiles unchanged
    precision: Optional[PrecisionPolicy] = None  # end-to-end mixed-precision
                                  # policy (compute/param/reduce dtypes +
                                  # dynamic loss scaling). None derives the
                                  # legacy behavior from ``dtype`` and
                                  # ``allreduce.dtype`` (resolve_precision);
                                  # part of the AOT config_fingerprint, so
                                  # fp32 and mixed arms never share an
                                  # executable or a perf baseline
    batch_ramp: Optional[str] = None  # staged global-batch ramp (arXiv
                                  # 1711.04325 recipe), e.g. "8192:600,32768":
                                  # comma stages of batch[:steps], last stage
                                  # (no :steps) runs to the horizon and must
                                  # equal global_batch_size. LR follows the
                                  # linear-scaling rule per stage; every
                                  # boundary must land on a checkpoint
                                  # cadence step (train/optim.py
                                  # parse_batch_ramp validates) so resume and
                                  # elastic re-formation compose unchanged
    grad_accum_steps: int = 1     # microbatches per optimizer step (config 5
                                  # at 32k runs on any mesh via accumulation)
    steps_per_loop: int = 1       # train steps fused into ONE XLA program
                                  # (lax.scan) when data is generated
                                  # on-device; amortizes per-step host
                                  # dispatch — the TPUEstimator
                                  # iterations_per_loop idiom
    seed: int = 0
    log_every: int = 100
    eval_every_epochs: float = 1.0
    checkpoint_dir: Optional[str] = None
    checkpoint_every_steps: int = 5000
    resume: bool = True
    profile_steps: Optional[tuple[int, int]] = None  # SURVEY.md §5.1
    profile_dir: Optional[str] = None  # trace output (TensorBoard-loadable)
    trace_dir: Optional[str] = None  # always-on phase telemetry
                                  # (observability/telemetry.py): per-step
                                  # phase spans + per-bucket collective
                                  # spans + fault/restart instants exported
                                  # as Chrome-trace JSON here. None = the
                                  # no-op disabled path
    trace_steps: Optional[tuple[int, int]] = None  # restrict step-tagged
                                  # telemetry events to [a, b); None = the
                                  # whole run (the ring buffer bounds
                                  # memory either way)
    trace_max_events: int = 200_000  # telemetry ring-buffer capacity
    flight_dir: Optional[str] = None  # flight recorder (observability/
                                  # flight.py): crash-surviving fsync'd
                                  # JSONL event log, one file per host.
                                  # None = the launcher-exported
                                  # DDL_FLIGHT_DIR, else disabled
    anomaly_detection: bool = True  # online anomaly detector (observability/
                                  # anomaly.py) over the chief's log-cadence
                                  # records: loss spikes, grad-norm drift,
                                  # throughput collapse, straggler trending.
                                  # Host-side medians only — no device cost
    straggler_threshold: float = 1.5  # multi-host only: warn when a host's
                                  # log-cadence step_time exceeds this x the
                                  # cross-host mean (observability/
                                  # straggler.py); 0 disables the allgather
    fail_at_step: Optional[int] = None  # DEPRECATED single-fault injection:
                                  # shimmed to fault_plan "crash@N:always"
                                  # (robustness/faults.py); kept so existing
                                  # flags/scripts run unchanged
    fault_plan: Optional[str] = None  # scheduled fault injection, e.g.
                                  # "nan_grads@5,corrupt_latest_ckpt@6,
                                  # sigkill@6" — grammar and semantics in
                                  # robustness/faults.py and
                                  # docs/fault_tolerance.md. None = zero
                                  # injection code anywhere on the hot path
    bad_step_guard: bool = False  # compile the non-finite-update skip guard
                                  # into the train step (auto-on when the
                                  # fault plan injects nan_grads). Opt-in
                                  # because the skip-select keeps pre-update
                                  # buffers alive, which re-fuses the XLA
                                  # program ~1 ULP off the guard-free (and
                                  # zero1-bitwise-pinned) trajectory
    bad_step_limit: int = 10      # abort after K CONSECUTIVE non-finite
                                  # (skipped) update steps — one bad batch
                                  # is skipped and counted, a divergent run
                                  # dies loudly instead of burning the
                                  # budget on no-op steps
    attention_impl: Optional[str] = None  # None=default; dense|ring|flash
    remat: bool = False           # recompute transformer-layer activations
                                  # in backward (less HBM, ~1/3 more FLOPs)
    fused_bn: bool = False        # Pallas fused BN+ReLU kernels (CNNs)
    fused_block: bool = False     # conv-epilogue fusion: bottleneck 1x1
                                  # convs as Pallas matmul+BN (resnet50+)
    sync_bn: bool = False         # cross-replica BN statistics (psum over
                                  # the data axis; torch SyncBatchNorm)
    optimizer_sharding: str = "none"  # none | zero1 | zero2 | zero3
                                  # (explicit-DP path only) — the ZeRO
                                  # ladder (parallel/zero.py): zero1 shards
                                  # optimizer state 1/N (reduce-scatter
                                  # grads, chunk update, all-gather updated
                                  # params); zero2 additionally never
                                  # materializes the full gradient tree
                                  # (grads born reduce-scattered during
                                  # backward, same update math as zero1);
                                  # zero3 additionally keeps the parameters
                                  # themselves 1/N-sharded, all-gathered
                                  # on demand per fusion bucket
    overlap_collectives: bool = True  # zero2/zero3 only: issue each fusion
                                  # bucket's gradient reduce-scatter inside
                                  # backward as its cotangents complete
                                  # (custom_vjp bucket boundaries) instead
                                  # of one serialized pass after backward.
                                  # Off = A/B baseline; update math is
                                  # unchanged either way
    compile_cache: bool = True    # persistent compile cache + AOT step
                                  # executables; WHERE is decided from
                                  # outside (perf/compile_cache.py:
                                  # $JAX_COMPILATION_CACHE_DIR, else
                                  # <repo>/.cache/jax_compile). Volatile
                                  # w.r.t. the config fingerprint — it
                                  # never changes the compiled program
    # GPipe microbatch count for *_pp models (None = model default). The
    # bubble wastes (P-1)/(M+P-1) of every stage-tick; M >= 4(P-1) keeps it
    # under ~20% (tools/bench_parallel_overhead.py measures this).
    pipeline_microbatches: Optional[int] = None
    pipeline_schedule: str = "gpipe"  # "gpipe" (fill/drain) or "1f1b"
                                  # (interleaved virtual stages, bubble
                                  # (P-1)/(M*V+P-1) — models/pipeline.py,
                                  # docs/pipeline.md). Both compile to one
                                  # XLA program; the fingerprint keeps
                                  # their AOT executables apart
    pipeline_virtual_stages: int = 1  # V chunks per stage under 1f1b; each
                                  # extra chunk divides the bubble at the
                                  # cost of V x more in-flight activation
                                  # shifts per microbatch
    parallel: ParallelConfig = dataclasses.field(default_factory=ParallelConfig)
    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    optimizer: OptimizerConfig = dataclasses.field(default_factory=OptimizerConfig)
    allreduce: AllReduceConfig = dataclasses.field(
        default_factory=AllReduceConfig)

    @property
    def per_device_batch(self) -> int:
        shards = self.parallel.data * self.parallel.fsdp
        if self.global_batch_size % max(shards, 1):
            raise ValueError(
                f"global_batch_size={self.global_batch_size} not divisible by "
                f"data-parallel shards={shards}")
        per_device = self.global_batch_size // max(shards, 1)
        if self.grad_accum_steps > 1 and per_device % self.grad_accum_steps:
            raise ValueError(
                f"per-device batch {per_device} not divisible by "
                f"grad_accum_steps={self.grad_accum_steps}")
        return per_device

    def replace(self, **kw: Any) -> "TrainConfig":
        return dataclasses.replace(self, **kw)


# ---------------------------------------------------------------------------
# Acceptance-config presets (BASELINE.json:6-12). Keyed by the names used by
# train.py --config=... ; each is a TrainConfig factory so tests can shrink
# them without mutation hazards.
# ---------------------------------------------------------------------------

def resolve_mlm_max_predictions(value: int, seq_len: int,
                                objective: str = "mlm") -> int:
    """One source of truth for the gather-head auto rule shared by
    train.py/bench.py: -1 resolves to the canonical ``round(0.15*seq_len)``
    for the mlm objective and to 0 (dense / no-op) for anything else, so a
    causal model can never silently carry a dead gather config. Explicit
    values are clamped to ``seq_len`` — a wider head is meaningless (at most
    seq_len positions can be masked) and the host pipeline's argsort-based
    masking would emit a narrower batch than the synthetic pipeline,
    crashing downstream with an opaque broadcast error (ADVICE r2 #1)."""
    if value >= 0:
        return min(value, seq_len) if objective == "mlm" else 0
    return int(round(0.15 * seq_len)) if objective == "mlm" else 0


def preset(name: str) -> TrainConfig:
    """Return one of the five acceptance configurations by name."""
    if name == "resnet50_synthetic":      # config 1
        return TrainConfig(
            model="resnet50", global_batch_size=32,
            data=DataConfig(synthetic=True))
    if name == "resnet50_dp":             # config 2
        return TrainConfig(
            model="resnet50", global_batch_size=256,
            parallel=ParallelConfig(data=8),
            data=DataConfig(synthetic=False))
    if name == "resnet152_dp":            # config 3
        return TrainConfig(
            model="resnet152", global_batch_size=256,
            parallel=ParallelConfig(data=8))
    if name == "densenet121_dp":          # config 3
        return TrainConfig(
            model="densenet121", global_batch_size=256,
            parallel=ParallelConfig(data=8))
    if name == "bert_base_mlm":           # config 4
        return TrainConfig(
            model="bert_base", global_batch_size=256,
            parallel=ParallelConfig(data=8),
            data=DataConfig(dataset="mlm", seq_len=128),
            optimizer=OptimizerConfig(
                name="adamw", learning_rate=1e-4, weight_decay=0.01,
                schedule="linear", warmup_epochs=0.0, label_smoothing=0.0))
    if name == "bert_base_mlm_longctx":   # long-context: ring attention over
        return TrainConfig(               # the seq axis (SURVEY.md §5.7)
            model="bert_base", global_batch_size=32,
            parallel=ParallelConfig(data=2, seq=4),
            attention_impl="ring",
            data=DataConfig(dataset="mlm", seq_len=2048),
            optimizer=OptimizerConfig(
                name="adamw", learning_rate=1e-4, weight_decay=0.01,
                schedule="linear", warmup_epochs=0.0, label_smoothing=0.0))
    if name == "resnet50_lars_32k":       # config 5
        # batch 32k as 8-way DP x 16 microbatches per update: the LARS recipe
        # (one optimizer step per 32768 examples) runs on any mesh; on a real
        # 256-chip pod pass --dp 256 --accum 1 to trade accumulation for
        # chips without touching the optimizer math.
        return TrainConfig(
            model="resnet50", global_batch_size=32768, dtype="bfloat16",
            grad_accum_steps=16,
            parallel=ParallelConfig(data=8),
            optimizer=OptimizerConfig(
                # peak LR 29.0 AT batch 32k (LARS paper recipe): pin
                # reference_batch so the linear-scaling rule is identity here.
                name="lars", learning_rate=29.0, reference_batch=32768,
                momentum=0.9, weight_decay=1e-4, warmup_epochs=5.0,
                schedule="warmup_poly", label_smoothing=0.1))
    raise KeyError(f"unknown preset {name!r}; see BASELINE.json configs")


PRESETS = (
    "resnet50_synthetic", "resnet50_dp", "resnet152_dp", "densenet121_dp",
    "bert_base_mlm", "bert_base_mlm_longctx", "resnet50_lars_32k",
)
