"""Continuous-batching generation engine: prefill/decode split over a
paged KV cache, slots admitted and retired every step.

Why not ``models/generate.py`` for serving: ``generate()`` runs one batch
shape to completion — chips idle whenever sequences finish early, and a
long prompt stalls every other request in the batch. This engine runs two
separately compiled programs instead (the same per-program decomposition
PAPERS.md motivates for MPMD pipeline training, applied to inference):

- **prefill** (one program per prompt-length bucket): a batch-1 dense
  decode forward over the right-padded prompt, whose K/V is packed into
  pool pages *inside the same program* (``kv_cache.pack_prefill_cache``
  with the real length as a traced scalar — one compile per bucket, any
  prompt length within it), returning the first generated token;
- **decode** (one static-shape program): every live slot advances exactly
  one token per call via the models' ``paged_state`` branch. Slots join
  and leave between calls by flipping rows of the page table / lengths /
  live mask — the compiled program never changes shape.

Both programs are lowered through ``perf/aot.py``'s executable cache
under a serve-specific config fingerprint, so a warm replica boots with
zero retraces (``Engine.warmup()`` + ``aot_stats()``).

Greedy (temperature=0) only in v1: preemption re-queues a request with
its generated prefix folded into the prompt, and greedy decoding is what
makes that continuation deterministic (tests pin token-identity against
sequential ``generate(use_cache=True)``, including across preemption and
mid-stream retire/admit). Sampled serving needs per-slot RNG lanes —
deliberately out of scope here.

Observability: per-request lifecycle events (``serve_admit`` /
``serve_prefill`` / ``serve_first_token`` / ``serve_retire`` /
``serve_preempt`` / ``serve_shed`` / ``serve_deadline_miss``) go to the
flight recorder; engine gauges (live slots, page occupancy, queue depth,
TTFT, shed/deadline-miss/retry counters) to ``observability/metrics.py``.
When telemetry is enabled at construction, ``serve/tracing.py`` adds
request-scoped Chrome-trace span trees and exact TTFT/latency
attribution (docs/serve_tracing.md); when it is not, the engine holds no
tracer and the hot loop pays one ``is not None`` check per site.

Failure modes (docs/serving.md "Failure modes and recovery"): the engine
accepts a serve fault plan (``robustness/faults.py`` grammar, resolved
attempt-scoped from ``DDL_FAULT_PLAN``) and fires it at step boundaries —
``crash``/``sigkill`` kill the replica mid-decode, ``decode_stall`` sleeps
a step, ``page_leak``/``corrupt_page_table`` sabotage the paged-KV host
state. Under an active plan every step opens with ``check_integrity()``
(page-table rows vs owned pages vs allocator accounting), so sabotage is
detected BEFORE the corrupt state reaches a dispatch; ``shutdown()`` runs
the same gate unconditionally. Requests lost with a replica are replayed
by the supervisor (``launch.run_serve``) through the same greedy
prefix-folding path preemption uses, which is what makes recovery
token-identical.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
import json
import time
from typing import Any, Callable, NamedTuple, Optional, Sequence

import numpy as np

from distributeddeeplearning_tpu.robustness import faults as faultslib
from distributeddeeplearning_tpu.serve import kv_cache
from distributeddeeplearning_tpu.serve import tracing as tracinglib
from distributeddeeplearning_tpu.serve.scheduler import (BrownoutController,
                                                         SloScheduler)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Everything that shapes the compiled serve programs, plus the one
    volatile knob (``compile_cache``) excluded from the fingerprint."""

    model: str = "gpt_tiny"
    vocab_size: int = 1024
    dtype: str = "float32"
    max_slots: int = 4                      # decode batch rows
    page_size: int = 16                     # tokens per KV page
    num_pages: int = 64                     # pool size, all slots share it
    max_pages_per_slot: int = 8             # page-table width
    prefill_buckets: tuple = (16, 32, 64)   # padded prompt lengths
    seed: int = 0
    # Serve fast path (both default OFF — the PR-12 engine exactly).
    # prefix_cache: radix-tree prefix reuse over the shared page pool —
    # admission maps cached full prompt pages into the slot's table
    # (refcount++) and prefills only the unmatched suffix.
    prefix_cache: bool = False
    # Speculative decoding: a shrunk same-family drafter proposes
    # spec_k tokens per round; one batched verify program accepts the
    # longest greedy-matching prefix (token-identical by construction).
    # Both must be set together.
    spec_draft_model: Optional[str] = None
    spec_k: int = 0
    compile_cache: bool = True

    @property
    def slot_capacity(self) -> int:
        """Max prompt+generated tokens a single slot can ever hold."""
        return self.page_size * self.max_pages_per_slot

    @property
    def spec_enabled(self) -> bool:
        return self.spec_k > 0 and self.spec_draft_model is not None


def serve_fingerprint(config: ServeConfig) -> str:
    """Stable hash of the program-shaping serve config (+ jax versions and
    the package's source digest, ``perf/aot.versions``) — the serving
    analogue of ``perf/aot.config_fingerprint``, which cannot be reused
    directly because it resolves TrainConfig-only fields (fault plans) that
    a ServeConfig does not have."""
    from distributeddeeplearning_tpu.perf import aot as aotlib

    d = dataclasses.asdict(config)
    d.pop("compile_cache", None)  # volatile: never shapes a program
    d["_versions"] = aotlib.versions()
    blob = json.dumps(d, sort_keys=True, default=repr)
    return hashlib.sha256(blob.encode()).hexdigest()[:32]


@dataclasses.dataclass
class Request:
    """One generation request and its accumulated lifecycle state."""

    uid: int
    tenant: str
    prompt: list
    max_new_tokens: int
    arrival_s: float
    tokens: list = dataclasses.field(default_factory=list)
    ttft_s: Optional[float] = None
    itl_s: list = dataclasses.field(default_factory=list)
    finished_s: Optional[float] = None
    preemptions: int = 0
    retries: int = 0            # re-admissions after preemption/loss
    not_before_s: float = 0.0   # retry backoff: ineligible before this
    failed: Optional[str] = None  # "deadline"/"shed"/"retries_exhausted"
    _last_emit_s: Optional[float] = None
    # tracing.RequestTrace when the engine was built with telemetry
    # enabled; stays None (zero per-request overhead) otherwise.
    trace: Any = None

    @property
    def total_tokens(self) -> int:
        """Full page budget: prompt + every token it may ever emit."""
        return len(self.prompt) + self.max_new_tokens

    @property
    def remaining(self) -> int:
        return self.max_new_tokens - len(self.tokens)

    @property
    def prefill_ids(self) -> list:
        """What a (re-)admission prefills: the prompt plus everything
        already emitted — after preemption the generated prefix is part
        of the context, and greedy decoding continues it exactly."""
        return list(self.prompt) + list(self.tokens)

    @property
    def output_ids(self) -> list:
        return list(self.prompt) + list(self.tokens)

    def emit(self, token: int, now: float) -> None:
        if self.ttft_s is None:
            self.ttft_s = now - self.arrival_s
        elif self._last_emit_s is not None:
            self.itl_s.append(now - self._last_emit_s)
        self.tokens.append(int(token))
        self._last_emit_s = now


class _SlotView(NamedTuple):
    """What the scheduler sees of a live slot."""

    slot: int
    tenant: str
    num_pages: int
    admitted_seq: int
    arrival_s: float = 0.0


@dataclasses.dataclass
class _Slot:
    request: Request
    pages: list
    admitted_seq: int


class Engine:
    """Continuous-batching engine over one model replica.

    ``clock`` is injectable (tests drive a fake clock; the bench uses
    ``time.monotonic``). All host state is plain numpy/python; device
    state is exactly (params, pools) with pools donated through both
    programs, so XLA updates the KV pool in place every step.
    """

    def __init__(self, config: ServeConfig, *, model=None, variables=None,
                 scheduler: Optional[SloScheduler] = None,
                 clock: Optional[Callable[[], float]] = None,
                 brownout: Optional[BrownoutController] = None,
                 fault_plan: Optional[str] = None,
                 stall: Optional[Callable[[float], None]] = None):
        import jax
        import jax.numpy as jnp

        from distributeddeeplearning_tpu.models import generate as genlib
        from distributeddeeplearning_tpu.perf import aot as aotlib
        from distributeddeeplearning_tpu.perf import compile_cache

        cfg = config
        if not cfg.prefill_buckets:
            raise ValueError("prefill_buckets must name at least one "
                             "padded prompt length")
        self.config = cfg
        # One replica, one device: everything below (params, KV pools, the
        # compiled programs) lives on the process's first device. A serve
        # replica process is given exactly one chip (launch._spawn_replica),
        # and a replica is told apart by which chip that is, not by a
        # device index in here.
        self.device = jax.devices()[0]
        compile_cache.activate(cfg.compile_cache)
        self.scheduler = scheduler or SloScheduler()
        self._clock = clock or time.monotonic
        # Resolved ONCE: telemetry must be configured before the engine
        # is built. None IS the disabled path — every instrumentation
        # site below is behind a single ``is not None`` check and no
        # per-request trace state is ever allocated (pinned by test).
        self._tracer = tracinglib.maybe_tracer()
        if model is None:
            from distributeddeeplearning_tpu import models as modelslib
            model = modelslib.model_spec(cfg.model).build(
                vocab_size=cfg.vocab_size, dtype=getattr(jnp, cfg.dtype))
        self.model = model
        if variables is None:
            probe = jnp.zeros((1, min(cfg.prefill_buckets)), jnp.int32)
            variables = model.init({"params": jax.random.key(cfg.seed)},
                                   probe, train=False)
        self._fresh = {k: v for k, v in variables.items() if k != "cache"}

        capacity = genlib.decode_capacity(model)
        if capacity is not None and cfg.slot_capacity > capacity:
            raise ValueError(
                f"slot capacity {cfg.slot_capacity} tokens (page_size x "
                f"max_pages_per_slot) exceeds the model's decode bound "
                f"{capacity} — positions past it cannot be generated")
        if max(cfg.prefill_buckets) > cfg.slot_capacity:
            raise ValueError(
                f"largest prefill bucket {max(cfg.prefill_buckets)} "
                f"exceeds slot capacity {cfg.slot_capacity}")

        self._pools = kv_cache.init_pools(
            model, {**self._fresh}, num_pages=cfg.num_pages,
            page_size=cfg.page_size)
        self.allocator = kv_cache.PageAllocator(cfg.num_pages)

        # Radix prefix cache: tree nodes hold allocator claims on cached
        # full prompt pages, so a retired slot's prefix survives for the
        # next request with the same prompt head.
        self.prefix = (kv_cache.RadixPrefixCache(self.allocator,
                                                 cfg.page_size)
                       if cfg.prefix_cache else None)
        self.prefix_hits = 0
        self.prefix_misses = 0
        self.prefix_tokens_reused = 0
        self.cow_copies = 0

        # Speculative decoding: a shrunk same-family drafter over its OWN
        # pools but the SAME page-id space (one allocator, one page
        # table), so shared prefix pages carry drafter K/V too. A drafter
        # named identically to the target shares its seed (bitwise-equal
        # params — the always-accept path tests exercise).
        if (cfg.spec_k > 0) != (cfg.spec_draft_model is not None):
            raise ValueError(
                f"speculative decoding needs BOTH spec_draft_model and "
                f"spec_k > 0 (got draft={cfg.spec_draft_model!r}, "
                f"k={cfg.spec_k})")
        self._draft_model = None
        if cfg.spec_enabled:
            from distributeddeeplearning_tpu import models as modelslib
            draft = modelslib.model_spec(cfg.spec_draft_model).build(
                vocab_size=cfg.vocab_size, dtype=getattr(jnp, cfg.dtype))
            dseed = (cfg.seed if cfg.spec_draft_model == cfg.model
                     else cfg.seed + 1)
            probe = jnp.zeros((1, min(cfg.prefill_buckets)), jnp.int32)
            dvars = draft.init({"params": jax.random.key(dseed)}, probe,
                               train=False)
            dcap = genlib.decode_capacity(draft)
            if dcap is not None and cfg.slot_capacity > dcap:
                raise ValueError(
                    f"slot capacity {cfg.slot_capacity} exceeds the "
                    f"drafter's decode bound {dcap}")
            self._draft_model = draft
            self._draft_fresh = {k: v for k, v in dvars.items()
                                 if k != "cache"}
            self._draft_pools = kv_cache.init_pools(
                draft, {**self._draft_fresh}, num_pages=cfg.num_pages,
                page_size=cfg.page_size)
        self.spec_rounds = 0
        self.spec_proposed = 0
        self.spec_accepted = 0

        s, p = cfg.max_slots, cfg.max_pages_per_slot
        # Drafter cached length per slot: the drafter may lag the target
        # by at most one token after a fully-accepted round.
        self._d_len = np.zeros((s,), np.int32)
        self._page_table = np.zeros((s, p), np.int32)
        self._lengths = np.zeros((s,), np.int32)
        self._live = np.zeros((s,), bool)
        self._feed = np.zeros((s, 1), np.int32)
        self._slots: list = [None] * s
        self.waiting: collections.deque = collections.deque()
        self.finished: list = []
        self.failed: list = []
        self._uid = 0
        self._admitted_seq = 0
        self.steps = 0
        self.preemptions = 0
        self.sheds = 0
        self.deadline_misses = 0
        self.retries = 0

        self.brownout = brownout
        # Serve chaos: the resolved (attempt-scoped) plan installs a stall
        # table and a boundary injector; a plan-free engine pays one
        # ``is not None`` check per step and no integrity sweep.
        plan = faultslib.resolve_serve(fault_plan)
        self._stalls = plan.serve_stalls()
        self._fault_fire = faultslib.make_serve_injector(plan, self)
        self._chaos = bool(plan)
        self._stall = stall or time.sleep

        self._aot = aotlib.StepExecutableCache(
            compile_cache.cache_dir(cfg.compile_cache),
            serve_fingerprint(cfg), [self.device])
        self._prefill_exec: dict = {}
        self._block_prefill_exec: dict = {}
        self._decode_exec = None
        self._draft_decode_exec = None
        self._verify_exec = None
        self._clone_exec: dict = {}

    # -- public surface ---------------------------------------------------

    def submit(self, prompt: Sequence[int], *, max_new_tokens: int,
               tenant: str = "default",
               arrival_s: Optional[float] = None,
               trace_id: Optional[int] = None,
               resumed: bool = False) -> Request:
        """Queue one request; admission happens on a later ``step()``.

        ``trace_id``/``resumed`` are tracing metadata: the supervisor
        passes its GLOBAL uid as the trace id (engine uids are local) so
        a re-dispatched request keeps one flow id across replicas, and
        ``resumed=True`` marks a continuation of a flow another process
        opened. Both are ignored when tracing is off."""
        from distributeddeeplearning_tpu.models import generate as genlib

        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt: prefill needs >= 1 token")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens={max_new_tokens}: a request "
                             f"that emits nothing never leaves its slot")
        total = len(prompt) + max_new_tokens
        genlib._require_decode(self.model, total, request_totals=[total])
        if total > self.config.slot_capacity:
            raise ValueError(
                f"request needs {total} tokens (prompt {len(prompt)} + "
                f"max_new {max_new_tokens}) but a slot holds at most "
                f"{self.config.slot_capacity} (page_size "
                f"{self.config.page_size} x max_pages_per_slot "
                f"{self.config.max_pages_per_slot})")
        if len(prompt) > max(self.config.prefill_buckets):
            raise ValueError(
                f"prompt of {len(prompt)} tokens exceeds the largest "
                f"prefill bucket {max(self.config.prefill_buckets)}")
        req = Request(uid=self._uid, tenant=tenant, prompt=prompt,
                      max_new_tokens=max_new_tokens,
                      arrival_s=(self._clock() if arrival_s is None
                                 else arrival_s))
        self._uid += 1
        self.waiting.append(req)
        if self._tracer is not None:
            self._tracer.on_submit(req, trace_id, resumed=resumed)
        return req

    @property
    def tracer(self):
        """The serve tracer (``serve/tracing.ServeTracer``), or None when
        telemetry was disabled at construction — callers branch on this
        for attribution-fed reporting (replica anomaly cadence, bench)."""
        return self._tracer

    @property
    def num_live(self) -> int:
        return int(self._live.sum())

    @property
    def idle(self) -> bool:
        return not self.waiting and self.num_live == 0

    def step(self) -> list:
        """One engine step: schedule, expire/cancel deadline-blown work,
        shed under brownout pressure, preempt, admit (+prefill), advance
        every live slot one token, retire finished. Returns the requests
        that finished during this step. Under an active fault plan the
        step opens with an integrity sweep (sabotage from the previous
        boundary must not reach a dispatch) and closes by firing the
        injector."""
        from distributeddeeplearning_tpu.observability import flight, metrics

        if self._chaos:
            self.check_integrity()
        stall_s = self._stalls.get(self.steps + 1)
        if stall_s:
            flight.get().record("fault", kind="decode_stall",
                                step=self.steps + 1, seconds=stall_s,
                                scope="serve")
            self._stall(stall_s)
        now = self._clock()
        finished_before = len(self.finished)
        tr = self._tracer
        if tr is not None:
            # Time since the previous step's end is queue time for
            # everything still waiting (accrued BEFORE the shed pass so
            # a shed request's attribution is complete at finalize).
            tr.on_step_start(self.waiting, now)
        if self.brownout is not None:
            for req in self.brownout.plan_shed(
                    now=now, waiting=list(self.waiting),
                    scheduler=self.scheduler,
                    free_pages=self._free_page_budget(),
                    num_pages=self.config.num_pages):
                self.waiting.remove(req)
                self._fail(req, "shed", now)
        t_plan0 = self._clock() if tr is not None else 0.0
        plan = self.scheduler.plan(
            now=now, waiting=list(self.waiting), live=self._slot_views(),
            free_slots=self.config.max_slots - self.num_live,
            free_pages=self._free_page_budget(),
            page_size=self.config.page_size,
            need_pages=(self._need_pages if self.prefix is not None
                        else None))
        if tr is not None:
            tr.on_plan(plan, t_plan0, self._clock(), step=self.steps,
                       waiting=len(self.waiting))
        for slot in plan.cancel:
            self._cancel(slot, now)
        for req in plan.expire:
            self.waiting.remove(req)
            self._fail(req, "deadline", now)
        for slot in plan.preempt:
            self._preempt(slot, now)
        for req in plan.admit:
            self.waiting.remove(req)
            self._admit(req)
        if self.num_live:
            if self._draft_model is not None:
                self._spec_decode_step()
            else:
                self._decode_step()
        if tr is not None:
            # Classify this step's waiting time per request from the
            # scheduler's non-admission reason (an allocator-race
            # requeue in _admit overrides its own).
            tr.on_step_end(self.waiting, plan, self._clock())
        self.steps += 1
        reg = metrics.get()
        reg.observe("serve_live_slots", self.num_live, step=self.steps)
        reg.observe("serve_page_occupancy",
                    self.allocator.pages_in_use / self.config.num_pages,
                    step=self.steps)
        reg.observe("serve_queue_depth", len(self.waiting), step=self.steps)
        reg.observe("serve_shed_total", self.sheds, step=self.steps)
        reg.observe("serve_deadline_miss_total", self.deadline_misses,
                    step=self.steps)
        reg.observe("serve_retry_total", self.retries, step=self.steps)
        reg.observe("serve_alloc_failures", self.allocator.alloc_failures,
                    step=self.steps)
        if self.prefix is not None:
            admits = self.prefix_hits + self.prefix_misses
            reg.observe("serve_prefix_hit_rate",
                        (self.prefix_hits / admits) if admits else 0.0,
                        step=self.steps)
        if self._draft_model is not None:
            reg.observe("serve_spec_acceptance",
                        (self.spec_accepted / self.spec_proposed)
                        if self.spec_proposed else 0.0, step=self.steps)
        if self._fault_fire is not None:
            self._fault_fire(self.steps)
        return self.finished[finished_before:]

    def run_until_idle(self, *, max_steps: int = 10_000) -> list:
        """Drain queue + slots; returns all finished requests. The step
        bound turns a scheduling livelock into a loud failure."""
        for _ in range(max_steps):
            if self.idle:
                return self.finished
            self.step()
        raise RuntimeError(
            f"engine not idle after {max_steps} steps: "
            f"{len(self.waiting)} waiting, {self.num_live} live — "
            f"scheduling livelock or a request that cannot ever fit")

    def warmup(self) -> dict:
        """Compile (or AOT-load) every program this engine's feature set
        will dispatch, without touching pool contents: dummy prefills
        pack zero positions (plen/n_suffix = 0), dummy decode/verify
        calls have no live rows, the dummy clone copies page 0 onto
        itself — every pool write is dropped or a no-op. Which programs
        exist depends on the config (prefix cache swaps the dense
        prefill for the block suffix prefill + COW clone; speculation
        swaps decode for drafter decode + verify), and all of them key
        off the extended ``serve_fingerprint``, so a warm replica boots
        with zero retraces whatever features are on. Returns
        ``aot_stats()``."""
        import jax.numpy as jnp

        cfg = self.config
        zero_row = np.zeros((cfg.max_pages_per_slot,), np.int32)
        for bucket in sorted(cfg.prefill_buckets):
            if self.prefix is not None:
                self._run_block_prefill(
                    np.zeros((1, bucket), np.int32), n_suffix=0,
                    prefix_len=0, page_row=zero_row, draft=False)
            else:
                self._run_prefill(np.zeros((1, bucket), np.int32), plen=0,
                                  page_row=zero_row)
            if self._draft_model is not None:
                self._run_block_prefill(
                    np.zeros((1, bucket), np.int32), n_suffix=0,
                    prefix_len=0, page_row=zero_row, draft=True)
        if self.prefix is not None:
            # Drive the COW clone program directly (page 0 onto itself):
            # a compile, not a real copy — no counter, no flight event.
            self._pools = self._clone_program(draft=False)(
                self._pools, jnp.int32(0), jnp.int32(0))
            if self._draft_model is not None:
                self._draft_pools = self._clone_program(draft=True)(
                    self._draft_pools, jnp.int32(0), jnp.int32(0))
        if self._draft_model is not None:
            toks, dpools = self._draft_decode_program()(
                self._draft_fresh, jnp.asarray(self._feed),
                jnp.asarray(self._page_table), jnp.asarray(self._d_len),
                jnp.asarray(self._live), self._draft_pools)
            toks.block_until_ready()
            self._draft_pools = dpools
            block = np.zeros((cfg.max_slots, cfg.spec_k + 1), np.int32)
            greedy, pools = self._verify_program()(
                self._fresh, jnp.asarray(block),
                jnp.asarray(self._page_table), jnp.asarray(self._lengths),
                jnp.asarray(self._live),
                jnp.zeros((cfg.max_slots,), jnp.int32), self._pools)
            greedy.block_until_ready()
            self._pools = pools
        else:
            tok, pools = self._decode_program()(
                self._fresh, jnp.asarray(self._feed),
                jnp.asarray(self._page_table), jnp.asarray(self._lengths),
                jnp.asarray(self._live), self._pools)
            tok.block_until_ready()
            self._pools = pools
        return self.aot_stats()

    def aot_stats(self) -> dict:
        return self._aot.stats()

    # -- internals --------------------------------------------------------

    def _slot_views(self) -> list:
        return [_SlotView(slot=i, tenant=s.request.tenant,
                          num_pages=len(s.pages),
                          admitted_seq=s.admitted_seq,
                          arrival_s=s.request.arrival_s)
                for i, s in enumerate(self._slots) if s is not None]

    def _bucket_for(self, plen: int) -> int:
        for b in sorted(self.config.prefill_buckets):
            if plen <= b:
                return b
        raise ValueError(
            f"prefill of {plen} tokens exceeds the largest bucket "
            f"{max(self.config.prefill_buckets)} — after preemption the "
            f"generated prefix re-prefills too; size buckets to "
            f"prompt + max_new_tokens")

    def _program(self, name: str, fn, example_args, donate_argnums):
        """Lower/compile through the AOT executable cache: warm replicas
        deserialize instead of retracing."""
        import jax

        from distributeddeeplearning_tpu.perf import aot as aotlib

        key = self._aot.key(name, example_args)
        cached = self._aot.load(name, key)
        if cached is not None:
            return cached
        compiled = aotlib.compile_lowered(
            jax.jit(fn, donate_argnums=donate_argnums).lower(*example_args))
        self._aot.save(name, key, compiled)
        return compiled

    def _prefill_program(self, bucket: int):
        import jax
        import jax.numpy as jnp

        if bucket in self._prefill_exec:
            return self._prefill_exec[bucket]

        def prefill(fresh, ids, plen, page_row, pools):
            logits, mut = self.model.apply(fresh, ids, train=False,
                                           decode=True, mutable=["cache"])
            pools = kv_cache.pack_prefill_cache(
                mut["cache"], pools, page_row=page_row, plen=plen)
            last = jax.lax.dynamic_slice_in_dim(
                logits, plen - 1, 1, axis=1)[:, 0]
            return jnp.argmax(last, axis=-1).astype(jnp.int32)[0], pools

        example = (self._fresh, jnp.zeros((1, bucket), jnp.int32),
                   jnp.int32(0),
                   jnp.zeros((self.config.max_pages_per_slot,), jnp.int32),
                   self._pools)
        exec_ = self._program(f"serve_prefill_{bucket}", prefill, example,
                              donate_argnums=(4,))
        self._prefill_exec[bucket] = exec_
        return exec_

    def _decode_program(self):
        import jax.numpy as jnp

        if self._decode_exec is not None:
            return self._decode_exec

        def decode(fresh, feed, page_table, lengths, live, pools):
            state = kv_cache.PagedState(page_table, lengths, live)
            logits, mut = self.model.apply(
                {**fresh, "cache": pools}, feed, train=False, decode=True,
                paged_state=state, mutable=["cache"])
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return tok, mut["cache"]

        example = (self._fresh, jnp.asarray(self._feed),
                   jnp.asarray(self._page_table),
                   jnp.asarray(self._lengths), jnp.asarray(self._live),
                   self._pools)
        self._decode_exec = self._program("serve_decode", decode, example,
                                          donate_argnums=(5,))
        return self._decode_exec

    def _block_prefill_program(self, bucket: int, *, draft: bool):
        """Suffix prefill over the paged block path: processes up to
        ``bucket`` suffix tokens at base position ``prefix_len`` against
        a page row whose leading pages already hold the cached prefix
        K/V (mapped shared from the radix tree). One compiled program
        per bucket per model; ``prefix_len``/``n_suffix`` are traced
        scalars, so any split within the bucket reuses it."""
        import jax
        import jax.numpy as jnp

        key = (bucket, draft)
        if key in self._block_prefill_exec:
            return self._block_prefill_exec[key]
        model = self._draft_model if draft else self.model
        fresh = self._draft_fresh if draft else self._fresh
        pools = self._draft_pools if draft else self._pools

        def prefill(fresh, ids, prefix_len, n_suffix, page_row, pools):
            state = kv_cache.PagedBlockState(
                page_table=page_row[None], lengths=prefix_len[None],
                live=jnp.ones((1,), bool), n_new=n_suffix[None])
            logits, mut = model.apply(
                {**fresh, "cache": pools}, ids, train=False, decode=True,
                paged_state=state, mutable=["cache"])
            last = jax.lax.dynamic_slice_in_dim(
                logits, jnp.maximum(n_suffix - 1, 0), 1, axis=1)[:, 0]
            return jnp.argmax(last, axis=-1).astype(jnp.int32)[0], \
                mut["cache"]

        example = (fresh, jnp.zeros((1, bucket), jnp.int32),
                   jnp.int32(0), jnp.int32(0),
                   jnp.zeros((self.config.max_pages_per_slot,), jnp.int32),
                   pools)
        name = (f"serve_draft_prefill_{bucket}" if draft
                else f"serve_prefix_prefill_{bucket}")
        exec_ = self._program(name, prefill, example, donate_argnums=(5,))
        self._block_prefill_exec[key] = exec_
        return exec_

    def _clone_program(self, *, draft: bool):
        """The COW copy: clone one pool page row across every leaf of the
        (target or drafter) pool tree — ``kv_cache.clone_page_rows``
        compiled with donated pools so the clone is in-place on device."""
        import jax.numpy as jnp

        if draft in self._clone_exec:
            return self._clone_exec[draft]
        pools = self._draft_pools if draft else self._pools

        def clone(pools, src, dst):
            return kv_cache.clone_page_rows(pools, src, dst)

        name = "serve_draft_page_clone" if draft else "serve_page_clone"
        exec_ = self._program(name, clone,
                              (pools, jnp.int32(0), jnp.int32(0)),
                              donate_argnums=(0,))
        self._clone_exec[draft] = exec_
        return exec_

    def _draft_decode_program(self):
        """One drafter token for every slot — same shape as the target
        decode program, over the drafter's pools and per-slot drafter
        lengths (the drafter may trail the target by one)."""
        import jax.numpy as jnp

        if self._draft_decode_exec is not None:
            return self._draft_decode_exec
        draft = self._draft_model

        def decode(fresh, feed, page_table, lengths, live, pools):
            state = kv_cache.PagedState(page_table, lengths, live)
            logits, mut = draft.apply(
                {**fresh, "cache": pools}, feed, train=False, decode=True,
                paged_state=state, mutable=["cache"])
            tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            return tok, mut["cache"]

        example = (self._draft_fresh, jnp.asarray(self._feed),
                   jnp.asarray(self._page_table),
                   jnp.asarray(self._d_len), jnp.asarray(self._live),
                   self._draft_pools)
        self._draft_decode_exec = self._program(
            "serve_draft_decode", decode, example, donate_argnums=(5,))
        return self._draft_decode_exec

    def _verify_program(self):
        """One batched target forward over each slot's [feed, proposals]
        block: returns the target's greedy token at every block position.
        Accepting the longest prefix where proposals match this greedy
        output IS sequential greedy decoding — token identity by
        construction. Rejected columns' pool writes land past the
        accepted length and are masked garbage the next block
        overwrites."""
        import jax.numpy as jnp

        if self._verify_exec is not None:
            return self._verify_exec

        def verify(fresh, block, page_table, lengths, live, n_new, pools):
            state = kv_cache.PagedBlockState(page_table, lengths, live,
                                             n_new)
            logits, mut = self.model.apply(
                {**fresh, "cache": pools}, block, train=False, decode=True,
                paged_state=state, mutable=["cache"])
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return greedy, mut["cache"]

        cfg = self.config
        example = (self._fresh,
                   jnp.zeros((cfg.max_slots, cfg.spec_k + 1), jnp.int32),
                   jnp.asarray(self._page_table),
                   jnp.asarray(self._lengths), jnp.asarray(self._live),
                   jnp.zeros((cfg.max_slots,), jnp.int32), self._pools)
        self._verify_exec = self._program("serve_verify", verify, example,
                                          donate_argnums=(6,))
        return self._verify_exec

    def _run_block_prefill(self, padded: np.ndarray, *, n_suffix: int,
                           prefix_len: int, page_row: np.ndarray,
                           draft: bool) -> int:
        import jax.numpy as jnp

        bucket = padded.shape[1]
        exec_ = self._block_prefill_program(bucket, draft=draft)
        fresh = self._draft_fresh if draft else self._fresh
        pools = self._draft_pools if draft else self._pools
        tok, pools = exec_(fresh, jnp.asarray(padded),
                           jnp.int32(prefix_len), jnp.int32(n_suffix),
                           jnp.asarray(page_row), pools)
        if draft:
            self._draft_pools = pools
        else:
            self._pools = pools
        return int(tok)

    def _run_page_copy(self, src: int, dst: int) -> None:
        """Copy-on-write a shared page into a slot-private one (target
        pools and, under speculation, drafter pools). Flight-logged
        BEFORE the copy dispatches — the ddl-lint ``cow-before-write``
        rule pins callers to the same record-then-dispatch discipline
        the page-table rule established."""
        import jax.numpy as jnp

        from distributeddeeplearning_tpu.observability import flight

        flight.get().record("serve_cow_copy", src=int(src), dst=int(dst))
        self.cow_copies += 1
        self._pools = self._clone_program(draft=False)(
            self._pools, jnp.int32(src), jnp.int32(dst))
        if self._draft_model is not None:
            self._draft_pools = self._clone_program(draft=True)(
                self._draft_pools, jnp.int32(src), jnp.int32(dst))

    def _free_page_budget(self) -> int:
        """Pages admission control may count on: the allocator's free
        list plus everything the prefix cache could evict on demand."""
        free = self.allocator.free_pages
        if self.prefix is not None:
            free += self.prefix.evictable_pages()
        return free

    def _need_pages(self, req: Request) -> int:
        """Scheduler callback under the prefix cache: charge only the
        NEW pages an admission would allocate — full pages matched in
        the radix tree are mapped shared, not taken from the free
        list (the COW clone of a partial trailing page counts as
        new)."""
        cfg = self.config
        matched, _ = self.prefix.match(req.prefill_ids)
        prefix_len = min(matched, len(req.prefill_ids) - 1)
        return (kv_cache.pages_needed(req.total_tokens, cfg.page_size)
                - prefix_len // cfg.page_size)

    def _assert_cow_writable(self, slot: int, start: int,
                             count: int) -> None:
        """Pages about to receive in-place writes for positions
        ``[start, start+count)`` of ``slot`` must be exclusively held —
        the runtime half of the COW discipline (a shared page here means
        admission mapped a page it should have cloned)."""
        if self.prefix is None or count <= 0:
            return
        ps = self.config.page_size
        row = self._page_table[slot]
        pages = {int(row[j]) for j in range(start // ps,
                                            (start + count - 1) // ps + 1)}
        self.allocator.assert_writable(pages)

    def _run_prefill(self, padded: np.ndarray, *, plen: int,
                     page_row: np.ndarray) -> int:
        import jax.numpy as jnp

        bucket = padded.shape[1]
        tok, pools = self._prefill_program(bucket)(
            self._fresh, jnp.asarray(padded), jnp.int32(plen),
            jnp.asarray(page_row), self._pools)
        self._pools = pools
        return int(tok)

    def _admit(self, req: Request) -> None:
        from distributeddeeplearning_tpu.observability import flight

        cfg = self.config
        tr = self._tracer
        t_adm0 = self._clock() if tr is not None else 0.0
        if tr is not None:
            # Time from step start to here served OTHER requests
            # (expire/preempt handling, earlier admissions' prefills).
            tr.on_admit_start(req, t_adm0)
        slot = next(i for i, s in enumerate(self._slots) if s is None)
        ids = req.prefill_ids
        plen = len(ids)

        # Radix walk: full matched pages map in shared; the partially
        # reused trailing page of a fully-cached prompt is cloned
        # copy-on-write (at least one suffix token always re-runs so the
        # prefill can emit). Matched pages are pinned (incref) up front
        # so the eviction below can never free them out from under us.
        prefix_len = 0
        shared: list = []
        cow_src: Optional[int] = None
        if self.prefix is not None:
            matched, mpages = self.prefix.match(ids)
            prefix_len = min(matched, plen - 1)
            full = prefix_len // cfg.page_size
            shared = [int(p) for p in mpages[:full]]
            self.allocator.incref(shared)
            if prefix_len % cfg.page_size:
                cow_src = int(mpages[full])
                self.allocator.incref([cow_src])
        need_total = kv_cache.pages_needed(req.total_tokens, cfg.page_size)
        need_new = need_total - len(shared)
        new_pages = self.allocator.alloc(need_new)
        if new_pages is None and self.prefix is not None:
            # The free list is short but the tree holds reclaimable
            # pages: evict LRU refcount-1 nodes and retry.
            self.prefix.evict(need_new - self.allocator.free_pages)
            new_pages = self.allocator.alloc(need_new)
        if new_pages is None:  # scheduler raced itself — re-queue
            self.allocator.decref(shared)
            if cow_src is not None:
                self.allocator.decref([cow_src])
            self.waiting.appendleft(req)
            if tr is not None:
                tr.on_requeue(req, self._clock(), step=self.steps)
            return
        pages = shared + new_pages
        self._admitted_seq += 1
        self._slots[slot] = _Slot(request=req, pages=pages,
                                  admitted_seq=self._admitted_seq)
        page_row = np.zeros((cfg.max_pages_per_slot,), np.int32)
        page_row[:need_total] = pages
        self._page_table[slot] = page_row

        if self.prefix is not None:
            if prefix_len > 0:
                self.prefix_hits += 1
                self.prefix_tokens_reused += prefix_len
            else:
                self.prefix_misses += 1
        flight.get().record("serve_admit", request=req.uid,
                            tenant=req.tenant, slot=slot, pages=need_total,
                            new_pages=need_new, prefix_tokens=prefix_len,
                            resumed=bool(req.tokens))
        if tr is not None:
            tr.on_alloc(req, t_adm0, self._clock(), step=self.steps,
                        slot=slot, new_pages=need_new,
                        shared_pages=len(shared),
                        prefix_tokens=prefix_len,
                        prefix_cache=self.prefix is not None,
                        cow=cow_src is not None)
        if cow_src is not None:
            t_cow0 = self._clock() if tr is not None else 0.0
            self._run_page_copy(cow_src, pages[len(shared)])
            self.allocator.decref([cow_src])  # unpin the clone source
            if tr is not None:
                tr.on_cow_copy(req, t_cow0, self._clock(),
                               step=self.steps, src=cow_src,
                               dst=pages[len(shared)])
        n_suffix = plen - prefix_len
        t_pf0 = self._clock() if tr is not None else 0.0
        if self.prefix is not None:
            self._assert_cow_writable(slot, prefix_len, n_suffix)
            bucket = self._bucket_for(n_suffix)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :n_suffix] = ids[prefix_len:]
            tok = self._run_block_prefill(padded, n_suffix=n_suffix,
                                          prefix_len=prefix_len,
                                          page_row=page_row, draft=False)
        else:
            bucket = self._bucket_for(plen)
            padded = np.zeros((1, bucket), np.int32)
            padded[0, :plen] = ids
            tok = self._run_prefill(padded, plen=plen, page_row=page_row)
        if self._draft_model is not None:
            # Drafter prefills the same suffix over its own pools (shared
            # prefix pages already hold drafter K/V from their original
            # admission), so proposals start from a fully-caught-up
            # drafter.
            dbucket = self._bucket_for(n_suffix)
            dpadded = np.zeros((1, dbucket), np.int32)
            dpadded[0, :n_suffix] = ids[prefix_len:]
            self._run_block_prefill(dpadded, n_suffix=n_suffix,
                                    prefix_len=prefix_len,
                                    page_row=page_row, draft=True)
            self._d_len[slot] = plen
        if self.prefix is not None:
            self.prefix.insert(ids, pages)
        now = self._clock()
        flight.get().record("serve_prefill", request=req.uid, slot=slot,
                            bucket=bucket, prompt_tokens=plen)
        first = req.ttft_s is None
        resumed = bool(req.tokens)  # read BEFORE emit appends
        req.emit(tok, now)
        if tr is not None:
            tr.on_prefill(req, t_pf0, now, step=self.steps, slot=slot,
                          bucket=bucket,
                          prefill_tokens=(n_suffix
                                          if self.prefix is not None
                                          else plen),
                          prefix_tokens=prefix_len, first=first,
                          resumed=resumed)
        if first:
            from distributeddeeplearning_tpu.observability import metrics
            metrics.get().observe("serve_ttft_s", req.ttft_s,
                                  step=self.steps)
            flight.get().record("serve_first_token", request=req.uid,
                                slot=slot, ttft_s=round(req.ttft_s, 6))
        self._lengths[slot] = plen
        self._live[slot] = True
        self._feed[slot, 0] = tok
        if req.remaining == 0:
            self._retire(slot, now)

    def _decode_step(self) -> None:
        import jax.numpy as jnp

        tr = self._tracer
        t_d0 = self._clock() if tr is not None else 0.0
        for i in np.flatnonzero(self._live):
            self._assert_cow_writable(int(i), int(self._lengths[i]), 1)
        toks, pools = self._decode_program()(
            self._fresh, jnp.asarray(self._feed),
            jnp.asarray(self._page_table), jnp.asarray(self._lengths),
            jnp.asarray(self._live), self._pools)
        self._pools = pools
        toks = np.asarray(toks)
        now = self._clock()
        if tr is not None:
            # Accrues decode for every participant BEFORE the retire
            # loop below finalizes any of them.
            tr.on_decode(t_d0, now, step=self.steps,
                         slots=[(int(i), self._slots[i].request)
                                for i in np.flatnonzero(self._live)])
        for i in np.flatnonzero(self._live):
            req = self._slots[i].request
            req.emit(toks[i], now)
            self._lengths[i] += 1
            self._feed[i, 0] = toks[i]
            if req.remaining == 0:
                self._retire(int(i), now)

    def _spec_decode_step(self) -> None:
        """One speculative round for every live slot: the drafter
        proposes up to ``spec_k`` tokens (catching up its one-token lag
        first), one batched target forward verifies the whole
        ``[feed, proposals]`` block, and the longest prefix of proposals
        matching the target's own greedy output is accepted — plus the
        target's next token after the accepted prefix (the "bonus"
        token), so even an all-rejected round advances one token exactly
        like ``_decode_step``. Token-identical to sequential greedy by
        construction: every emitted token is the target's argmax given
        the same cached context.

        Per-slot bounds: ``n <= remaining - 1`` (the round emits at most
        ``n + 1`` tokens), and the drafter only steps while a slot still
        needs catch-up or proposals (``active`` mask) so its writes can
        never run past the slot's page budget."""
        import jax.numpy as jnp

        cfg = self.config
        tr = self._tracer
        t_d0 = self._clock() if tr is not None else 0.0
        live_idx = [int(i) for i in np.flatnonzero(self._live)]
        L = self._lengths.copy()
        d = self._d_len.copy()
        n_prop = np.zeros((cfg.max_slots,), np.int32)
        steps_needed = np.zeros((cfg.max_slots,), np.int32)
        proposals: list = [[] for _ in range(cfg.max_slots)]
        for i in live_idx:
            req = self._slots[i].request
            lag = int(L[i]) - int(d[i])
            n_prop[i] = min(cfg.spec_k, req.remaining - 1)
            steps_needed[i] = lag + int(n_prop[i])
            # Drafter writes [d, L+n) and verify writes [L, L+n]: all of
            # it must be exclusively-held pages (COW discipline).
            self._assert_cow_writable(i, int(d[i]),
                                      int(L[i]) + int(n_prop[i]) + 1
                                      - int(d[i]))
        feed = np.zeros((cfg.max_slots, 1), np.int32)
        for r in range(int(steps_needed.max()) if live_idx else 0):
            active = np.zeros((cfg.max_slots,), bool)
            for i in live_idx:
                if r >= steps_needed[i]:
                    continue
                active[i] = True
                pos = int(d[i])
                if pos <= int(L[i]):
                    # Catch-up / first proposal: the token at this
                    # position is already known (prompt + emitted).
                    feed[i, 0] = self._slots[i].request.output_ids[pos]
                else:
                    feed[i, 0] = proposals[i][pos - int(L[i]) - 1]
            toks, dpools = self._draft_decode_program()(
                self._draft_fresh, jnp.asarray(feed),
                jnp.asarray(self._page_table), jnp.asarray(d),
                jnp.asarray(active), self._draft_pools)
            self._draft_pools = dpools
            toks = np.asarray(toks)
            for i in live_idx:
                if active[i]:
                    if int(d[i]) >= int(L[i]):
                        proposals[i].append(int(toks[i]))
                    d[i] += 1
        t_draft1 = self._clock() if tr is not None else 0.0
        block = np.zeros((cfg.max_slots, cfg.spec_k + 1), np.int32)
        n_new = np.zeros((cfg.max_slots,), np.int32)
        for i in live_idx:
            block[i, 0] = self._feed[i, 0]
            for j in range(int(n_prop[i])):
                block[i, 1 + j] = proposals[i][j]
            n_new[i] = int(n_prop[i]) + 1
        greedy, pools = self._verify_program()(
            self._fresh, jnp.asarray(block), jnp.asarray(self._page_table),
            jnp.asarray(self._lengths), jnp.asarray(self._live),
            jnp.asarray(n_new), self._pools)
        self._pools = pools
        greedy = np.asarray(greedy)
        now = self._clock()
        self.spec_rounds += 1
        round_proposed = round_accepted = 0
        if tr is not None:
            tr.on_decode(t_d0, now, step=self.steps,
                         slots=[(i, self._slots[i].request,
                                 {"spec": True,
                                  "proposed": int(n_prop[i])})
                                for i in live_idx])
        for i in live_idx:
            req = self._slots[i].request
            n = int(n_prop[i])
            m = 0
            while m < n and proposals[i][m] == int(greedy[i, m]):
                m += 1
            self.spec_proposed += n
            self.spec_accepted += m
            round_proposed += n
            round_accepted += m
            for j in range(m + 1):
                req.emit(int(greedy[i, j]), now)
            new_len = int(L[i]) + m + 1
            self._lengths[i] = new_len
            self._feed[i, 0] = int(greedy[i, m])
            # Drafter cache is valid through the last position fed a
            # true token — at most one behind the target after a fully
            # accepted round.
            self._d_len[i] = min(int(d[i]), new_len)
            if req.remaining == 0:
                self._retire(i, now)
        if tr is not None:
            tr.on_spec_phases(
                t_d0, t_draft1, now, step=self.steps,
                rounds=int(steps_needed.max()) if live_idx else 0,
                proposed=round_proposed, accepted=round_accepted)

    def _retire(self, slot: int, now: float) -> None:
        from distributeddeeplearning_tpu.observability import flight

        entry = self._slots[slot]
        req = entry.request
        req.finished_s = now
        # release() + pages=[]: retirement is idempotent — a request that
        # already walked a victim path cannot double-free (the one bug the
        # strict free() exists to catch in non-victim paths).
        self.allocator.release(entry.pages)
        entry.pages = []
        self._clear_slot(slot)
        self.finished.append(req)
        flight.get().record("serve_retire", request=req.uid, slot=slot,
                            tokens=len(req.tokens),
                            preemptions=req.preemptions)
        if self._tracer is not None:
            self._tracer.finalize(req, now, status="ok")

    def _preempt(self, slot: int, now: float) -> None:
        from distributeddeeplearning_tpu.observability import flight

        entry = self._slots[slot]
        req = entry.request
        req.preemptions += 1
        req._last_emit_s = None  # the gap back through the queue is not ITL
        self.allocator.release(entry.pages)
        entry.pages = []
        self._clear_slot(slot)
        self.preemptions += 1
        flight.get().record("serve_preempt", request=req.uid, slot=slot,
                            tenant=req.tenant,
                            tokens_done=len(req.tokens))
        if self._tracer is not None:
            self._tracer.on_preempt(req, now, step=self.steps, slot=slot)
        # Bounded retry with exponential backoff: the scheduler owns the
        # policy, the engine applies it on every re-queue.
        req.retries += 1
        self.retries += 1
        max_r = self.scheduler.max_retries
        if max_r is not None and req.retries > max_r:
            self._fail(req, "retries_exhausted", now)
            return
        delay = self.scheduler.retry_delay_s(req.retries)
        if delay > 0:
            req.not_before_s = now + delay
        self.waiting.append(req)

    def _cancel(self, slot: int, now: float) -> None:
        """A live slot whose request blew its total-latency deadline:
        return the slot and pages, fail the request as a deadline miss."""
        entry = self._slots[slot]
        req = entry.request
        self.allocator.release(entry.pages)
        entry.pages = []
        self._clear_slot(slot)
        if self._tracer is not None:
            self._tracer.on_cancel(req, now)
        self._fail(req, "deadline", now)

    def _fail(self, req: Request, reason: str, now: float) -> None:
        from distributeddeeplearning_tpu.observability import flight

        req.failed = reason
        req.finished_s = now
        self.failed.append(req)
        if reason == "deadline":
            self.deadline_misses += 1
            flight.get().record("serve_deadline_miss", request=req.uid,
                                tenant=req.tenant,
                                waited_s=round(now - req.arrival_s, 6),
                                tokens_done=len(req.tokens))
        else:
            self.sheds += 1
            flight.get().record("serve_shed", request=req.uid,
                                tenant=req.tenant, reason=reason,
                                tokens_done=len(req.tokens))
        if self._tracer is not None:
            self._tracer.on_fail(req, now, reason=reason)

    def _clear_slot(self, slot: int) -> None:
        self._slots[slot] = None
        self._live[slot] = False
        self._lengths[slot] = 0
        self._d_len[slot] = 0
        self._feed[slot, 0] = 0
        self._page_table[slot] = 0

    # -- integrity / chaos hooks ------------------------------------------

    def check_integrity(self) -> None:
        """Reconcile the three views of page ownership — slot page-table
        rows, slot owned-page lists, allocator accounting — and raise on
        any divergence. Runs before every dispatch under an active fault
        plan and unconditionally at shutdown: a leaked page starves
        admission later; a corrupt row serves another slot's K/V now."""
        owned: list = []
        for i, entry in enumerate(self._slots):
            if entry is None:
                continue
            row = [int(p) for p in self._page_table[i, :len(entry.pages)]]
            pages = [int(p) for p in entry.pages]
            if row != pages:
                raise RuntimeError(
                    f"page-table corruption: slot {i} row {row} != owned "
                    f"pages {pages}")
            owned.extend(pages)
        if self.prefix is not None:
            # Tree nodes hold their own claims: one per node, and a page
            # shared with live slots must be counted once per holder.
            owned.extend(self.prefix.owned_pages())
        self.allocator.check_leaks(owned)

    def corrupt_page_table(self) -> Optional[int]:
        """Fault-injection hook (``corrupt_page_table@N``): scribble over
        the first live slot's page-table row. Returns the slot hit, or
        None when nothing is live to corrupt."""
        for i, entry in enumerate(self._slots):
            if entry is not None and entry.pages:
                self._page_table[i, 0] = (
                    int(self._page_table[i, 0]) + 1) % self.config.num_pages
                return i
        return None

    def shutdown(self) -> None:
        """Final gate: flight-record the lifetime counters, then assert
        page accounting balances (allocated == sum of live page tables).
        Raises RuntimeError on a leak — a replica that leaks pages must
        exit loudly, not report success."""
        from distributeddeeplearning_tpu.observability import flight

        flight.get().record("serve_shutdown", steps=self.steps,
                            finished=len(self.finished),
                            failed=len(self.failed),
                            preemptions=self.preemptions,
                            sheds=self.sheds,
                            deadline_misses=self.deadline_misses,
                            prefix_hits=self.prefix_hits,
                            prefix_misses=self.prefix_misses,
                            prefix_tokens_reused=self.prefix_tokens_reused,
                            prefix_evictions=(self.prefix.evictions
                                              if self.prefix is not None
                                              else 0),
                            cow_copies=self.cow_copies,
                            spec_rounds=self.spec_rounds,
                            spec_proposed=self.spec_proposed,
                            spec_accepted=self.spec_accepted)
        self.check_integrity()
