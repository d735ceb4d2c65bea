"""Continuous-batching serve engine (distributeddeeplearning_tpu/serve/).

The load-bearing pin is TOKEN-IDENTITY: the engine's greedy output must
equal sequential ``generate(use_cache=True)`` request-by-request — with
slots retiring and admitting mid-stream, for both model families, and
across a preemption/resume cycle. If that holds, the paged cache, the
prefill packing, the per-row positions, and the masked paged attention
are all simultaneously correct (any one of them wrong changes tokens).
Around the pin: numeric paged-vs-dense attention equivalence, allocator
and scheduler policy units, per-request capacity errors, the AOT
zero-retrace warm boot, and a bench_serve smoke through the
provenance-validated record schema.
"""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributeddeeplearning_tpu.models import generate as genlib
from distributeddeeplearning_tpu.models import model_spec
from distributeddeeplearning_tpu.serve import kv_cache
from distributeddeeplearning_tpu.serve.engine import (Engine, ServeConfig,
                                                      serve_fingerprint)
from distributeddeeplearning_tpu.serve.scheduler import (Plan, SloScheduler,
                                                         TenantPolicy)

pytestmark = pytest.mark.serve

VOCAB = 97


def _engine(model="gpt_tiny", **kw):
    kw.setdefault("vocab_size", VOCAB)
    kw.setdefault("max_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 32)
    kw.setdefault("max_pages_per_slot", 8)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("compile_cache", False)
    t = [0.0]

    def clock():
        t[0] += 0.001  # strictly increasing: every emit gets a distinct time
        return t[0]

    return Engine(ServeConfig(model=model, **kw), clock=clock)


def _reference_tokens(eng, prompt, max_new):
    out = genlib.generate(eng.model, {**eng._fresh},
                          jnp.asarray([prompt], jnp.int32),
                          max_new_tokens=max_new, use_cache=True)
    return [int(x) for x in np.asarray(out)[0, len(prompt):]]


# --- kv_cache units ---------------------------------------------------------

def test_pages_needed_is_ceil_division():
    assert kv_cache.pages_needed(1, 4) == 1
    assert kv_cache.pages_needed(4, 4) == 1
    assert kv_cache.pages_needed(5, 4) == 2
    assert kv_cache.pages_needed(17, 4) == 5


def test_allocator_all_or_nothing_reuse_and_double_free():
    alloc = kv_cache.PageAllocator(4)
    a = alloc.alloc(3)
    assert len(a) == 3 and alloc.free_pages == 1
    # All-or-nothing: a 2-page ask against 1 free page takes NOTHING.
    assert alloc.alloc(2) is None
    assert alloc.free_pages == 1
    alloc.free(a)
    assert alloc.free_pages == 4
    # Freed pages are immediately reusable...
    b = alloc.alloc(4)
    assert sorted(b) == sorted(range(4))
    # ...and a page can never sit on two tables at once.
    alloc.free([b[0]])
    with pytest.raises(ValueError, match="double-free"):
        alloc.free([b[0]])


def test_paged_attention_matches_dense_reference():
    """Paged gather+mask attention == plain softmax attention over each
    slot's logical [0, length] context, per (grouped) head — the numeric
    core the token-identity pins rest on."""
    rng = np.random.default_rng(0)
    slots, page_size, pages_per_slot, num_pages = 3, 4, 2, 8
    kvh, heads, d = 2, 4, 8
    rep = heads // kvh
    lengths = np.array([3, 5, 0], np.int32)
    live = np.array([True, True, False])
    table = np.array([[2, 5], [1, 6], [0, 0]], np.int32)

    pool_k = rng.standard_normal((num_pages, page_size, kvh, d)).astype(
        np.float32)
    pool_v = rng.standard_normal((num_pages, page_size, kvh, d)).astype(
        np.float32)
    q = rng.standard_normal((slots, 1, heads, d)).astype(np.float32)
    k_new = rng.standard_normal((slots, 1, kvh, d)).astype(np.float32)
    v_new = rng.standard_normal((slots, 1, kvh, d)).astype(np.float32)

    out, pk, pv = kv_cache.paged_attention_step(
        jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
        jnp.asarray(pool_k), jnp.asarray(pool_v),
        kv_cache.PagedState(jnp.asarray(table), jnp.asarray(lengths),
                            jnp.asarray(live)))
    out, pk, pv = np.asarray(out), np.asarray(pk), np.asarray(pv)

    # Live slots' k_new landed at position lengths[i]; the dead slot's
    # write was dropped (pool unchanged everywhere it didn't own).
    for i in range(slots):
        if not live[i]:
            continue
        page = table[i, lengths[i] // page_size]
        np.testing.assert_array_equal(
            pk[page, lengths[i] % page_size], k_new[i, 0])
    np.testing.assert_array_equal(pv[3], pool_v[3])  # page 3: never owned

    for i in range(slots):
        if not live[i]:
            continue
        # Logical context rows 0..lengths[i], gathered in page order.
        rows_k = [pk[table[i, t // page_size], t % page_size]
                  for t in range(lengths[i] + 1)]
        rows_v = [pv[table[i, t // page_size], t % page_size]
                  for t in range(lengths[i] + 1)]
        K, V = np.stack(rows_k), np.stack(rows_v)  # (len+1, kvh, d)
        for h in range(heads):
            g = h // rep
            s = (q[i, 0, h] @ K[:, g].T) * d ** -0.5
            p = np.exp(s - s.max())
            p /= p.sum()
            np.testing.assert_allclose(out[i, 0, h * d:(h + 1) * d],
                                       p @ V[:, g], rtol=1e-5, atol=1e-5)


def test_beam_path_rejects_paged_pool_leaves():
    cache = {"layer_0": {"attn": {"pages_k": jnp.zeros((4, 2, 1, 8))}}}
    with pytest.raises(ValueError, match="beam context"):
        genlib._map_batched_cache(cache, lambda x: x)


# --- scheduler policy units -------------------------------------------------

def _req(uid, tenant="default", arrival=0.0, total=8):
    class R:
        pass
    r = R()
    r.uid, r.tenant, r.arrival_s, r.total_tokens = uid, tenant, arrival, total
    return r


def _slot(slot, tenant, num_pages, seq):
    from distributeddeeplearning_tpu.serve.engine import _SlotView
    return _SlotView(slot=slot, tenant=tenant, num_pages=num_pages,
                     admitted_seq=seq)


def test_scheduler_orders_by_deadline_slack_then_fifo():
    sched = SloScheduler([TenantPolicy("rt", ttft_slo_s=0.1),
                          TenantPolicy("batch", ttft_slo_s=10.0)])
    # batch arrived FIRST but has 10 s of slack; rt is nearly overdue.
    plan = sched.plan(now=1.0,
                      waiting=[_req(0, "batch", arrival=0.0),
                               _req(1, "rt", arrival=0.95)],
                      live=[], free_slots=2, free_pages=100, page_size=4)
    assert [r.uid for r in plan.admit] == [1, 0]
    # Same tenant class: FIFO by arrival.
    plan = sched.plan(now=1.0,
                      waiting=[_req(3, "rt", arrival=0.6),
                               _req(2, "rt", arrival=0.5)],
                      live=[], free_slots=2, free_pages=100, page_size=4)
    assert [r.uid for r in plan.admit] == [2, 3]


def test_scheduler_admission_respects_pages_and_tenant_budget():
    sched = SloScheduler([TenantPolicy("capped", max_pages=3)])
    # 2 free pages cannot cover a 3-page request: nothing admitted.
    plan = sched.plan(now=0.0, waiting=[_req(0, total=12)], live=[],
                      free_slots=1, free_pages=2, page_size=4)
    assert plan.empty
    # Tenant budget counts LIVE pages: capped holds 2, another 2-page
    # request would exceed max_pages=3 and is skipped — but an uncapped
    # tenant behind it still admits (the capped one holds its queue spot,
    # not the whole engine).
    plan = sched.plan(now=0.0,
                      waiting=[_req(0, "capped", arrival=0.0, total=8),
                               _req(1, "other", arrival=1.0, total=8)],
                      live=[_slot(0, "capped", 2, seq=1)],
                      free_slots=1, free_pages=10, page_size=4)
    assert [r.uid for r in plan.admit] == [1]
    assert not plan.preempt


def test_scheduler_preempts_newest_overbudget_slot_only():
    sched = SloScheduler([TenantPolicy("bg", max_pages=2)])
    live = [_slot(0, "bg", 3, seq=1), _slot(1, "bg", 3, seq=2)]
    # bg holds 6 pages against a budget of 2; a starved request (needs 2,
    # 0 free) evicts exactly ONE bg slot — the newest (seq=2), minimizing
    # wasted decode work.
    plan = sched.plan(now=0.0, waiting=[_req(0, "rt", total=8)], live=live,
                      free_slots=0, free_pages=0, page_size=4)
    assert plan.preempt == (1,)
    assert [r.uid for r in plan.admit] == [0]
    # Within-budget work is never evicted.
    sched2 = SloScheduler()
    plan = sched2.plan(now=0.0, waiting=[_req(0, total=8)],
                       live=[_slot(0, "default", 3, seq=1)],
                       free_slots=1, free_pages=0, page_size=4)
    assert plan.empty


# --- the token-identity pins ------------------------------------------------

@pytest.mark.parametrize("model", ["gpt_tiny", "llama_tiny"])
def test_engine_token_identity_with_midstream_retire_admit(model):
    """Five requests through two slots: slots retire and re-admit while
    others are mid-decode, and every request's greedy tokens must equal a
    sequential generate(use_cache=True) run of that request alone."""
    eng = _engine(model)
    rng = np.random.default_rng(0)
    lens = [(5, 6), (7, 4), (3, 8), (6, 5), (8, 3)]
    reqs = [eng.submit([int(x) for x in rng.integers(1, VOCAB, p)],
                       max_new_tokens=m) for p, m in lens]
    eng.run_until_idle()
    assert eng.idle and len(eng.finished) == len(reqs)
    for r in reqs:
        assert r.tokens == _reference_tokens(eng, r.prompt,
                                             r.max_new_tokens), r.uid
        assert r.ttft_s is not None and r.finished_s is not None
        assert len(r.tokens) == r.max_new_tokens
    # Every page came back to the free list.
    assert eng.allocator.free_pages == eng.config.num_pages


def test_engine_preemption_resumes_token_identical():
    """Tighten a tenant's page budget mid-run (the operational reconfig
    path), submit a starved higher-urgency request, and the over-budget
    victim must be preempted, re-queued, and finish with EXACTLY the
    tokens of an uninterrupted sequential run."""
    eng = _engine("gpt_tiny", max_slots=2, page_size=4, num_pages=8,
                  max_pages_per_slot=8, prefill_buckets=(8, 16))
    rng = np.random.default_rng(1)
    bg_prompt = [int(x) for x in rng.integers(1, VOCAB, 4)]
    bg = eng.submit(bg_prompt, max_new_tokens=12, tenant="bg")  # 4 pages
    eng.step()
    eng.step()
    assert eng.num_live == 1 and len(bg.tokens) >= 2

    eng.scheduler.policies["bg"] = TenantPolicy("bg", max_pages=3)
    rt_prompt = [int(x) for x in rng.integers(1, VOCAB, 8)]
    rt = eng.submit(rt_prompt, max_new_tokens=12, tenant="rt")  # 5 pages
    eng.step()  # rt needs 5 of 4 free pages -> bg (4 held > 3) evicted
    assert eng.preemptions == 1 and bg.preemptions == 1
    assert bg in list(eng.waiting)

    del eng.scheduler.policies["bg"]  # restore so bg can re-admit
    eng.run_until_idle()
    assert rt.tokens == _reference_tokens(eng, rt_prompt, 12)
    assert bg.tokens == _reference_tokens(eng, bg_prompt, 12)
    assert eng.allocator.free_pages == eng.config.num_pages


def test_engine_aot_warm_boot_zero_retrace(tmp_path, monkeypatch):
    """Second engine with the same fingerprint deserializes every program
    (prefill per bucket + decode) instead of retracing — and still
    decodes token-identically."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    kw = dict(max_slots=2, page_size=4, num_pages=16, max_pages_per_slot=4,
              prefill_buckets=(8,), compile_cache=True)
    cold = _engine("gpt_tiny", **kw)
    stats = cold.warmup()
    assert stats["aot_misses"] == 2 and stats["aot_saves"] == 2
    prompt = list(range(1, 6))
    cold_req = cold.submit(prompt, max_new_tokens=4)
    cold.run_until_idle()

    warm = _engine("gpt_tiny", **kw)
    stats = warm.warmup()
    assert stats["aot_hits"] == 2 and stats["aot_misses"] == 0
    warm_req = warm.submit(prompt, max_new_tokens=4)
    warm.run_until_idle()
    assert warm_req.tokens == cold_req.tokens


def test_serve_fingerprint_tracks_program_shape_not_cache_switch():
    a = ServeConfig(compile_cache=True)
    b = ServeConfig(compile_cache=False)
    c = ServeConfig(page_size=a.page_size * 2)
    assert serve_fingerprint(a) == serve_fingerprint(b)
    assert serve_fingerprint(a) != serve_fingerprint(c)


# --- capacity errors --------------------------------------------------------

def test_require_decode_names_offending_request():
    model = model_spec("gpt_tiny").build(vocab_size=VOCAB)  # max_position 128
    with pytest.raises(ValueError, match=r"request 1 .*over by 72"):
        genlib._require_decode(model, 200, request_totals=[100, 200, 120])


def test_submit_rejects_oversized_requests():
    eng = _engine("gpt_tiny", max_slots=1, page_size=4, num_pages=16,
                  max_pages_per_slot=4, prefill_buckets=(8,))
    with pytest.raises(ValueError, match="slot holds at most 16"):
        eng.submit(list(range(1, 9)), max_new_tokens=9)
    with pytest.raises(ValueError, match="largest prefill bucket"):
        eng.submit(list(range(1, 11)), max_new_tokens=2)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.submit([], max_new_tokens=2)


def test_engine_rejects_capacity_exceeding_config():
    with pytest.raises(ValueError, match="decode bound"):
        # gpt_tiny's max_position is 128; 64-token pages x 4 = 256 > 128.
        Engine(ServeConfig(model="gpt_tiny", vocab_size=VOCAB, max_slots=1,
                           page_size=64, num_pages=8, max_pages_per_slot=4,
                           prefill_buckets=(16,), compile_cache=False))


# --- bench record smoke -----------------------------------------------------

def test_bench_serve_emits_valid_provenance_record(tmp_path, monkeypatch,
                                                   capsys):
    from distributeddeeplearning_tpu.observability import perf_report
    from tools import bench_serve

    written = {}
    from distributeddeeplearning_tpu.observability import sidecars
    monkeypatch.setattr(sidecars, "write",
                        lambda name, payload: written.update(
                            {name: payload}) or str(tmp_path / "s.json"))
    rc = bench_serve.main([
        "--platform", "cpu",
        "--model", "gpt_tiny", "--vocab-size", str(VOCAB),
        "--requests", "3", "--rate", "1000", "--max-new", "3",
        "--prompt-lens", "4,6", "--max-slots", "2", "--page-size", "4",
        "--num-pages", "16", "--max-pages-per-slot", "4",
        "--prefill-buckets", "8", "--no-compile-cache"])
    assert rc == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert perf_report.validate(rec) == []
    assert rec["provenance"] == "fresh"
    assert rec["token_identity_checked"] is True
    assert rec["continuous"]["finished"] == 3
    assert rec["sequential_baseline"]["tokens_per_sec_per_chip"] > 0
    assert "speedup_vs_sequential" in rec
    assert "last_serve" in written


# --- serve chaos: grammar, integrity sweeps, injected stalls ----------------

@pytest.fixture(scope="module")
def chaos_aot(tmp_path_factory):
    """One AOT executable cache shared by every chaos-arm engine in this
    module: identical ServeConfig -> identical fingerprint -> the first
    test pays the compile, the rest warm-boot (tier-1 stays cheap)."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("JAX_COMPILATION_CACHE_DIR",
                  str(tmp_path_factory.mktemp("serve-chaos-aot")))
        yield


def _chaos_engine(_placed_cache, **engine_kw):
    t = [0.0]

    def clock():
        t[0] += 0.001
        return t[0]

    cfg = ServeConfig(model="gpt_tiny", vocab_size=VOCAB, max_slots=2,
                      page_size=4, num_pages=32, max_pages_per_slot=8,
                      prefill_buckets=(8, 16))
    return Engine(cfg, clock=clock, **engine_kw)


def test_resolve_serve_filters_kinds_and_attempt_scope(monkeypatch):
    from distributeddeeplearning_tpu.robustness import faults
    monkeypatch.delenv(faults.ENV_PLAN, raising=False)
    monkeypatch.delenv(faults.ENV_ATTEMPT, raising=False)
    plan = faults.resolve_serve(
        "page_leak@2,decode_stall@4:0.25s,nan_grads@3,sigkill@5:a1")
    # Training-only kinds never reach the serve injector...
    assert all(f.kind in faults.SERVE_KINDS for f in plan.faults)
    assert plan.serve_stalls() == {4: 0.25}
    assert [f.kind for f in plan.serve_faults_at(2)] == ["page_leak"]
    # ...and attempt-scoped faults resolve per incarnation: sigkill@5:a1
    # is invisible on attempt 0, live on attempt 1 (a restarted replica
    # must not be re-killed by the fault that killed its predecessor).
    assert not plan.serve_faults_at(5)
    monkeypatch.setenv(faults.ENV_ATTEMPT, "1")
    replan = faults.resolve_serve("sigkill@5:a1")
    assert [f.kind for f in replan.serve_faults_at(5)] == ["sigkill"]
    with pytest.raises(ValueError, match="unknown fault kind"):
        faults.parse_plan("page_fault@2")


def test_allocator_release_is_idempotent_and_leak_check_is_loud():
    alloc = kv_cache.PageAllocator(8)
    held = alloc.alloc(3)
    assert alloc.release(held) == 3
    # Victim retirement may race engine cleanup: the second release of the
    # same pages frees nothing and never raises.
    assert alloc.release(held) == 0
    assert alloc.free_pages == 8

    owned = alloc.alloc(2)
    alloc.check_leaks(owned)  # balanced: every held page owned exactly once
    leaked = alloc.alloc(1)   # dropped on the floor, no table owns it
    with pytest.raises(RuntimeError, match="KV page leak"):
        alloc.check_leaks(owned)
    alloc.release(leaked)
    alloc.check_leaks(owned)
    with pytest.raises(RuntimeError, match="page-table corruption"):
        alloc.check_leaks(owned + owned)  # one page on two slots' tables


@pytest.mark.chaos
def test_page_leak_fault_trips_next_step_integrity_sweep(chaos_aot):
    eng = _chaos_engine(chaos_aot, fault_plan="page_leak@1")
    eng.submit([1, 2, 3, 4], max_new_tokens=3)
    eng.step()  # boundary injector leaks one page AFTER this step
    with pytest.raises(RuntimeError, match="KV page leak"):
        eng.step()  # the sweep fires before anything dispatches


@pytest.mark.chaos
def test_corrupt_page_table_fault_trips_next_step_integrity_sweep(chaos_aot):
    eng = _chaos_engine(chaos_aot, fault_plan="corrupt_page_table@1")
    eng.submit([1, 2, 3, 4], max_new_tokens=3)
    eng.step()
    with pytest.raises(RuntimeError, match="page-table corruption"):
        eng.step()


@pytest.mark.chaos
def test_decode_stall_fault_injects_sleep_once(chaos_aot):
    stalls = []
    eng = _chaos_engine(chaos_aot, fault_plan="decode_stall@1:0.25s",
                        stall=stalls.append)
    eng.step()
    assert stalls == [0.25]
    eng.step()
    assert stalls == [0.25]  # step-scoped: fires exactly once


# --- deadlines, bounded retry, brownout -------------------------------------

def test_ttft_deadline_expires_waiting_request(chaos_aot):
    sched = SloScheduler([TenantPolicy("rt", ttft_deadline_s=0.0)])
    eng = _chaos_engine(chaos_aot, scheduler=sched)
    req = eng.submit([1, 2, 3, 4], max_new_tokens=3, tenant="rt")
    eng.step()  # already past the (zero) first-token budget: never admits
    assert req.failed == "deadline"
    assert eng.deadline_misses == 1 and eng.failed == [req]
    assert eng.num_live == 0 and not eng.waiting


def test_total_deadline_cancels_live_slot_and_returns_pages(chaos_aot):
    sched = SloScheduler([TenantPolicy("rt", total_deadline_s=0.004)])
    eng = _chaos_engine(chaos_aot, scheduler=sched)
    req = eng.submit([1, 2, 3, 4], max_new_tokens=16, tenant="rt")
    for _ in range(16):
        if req.failed is not None:
            break
        eng.step()
    assert req.failed == "deadline"
    assert len(req.tokens) >= 1  # it WAS streaming when the budget blew
    assert eng.deadline_misses == 1
    assert eng.num_live == 0 and eng.allocator.pages_in_use == 0


def test_retry_backoff_schedule_and_admission_hold():
    sched = SloScheduler(max_retries=2, retry_backoff_s=0.5)
    assert sched.retry_delay_s(0) == 0.0
    assert sched.retry_delay_s(1) == 0.5
    assert sched.retry_delay_s(2) == 1.0
    assert sched.retry_delay_s(3) == 2.0
    # A backing-off victim holds its queue place but is not admitted.
    r = _req(0)
    r.not_before_s = 5.0
    plan = sched.plan(now=1.0, waiting=[r], live=[], free_slots=2,
                      free_pages=100, page_size=4)
    assert plan.empty
    plan = sched.plan(now=6.0, waiting=[r], live=[], free_slots=2,
                      free_pages=100, page_size=4)
    assert [q.uid for q in plan.admit] == [0]


def test_preemption_retry_budget_exhaustion_fails_request(chaos_aot):
    sched = SloScheduler([TenantPolicy("bg", max_pages=8)], max_retries=0)
    eng = _chaos_engine(chaos_aot, scheduler=sched)
    bg0 = eng.submit([1, 2, 3, 4], max_new_tokens=12, tenant="bg")
    bg1 = eng.submit([5, 6, 7, 8], max_new_tokens=12, tenant="bg")
    eng.step()  # both bg requests admitted: engine full
    assert eng.num_live == 2
    # The bg tenant's budget collapses; a starved rt arrival evicts the
    # newest bg slot, and with max_retries=0 the victim is not re-queued —
    # it fails loudly instead of thrashing admission forever.
    sched.policies["bg"] = TenantPolicy("bg", max_pages=0)
    eng.submit([9, 10, 11, 12], max_new_tokens=3, tenant="rt")
    for _ in range(6):
        if bg0.failed or bg1.failed:
            break
        eng.step()
    assert [bg0.failed, bg1.failed].count("retries_exhausted") == 1
    assert eng.retries == 1


def test_brownout_plan_shed_orders_most_overdue_first_and_caps():
    from distributeddeeplearning_tpu.serve.scheduler import (
        BrownoutController)
    sched = SloScheduler([TenantPolicy("rt", ttft_slo_s=0.1)])
    ctrl = BrownoutController(queue_pressure=3, max_shed_per_step=2)
    waiting = [_req(0, "rt", arrival=0.9), _req(1, "rt", arrival=0.2),
               _req(2, "rt", arrival=0.5)]
    # Everything is overdue, but with no pressure NOTHING is shed.
    assert ctrl.plan_shed(now=2.0, waiting=waiting[:2], scheduler=sched,
                          free_pages=10, num_pages=10) == []
    # Pressured: most-overdue first, capped at max_shed_per_step.
    shed = ctrl.plan_shed(now=2.0, waiting=waiting, scheduler=sched,
                          free_pages=10, num_pages=10)
    assert [r.uid for r in shed] == [1, 2]
    # Page pressure alone also arms it; positive slack is never shed.
    ctrl2 = BrownoutController(page_pressure=0.5, queue_pressure=99,
                               shed_slack_s=0.0)
    fresh = _req(3, "rt", arrival=1.99)
    shed = ctrl2.plan_shed(now=2.0, waiting=[waiting[1], fresh],
                           scheduler=sched, free_pages=4, num_pages=10)
    assert [r.uid for r in shed] == [1]


def test_engine_brownout_sheds_on_queue_pressure(chaos_aot):
    from distributeddeeplearning_tpu.serve.scheduler import (
        BrownoutController)
    sched = SloScheduler([TenantPolicy("rt", ttft_slo_s=0.0)])
    eng = _chaos_engine(chaos_aot, scheduler=sched,
                        brownout=BrownoutController(queue_pressure=2,
                                                    max_shed_per_step=2))
    a = eng.submit([1, 2, 3, 4], max_new_tokens=3, tenant="rt")
    b = eng.submit([5, 6, 7, 8], max_new_tokens=3, tenant="rt")
    eng.step()  # depth 2 >= queue_pressure, both already past their SLO
    assert a.failed == "shed" and b.failed == "shed"
    assert eng.sheds == 2 and eng.num_live == 0


def test_anomaly_update_serve_kinds():
    from distributeddeeplearning_tpu.observability import anomaly
    det = anomaly.AnomalyDetector()
    # A healthy engine never trips: steady queue, zero sheds, on-time work.
    for s in range(1, 7):
        assert det.update_serve(s, queue_depth=2, sheds=0,
                                deadline_misses=0, finished=3) == []
    kinds = [a["kind"] for a in det.update_serve(
        7, queue_depth=40, sheds=3, deadline_misses=2, finished=2)]
    assert kinds == ["queue_blowup", "shed_storm", "deadline_miss_rate"]
    # Below-volume misses stay quiet (1 of 100 is not a miss-rate storm).
    assert det.update_serve(8, deadline_misses=1, finished=99) == []


# --- the serve chaos soak: SIGKILL a replica mid-stream ---------------------

@pytest.mark.chaos
def test_serve_chaos_soak_sigkill_replica_token_identical(tmp_path,
                                                          monkeypatch):
    """SIGKILL replica 0 at engine step 3 through the supervised launch
    path: its in-flight requests are re-dispatched with their received
    prefix folded, every completion is token-identical to an uninterrupted
    run, the replacement replica warm-boots from the shared AOT cache, no
    page leaks survive the drain, the flight recorder tells the whole
    story end to end — and the merged Chrome trace links each
    re-dispatched request's spans across BOTH replica processes under
    one flow id (docs/serve_tracing.md)."""
    import dataclasses
    import os

    from distributeddeeplearning_tpu import launch as launchlib
    from distributeddeeplearning_tpu.observability import flight as flightlib
    from tools import postmortem

    cfg = ServeConfig(model="gpt_tiny", vocab_size=VOCAB, max_slots=2,
                      page_size=4, num_pages=32, max_pages_per_slot=8,
                      prefill_buckets=(16,))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "aot"))
    prompts = [[(7 * i + j) % (VOCAB - 1) + 1 for j in range(4 + i % 3)]
               for i in range(4)]

    # Fault-free reference through one in-process engine. This also
    # compiles into the shared AOT cache, so both replicas (and the warm
    # restart) boot with zero retraces — the soak stays tier-1 cheap.
    ref = Engine(cfg)
    for p in prompts:
        ref.submit(p, max_new_tokens=6)
    ref.run_until_idle()
    expected = {r.uid: list(r.tokens) for r in ref.finished}
    ref.shutdown()
    assert len(expected) == 4

    requests = [{"uid": i, "prompt": prompts[i], "max_new_tokens": 6}
                for i in range(4)]
    flight_dir = str(tmp_path / "flight")
    try:
        out = launchlib.run_serve(
            2, requests, dataclasses.asdict(cfg),
            workdir=str(tmp_path / "serve"),
            heartbeat_dir=str(tmp_path / "hb"),
            max_restarts=1, child_fault_plans={0: "sigkill@3"},
            flight_dir=flight_dir, timeout_s=150.0,
            trace_dir=str(tmp_path / "trace"))
    finally:
        # run_serve exports the flight env for its children; scrub it so
        # later tests see a pristine recorder.
        flightlib.reset()
        os.environ.pop(flightlib.ENV_FLIGHT_DIR, None)
        os.environ.pop(flightlib.ENV_RUN_ID, None)

    # Token identity across the kill: every stream equals the fault-free
    # reference, including the re-dispatched victims.
    for uid, exp in expected.items():
        res = out["results"][uid]
        assert res["finished"] and res["failed"] is None
        assert res["tokens"] == exp, f"request {uid} diverged after replay"
    assert out["restarts"] == 1
    assert out["redispatched"] >= 1
    assert any(out["results"][u]["retries"] for u in expected)
    assert out["leak_check_ok"] is True
    assert out["replica_rcs"] == {0: 0, 1: 0}

    # The incident chain reads end-to-end: lost -> re-dispatched ->
    # token-identical replay -> warm restart -> clean drain.
    chain = " | ".join(postmortem.build_report(flight_dir)["incident"])
    assert "serve replica 0 lost" in chain
    assert "re-dispatched to survivors" in chain
    assert "replayed token-identically" in chain
    assert "restarted warm" in chain
    assert "drained with leak check ok" in chain

    # The kill-replica acceptance pin for the tracing layer: the merged
    # Chrome trace must link a re-dispatched request's spans across both
    # replica processes — one flow id, two pids — and every emitted
    # serve span name must come from the registered schema.
    from distributeddeeplearning_tpu.observability import telemetry
    from distributeddeeplearning_tpu.serve import tracing

    assert out["merged_trace"] and os.path.exists(out["merged_trace"])
    events = telemetry.load_events(out["merged_trace"])
    emitted = {e["name"] for e in events
               if str(e.get("name", "")).startswith("serve:")}
    assert emitted <= set(tracing.REGISTERED_PHASES)
    assert "serve:replica_lost" in emitted  # the supervisor's own track
    flow_pids: dict = {}
    for e in events:
        if e.get("ph") in ("s", "t", "f") and e.get("cat") == "serve":
            flow_pids.setdefault(e["id"], set()).add(e["pid"])
    cross = {fid for fid, pids in flow_pids.items() if len(pids) > 1}
    assert cross, "no flow chain spans both replica pids after the kill"
    # The cross-process flows ARE the re-dispatched victims: each also
    # left a final attribution instant on its second replica.
    att_ids = {e["args"]["trace"] for e in events
               if e.get("name") == "serve:attribution"}
    assert cross <= att_ids


@pytest.mark.slow
@pytest.mark.chaos
def test_bench_serve_chaos_arm_record(tmp_path, monkeypatch, capsys):
    from distributeddeeplearning_tpu.observability import perf_report
    from distributeddeeplearning_tpu.observability import sidecars
    from tools import bench_serve

    monkeypatch.setattr(sidecars, "write",
                        lambda name, payload: str(tmp_path / "s.json"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "aot"))
    rc = bench_serve.main([
        "--chaos", "--platform", "cpu", "--model", "gpt_tiny", "--vocab-size", str(VOCAB),
        "--requests", "4", "--rate", "1000", "--max-new", "6",
        "--prompt-lens", "4,6", "--max-slots", "2", "--page-size", "4",
        "--num-pages", "32", "--max-pages-per-slot", "8",
        "--prefill-buckets", "16"])
    assert rc == 0
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert perf_report.validate(rec) == []
    ch = rec["chaos"]
    assert ch["token_identity_checked"] is True
    assert ch["leak_check_ok"] is True
    assert ch["restarts"] >= 1 and ch["redispatched"] >= 1
    assert ch["tokens_per_sec_per_chip"] > 0
    assert isinstance(ch["recovery_overhead_frac"], float)
