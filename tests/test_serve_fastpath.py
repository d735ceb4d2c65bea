"""Serve fast path: COW radix prefix cache + speculative decoding.

The load-bearing pin is unchanged from test_serve.py — TOKEN IDENTITY
against sequential ``generate(use_cache=True)`` — but now with the two
fast-path features on: shared prefix pages mapped by refcount instead of
re-prefilled (partial trailing page copy-on-write), and a shrunk
same-family drafter proposing k tokens per target verify. Either feature
wrong changes tokens; both right, they only change *speed*. Around the
pin: allocator refcount units (share / double-decref / write-to-shared /
multiset leak check), radix-tree units (match / insert / LRU evict /
evictable accounting), eviction under pool pressure, the AOT warm boot
of every fast-path program, the spec-acceptance anomaly kind, and the
replica-SIGKILL chaos soak with both features on.
"""

import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from distributeddeeplearning_tpu.models import generate as genlib
from distributeddeeplearning_tpu.serve import kv_cache
from distributeddeeplearning_tpu.serve.engine import (Engine, ServeConfig,
                                                      serve_fingerprint)
from distributeddeeplearning_tpu.serve.scheduler import (SloScheduler,
                                                         TenantPolicy)

pytestmark = pytest.mark.serve

VOCAB = 97


def _engine(model="gpt_tiny", **kw):
    kw.setdefault("vocab_size", VOCAB)
    kw.setdefault("max_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 32)
    kw.setdefault("max_pages_per_slot", 8)
    kw.setdefault("prefill_buckets", (16,))
    kw.setdefault("compile_cache", False)
    t = [0.0]

    def clock():
        t[0] += 0.001
        return t[0]

    return Engine(ServeConfig(model=model, **kw), clock=clock)


def _reference_tokens(eng, prompt, max_new):
    out = genlib.generate(eng.model, {**eng._fresh},
                          jnp.asarray([prompt], jnp.int32),
                          max_new_tokens=max_new, use_cache=True)
    return [int(x) for x in np.asarray(out)[0, len(prompt):]]


def _shared_prefix_prompts(rng, head_len=9, tails=(5, 5, 5)):
    """One shared head + distinct tails: the serving-traffic shape the
    radix cache exists for. head_len=9 with page_size=4 leaves a partial
    trailing chunk, so admission exercises the COW path too. Tails are
    equal-length (distinct content) so the reference generate() compiles
    one prompt shape, not one per request."""
    head = [int(x) for x in rng.integers(1, VOCAB, head_len)]
    return [head + [int(x) for x in rng.integers(1, VOCAB, t)]
            for t in tails]


# --- allocator refcount units -----------------------------------------------

def test_allocator_share_refuses_writes_and_double_decref():
    alloc = kv_cache.PageAllocator(4)
    (p,) = alloc.alloc(1)
    alloc.assert_writable([p])  # exclusive: in-place writes legal
    alloc.incref([p])           # second holder (tree node / shared slot)
    assert alloc.refcount(p) == 2
    with pytest.raises(RuntimeError, match="shared page"):
        alloc.assert_writable([p])
    # First decref drops to 1 (still held), second frees, third raises.
    alloc.decref([p])
    assert alloc.refcount(p) == 1 and alloc.free_pages == 3
    alloc.assert_writable([p])  # back to exclusive
    alloc.decref([p])
    assert alloc.free_pages == 4
    with pytest.raises(ValueError, match="double-decref"):
        alloc.decref([p])
    # Sharing can only extend a LIVE allocation.
    with pytest.raises(ValueError, match="not currently allocated"):
        alloc.incref([p])


def test_allocator_check_leaks_is_multiset_aware():
    """A page shared by a slot AND the tree must appear once per claim in
    the owned multiset — shared-but-live balances, a dropped claim or an
    unshared double-owner still fails loudly (exact message prefixes are
    load-bearing: the chaos sweep tests match on them)."""
    alloc = kv_cache.PageAllocator(4)
    (a, b) = alloc.alloc(2)
    alloc.incref([a])  # a: slot + tree
    alloc.check_leaks([a, a, b])        # balanced multiset
    with pytest.raises(RuntimeError, match="KV page leak"):
        alloc.check_leaks([a, b])       # one of a's two claims dropped
    with pytest.raises(RuntimeError, match="page-table corruption"):
        alloc.check_leaks([a, a, b, b])  # b double-owned without a share
    alloc.decref([a])
    alloc.check_leaks([a, b])


# --- radix tree units -------------------------------------------------------

def test_radix_match_insert_full_pages_only():
    alloc = kv_cache.PageAllocator(8)
    tree = kv_cache.RadixPrefixCache(alloc, page_size=4)
    ids = list(range(1, 11))       # 10 tokens: 2 full pages + partial
    pages = alloc.alloc(3)
    assert tree.insert(ids, pages) == 2     # the partial chunk never enters
    assert [alloc.refcount(p) for p in pages] == [2, 2, 1]
    matched, shared = tree.match(ids)
    assert matched == 8 and shared == pages[:2]
    # Diverging token in the second chunk: only the first page matches.
    fork = ids[:5] + [77] + ids[6:]
    matched, shared = tree.match(fork)
    assert matched == 4 and shared == [pages[0]]
    assert tree.match([50, 51]) == (0, [])
    # Re-inserting the same prompt creates nothing and bumps no refcount.
    assert tree.insert(ids, pages) == 0
    assert [alloc.refcount(p) for p in pages] == [2, 2, 1]


def test_radix_evict_lru_and_refcount_pinning():
    alloc = kv_cache.PageAllocator(8)
    tree = kv_cache.RadixPrefixCache(alloc, page_size=2)
    old = alloc.alloc(1)
    new = alloc.alloc(1)
    tree.insert([1, 2], old)
    tree.insert([3, 4], new)
    tree.match([3, 4])            # refresh: [3,4] is now most-recent
    alloc.decref(old + new)       # tree holds the only claims
    assert tree.evictable_pages() == 2
    assert tree.evict(1) == 1     # LRU order: [1,2] goes first
    assert tree.evictions == 1
    assert tree.match([1, 2]) == (0, [])
    assert tree.match([3, 4])[0] == 2
    # A page a live slot still maps is pinned: eviction comes up short.
    alloc.incref([tree.match([3, 4])[1][0]])
    assert tree.evictable_pages() == 0
    assert tree.evict(1) == 0
    assert tree.num_nodes() == 1


def test_radix_evict_cascades_into_parents():
    alloc = kv_cache.PageAllocator(8)
    tree = kv_cache.RadixPrefixCache(alloc, page_size=2)
    pages = alloc.alloc(2)
    tree.insert([1, 2, 3, 4], pages)   # chain: [1,2] -> [3,4]
    alloc.decref(pages)
    # The parent only becomes a leaf once its child is gone; evict(2)
    # must free both in one call.
    assert tree.evict(2) == 2
    assert tree.num_nodes() == 0 and alloc.free_pages == 8


# --- token identity: prefix cache -------------------------------------------

@pytest.mark.parametrize("model", ["gpt_tiny", "llama_tiny"])
def test_prefix_cache_token_identity_and_reuse(model):
    """Shared-head requests through a prefix-cache engine: every stream
    must equal its solo sequential run, later admissions must HIT (shared
    pages mapped, only the tail prefilled), and the partial trailing page
    must be COW'd — identity plus the counters that prove the fast path
    actually engaged."""
    eng = _engine(model, prefix_cache=True)
    rng = np.random.default_rng(3)
    prompts = _shared_prefix_prompts(rng)
    # Sequential submission so request 0 populates the tree first.
    reqs = []
    for p in prompts:
        r = eng.submit(p, max_new_tokens=5)
        reqs.append(r)
        eng.run_until_idle()
    for r in reqs:
        assert r.tokens == _reference_tokens(eng, r.prompt, 5), r.uid
    assert eng.prefix_hits == 2 and eng.prefix_misses == 1
    assert eng.prefix_tokens_reused == 16  # 2 hits x 2 full head pages
    assert eng.cow_copies == 0  # head is 9 tokens: matched 8 is page-aligned
    eng.shutdown()  # leak gate with tree pages still live


def test_prefix_cache_cow_on_partial_trailing_page():
    """A fully-cached page-aligned prompt re-submitted: the engine may
    reuse at most plen-1 tokens (the last position must re-run to emit
    the first token), which lands mid-page — that page MUST be cloned,
    not written in place, and tokens must not change."""
    eng = _engine("gpt_tiny", prefix_cache=True)
    prompt = list(range(1, 9))  # 8 tokens: exactly 2 full pages
    a = eng.submit(prompt, max_new_tokens=5)
    eng.run_until_idle()
    b = eng.submit(prompt, max_new_tokens=5)
    eng.run_until_idle()
    assert eng.cow_copies == 1 and eng.prefix_hits == 1
    ref = _reference_tokens(eng, prompt, 5)
    assert a.tokens == ref and b.tokens == ref
    eng.shutdown()


def test_prefix_cache_eviction_under_pool_pressure():
    """A pool too small to hold every retired prefix: admission must
    evict LRU tree pages instead of failing, tokens stay identical, and
    the drain leak-check passes with shared pages still in the tree."""
    eng = _engine("gpt_tiny", max_slots=1, num_pages=4,
                  prefix_cache=True, prefill_buckets=(8,))
    rng = np.random.default_rng(5)
    reqs = []
    for i in range(4):
        p = [int(x) for x in rng.integers(1, VOCAB, 6)]
        r = eng.submit(p, max_new_tokens=4)
        reqs.append((r, p))
        eng.run_until_idle()
    assert eng.prefix.evictions > 0
    for r, p in reqs:
        assert r.tokens == _reference_tokens(eng, p, 4)
    assert eng.prefix.num_nodes() > 0  # shared pages live at drain...
    eng.shutdown()                     # ...and the multiset check passes


# --- token identity: speculative decoding -----------------------------------

@pytest.mark.parametrize("model,draft", [("gpt_tiny", "gpt_nano")])
def test_spec_decode_token_identity_nano_drafter(model, draft):
    """Drafter proposals verified by the target: output must be bitwise
    the target's greedy stream no matter what the drafter proposes.
    (llama+nano spec identity is pinned by the preemption test below,
    which runs both features for both families.)"""
    eng = _engine(model, spec_draft_model=draft, spec_k=3)
    rng = np.random.default_rng(7)
    reqs = [eng.submit([int(x) for x in rng.integers(1, VOCAB, n)],
                       max_new_tokens=7) for n in (8, 8)]
    eng.run_until_idle()
    for r in reqs:
        assert r.tokens == _reference_tokens(eng, r.prompt, 7), r.uid
    assert eng.spec_rounds > 0 and eng.spec_proposed > 0
    assert 0 <= eng.spec_accepted <= eng.spec_proposed
    eng.shutdown()


def test_spec_decode_self_draft_accepts_everything():
    """Drafter == target (same seed, bitwise-equal params): every
    proposal matches the target's argmax, acceptance is exactly 1.0 —
    the upper bound that pins the accept/emit bookkeeping."""
    eng = _engine("gpt_tiny", spec_draft_model="gpt_tiny", spec_k=4)
    r = eng.submit(list(range(1, 7)), max_new_tokens=8)
    eng.run_until_idle()
    assert r.tokens == _reference_tokens(eng, r.prompt, 8)
    assert eng.spec_proposed > 0
    assert eng.spec_accepted == eng.spec_proposed
    eng.shutdown()


# --- both features + preemption ---------------------------------------------

@pytest.mark.parametrize("model,draft", [("gpt_tiny", "gpt_nano"),
                                         ("llama_tiny", "llama_nano")])
def test_fast_path_preemption_resumes_token_identical(model, draft):
    """Prefix cache AND spec decoding on, a victim preempted mid-decode:
    the resume (prefix folded, drafter re-prefilled, shared pages
    re-mapped) must finish with exactly the uninterrupted tokens."""
    # num_pages=8: rt's 5-page ask cannot fit beside bg's 4 pages, so the
    # budget-tightened bg slot must actually be preempted (the same
    # geometry as test_serve.py's prefix-off preemption pin).
    eng = _engine(model, num_pages=8, prefix_cache=True,
                  spec_draft_model=draft, spec_k=3)
    rng = np.random.default_rng(11)
    bg_prompt = [int(x) for x in rng.integers(1, VOCAB, 4)]
    bg = eng.submit(bg_prompt, max_new_tokens=12, tenant="bg")
    eng.step()
    eng.step()
    assert eng.num_live == 1 and len(bg.tokens) >= 1

    eng.scheduler.policies["bg"] = TenantPolicy("bg", max_pages=3)
    rt_prompt = [int(x) for x in rng.integers(1, VOCAB, 8)]
    rt = eng.submit(rt_prompt, max_new_tokens=12, tenant="rt")
    for _ in range(8):
        if eng.preemptions:
            break
        eng.step()
    assert eng.preemptions == 1 and bg.preemptions == 1

    del eng.scheduler.policies["bg"]
    eng.run_until_idle()
    assert rt.tokens == _reference_tokens(eng, rt_prompt, 12)
    assert bg.tokens == _reference_tokens(eng, bg_prompt, 12)
    eng.shutdown()


# --- AOT warm boot of the fast-path programs --------------------------------

def test_fast_path_aot_warm_boot_zero_retrace(tmp_path, monkeypatch):
    """Both features on: the block-prefill, page-clone, draft, and verify
    programs all ride the serve fingerprint — a second engine must
    deserialize every one (zero retraces) and decode identically."""
    kw = dict(max_slots=2, page_size=4, num_pages=16, max_pages_per_slot=4,
              prefill_buckets=(8,), prefix_cache=True,
              spec_draft_model="gpt_nano", spec_k=3, compile_cache=True)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    cold = _engine("gpt_tiny", **kw)
    stats = cold.warmup()
    assert stats["aot_misses"] == stats["aot_saves"] > 2  # > base engine
    prompt = list(range(1, 7))
    cold_req = cold.submit(prompt, max_new_tokens=5)
    cold.run_until_idle()

    warm = _engine("gpt_tiny", **kw)
    wstats = warm.warmup()
    assert wstats["aot_misses"] == 0
    assert wstats["aot_hits"] == stats["aot_misses"]
    warm_req = warm.submit(prompt, max_new_tokens=5)
    warm.run_until_idle()
    assert warm_req.tokens == cold_req.tokens


def test_fast_path_fields_extend_serve_fingerprint():
    base = ServeConfig()
    assert serve_fingerprint(base) != serve_fingerprint(
        dataclasses.replace(base, prefix_cache=True))
    assert serve_fingerprint(base) != serve_fingerprint(
        dataclasses.replace(base, spec_draft_model="gpt_nano", spec_k=3))


# --- spec-acceptance anomaly kind -------------------------------------------

def test_anomaly_spec_acceptance_collapse_fires_and_stays_quiet():
    from distributeddeeplearning_tpu.observability import anomaly
    det = anomaly.AnomalyDetector()
    # Healthy soak at ~80% acceptance: never fires.
    for s in range(1, 13):
        assert det.update_serve(s, spec_proposed=16, spec_accepted=13) == []
    # Below-volume interval stays quiet (one unlucky round is not drift).
    assert det.update_serve(13, spec_proposed=2, spec_accepted=0) == []
    out = det.update_serve(14, spec_proposed=16, spec_accepted=1)
    assert [a["kind"] for a in out] == ["spec_acceptance_collapse"]
    # A drafter that was never any good is a config problem, not an
    # anomaly: median below the floor keeps the kind silent forever.
    det2 = anomaly.AnomalyDetector()
    for s in range(1, 13):
        assert det2.update_serve(s, spec_proposed=16, spec_accepted=1) == []
    assert det2.update_serve(13, spec_proposed=16, spec_accepted=0) == []


# --- chaos soak: replica SIGKILL with both features on ----------------------

@pytest.mark.chaos
@pytest.mark.slow
def test_fast_path_chaos_soak_sigkill_token_identical(tmp_path,
                                                      monkeypatch):
    """SIGKILL a replica mid-stream with prefix cache + spec decoding on:
    re-dispatched victims must replay token-identically (the survivor's
    radix tree and drafter state are its own — correctness can't depend
    on the dead replica's cache), and every replica's drain leak-check
    must pass with shared tree pages live.

    Marked slow: ~20s of process-boot + compile on the 1-vCPU box, and
    tier-1's budget is already carried by test_serve.py's fast chaos
    soak (same supervised SIGKILL path, fast-path features off)."""
    import os

    from distributeddeeplearning_tpu import launch as launchlib
    from distributeddeeplearning_tpu.observability import flight as flightlib

    cfg = ServeConfig(model="gpt_tiny", vocab_size=VOCAB, max_slots=2,
                      page_size=4, num_pages=32, max_pages_per_slot=8,
                      prefill_buckets=(16,), prefix_cache=True,
                      spec_draft_model="gpt_nano", spec_k=3)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "aot"))
    head = [(3 * j) % (VOCAB - 1) + 1 for j in range(6)]
    prompts = [head + [(7 * i + j) % (VOCAB - 1) + 1
                       for j in range(2 + i % 3)] for i in range(4)]

    ref = Engine(cfg)
    for p in prompts:
        ref.submit(p, max_new_tokens=6)
    ref.run_until_idle()
    expected = {r.uid: list(r.tokens) for r in ref.finished}
    ref.shutdown()

    requests = [{"uid": i, "prompt": prompts[i], "max_new_tokens": 6}
                for i in range(4)]
    try:
        out = launchlib.run_serve(
            2, requests, dataclasses.asdict(cfg),
            workdir=str(tmp_path / "serve"),
            heartbeat_dir=str(tmp_path / "hb"),
            max_restarts=1, child_fault_plans={0: "sigkill@3"},
            flight_dir=str(tmp_path / "flight"), timeout_s=150.0)
    finally:
        flightlib.reset()
        os.environ.pop(flightlib.ENV_FLIGHT_DIR, None)
        os.environ.pop(flightlib.ENV_RUN_ID, None)

    for uid, exp in expected.items():
        res = out["results"][uid]
        assert res["finished"] and res["failed"] is None
        assert res["tokens"] == exp, f"request {uid} diverged after replay"
    assert out["restarts"] == 1 and out["redispatched"] >= 1
    assert out["leak_check_ok"] is True
    assert out["replica_rcs"] == {0: 0, 1: 0}
