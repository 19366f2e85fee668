"""Paged-KV prefix cache: refcounted copy-on-write page sharing
(serve/prefix_cache.py + the allocator/admission changes in serve/llm.py).

Exactness first: a warm admission — prefill skipped up to the first cold
token, shared pages bound read-only, divergence tail COW-copied — must
emit token streams byte-identical to the cache-off engine (itself pinned
byte-identical to dense by tests/test_chunked_prefill.py), for both
attention implementations, under concurrent sharing, multi-turn reuse,
preempt-by-recompute pressure, and drain/migration. Then the accounting
contracts: every pool page is exactly one of free/live/cached with
refcounts owned by slot tables + cache entries (closure: free + distinct
allocated == total), pressure evicts zero-active LRU entries BEFORE any
live decode is preempted, and donation respects the page budget.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import gpt
from ray_tpu.serve.llm import LLMEngine
from ray_tpu.serve.prefix_cache import PrefixCache, chunk_hashes

CFG = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return gpt.init_params(CFG, jax.random.key(42))


def _drive(eng, reqs, max_steps=2000):
    for _ in range(max_steps):
        if all(r.done.is_set() for r in reqs):
            break
        eng.step()
    assert all(r.done.is_set() for r in reqs)
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    return [r.out_ids for r in reqs]


def _engine(params, **kw):
    base = dict(n_slots=4, max_len=128, page_size=16,
                prefill_chunk=16, prefill_token_budget=32)
    base.update(kw)
    return LLMEngine(CFG, params, **base)


def _prompts_with_shared_prefix(seed, shared_len, suffixes):
    rng = np.random.default_rng(seed)
    shared = list(map(int, rng.integers(1, CFG.vocab_size, shared_len)))
    return [shared + list(map(int, rng.integers(1, CFG.vocab_size, n)))
            for n in suffixes]


def _closure(eng):
    acc = eng.page_accounting()
    assert acc["closure"], acc
    assert acc["refs_consistent"], acc
    return acc


class TestExactness:
    """Warm == cold, token for token."""

    @pytest.mark.parametrize("attn_impl", ["gather", "kernel"])
    def test_warm_equals_cold_byte_identical(self, params, attn_impl):
        """Sequential requests sharing a prefix: the first populates the
        cache (insert-on-free), the rest admit warm — and every stream
        matches the cache-off engine exactly, on BOTH attention paths
        (the kernel reads shared pages through the same page table)."""
        prompts = _prompts_with_shared_prefix(0, 48, (5, 9, 13, 7, 11))
        cold_eng = _engine(params, attn_impl=attn_impl)
        cold = [_drive(cold_eng, [cold_eng.submit(p, max_tokens=6)])[0]
                for p in prompts]
        eng = _engine(params, attn_impl=attn_impl, prefix_cache=True)
        warm = [_drive(eng, [eng.submit(p, max_tokens=6)])[0]
                for p in prompts]
        assert warm == cold
        m = eng.metrics()
        assert m["prefix_hits"] >= len(prompts) - 1
        # The hits really skipped prefill work: warm prefilled fewer
        # tokens than cache-off for the identical workload.
        assert (m["prefill_tokens"] + m["prefix_cached_tokens"]
                >= cold_eng.metrics()["prefill_tokens"])
        assert m["prefill_tokens"] < cold_eng.metrics()["prefill_tokens"]
        _closure(eng)

    def test_cow_divergence_exact(self, params):
        """Chunk NOT page-aligned (chunk 12, page 8): every warm bind
        lands mid-page, forcing a copy-on-write of the tail page that
        the cold suffix then overwrites from its divergence point.
        Streams stay byte-identical to cache-off."""
        prompts = _prompts_with_shared_prefix(3, 36, (5, 9, 13, 7))
        cold_eng = _engine(params, page_size=8, prefill_chunk=12,
                           prefill_token_budget=24)
        cold = [_drive(cold_eng, [cold_eng.submit(p, max_tokens=8)])[0]
                for p in prompts]
        eng = _engine(params, page_size=8, prefill_chunk=12,
                      prefill_token_budget=24, prefix_cache=True)
        warm = [_drive(eng, [eng.submit(p, max_tokens=8)])[0]
                for p in prompts]
        assert warm == cold
        m = eng.metrics()
        assert m["cow_copies"] >= 3
        assert m["prefix_hits"] >= 3
        _closure(eng)

    def test_concurrent_sharing_exact(self, params):
        """Several live slots bound to the SAME cached pages at once
        (the refcount > 1 case), driven tick-by-tick with the closure
        checked mid-flight while pages are genuinely shared."""
        prompts = _prompts_with_shared_prefix(5, 48, (5, 9, 13, 7))
        cold_eng = _engine(params)
        cold = [_drive(cold_eng, [cold_eng.submit(p, max_tokens=8)])[0]
                for p in prompts]
        eng = _engine(params, prefix_cache=True)
        # Populate via the first request, then run the rest CONCURRENTLY.
        first = _drive(eng, [eng.submit(prompts[0], max_tokens=8)])[0]
        reqs = [eng.submit(p, max_tokens=8) for p in prompts[1:]]
        saw_shared = False
        for _ in range(2000):
            if all(r.done.is_set() for r in reqs):
                break
            eng.step()
            acc = _closure(eng)
            saw_shared = saw_shared or acc["shared"] > 0
        outs = [first] + [r.out_ids for r in reqs]
        assert all(r.error is None for r in reqs)
        assert outs == cold
        assert saw_shared, "pages were never actually shared mid-flight"
        assert eng.metrics()["prefix_hits"] >= 3
        _closure(eng)

    def test_multiturn_reuse_covers_generated_tokens(self, params):
        """Donation indexes the full written sequence — prompt AND
        generated tokens — so turn 2 of a chat (context = turn-1 prompt
        + response + new message) admits warm PAST the original prompt."""
        rng = np.random.default_rng(7)
        p1 = list(map(int, rng.integers(1, CFG.vocab_size, 33)))
        followup = list(map(int, rng.integers(1, CFG.vocab_size, 9)))

        def conversation(eng):
            out1 = _drive(eng, [eng.submit(p1, max_tokens=8)])[0]
            ctx = p1 + [int(t) for t in out1] + followup
            req2 = eng.submit(ctx, max_tokens=8)
            out2 = _drive(eng, [req2])[0]
            return out1, out2, req2

        cold = conversation(_engine(params, prefill_chunk=8,
                                    prefill_token_budget=16))
        eng = _engine(params, prefill_chunk=8, prefill_token_budget=16,
                      prefix_cache=True)
        out1, out2, req2 = conversation(eng)
        assert (out1, out2) == (cold[0], cold[1])
        # The turn-2 hit reaches beyond the turn-1 prompt into tokens the
        # engine itself decoded (written = prompt + out[:-1], chunk 8).
        assert req2.cached_tokens > len(p1)
        _closure(eng)


class TestLifecycle:
    """Refcounts, eviction under pressure, preempt, drain/migration."""

    def test_eviction_before_preemption_under_pressure(self, params):
        """Pool sized so cached pages MUST be reclaimed for new work:
        the pressure valve evicts zero-active LRU entries and the
        workload completes with ZERO preemptions — cached pages always
        go before live-decode recompute."""
        rng = np.random.default_rng(11)
        prompts = [list(map(int, rng.integers(1, CFG.vocab_size, 48)))
                   for _ in range(4)]        # distinct: each donation
        eng = _engine(params, n_slots=2, max_len=64, page_size=8,
                      n_pages=14, prefill_chunk=8, prefill_token_budget=16,
                      prefix_cache=True, prefix_cache_pages=12)
        for p in prompts:
            _drive(eng, [eng.submit(p, max_tokens=4)])
            _closure(eng)
        m = eng.metrics()
        assert m["prefix_evictions"] > 0
        assert m["preemptions"] == 0
        # Budget respected throughout.
        assert eng.prefix_cache.n_pages_cached() <= 12
        _closure(eng)

    def test_preempt_with_shared_pages_exact(self, params):
        """Warm slots under preempt-by-recompute pool pressure: the
        preempted request re-enters the queue, may re-admit warm or
        cold, and the streams still match the cache-off engine."""
        prompts = _prompts_with_shared_prefix(13, 16, (3, 2, 5, 4))
        cold_eng = _engine(params, n_slots=4, max_len=64, page_size=4,
                           n_pages=9, prefill_chunk=4,
                           prefill_token_budget=8)
        cold = [_drive(cold_eng, [cold_eng.submit(p, max_tokens=10)])[0]
                for p in prompts]
        eng = _engine(params, n_slots=4, max_len=64, page_size=4,
                      n_pages=9, prefill_chunk=4, prefill_token_budget=8,
                      prefix_cache=True, prefix_cache_pages=4)
        _drive(eng, [eng.submit(prompts[0], max_tokens=10)])
        reqs = [eng.submit(p, max_tokens=10) for p in prompts[1:]]
        for _ in range(4000):
            if all(r.done.is_set() for r in reqs):
                break
            eng.step()
            _closure(eng)
        outs = [cold[0]] + [r.out_ids for r in reqs]
        assert all(r.done.is_set() and r.error is None for r in reqs)
        assert outs == cold
        assert eng.metrics()["preemptions"] > 0
        _closure(eng)

    def test_drain_migration_re_resolves_on_destination(self, params):
        """PR 9 drain export composes with the cache: a continuation
        migrated off a draining replica re-resolves against the
        DESTINATION replica's cache (context = prompt + generated, which
        the destination's own completed run donated) and the spliced
        stream is byte-identical to an uninterrupted run."""
        prompts = _prompts_with_shared_prefix(17, 48, (5, 9))
        # Uninterrupted reference (cache-off).
        ref_eng = _engine(params)
        ref = [_drive(ref_eng, [ref_eng.submit(p, max_tokens=12)])[0]
               for p in prompts]
        # Destination replica, cache primed by its own completed traffic.
        dst = _engine(params, prefix_cache=True)
        assert _drive(dst, [dst.submit(prompts[0], max_tokens=12)])[0] \
            == ref[0]
        # Source replica: drain mid-generation, requests exported.
        src = _engine(params, prefix_cache=True)
        req = src.submit(prompts[1], max_tokens=12)
        while len(req.out_ids) < 4:
            src.step()
        out = src.drain(timeout_s=0.0)
        assert out["exported"] == 1 and req.migrated
        cont = out["continuations"][0]
        acc = src.page_accounting()
        assert acc["closure"] and acc["refs_consistent"] and acc["live"] == 0
        # Resume on the destination: teacher-forced continuation admits
        # WARM (the shared 48-token prefix is cached there) and the
        # spliced stream matches the uninterrupted reference exactly.
        resumed = dst.submit(cont["prompt_ids"],
                             max_tokens=cont["max_tokens"],
                             temperature=cont["temperature"],
                             eos_id=cont["eos_id"],
                             generated_ids=cont["generated_ids"])
        _drive(dst, [resumed])
        assert resumed.out_ids == ref[1]
        assert resumed.cached_tokens > 0
        _closure(dst)

    def test_page_accounting_closure_after_kill(self, params):
        """Chaos-style kill (PR 9 protocol: export + abrupt stop) with
        warm SHARED pages live in several slots: the dying engine's
        accounting still closes (free + cached == total, zero live), and
        the continuations finish exactly elsewhere."""
        prompts = _prompts_with_shared_prefix(19, 48, (5, 9, 13))
        ref_eng = _engine(params)
        ref = [_drive(ref_eng, [ref_eng.submit(p, max_tokens=24)])[0]
               for p in prompts]
        eng = _engine(params, prefix_cache=True)
        _drive(eng, [eng.submit(prompts[0], max_tokens=24)])
        reqs = [eng.submit(p, max_tokens=24) for p in prompts[1:]]
        # A couple of ticks in, slots share cached pages mid-decode;
        # then the kill.
        for _ in range(2):
            eng.step()
        conts = eng._export_unfinished()
        acc = eng.page_accounting()
        assert acc["closure"] and acc["refs_consistent"], acc
        assert acc["live"] == 0
        assert conts, "kill landed after all requests finished"
        assert all(r.migrated for r in reqs)
        # Survivor decodes the continuations to the exact reference.
        dst = _engine(params, prefix_cache=True)
        by_id = {c["request_id"]: c for c in conts}
        for req, want in zip(reqs, ref[1:]):
            c = by_id[req.request_id]
            r = dst.submit(c["prompt_ids"], max_tokens=c["max_tokens"],
                           temperature=c["temperature"], eos_id=c["eos_id"],
                           generated_ids=c["generated_ids"])
            _drive(dst, [r])
            assert r.out_ids == want
        _closure(dst)


class TestConfigAndParity:
    def test_negative_page_budget_rejected(self, params):
        with pytest.raises(ValueError, match="prefix_cache_pages"):
            _engine(params, prefix_cache=True, prefix_cache_pages=-1)

    def test_global_knob_turns_the_cache_on(self, params, monkeypatch):
        """Every gpt engine can carry the cache (its granularity is the
        prefill chunk, and every engine has one): the GLOBAL knob turns
        it on, beside the default engine (no argument at all) too."""
        monkeypatch.setenv("RAY_TPU_LLM_PREFIX_CACHE", "1")
        eng = LLMEngine(CFG, params, max_len=64)
        assert eng.prefix_cache is not None
        assert eng.prefix_cache.chunk == eng.prefill_chunk == 64
        assert _engine(params).prefix_cache is not None

    def test_cache_off_parity(self, params):
        """Cache-off engines are byte-for-byte today's engine: same
        streams as a cache-on engine serving the same (cold) traffic,
        no prefix fields in metrics, refcounted allocator invisible."""
        prompts = _prompts_with_shared_prefix(23, 32, (5, 9))
        off = _engine(params)
        on = _engine(params, prefix_cache=True)
        got_off = _drive(off, [off.submit(p, max_tokens=6)
                               for p in prompts])
        got_on = _drive(on, [on.submit(p, max_tokens=6) for p in prompts])
        assert got_off == got_on
        m = off.metrics()
        assert "prefix_cache" not in m and "prefix_cache_pages" not in m
        assert m["prefix_hits"] == 0 and m["cow_copies"] == 0
        assert m["kv_pages_free"] == m["kv_pages_total"]
        assert "prefix_cache_pages" not in off.load_snapshot()
        snap = on.load_snapshot()
        assert snap["prefix_cache_pages"] >= 0

    def test_observability_counters_and_snapshot(self, params):
        """Satellite wiring: hits/misses/cow/evictions reach the stats
        dict AND the load snapshot the controller probes."""
        prompts = _prompts_with_shared_prefix(29, 48, (5, 9, 13))
        eng = _engine(params, prefix_cache=True)
        for p in prompts:
            _drive(eng, [eng.submit(p, max_tokens=4)])
        snap = eng.load_snapshot()
        assert snap["prefix_cache_pages"] > 0
        assert snap["prefix_cache_entries"] > 0
        assert 0 < snap["prefix_cache_hit_rate"] <= 1
        m = eng.metrics()
        assert m["prefix_cache_hit_rate"] == snap["prefix_cache_hit_rate"]
        assert m["prefix_cached_tokens"] > 0
        # Warm/cold TTFT split populated on the warm engine.
        assert "ttft_warm_ms_p50" in m and "ttft_cold_ms_p50" in m


class TestPrefixCacheUnit:
    """Pure host-side structure, fake refcounts."""

    def _cache(self, **kw):
        refs = {}

        def ref(p):
            refs[p] = refs.get(p, 0) + 1

        def unref(p):
            refs[p] -= 1

        base = dict(chunk=4, page_size=4, max_pages=64,
                    ref_page=ref, unref_page=unref)
        base.update(kw)
        return PrefixCache(**base), refs

    def test_chunk_hash_chaining(self):
        a = chunk_hashes([1, 2, 3, 4, 5, 6, 7, 8], 4)
        b = chunk_hashes([1, 2, 3, 4, 5, 6, 7, 9], 4)
        c = chunk_hashes([1, 2, 3, 4, 5, 6, 7, 8, 0], 4)
        assert len(a) == 2 and a[0] == b[0] and a[1] != b[1]
        assert c == a                       # partial tail chunk ignored
        # Parent chaining: same chunk content at depth 2 under a
        # different depth-1 parent must NOT collide.
        d = chunk_hashes([9, 9, 9, 9, 5, 6, 7, 8], 4)
        assert d[1] != a[1]

    def test_lookup_longest_and_cold_token_cap(self):
        cache, _ = self._cache()
        cache.donate(list(range(12)), [1, 2, 3, 0, 0])
        # Full 12-token chain cached, but a 12-token prompt may only be
        # served 8 (>= one cold token must remain for first-token logits).
        assert cache.match_len(list(range(12))) == 8
        assert cache.match_len(list(range(12)) + [99]) == 12
        assert cache.match_len([7] * 12) == 0
        # Chain-gap tolerance: evicting a middle entry keeps the deeper
        # self-contained entry reachable.
        hs = chunk_hashes(list(range(12)), 4)
        mid = cache.entries.pop(hs[1])
        for p in mid.pages:
            cache._page_owners[p] -= 1
            if not cache._page_owners[p]:
                del cache._page_owners[p]
            cache._unref_page(p)
        assert cache.match_len(list(range(12)) + [99]) == 12

    def test_donation_refs_and_eviction_unrefs(self):
        cache, refs = self._cache()
        cache.donate(list(range(8)), [5, 6, 0, 0])
        assert refs == {5: 2, 6: 1}         # depth-1 and depth-2 entries
        assert cache.n_pages_cached() == 2
        pinned = cache.acquire(list(range(8)) + [42])
        assert pinned is not None and pinned.active == 1
        # Zero-active-only eviction: the pinned (deeper, newer) entry
        # survives; the shallow one goes.
        v = cache.evict_one()
        assert v is not None and v.n_tokens == 4
        assert cache.evict_one() is None    # nothing evictable left
        cache.release(pinned)
        assert cache.evict_one() is pinned
        assert refs == {5: 0, 6: 0}
        assert cache.n_pages_cached() == 0

    def test_budget_bounds_donation(self):
        cache, refs = self._cache(max_pages=2)
        cache.donate(list(range(16)), [3, 4, 5, 6, 0])
        # Only depths fitting 2 distinct pages were admitted.
        assert cache.n_pages_cached() <= 2
        assert max((e.n_tokens for e in cache.entries.values()),
                   default=0) <= 8
        # A newer donation LRU-evicts the old zero-active entries to fit.
        cache.donate(list(range(100, 108)), [9, 10, 0])
        assert cache.n_pages_cached() <= 2
        assert cache.match_len(list(range(100, 108)) + [1]) == 8
        assert all(v >= 0 for v in refs.values())

    def test_lru_order(self):
        cache, _ = self._cache()
        cache.donate([1] * 4, [11, 0])
        cache.donate([2] * 4, [12, 0])
        cache.acquire([1] * 4 + [9])        # touch the older entry
        cache.release(cache.entries[chunk_hashes([1] * 4, 4)[0]])
        v = cache.evict_one()
        assert v.pages == (12,)             # untouched entry went first
