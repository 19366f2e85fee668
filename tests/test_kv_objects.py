"""KV page-set objects: disaggregated prefill/decode pools with
adoption-based failover (serve/kv_objects.py + the engine
donation/adoption ladder in serve/llm.py).

Exactness first: every rung of the adoption ladder — full adopt,
partial-adopt + cold-suffix prefill, and the teacher-forced re-prefill
fallback — must emit token streams byte-identical to an uninterrupted
cold engine, including when the transfer is chaos-dropped and when the
donor's entries vanish MID-adoption (the SIGKILLed-donor scenario).
Then the accounting contracts: page-accounting closure (free + live +
cached + in-flight-donated == total) holds after donation, after
adoption, and under every fault; donated objects are budget-bounded and
orphan-swept. Finally the client-adjacent constructor audit: none of
the paths a unit test touches may auto-boot a cluster via
_ensure_client (the PR 12 lesson, now pinned for serve/api.py,
state.py, and the KV store's backend selection).
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import gpt
from ray_tpu import chaos
from ray_tpu.serve import kv_objects
from ray_tpu.serve.kv_objects import (LocalKVStore, engine_fingerprint,
                                      make_meta, page_span,
                                      pages_for_tokens)
from ray_tpu.serve.llm import LLMEngine
from ray_tpu.serve.prefix_cache import chunk_hashes

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CFG = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32)
CHUNK = 16
PAGE = 16


@pytest.fixture(scope="module")
def params():
    return gpt.init_params(CFG, jax.random.key(42))


def _engine(params, **kw):
    base = dict(n_slots=4, max_len=256, page_size=PAGE,
                prefill_chunk=CHUNK, prefill_token_budget=64,
                decode_block=4)
    base.update(kw)
    return LLMEngine(CFG, params, **base)


def _drive(eng, reqs, max_steps=2000):
    for _ in range(max_steps):
        if all(r.done.is_set() for r in reqs):
            break
        eng.step()
    assert all(r.done.is_set() for r in reqs)
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    return [r.out_ids for r in reqs]


def _closure(eng):
    acc = eng.page_accounting()
    assert acc["closure"], acc
    assert acc["refs_consistent"], acc
    return acc


def _prompt(seed, n):
    rng = np.random.default_rng(seed)
    return list(map(int, rng.integers(1, CFG.vocab_size, n)))


def _export_mid_decode(params, prompt, store, *, max_tokens=24,
                       steps=5, **kw):
    """Donor engine: run a stream partway, export it with KV donated."""
    donor = _engine(params, kv_transfer=True, kv_store=store, **kw)
    req = donor.submit(prompt, max_tokens=max_tokens, stream=True)
    for _ in range(steps):
        donor.step()
    assert not req.done.is_set(), "stream finished before export"
    conts = donor._export_unfinished()
    assert len(conts) == 1
    _closure(donor)
    return donor, conts[0]


def _resume(params, cont, store, **kw):
    adopter = _engine(params, kv_transfer=True, kv_store=store, **kw)
    req = adopter.submit(
        cont["prompt_ids"], max_tokens=cont["max_tokens"],
        generated_ids=cont["generated_ids"], kv=cont.get("kv"),
        prefix_hashes=cont.get("prefix_hashes"),
        prefix_chunk=cont.get("prefix_chunk", 0))
    out = _drive(adopter, [req])[0]
    _closure(adopter)
    return adopter, out


class TestUnits:
    """Pure key/span/meta arithmetic."""

    def test_pages_for_tokens(self):
        assert pages_for_tokens(0, 16) == 0
        assert pages_for_tokens(1, 16) == 1
        assert pages_for_tokens(16, 16) == 1
        assert pages_for_tokens(17, 16) == 2

    def test_page_span_aligned(self):
        # chunk == page: depth d owns exactly page d-1.
        assert page_span(1, 16, 16) == (0, 1)
        assert page_span(3, 16, 16) == (2, 3)
        # chunk = 2 pages.
        assert page_span(1, 32, 16) == (0, 2)
        assert page_span(2, 32, 16) == (2, 4)

    def test_page_span_mid_page_boundary(self):
        """chunk % page != 0: the boundary page belongs to the SHALLOWER
        depth; spans never overlap and union to the full covered run."""
        spans = [page_span(d, 24, 16) for d in (1, 2, 3, 4)]
        assert spans == [(0, 2), (2, 3), (3, 5), (5, 6)]
        covered = []
        for s, e in spans:
            assert s == len(covered)          # contiguous, no overlap
            covered.extend(range(s, e))
        assert len(covered) == pages_for_tokens(4 * 24, 16)

    def test_fingerprint_discriminates(self):
        a = engine_fingerprint(CFG, 16, 16)
        assert a == engine_fingerprint(CFG, 16, 16)
        assert a != engine_fingerprint(CFG, 32, 16)   # page size
        assert a != engine_fingerprint(CFG, 16, 32)   # chunk
        draft = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32,
                                   n_layers=1)
        assert a != engine_fingerprint(CFG, 16, 16, draft)

    def test_make_meta_shape(self):
        m = make_meta("ab" * 16, 2, 16, 16, "fp", "donor-1", 1, False)
        assert m["n_tokens"] == 32 and m["depth"] == 2
        assert m["donor"] == "donor-1" and not m["draft"]
        assert m["ts"] > 0


class TestLocalStore:
    def test_donate_resolve_fetch_roundtrip(self):
        st = LocalKVStore(budget=8)
        payload = {"k": np.ones((2, 1, 4)), "v": np.zeros((2, 1, 4))}
        meta = make_meta("aa", 1, 16, 16, "fp", "d1", 1, False)
        st.donate(meta, payload)
        assert set(st.resolve(["aa", "bb"])) == {"aa"}
        got = st.fetch(st.resolve(["aa"])["aa"])
        assert np.array_equal(got["k"], payload["k"])
        assert st.withdraw("aa") and not st.resolve(["aa"])

    def test_budget_withdraws_oldest(self):
        st = LocalKVStore(budget=2)
        for i in range(4):
            st.donate(make_meta(f"k{i}", 1, 16, 16, "fp", "d", 1, False),
                      {"k": np.zeros(1), "v": np.zeros(1)})
        assert set(st.resolve([f"k{i}" for i in range(4)])) == {"k2", "k3"}
        assert st.withdrawals == 2

    def test_withdrawals_counter_guarded_by_lock(self):
        """GUARDED-BY (PR 19): sweep() bumped `withdrawals` after
        releasing `_lock` while withdraw()/donate() bump it inside —
        a sweep racing a withdraw loses counts (read-modify-write on
        an unguarded int). Pin: every write of the counter happens
        with the store lock held."""

        class Probe(LocalKVStore):
            def __setattr__(self, name, value):
                if name == "withdrawals" and self.__dict__.get("_probe_on"):
                    self.__dict__.setdefault("locked_at_write", []).append(
                        self._lock.locked())
                object.__setattr__(self, name, value)

        st = Probe(budget=8)
        st._probe_on = True
        for i in range(3):
            st.donate(make_meta(f"k{i}", 1, 16, 16, "fp", "d0", 1, False),
                      {"k": np.zeros(1), "v": np.zeros(1)})
        assert st.withdraw("k0")
        assert st.sweep(live_donors=set()) == 2
        assert st.withdrawals == 3
        assert st.locked_at_write and all(st.locked_at_write), \
            f"withdrawals written without _lock held: {st.locked_at_write}"

    def test_withdraw_is_compare_and_delete(self):
        """A donor withdrawing its own STALE donation (its index row
        already swept and re-published by another donor) must not
        delete the other donor's live row — withdraw compares the
        row's ref against the owned object first."""
        from ray_tpu.serve.kv_objects import INDEX_NS, ObjectKVStore

        class FakeRef:
            def __init__(self, h):
                self._h = h

            def hex(self):
                return self._h

        class FakeClient:
            def __init__(self):
                self.kv = {}
                self.freed = []
                self.n = 0

            def put(self, v, cache_local=True):
                self.n += 1
                return FakeRef(f"{self.n:032x}")

            def kv_get(self, ns, k):
                return self.kv.get((ns, bytes(k)))

            def kv_put(self, ns, k, v):
                self.kv[(ns, bytes(k))] = v

            def kv_del(self, ns, k):
                self.kv.pop((ns, bytes(k)), None)
                return True

            def kv_keys(self, ns, prefix=b""):
                return [k for (n, k) in self.kv if n == ns]

            def free(self, refs):
                self.freed.extend(r.hex() for r in refs)

        client = FakeClient()
        a = ObjectKVStore(client, budget=8, donor="a")
        b = ObjectKVStore(client, budget=8, donor="b")
        meta = make_meta("kk", 1, 16, 16, "fp", "a", 1, False)
        payload = {"k": np.zeros(1), "v": np.zeros(1)}
        a.donate(meta, payload)
        # Sweep reaps A's row (TTL); B re-publishes the same digest.
        client.kv_del(INDEX_NS, b"kk")
        b.donate(make_meta("kk", 1, 16, 16, "fp", "b", 1, False),
                 payload)
        live = json.loads(client.kv_get(INDEX_NS, b"kk"))
        a.withdraw("kk")        # budget roll of A's STALE entry
        after = client.kv_get(INDEX_NS, b"kk")
        assert after is not None, "A's withdraw deleted B's live row"
        assert json.loads(after)["ref"] == live["ref"]
        assert client.freed, "A's own object must still be freed"

    def test_sweep_dead_donor_and_ttl(self):
        st = LocalKVStore(budget=8)
        st.donate(make_meta("live", 1, 16, 16, "fp", "alive", 1, False),
                  {"k": np.zeros(1), "v": np.zeros(1)})
        st.donate(make_meta("orphan", 1, 16, 16, "fp", "dead", 1, False),
                  {"k": np.zeros(1), "v": np.zeros(1)})
        assert st.sweep(live_donors={"alive"}) == 1
        assert set(st.resolve(["live", "orphan"])) == {"live"}
        # TTL: everything older than 0s is stale.
        assert st.sweep(ttl_s=0.0, now=time.time() + 1) == 1
        assert not st.resolve(["live"])


class TestDonation:
    """Drain export donates the written prefix, keyed by the SAME
    chunk-chain digests the prefix cache uses."""

    def test_export_donates_chain_keyed_pages(self, params):
        store = LocalKVStore(budget=64)
        prompt = _prompt(0, 50)
        donor, cont = _export_mid_decode(params, prompt, store)
        assert cont["kv"], "continuation carries no kv descriptor"
        desc = cont["kv"]
        # Keys ARE the prefix-cache digest chain over the written
        # sequence (prompt + generated prefix), hex-encoded.
        written = (prompt + cont["generated_ids"])[:desc["n_tokens"]]
        expect = [h.hex() for h in chunk_hashes(written, CHUNK)]
        assert desc["keys"] == expect
        assert store.stats()["entries"] == len(expect)
        m = donor.metrics()
        assert m["kv_donations"] == len(expect)
        assert m["kv_donated_pages"] == pages_for_tokens(
            desc["n_tokens"], PAGE)

    def test_continuation_carries_memoized_hashes(self, params):
        """Satellite: `_export_unfinished` continuations carry the
        memoized prefix_hashes (hex + the chunk they were computed at),
        and the destination seeds its memo from them instead of
        re-hashing the full context."""
        store = LocalKVStore(budget=64)
        _donor, cont = _export_mid_decode(params, _prompt(1, 50), store)
        assert cont["prefix_chunk"] == CHUNK
        assert cont["prefix_hashes"], "no memo exported"
        adopter = _engine(params, kv_transfer=True, kv_store=store)
        from ray_tpu.serve import prefix_cache as pc

        calls = {"n": 0}
        real = pc.hashlib.blake2b

        def counting(*a, **kw):
            calls["n"] += 1
            return real(*a, **kw)

        pc.hashlib.blake2b = counting
        try:
            req = adopter.submit(
                cont["prompt_ids"], max_tokens=cont["max_tokens"],
                generated_ids=cont["generated_ids"], kv=cont["kv"],
                prefix_hashes=cont["prefix_hashes"],
                prefix_chunk=cont["prefix_chunk"])
            assert len(req.prefix_hashes) == len(cont["prefix_hashes"])
            _drive(adopter, [req])
        finally:
            pc.hashlib.blake2b = real
        context = cont["prompt_ids"] + cont["generated_ids"]
        memo_free = len(context) // CHUNK
        # Only chunks past the memo are ever hashed (admission +
        # insert-on-free donation over the full written sequence).
        assert calls["n"] < memo_free, (calls["n"], memo_free)

    def test_wrong_chunk_memo_is_dropped(self, params):
        eng = _engine(params, kv_transfer=True,
                      kv_store=LocalKVStore(budget=4))
        req = eng.submit(_prompt(2, 40), max_tokens=2,
                         prefix_hashes=["ab" * 16], prefix_chunk=CHUNK + 8)
        assert req.prefix_hashes == []
        _drive(eng, [req])

    def test_donation_chaos_raise_keeps_serving(self, params):
        """serve.kv.donate raise: the donation is skipped, the request
        still completes/export closes, and no in-flight-donated ref
        leaks (closure + exporting == 0)."""
        store = LocalKVStore(budget=64)
        chaos.install([{"site": "serve.kv.donate", "action": "raise",
                        "count": -1}])
        try:
            donor, cont = _export_mid_decode(
                params, _prompt(3, 50), store)
        finally:
            chaos.uninstall()
        acc = _closure(donor)
        assert acc["exporting"] == 0
        assert store.stats()["entries"] == 0
        # Descriptor still rides (keys are knowable without the store);
        # adoption simply resolves nothing and re-prefills.
        cold = _engine(params)
        exp = _drive(cold, [cold.submit(_prompt(3, 50),
                                        max_tokens=24)])[0]
        _adopter, out = _resume(params, cont, store)
        assert out == exp


class TestAdoptionLadder:
    """adopt → partial-adopt + cold suffix → re-prefill, all
    byte-identical to the uninterrupted stream."""

    def _expected(self, params, prompt, max_tokens=24):
        cold = _engine(params)
        return _drive(cold, [cold.submit(prompt,
                                         max_tokens=max_tokens)])[0]

    def test_full_adoption_byte_identical(self, params):
        prompt = _prompt(10, 50)
        exp = self._expected(params, prompt)
        store = LocalKVStore(budget=64)
        _donor, cont = _export_mid_decode(params, prompt, store)
        adopter, out = _resume(params, cont, store)
        assert out == exp
        m = adopter.metrics()
        assert m["kv_adoptions"] == 1 and m["kv_adopt_failures"] == 0
        assert m["kv_adopted_tokens"] == cont["kv"]["n_tokens"]

    def test_partial_adoption_when_deep_entries_gone(self, params):
        """Only a chain PREFIX survives (deep entries withdrawn — e.g.
        the donor's budget or a sweep took them): the surviving depths
        adopt, the rest cold-prefills, stream byte-identical."""
        prompt = _prompt(11, 60)
        exp = self._expected(params, prompt)
        store = LocalKVStore(budget=64)
        _donor, cont = _export_mid_decode(params, prompt, store)
        keys = cont["kv"]["keys"]
        assert len(keys) >= 3
        for k in keys[2:]:              # keep only depths 1-2
            store.withdraw(k)
        adopter, out = _resume(params, cont, store)
        assert out == exp
        m = adopter.metrics()
        assert m["kv_adoptions"] == 1
        assert m["kv_adopted_tokens"] == 2 * CHUNK

    def test_all_entries_gone_falls_to_reprefill(self, params):
        prompt = _prompt(12, 50)
        exp = self._expected(params, prompt)
        store = LocalKVStore(budget=64)
        _donor, cont = _export_mid_decode(params, prompt, store)
        store.sweep(live_donors=set())      # donor "dead", all swept
        adopter, out = _resume(params, cont, store)
        assert out == exp
        m = adopter.metrics()
        assert m["kv_adoptions"] == 0

    def test_chaos_dropped_transfer_engages_fallback(self, params):
        """serve.kv.adopt drop on EVERY fetch: the transfer rung fails,
        the re-prefill rung engages, zero dropped tokens, closure."""
        prompt = _prompt(13, 50)
        exp = self._expected(params, prompt)
        store = LocalKVStore(budget=64)
        _donor, cont = _export_mid_decode(params, prompt, store)
        chaos.install([{"site": "serve.kv.adopt", "action": "drop",
                        "count": -1}])
        try:
            adopter, out = _resume(params, cont, store)
        finally:
            chaos.uninstall()
        assert out == exp
        m = adopter.metrics()
        assert m["kv_adoptions"] == 0 and m["kv_adopt_failures"] >= 1

    def test_chaos_dropped_tail_is_partial_adoption(self, params):
        """serve.kv.adopt drop AFTER the first fetch: depth 1 lands,
        the rest degrade to cold prefill — the partial rung under
        chaos, still byte-exact."""
        prompt = _prompt(14, 60)
        exp = self._expected(params, prompt)
        store = LocalKVStore(budget=64)
        _donor, cont = _export_mid_decode(params, prompt, store)
        chaos.install([{"site": "serve.kv.adopt", "action": "drop",
                        "after": 1, "count": -1}])
        try:
            adopter, out = _resume(params, cont, store)
        finally:
            chaos.uninstall()
        assert out == exp
        m = adopter.metrics()
        assert m["kv_adoptions"] == 1
        assert m["kv_partial_adoptions"] == 1
        assert m["kv_adopted_tokens"] == CHUNK

    def test_donor_dies_mid_adoption(self, params):
        """The donor vanishes BETWEEN resolve and fetch (engine-level
        twin of the SIGKILL-mid-adoption scenario — the cluster test
        and bench kill the real process): fetch finds entries gone, the
        ladder falls a rung, zero dropped tokens, accounting closed."""
        prompt = _prompt(15, 60)
        exp = self._expected(params, prompt)

        class DyingDonorStore(LocalKVStore):
            def __init__(self):
                super().__init__(budget=64)
                self.fetches = 0

            def fetch(self, meta, timeout=30.0):
                self.fetches += 1
                if self.fetches == 2:
                    # Donor SIGKILLed after one page-set transferred:
                    # every remaining entry is gone at once.
                    with self._lock:
                        self._entries.clear()
                return super().fetch(meta, timeout)

        store = DyingDonorStore()
        _donor, cont = _export_mid_decode(params, prompt, store)
        adopter, out = _resume(params, cont, store)
        assert out == exp
        m = adopter.metrics()
        assert m["kv_adoptions"] == 1 and m["kv_partial_adoptions"] == 1

    def test_local_prefix_cache_beats_shallower_kv(self, params):
        """Adoption only plans when it covers MORE tokens than the
        local warm hit — a deeper local prefix wins (zero-copy beats a
        transfer)."""
        prompt = _prompt(16, 60)
        store = LocalKVStore(budget=64)
        _donor, cont = _export_mid_decode(params, prompt, store)
        keys = cont["kv"]["keys"]
        for k in keys[1:]:
            store.withdraw(k)           # kv offers only depth 1
        adopter = _engine(params, kv_transfer=True, kv_store=store,
                          prefix_cache=True)
        # Warm the LOCAL cache to full depth first.
        warm = adopter.submit(prompt, max_tokens=24)
        exp = _drive(adopter, [warm])[0]
        r2 = adopter.submit(cont["prompt_ids"],
                            max_tokens=cont["max_tokens"],
                            generated_ids=cont["generated_ids"],
                            kv=cont["kv"])
        out = _drive(adopter, [r2])[0]
        m = adopter.metrics()
        assert m["kv_adoptions"] == 0      # local cache won
        assert m["prefix_hits"] >= 1
        assert out == exp[len(cont["generated_ids"]):] or out == exp
        _closure(adopter)

    @pytest.mark.parametrize("stale", ["fingerprint", "payload"])
    def test_old_layout_page_set_refuses_to_adopt(self, params, stale):
        """A page set donated in the pool's older [L, n, ps, H, K] shape
        never binds: its fingerprint lacks the layout token, so nothing
        resolves; and even under a matching fingerprint a payload of that
        shape fails the bind's shape check. Both fall through to
        re-prefill with the cold stream."""
        prompt = _prompt(23, 50)
        store = LocalKVStore(budget=64)
        _donor, cont = _export_mid_decode(params, prompt, store)
        fp = cont["kv"]["fingerprint"]
        assert fp.endswith(":hk")
        for ent in store._entries.values():
            if stale == "fingerprint":
                ent["meta"]["fingerprint"] = fp[:-len(":hk")]
            else:
                ent["payload"] = {
                    name: a.reshape(a.shape[:3] + (CFG.n_heads,
                                                   CFG.head_dim))
                    if a.ndim == 4 else a
                    for name, a in ent["payload"].items()}
        adopter, out = _resume(params, cont, store)
        m = adopter.metrics()
        assert m["kv_adoptions"] == 0
        assert m["kv_adopt_failures"] == (1 if stale == "payload" else 0)
        assert out == self._expected(params, prompt)

    def test_fingerprint_mismatch_never_adopts(self, params):
        prompt = _prompt(17, 50)
        store = LocalKVStore(budget=64)
        _donor, cont = _export_mid_decode(params, prompt, store)
        bad = dict(cont, kv=dict(cont["kv"], fingerprint="other"))
        adopter, out = _resume(params, bad, store)
        assert adopter.metrics()["kv_adoptions"] == 0
        assert out == self._expected(params, prompt)


class TestPoolHandoff:
    """pool_role='prefill': first token here, decode elsewhere."""

    def test_prefill_engine_hands_off_after_first_token(self, params):
        store = LocalKVStore(budget=64)
        pre = _engine(params, pool_role="prefill", kv_store=store)
        req = pre.submit(_prompt(20, 50), max_tokens=24, stream=True)
        _drive(pre, [req], max_steps=50)
        assert req.migrated and len(req.out_ids) == 1
        assert req.kv_handoff and req.kv_handoff["keys"]
        acc = _closure(pre)
        assert acc["exporting"] == 0

    def test_handoff_resume_byte_identical(self, params):
        prompt = _prompt(21, 50)
        cold = _engine(params)
        exp = _drive(cold, [cold.submit(prompt, max_tokens=24)])[0]
        store = LocalKVStore(budget=64)
        pre = _engine(params, pool_role="prefill", kv_store=store)
        req = pre.submit(prompt, max_tokens=24, stream=True)
        _drive(pre, [req], max_steps=50)
        dec = _engine(params, pool_role="decode", kv_store=store)
        r2 = dec.submit(prompt, max_tokens=24,
                        generated_ids=list(req.out_ids),
                        kv=req.kv_handoff,
                        prefix_hashes=[h.hex()
                                       for h in req.prefix_hashes],
                        prefix_chunk=CHUNK)
        out = _drive(dec, [r2])[0]
        assert out == exp
        assert dec.metrics()["kv_adoptions"] == 1
        _closure(dec)

    def test_one_token_prompt_budget_finishes_without_handoff(self,
                                                              params):
        """max_tokens=1 finishes AT the first token — a natural
        completion, not a handoff."""
        pre = _engine(params, pool_role="prefill",
                      kv_store=LocalKVStore(budget=8))
        req = pre.submit(_prompt(22, 40), max_tokens=1)
        _drive(pre, [req], max_steps=50)
        assert not req.migrated and len(req.out_ids) == 1


class TestPreemptRegrow:
    """The regrow invariant `context == prompt_ids[:n_prompt] +
    out_ids` across REPEATED preempts (the old append-form duplicated
    the pre-preempt generated tokens on the second preempt, corrupting
    both the recompute context and every digest keyed off it)."""

    def _force_preempt(self, eng, req):
        slot = next(s for s, r in enumerate(eng.slot_req) if r is req)
        eng._preempt(slot)

    def test_double_preempt_context_and_stream_exact(self, params):
        prompt = _prompt(60, 40)
        cold = _engine(params)
        exp = _drive(cold, [cold.submit(prompt, max_tokens=40)])[0]
        eng = _engine(params)
        req = eng.submit(prompt, max_tokens=40)
        for _ in range(3):
            eng.step()
        self._force_preempt(eng, req)
        assert req.prompt_ids == prompt + req.out_ids
        for _ in range(5):
            eng.step()
        self._force_preempt(eng, req)
        # The SECOND regrow must not duplicate the first preempt's
        # generated tokens.
        assert req.prompt_ids == prompt + req.out_ids, (
            len(req.prompt_ids), len(prompt) + len(req.out_ids))
        out = _drive(eng, [req])[0]
        assert out == exp
        _closure(eng)

    def test_donation_after_preempt_keys_true_sequence(self, params):
        """A preempt-resumed request that completes donates under the
        digests of the sequence its pages actually hold — a stale key
        (the duplicated-context digest) would serve WRONG KV to any
        later prompt that matched it."""
        store = LocalKVStore(budget=64)
        prompt = _prompt(61, 40)
        eng = _engine(params, kv_transfer=True, kv_store=store,
                      prefix_cache=True)
        req = eng.submit(prompt, max_tokens=40, stream=True)
        for _ in range(4):
            eng.step()
        self._force_preempt(eng, req)
        for _ in range(4):
            eng.step()
        conts = eng._export_unfinished()
        assert conts and conts[0]["kv"]
        true_written = (prompt + req.out_ids)[:conts[0]["kv"]["n_tokens"]]
        expect_keys = [h.hex() for h in chunk_hashes(true_written, CHUNK)]
        assert conts[0]["kv"]["keys"] == expect_keys
        _closure(eng)


class TestReshardingAdoption:
    """Sharded donation + resharding adoption (ISSUE 20 tentpole a):
    tp>1 donors publish per-shard head planes (`k@s`/`v@s`); an adopter
    at a DIFFERENT tp degree re-splits the concatenated heads at bind
    time — the head axis is shard-invariant math, so the spliced stream
    must stay byte-identical to an uninterrupted single-shard engine."""

    pytestmark = pytest.mark.skipif(
        len(jax.devices()) < 4,
        reason="resharding tests need >= 4 (virtual) devices")

    def _expected(self, params, prompt, **kw):
        cold = _engine(params, **kw)
        return _drive(cold, [cold.submit(prompt, max_tokens=24)])[0]

    @pytest.mark.parametrize("donor_tp,adopter_tp", [(2, 4), (4, 2)])
    @pytest.mark.parametrize("attn_impl", ["gather", "kernel"])
    def test_reshard_byte_exact(self, params, donor_tp, adopter_tp,
                                attn_impl):
        prompt = _prompt(70, 50)
        exp = self._expected(params, prompt, attn_impl=attn_impl)
        store = LocalKVStore(budget=64)
        _donor, cont = _export_mid_decode(
            params, prompt, store, attn_impl=attn_impl, tp=donor_tp)
        # The wire schema is sharded: per-depth rows carry the donor tp
        # and suffixed head planes, never an unsharded "k".
        metas = store.resolve(cont["kv"]["keys"])
        assert metas and all(m["tp"] == donor_tp for m in metas.values())
        p = store.fetch(next(iter(metas.values())))
        assert f"k@{donor_tp - 1}" in p and "k" not in p
        adopter, out = _resume(params, cont, store,
                               attn_impl=attn_impl, tp=adopter_tp)
        assert out == exp
        m = adopter.metrics()
        assert m["kv_adoptions"] == 1 and m["kv_adopt_failures"] == 0
        assert m["kv_adopted_tokens"] == cont["kv"]["n_tokens"]

    @pytest.mark.parametrize("donor_tp,adopter_tp", [(2, 4), (4, 2)])
    def test_reshard_int8_scale_planes(self, params, donor_tp,
                                       adopter_tp):
        """int8 pool across a reshard: the quantized page planes split
        per shard while the per-page scale planes (head-free, [L, n])
        ride UNSUFFIXED as one replicated copy."""
        prompt = _prompt(71, 50)
        exp = self._expected(params, prompt, kv_dtype="int8")
        store = LocalKVStore(budget=64)
        _donor, cont = _export_mid_decode(
            params, prompt, store, kv_dtype="int8", tp=donor_tp)
        metas = store.resolve(cont["kv"]["keys"])
        p = store.fetch(next(iter(metas.values())))
        assert f"k@{donor_tp - 1}" in p
        assert "k_scale" in p and "k_scale@0" not in p
        adopter, out = _resume(params, cont, store,
                               kv_dtype="int8", tp=adopter_tp)
        assert out == exp
        assert adopter.metrics()["kv_adoptions"] == 1

    def test_tp_donor_to_tp1_adopter(self, params):
        """Degenerate reshard: a tp=2 donor's sharded rows concatenate
        back to full heads on a single-shard adopter."""
        prompt = _prompt(73, 50)
        exp = self._expected(params, prompt)
        store = LocalKVStore(budget=64)
        _donor, cont = _export_mid_decode(params, prompt, store, tp=2)
        adopter, out = _resume(params, cont, store)
        assert out == exp
        assert adopter.metrics()["kv_adoptions"] == 1

    def test_donor_dies_mid_sharded_donation_index_consistent(
            self, params):
        """The donor dies partway through a SHARDED donation (some
        depths stored, the rest never made it): the index never holds a
        torn row — every surviving depth fetches a COMPLETE shard set —
        so the adopter partial-adopts the surviving prefix, re-prefills
        the rest, and stays byte-exact at a different tp degree."""
        prompt = _prompt(72, 60)
        exp = self._expected(params, prompt)

        class DyingDonorStore(LocalKVStore):
            def __init__(self):
                super().__init__(budget=64)
                self.calls = 0   # NOT `donations`: the store counts those

            def donate(self, meta, payload):
                self.calls += 1
                if self.calls > 2:
                    raise RuntimeError("donor SIGKILLed mid-donation")
                return super().donate(meta, payload)

        store = DyingDonorStore()
        donor, cont = _export_mid_decode(params, prompt, store, tp=2)
        acc = _closure(donor)
        assert acc["exporting"] == 0
        keys = cont["kv"]["keys"]
        assert len(keys) >= 3 and store.calls > 2
        metas = store.resolve(keys)
        assert set(metas) == set(keys[:2])
        for meta in metas.values():
            p = store.fetch(meta)
            assert {f"k@{s}" for s in range(2)} <= set(p)
        adopter, out = _resume(params, cont, store, tp=4)
        assert out == exp
        m = adopter.metrics()
        assert m["kv_adoptions"] == 1 and m["kv_adopt_failures"] == 0
        assert m["kv_adopted_tokens"] == 2 * CHUNK


class TestWarmDiscovery:
    """Descriptor-less adoption (ISSUE 20 tentpole b): donated chain
    heads ride load_snapshot() as a bounded summary, and a
    ``kv={"discover": True}`` hint — attached by the handle from the
    PUSHED summary, zero request-path RPCs — authorizes the adopt-plan
    to walk the store index at admission without any descriptor."""

    def _head(self, prompt):
        return chunk_hashes(prompt[:CHUNK], CHUNK)[0].hex()[:16]

    def test_completion_donates_and_populates_summary(self, params):
        """Insert-on-free: a normally-completed request's written
        prefix lands in the index (no drain/handoff needed), and its
        chain head shows up in the exported summary."""
        store = LocalKVStore(budget=64)
        donor = _engine(params, kv_transfer=True, kv_store=store)
        prompt = _prompt(80, 50)
        _drive(donor, [donor.submit(prompt, max_tokens=24)])
        assert store.stats()["entries"] > 0
        snap = donor.load_snapshot()
        assert self._head(prompt) in snap["kv_summary"]
        m = donor.metrics()
        assert m["kv_summary_entries"] == len(snap["kv_summary"])
        assert m["kv_summary_max"] > 0
        _closure(donor)

    def test_discover_hint_adopts_without_descriptor(self, params):
        """A replica that NEVER saw the prefix adopts on the hint
        alone: the adopt-plan derives keys from the request's own chain
        and resolves them locally — byte-exact, one resolve round."""
        prompt = _prompt(81, 50)
        cold = _engine(params)
        exp = _drive(cold, [cold.submit(prompt, max_tokens=24)])[0]
        store = LocalKVStore(budget=64)
        donor = _engine(params, kv_transfer=True, kv_store=store)
        _drive(donor, [donor.submit(prompt, max_tokens=24)])
        adopter = _engine(params, kv_transfer=True, kv_store=store)
        req = adopter.submit(prompt, max_tokens=24,
                             kv={"discover": True})
        out = _drive(adopter, [req])[0]
        assert out == exp
        m = adopter.metrics()
        assert m["kv_adoptions"] == 1
        assert m["kv_digest_lookups"] == 1
        # Keys come from the adopter's OWN prompt chain: 3 full chunks.
        assert m["kv_adopted_tokens"] == (len(prompt) // CHUNK) * CHUNK
        _closure(adopter)

    def test_unhinted_request_never_touches_index(self, params):
        """No hint, no descriptor → zero resolve rounds: the discovery
        cost lives on the routing push, never the request path."""
        prompt = _prompt(82, 50)
        store = LocalKVStore(budget=64)
        donor = _engine(params, kv_transfer=True, kv_store=store)
        _drive(donor, [donor.submit(prompt, max_tokens=24)])
        adopter = _engine(params, kv_transfer=True, kv_store=store)
        _drive(adopter, [adopter.submit(prompt, max_tokens=24)])
        m = adopter.metrics()
        assert m["kv_digest_lookups"] == 0
        assert m["kv_adoptions"] == 0

    def test_discover_false_positive_falls_through(self, params):
        """A stale summary (donation swept/evicted) hints a prefix the
        index no longer holds: one resolve finds nothing and the ladder
        falls to a plain re-prefill — still byte-exact."""
        prompt = _prompt(83, 50)
        cold = _engine(params)
        exp = _drive(cold, [cold.submit(prompt, max_tokens=24)])[0]
        adopter = _engine(params, kv_transfer=True,
                          kv_store=LocalKVStore(budget=64))
        req = adopter.submit(prompt, max_tokens=24,
                             kv={"discover": True})
        out = _drive(adopter, [req])[0]
        assert out == exp
        m = adopter.metrics()
        assert m["kv_digest_lookups"] == 1
        assert m["kv_adoptions"] == 0

    def test_summary_bounded_newest_kept(self, params):
        """serve_kv_summary_max bounds the export; eviction drops the
        OLDEST head, re-donation refreshes recency and keeps the
        deepest donated depth."""
        eng = _engine(params, kv_transfer=True,
                      kv_store=LocalKVStore(budget=8))
        eng._kv_summary_max = 3
        for i in range(5):
            eng._kv_note_donation(f"h{i}", 1)
        assert list(eng._kv_donated) == ["h2", "h3", "h4"]
        eng._kv_note_donation("h2", 4)
        eng._kv_note_donation("h2", 2)
        assert list(eng._kv_donated) == ["h3", "h4", "h2"]
        assert eng._kv_donated["h2"] == 4
        assert eng.load_snapshot()["kv_summary"] == ["h3", "h4", "h2"]


class TestKnobValidation:
    def test_kv_transfer_fits_the_knobs_chunk(self, params):
        """The transfer asks for nothing but page-aligned chunks, and
        the chunk the knob resolves to beside a short cache is whole
        pages: an engine given `kv_transfer` and no chunk builds. Where
        one page is longer than the cache no chunk can be aligned."""
        eng = LLMEngine(CFG, params, max_len=100, page_size=16,
                        kv_transfer=True, kv_store=LocalKVStore(budget=4))
        assert eng.kv_transfer and eng.prefill_chunk == 96
        with pytest.raises(ValueError, match="page-set transfer"):
            LLMEngine(CFG, params, max_len=48, page_size=64,
                      kv_transfer=True)

    def test_kv_transfer_requires_page_aligned_chunks(self, params):
        """chunk % page_size == 0 is load-bearing: cross-donation dedup
        composes chains from different donations, and only page-aligned
        depth spans make the composite self-contained (a mid-page
        boundary page would carry one donation's unwritten tail)."""
        with pytest.raises(ValueError, match="page-set transfer"):
            _engine(params, prefill_chunk=24, kv_transfer=True)

    def test_global_knob_soft_disables_on_unaligned_chunk(
            self, params, monkeypatch):
        monkeypatch.setenv("RAY_TPU_LLM_KV_TRANSFER", "1")
        from ray_tpu.core import config as _config

        monkeypatch.setattr(_config, "GLOBAL_CONFIG",
                            _config.Config.from_env())
        eng = _engine(params, prefill_chunk=24)
        assert eng.kv_transfer is False

    def test_soft_disable_reason_is_observable(self, params, monkeypatch,
                                               caplog):
        """Satellite (ISSUE 20): a fleet-wide llm_kv_transfer export
        that misfits an engine must degrade OBSERVABLY — one warning at
        construction and a kv_transfer_disabled_reason on both the
        metrics and load_snapshot surfaces — not silently serve cold."""
        import logging

        monkeypatch.setenv("RAY_TPU_LLM_KV_TRANSFER", "1")
        from ray_tpu.core import config as _config

        monkeypatch.setattr(_config, "GLOBAL_CONFIG",
                            _config.Config.from_env())
        with caplog.at_level(logging.WARNING):
            eng = _engine(params, prefill_chunk=24)
        assert eng.kv_transfer is False
        assert any("soft-disabled" in r.getMessage()
                   for r in caplog.records), caplog.records
        m = eng.metrics()
        assert m["kv_transfer"] is False
        assert "page-set transfer" in m["kv_transfer_disabled_reason"]
        snap = eng.load_snapshot()
        assert "page-set transfer" in snap["kv_transfer_disabled_reason"]
        # An ENABLED engine exports no reason (the field is a flag).
        on = _engine(params, kv_transfer=True,
                     kv_store=LocalKVStore(budget=4))
        assert "kv_transfer_disabled_reason" not in on.metrics()
        assert "kv_transfer_disabled_reason" not in on.load_snapshot()

    def test_pool_role_validation(self, params):
        with pytest.raises(ValueError, match="pool_role"):
            _engine(params, pool_role="both")
        with pytest.raises(ValueError, match="requires kv_transfer"):
            _engine(params, pool_role="prefill", kv_transfer=False)

    def test_global_knob_applies(self, params, monkeypatch):
        monkeypatch.setenv("RAY_TPU_LLM_KV_TRANSFER", "1")
        from ray_tpu.core import config as _config

        monkeypatch.setattr(_config, "GLOBAL_CONFIG",
                            _config.Config.from_env())
        paged = _engine(params)
        assert paged.kv_transfer is True
        assert paged._kv_store is not None

    def test_deployment_prefill_requires_peer(self):
        from ray_tpu.serve.llm import LLMDeployment

        with pytest.raises(ValueError, match="pool_peer"):
            LLMDeployment("tiny", n_slots=2, max_len=64,
                          pool_role="prefill",
                          engine_kwargs={"page_size": 16,
                                         "prefill_chunk": 16})


class TestEnsureClientAudit:
    """Satellite: client-adjacent constructors must never auto-boot a
    cluster (`_ensure_client` gates on `_client is not None`)."""

    def _assert_no_client(self):
        from ray_tpu import api as _api

        assert _api._client is None, \
            "a unit-test path auto-booted a cluster"

    def test_handle_and_push_paths_stay_clusterless(self):
        from ray_tpu import api as _api
        from ray_tpu.serve import api as sapi

        if _api._client is not None:
            pytest.skip("a cluster is already up in this process")
        h = sapi.DeploymentHandle("nonexistent")
        assert sapi._pushed_version() == sapi._push_state["version"]
        sapi._dead_actors()
        assert h._alive([]) == []
        self._assert_no_client()

    def test_state_queries_raise_instead_of_booting(self):
        from ray_tpu import api as _api
        from ray_tpu import state

        if _api._client is not None:
            pytest.skip("a cluster is already up in this process")
        with pytest.raises(RuntimeError, match="running cluster"):
            state.list_nodes()
        assert state.emit_cluster_event("t", "m") is False
        self._assert_no_client()

    def test_kv_store_selection_stays_clusterless(self, params):
        from ray_tpu import api as _api

        if _api._client is not None:
            pytest.skip("a cluster is already up in this process")
        kv_objects.reset_local_store()
        eng = _engine(params, kv_transfer=True)
        assert isinstance(eng._kv_store, LocalKVStore)
        self._assert_no_client()
        kv_objects.reset_local_store()


class TestClusterPoolSplit:
    """Live disaggregated stack: prefill pool + decode pool behind the
    async proxy, page-set handoff + adoption end to end, and the donor
    SIGKILL mid-run — marked slow-adjacent but kept in the quick tier
    (one cluster boot, two scenarios)."""

    N_SLOTS = 4
    MAX_LEN = 256
    MAX_TOKENS = 16
    ENGINE_KW = {"page_size": 16,
                 "prefill_chunk": 16, "prefill_token_budget": 64,
                 "decode_block": 4}

    @pytest.fixture(scope="class")
    def stack(self):
        import ray_tpu
        from ray_tpu import serve
        from ray_tpu.serve.llm import LLMDeployment

        ray_tpu.init(num_cpus=6, _system_config={
            "serve_kv_sweep_interval_s": 2.0,
            "serve_kv_object_ttl_s": 60.0,
        })
        try:
            decode = serve.deployment(
                LLMDeployment, name="kvd", pool_role="decode").options(
                num_replicas=1, route_prefix=None).bind(
                "tiny", n_slots=self.N_SLOTS, max_len=self.MAX_LEN,
                jax_platform="cpu", pool_role="decode",
                engine_kwargs=dict(self.ENGINE_KW))
            prefill = serve.deployment(
                LLMDeployment, name="kvp", pool_role="prefill").options(
                num_replicas=2, route_prefix="/kv").bind(
                "tiny", n_slots=self.N_SLOTS, max_len=self.MAX_LEN,
                jax_platform="cpu", pool_role="prefill",
                pool_peer="kvd",
                engine_kwargs=dict(self.ENGINE_KW))
            serve.run(decode, timeout=300.0)
            serve.run(prefill, timeout=300.0)
            _proxy, port = serve.start_proxy()
            yield port
        finally:
            serve.shutdown()
            ray_tpu.shutdown()

    def _expected(self, prompts):
        eng = LLMEngine(gpt.GPTConfig.by_name("tiny"), None,
                        n_slots=self.N_SLOTS, max_len=self.MAX_LEN,
                        **self.ENGINE_KW)
        out = []
        for p in prompts:
            req = eng.submit(p, max_tokens=self.MAX_TOKENS)
            while not req.done.is_set():
                eng.step()
            out.append(list(req.out_ids))
        return out

    def _decode_load(self):
        import ray_tpu
        from ray_tpu.serve.api import _get_controller

        ctrl = _get_controller()
        load = ray_tpu.get(ctrl.get_load.remote(), timeout=30)
        rows = load.get("kvd", {}).get("replicas", [])
        return (rows[0].get("load") or {}) if rows else {}

    def test_stream_handoff_adopts_byte_exact(self, stack):
        import bench_chaos

        port = stack
        prompts = [_prompt(30 + i, 48) for i in range(4)]
        expected = self._expected(prompts)
        for i, p in enumerate(prompts):
            r = bench_chaos._sse_stream(port, "/kv", {
                "prompt_ids": p, "max_tokens": self.MAX_TOKENS},
                timeout_s=300)
            assert r["error"] is None and r["done"], r["error"]
            assert r["tokens"] == expected[i], (i, r["tokens"])
        deadline = time.time() + 15
        while time.time() < deadline:
            eng = self._decode_load()
            if eng.get("kv_adoptions", 0) >= 1:
                break
            time.sleep(0.5)
        assert eng.get("pool_role") == "decode"
        assert eng.get("kv_adoptions", 0) >= 1, eng

    def test_unary_handoff_through_proxy(self, stack):
        import json
        import urllib.request

        port = stack
        prompt = _prompt(40, 48)
        exp = self._expected([prompt])[0]
        body = json.dumps({"prompt_ids": prompt,
                           "max_tokens": self.MAX_TOKENS}).encode()
        r = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/kv", data=body, timeout=300)
        out = json.loads(r.read())["result"]
        assert out["output_ids"] == exp, out

    def test_summary_and_push_bytes_ride_routing_table(self, stack):
        """Tentpole (b) on the live stack: donated chain heads reach
        handles through the routing push itself — kv_summary in the
        per-replica load rows, push_bytes accounted in-band — so warm
        discovery costs the request path zero RPCs."""
        import ray_tpu
        from ray_tpu.serve.api import _get_controller

        _ = stack   # the handoff tests above already drove donations
        ctrl = _get_controller()
        heads = []
        deadline = time.time() + 30
        while time.time() < deadline:
            table = ray_tpu.get(ctrl.get_routing.remote(-1), timeout=30)
            assert table["push_bytes"] > 0
            rows = table["routes"]["kvp"]["loads"]
            heads = [h for row in rows.values()
                     for h in row.get("kv_summary", ())]
            if heads:
                break
            time.sleep(0.5)
        assert heads, "no kv_summary ever rode the routing push"
        assert all(isinstance(h, str) and len(h) == 16 for h in heads)

    def test_donor_sigkill_mid_donation_zero_drop(self, stack):
        """A prefill replica SIGKILLed INSIDE a donation (chaos kill at
        serve.kv.donate): in-flight streams fail over and complete with
        0 dropped / 0 mismatched tokens — by adoption when the pages
        made it, by re-prefill when they didn't — and the decode
        engine's page accounting closes afterwards."""
        import ray_tpu
        import bench_chaos
        from ray_tpu.serve.api import _get_controller

        port = stack
        prompts = [_prompt(50 + i, 48) for i in range(6)]
        expected = self._expected(prompts)
        ctrl = _get_controller()
        table = ray_tpu.get(ctrl.get_routing.remote(-1), timeout=30)
        victim = table["routes"]["kvp"]["replicas"][0]
        ray_tpu.get(victim.install_chaos.remote(
            [{"site": "serve.kv.donate", "action": "kill", "after": 1}]),
            timeout=30)
        results = [None] * len(prompts)

        def client(i):
            results[i] = bench_chaos._sse_stream(port, "/kv", {
                "prompt_ids": prompts[i],
                "max_tokens": self.MAX_TOKENS}, timeout_s=300)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        dropped = [i for i, r in enumerate(results)
                   if r is None or r["error"] or not r["done"]]
        assert not dropped, [results[i] and results[i]["error"]
                             for i in dropped]
        mismatched = [i for i, r in enumerate(results)
                      if r["tokens"] != expected[i]]
        assert not mismatched, mismatched
        # Page accounting on the (quiescent) decode replica closes.
        rows = ray_tpu.get(ctrl.get_routing.remote(-1),
                           timeout=30)["routes"]["kvd"]["replicas"]
        acc = ray_tpu.get(rows[0].handle_request.remote(
            "page_accounting", (), {}), timeout=60)
        assert acc["closure"] and acc["refs_consistent"], acc
