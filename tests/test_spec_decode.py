"""Speculative decoding on the paged engine (serve/llm.py).

Exactness first: greedy speculative output must be byte-identical to
non-speculative decode — for any draft, because every emitted token is
the argmax of the TARGET's own logits at its position (accepted
proposals just happen to equal it). Pinned across k ∈ {2, 4}, both
attention implementations, a fully-agreeing draft (acceptance ≈ 100%,
no rollback) and an adversarial fully-rejecting draft (acceptance 0,
rollback every tick), and under preempt-by-recompute pool pressure.
Then the scheduler contracts: rejected proposals' pages roll back to
the pool (accounting closure), drained continuations carry only
ACCEPTED tokens, the draft reads prefix-cache shared pages read-only
(refcounts unchanged), and temperature>0 rejection sampling reproduces
the target distribution exactly (unit-level Monte Carlo pin).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import plain_reference
from ray_tpu.models import gpt
from ray_tpu.serve.llm import LLMEngine, spec_accept_tokens

CFG = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32)
# Same GPTConfig family, tied tokenizer (vocab), separately loadable
# weights — a 1-layer half-width draft, the shape the knob is for.
DRAFT_CFG = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32,
                               n_layers=1, d_model=32, n_heads=4, d_ff=64)


@pytest.fixture(scope="module")
def params():
    return gpt.init_params(CFG, jax.random.key(42))


@pytest.fixture(scope="module")
def draft_params():
    return gpt.init_params(DRAFT_CFG, jax.random.key(7))


@pytest.fixture(scope="module")
def reject_params(params):
    """Adversarial draft: the target's own weights NEGATED — proposals
    are maximally wrong, so greedy verification rejects everything and
    every tick exercises the rollback path."""
    return jax.tree.map(lambda a: -a, params)


def _drive(eng, reqs, max_steps=2000):
    for _ in range(max_steps):
        if all(r.done.is_set() for r in reqs):
            break
        eng.step()
    assert all(r.done.is_set() for r in reqs)
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    return [r.out_ids for r in reqs]


def _engine(params, *, spec=None, spec_params=None, spec_k=4, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_len", 128)
    kw.setdefault("page_size", 16)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("prefill_token_budget", 32)
    if spec is not None:
        kw.update(spec_draft=spec, spec_draft_params=spec_params,
                  spec_k=spec_k)
    return LLMEngine(CFG, params, **kw)


def _ragged_prompts(rng, lengths):
    return [list(map(int, rng.integers(1, CFG.vocab_size, n)))
            for n in lengths]


class TestExactness:
    """Speculative greedy == non-speculative greedy, token-for-token."""

    @pytest.mark.parametrize("attn_impl", ["gather", "kernel"])
    @pytest.mark.parametrize("k", [2, 4])
    def test_greedy_byte_exact(self, params, draft_params, k, attn_impl):
        prompts = _ragged_prompts(np.random.default_rng(1), (5, 23, 41, 11))
        base = _engine(params, attn_impl=attn_impl)
        ref = _drive(base, [base.submit(p, max_tokens=24) for p in prompts])
        eng = _engine(params, spec=DRAFT_CFG, spec_params=draft_params,
                      spec_k=k, attn_impl=attn_impl)
        out = _drive(eng, [eng.submit(p, max_tokens=24) for p in prompts])
        assert out == ref
        m = eng.metrics()
        assert m["spec_ticks"] > 0 and m["spec_proposed"] > 0
        assert m["kv_pages_free"] == m["kv_pages_total"]

    def test_greedy_exact_under_full_rejection(self, params, reject_params):
        """Adversarial draft: zero acceptance, rollback every tick —
        the stream is still byte-identical (emitted tokens are always
        the target's own argmax chain) and no page leaks."""
        prompts = _ragged_prompts(np.random.default_rng(2), (9, 30, 17))
        base = _engine(params)
        ref = _drive(base, [base.submit(p, max_tokens=16) for p in prompts])
        eng = _engine(params, spec=CFG, spec_params=reject_params, spec_k=4)
        out = _drive(eng, [eng.submit(p, max_tokens=16) for p in prompts])
        assert out == ref
        m = eng.metrics()
        assert m["spec_accepted"] == 0 and m["spec_proposed"] > 0
        assert m["spec_accepted_per_step"] == 1.0
        assert m["kv_pages_free"] == m["kv_pages_total"]
        acct = eng.page_accounting()
        assert acct["closure"] and acct["refs_consistent"]

    def test_exact_under_preemption(self, params, draft_params):
        """Pool sized so concurrent slots MUST run dry mid-generation:
        speculative growth + preempt-by-recompute still reproduce the
        plain forward's streams exactly."""
        prompts = [[5, 9, 2], [17, 3], [2, 4, 6], [8, 1, 0]]
        eng = _engine(params, spec=DRAFT_CFG, spec_params=draft_params,
                      spec_k=2, max_len=64, page_size=4, n_pages=7,
                      prefill_chunk=4, prefill_token_budget=8)
        out = _drive(eng, [eng.submit(p, max_tokens=10) for p in prompts])
        plain_reference.assert_gpt_greedy(CFG, params, prompts, out, n=10)
        m = eng.metrics()
        assert m["preemptions"] > 0
        assert m["kv_pages_free"] == m["kv_pages_total"]

    def test_temperature_smoke(self, params, draft_params):
        """temperature>0 engine path runs to completion with sane
        acceptance bookkeeping and closed page accounting (the
        distribution itself is pinned at unit level below)."""
        prompts = _ragged_prompts(np.random.default_rng(3), (7, 19, 12))
        eng = _engine(params, spec=DRAFT_CFG, spec_params=draft_params)
        reqs = [eng.submit(p, max_tokens=12, temperature=0.9)
                for p in prompts]
        out = _drive(eng, reqs)
        assert all(len(o) == 12 for o in out)
        m = eng.metrics()
        assert 0 <= m["spec_accepted"] <= m["spec_proposed"]
        acct = eng.page_accounting()
        assert acct["closure"] and acct["refs_consistent"]


class TestKnobValidation:
    """Typed construction-time errors, the llm_prefill_chunk pattern."""

    def test_spec_k_floor(self, params, draft_params):
        with pytest.raises(ValueError, match="llm_spec_k"):
            _engine(params, spec=DRAFT_CFG, spec_params=draft_params,
                    spec_k=0)

    def test_vocab_mismatch_rejected(self, params):
        bad = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32,
                                 vocab_size=128)
        with pytest.raises(ValueError, match="vocab"):
            _engine(params, spec=bad,
                    spec_params=gpt.init_params(bad, jax.random.key(0)))

    def test_draft_params_without_spec_rejected(self, params, draft_params):
        """Supplying draft weights without enabling speculation would
        silently read-then-discard a checkpoint and serve plain decode;
        the engine rejects the combination instead."""
        with pytest.raises(ValueError, match="spec_draft_params"):
            LLMEngine(CFG, params, n_slots=4, max_len=128,
                      page_size=16, prefill_chunk=16,
                      prefill_token_budget=32, spec_draft="",
                      spec_draft_params=draft_params)

    def test_negative_temperature_rejected(self, params):
        """Sampling paths branch on '0 = greedy, > 0 = sample'; a
        negative value would invert the softmax on the host rejection
        path while the on-device draft loop clamps it to greedy —
        rejected at submit() before it can reach either."""
        eng = _engine(params)
        with pytest.raises(ValueError, match="temperature"):
            eng.submit([1, 2, 3], max_tokens=4, temperature=-1.0)

    def test_global_knob_soft_off(self, params, monkeypatch):
        """The GLOBAL llm_spec_draft knob beside a model family that
        cannot carry it soft-disables instead of erroring — only
        explicit constructor args are strict. The positive path pins the
        env→Config plumb actually works: the same knob on a gpt engine,
        the default one (no argument at all) included, turns
        speculation ON."""
        from ray_tpu.models import zaya
        from ray_tpu.serve.llm_options import _KNOBS, resolve_options

        monkeypatch.setenv("RAY_TPU_LLM_SPEC_DRAFT", "tiny")
        o = resolve_options(
            zaya.ZayaConfig.tiny(), max_len=128, spec_draft_params=None,
            pool_role=None, **{kw: None for kw, _field in _KNOBS})
        assert o.spec_draft == "" and o.draft_cfg is None
        assert LLMEngine(CFG, params, max_len=64).spec_k > 0
        eng = _engine(params)
        assert eng.spec_k > 0
        assert eng.draft_cfg is not None


class TestRollbackAccounting:
    def test_closure_with_live_slots(self, params, reject_params):
        """Mid-flight (slots live, rollback happening every tick) the
        page accounting still closes: free + allocated == total, every
        reference owned, nothing leaked by rejected proposals."""
        eng = _engine(params, spec=CFG, spec_params=reject_params,
                      spec_k=4)
        reqs = [eng.submit(p, max_tokens=24)
                for p in _ragged_prompts(np.random.default_rng(4),
                                         (20, 33))]
        for _ in range(6):
            eng.step()
        assert any(not r.done.is_set() for r in reqs)
        acct = eng.page_accounting()
        assert acct["closure"] and acct["refs_consistent"]
        assert acct["live"] > 0
        _drive(eng, reqs)
        m = eng.metrics()
        assert m["kv_pages_free"] == m["kv_pages_total"]


class TestDrain:
    def test_continuations_carry_only_accepted_tokens(self, params,
                                                      draft_params):
        """Drain mid-speculation: exported continuations' generated_ids
        must be exact prefixes of the uninterrupted greedy stream (no
        unverified draft token ever leaves the engine), and resuming
        them elsewhere completes byte-identically."""
        prompts = _ragged_prompts(np.random.default_rng(5), (13, 26, 8))
        base = _engine(params)
        full = _drive(base, [base.submit(p, max_tokens=20)
                             for p in prompts])
        eng = _engine(params, spec=DRAFT_CFG, spec_params=draft_params)
        reqs = [eng.submit(p, max_tokens=20) for p in prompts]
        for _ in range(2):   # some accepted tokens, none finished
            eng.step()
        assert any(r.out_ids for r in reqs)
        assert not any(r.finished_at for r in reqs)
        out = eng.drain(timeout_s=0.0)
        assert out["exported"] == len([r for r in reqs
                                       if not r.finished_at])
        conts = {tuple(c["prompt_ids"]): c for c in out["continuations"]}
        resume = _engine(params)
        resumed = []
        for i, p in enumerate(prompts):
            c = conts.get(tuple(p))
            if c is None:        # finished before the drain
                continue
            gen = c["generated_ids"]
            assert gen == full[i][:len(gen)]   # accepted tokens only
            resumed.append((i, resume.submit(
                c["prompt_ids"], max_tokens=c["max_tokens"],
                temperature=c["temperature"], eos_id=c["eos_id"],
                generated_ids=gen)))
        assert resumed
        _drive(resume, [r for _i, r in resumed])
        for i, r in resumed:
            assert r.out_ids == full[i]


class TestPrefixCacheComposition:
    def test_warm_binds_share_pages_readonly(self, params, draft_params):
        """The draft reads prefix-cache shared pages through the
        target's tables without holding references of its own: warm
        admissions stay byte-exact, refcounts stay consistent, and the
        accounting closes with entries still cached."""
        rng = np.random.default_rng(6)
        shared = list(map(int, rng.integers(1, CFG.vocab_size, 48)))
        prompts = [shared + list(map(int, rng.integers(1, CFG.vocab_size, 6)))
                   for _ in range(3)]
        base = _engine(params)
        ref = _drive(base, [base.submit(p, max_tokens=8) for p in prompts])
        eng = _engine(params, spec=DRAFT_CFG, spec_params=draft_params,
                      n_pages=48, prefix_cache=True)
        wave1 = _drive(eng, [eng.submit(p, max_tokens=8) for p in prompts])
        wave2 = _drive(eng, [eng.submit(p, max_tokens=8) for p in prompts])
        assert wave1 == ref and wave2 == ref
        m = eng.metrics()
        assert m["prefix_hits"] > 0
        assert m["prefix_cached_tokens"] > 0
        acct = eng.page_accounting()
        assert acct["closure"] and acct["refs_consistent"]
        assert acct["cached"] > 0


class TestDistributional:
    """The rejection-sampling correctness argument, pinned Monte Carlo:
    whatever the proposal distribution q, the emitted marginal is the
    target distribution p."""

    def test_first_token_marginal_matches_target(self):
        rng = np.random.default_rng(0)
        V, k, trials = 8, 3, 20000
        p_logits = rng.normal(size=(k + 1, V)).astype(np.float32)
        q_logits = rng.normal(size=(k, V))
        q = np.exp(q_logits - q_logits.max(axis=1, keepdims=True))
        q /= q.sum(axis=1, keepdims=True)              # draft dists
        counts = np.zeros(V)
        for _ in range(trials):
            props = np.array([rng.choice(V, p=q[i]) for i in range(k)])
            emitted, j = spec_accept_tokens(rng, 1.0, props, q,
                                            p_logits, k)
            assert 1 <= len(emitted) <= k + 1
            assert j <= k
            counts[emitted[0]] += 1
        z = p_logits[0].astype(np.float64)
        z -= z.max()
        target = np.exp(z) / np.exp(z).sum()
        tv = 0.5 * np.abs(counts / trials - target).sum()
        assert tv < 0.03, f"total variation {tv} vs target distribution"

    def test_greedy_is_argmax_chain(self):
        rng = np.random.default_rng(1)
        V, k = 16, 4
        logits = rng.normal(size=(k + 1, V)).astype(np.float32)
        chain = [int(np.argmax(logits[i])) for i in range(k + 1)]
        # Fully-agreeing proposals: k accepted + bonus.
        emitted, j = spec_accept_tokens(rng, 0.0, np.array(chain[:k]),
                                        None, logits, k)
        assert (emitted, j) == (chain, k)
        # First proposal wrong: exactly one corrected token emitted.
        bad = [(chain[0] + 1) % V] + chain[1:k]
        emitted, j = spec_accept_tokens(rng, 0.0, np.array(bad),
                                        None, logits, k)
        assert (emitted, j) == ([chain[0]], 0)


class TestObservability:
    def test_metrics_and_load_snapshot(self, params, draft_params):
        eng = _engine(params, spec=DRAFT_CFG, spec_params=draft_params)
        _drive(eng, [eng.submit([3, 1, 4, 1, 5], max_tokens=8)])
        m = eng.metrics()
        assert m["spec_k"] == 4 and m["spec_draft"] == "custom"
        assert m["spec_accepted_per_step"] >= 1.0
        snap = eng.load_snapshot()
        assert snap["spec_k"] == 4
        assert snap["spec_accepted_per_step"] >= 1.0
