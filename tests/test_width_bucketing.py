"""Width-bucketed paged chunk dispatch (serve/llm.py + models/paged_kv.py).

Exactness first, the house pattern: grouping packed chunk rows by the
pow-2 page-table width each row actually attends over (`_pow2_width` of
pages covering written prefix + chunk, the decode ladder's rule) and
dispatching one width-sliced `prefill_chunk_paged` per bucket must emit
token streams byte-identical to the full-width PR 4 grid — across both
attention implementations, speculative verify (k ∈ {2, 4}, which rides
the width-sliced decode table view), warm-prefix COW admission, the
int8 KV scale-plane path, and tp=2 shard_map twins. Then the budget
contracts: the lowered chunk-program count stays within the width
ladder (2·log₂(max_pages)+2), the opt-in bucket-ladder warmup
pre-compiles exactly that ladder so live traffic adds zero compiles,
warmup compiles are marked so a clean engine boot never files a
`recompile.storm` event, and a mixed short+long tick really issues
multiple dispatch widths (the observability counters prove it).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu import compile_watch
from ray_tpu.models import gpt
from ray_tpu.serve.llm import LLMEngine

CFG = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32)   # 8 heads
DRAFT_CFG = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32,
                               n_layers=1, d_model=32, n_heads=4, d_ff=64)


@pytest.fixture(scope="module")
def params():
    return gpt.init_params(CFG, jax.random.key(42))


@pytest.fixture(scope="module")
def draft_params():
    return gpt.init_params(DRAFT_CFG, jax.random.key(7))


def _drive(eng, reqs, max_steps=2000):
    for _ in range(max_steps):
        if all(r.done.is_set() for r in reqs):
            break
        eng.step()
    assert all(r.done.is_set() for r in reqs)
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    return [r.out_ids for r in reqs]


def _engine(params, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_len", 128)
    kw.setdefault("page_size", 16)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("prefill_token_budget", 32)
    return LLMEngine(CFG, params, **kw)


def _ragged_prompts(rng, lengths):
    return [list(map(int, rng.integers(1, CFG.vocab_size, n)))
            for n in lengths]


# Prompt lengths spanning the whole width ladder at page_size 16,
# max_len 128 (max_pages 8): widths 1, 2, 4 and 8 all occur.
_LADDER_LENGTHS = (5, 16, 30, 47, 70, 100, 11)


def _both_arms(params, prompts, *, max_tokens=8, **kw):
    bucketed = _engine(params, prefill_width_bucketing=True, **kw)
    out_b = _drive(bucketed,
                   [bucketed.submit(p, max_tokens=max_tokens)
                    for p in prompts])
    full = _engine(params, prefill_width_bucketing=False, **kw)
    out_f = _drive(full, [full.submit(p, max_tokens=max_tokens)
                          for p in prompts])
    return out_b, out_f, bucketed, full


class TestExactness:
    """Bucketed == full-width, token-for-token, across the matrix."""

    @pytest.mark.parametrize("attn_impl", ["gather", "kernel"])
    def test_bucketed_equals_fullwidth(self, params, attn_impl):
        prompts = _ragged_prompts(np.random.default_rng(0),
                                  _LADDER_LENGTHS)
        out_b, out_f, bucketed, full = _both_arms(
            params, prompts, attn_impl=attn_impl)
        assert out_b == out_f
        mb, mf = bucketed.metrics(), full.metrics()
        # The bucketed arm really dispatched at interior widths; the
        # control arm never left max_pages.
        assert len(mb["prefill_dispatch_widths"]) >= 2
        assert mb["prefill_dispatch_width_p50"] < bucketed.max_pages_per_slot
        assert list(mf["prefill_dispatch_widths"]) == [
            str(full.max_pages_per_slot)]
        # No page leaks in either arm.
        assert mb["kv_pages_free"] == mb["kv_pages_total"]
        assert mf["kv_pages_free"] == mf["kv_pages_total"]

    @pytest.mark.parametrize("k", [2, 4])
    def test_spec_verify_bucketed_exact(self, params, draft_params, k):
        """Spec verify rides the width-sliced decode table view: greedy
        speculative output on the bucketed arm must stay byte-identical
        to the non-speculative full-width baseline."""
        prompts = _ragged_prompts(np.random.default_rng(1), (5, 30, 70, 41))
        spec = dict(spec_draft=DRAFT_CFG, spec_draft_params=draft_params,
                    spec_k=k)
        out_b, out_f, bucketed, _ = _both_arms(
            params, prompts, max_tokens=16, **spec)
        assert out_b == out_f
        base = _engine(params, prefill_width_bucketing=False)
        ref = _drive(base, [base.submit(p, max_tokens=16) for p in prompts])
        assert out_b == ref
        m = bucketed.metrics()
        assert m["spec_ticks"] > 0 and m["spec_proposed"] > 0

    def test_warm_prefix_cow_bucketed_exact(self, params):
        """Warm COW admission (prefill skipped to the first cold token
        — dispatch offsets start mid-sequence) buckets exactly: warm
        streams == cold streams == full-width streams."""
        rng = np.random.default_rng(2)
        shared = _ragged_prompts(rng, (40,))[0]
        prompts = [shared + s
                   for s in _ragged_prompts(rng, (9, 17, 30))]
        cold_b, cold_f, *_ = _both_arms(params, prompts)
        assert cold_b == cold_f
        eng = _engine(params, prefill_width_bucketing=True,
                      prefix_cache=True)
        warm = [_drive(eng, [eng.submit(p, max_tokens=8)])[0]
                for p in prompts for _ in (0, 1)]
        assert warm == [o for o in cold_b for _ in (0, 1)]
        m = eng.metrics()
        assert m["prefix_hits"] > 0

    def test_int8_kv_bucketed_exact(self, params):
        """The quantized pool's per-page scale planes ride the same
        sliced tables: int8 bucketed == int8 full-width."""
        prompts = _ragged_prompts(np.random.default_rng(3),
                                  _LADDER_LENGTHS[:5])
        out_b, out_f, *_ = _both_arms(params, prompts, kv_dtype="int8")
        assert out_b == out_f

    @pytest.mark.skipif(
        len(jax.devices()) < 2,
        reason="tensor-parallel arm needs >= 2 (virtual) devices "
               "(XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    def test_tp2_bucketed_exact(self, params):
        """shard_map twins take the sliced tables replicated: tp=2
        bucketed == tp=2 full-width == tp=1 bucketed."""
        prompts = _ragged_prompts(np.random.default_rng(4), (5, 30, 70))
        out_b, out_f, *_ = _both_arms(params, prompts, tp=2)
        assert out_b == out_f
        one = _engine(params, prefill_width_bucketing=True, tp=1)
        ref = _drive(one, [one.submit(p, max_tokens=8) for p in prompts])
        assert out_b == ref


class TestCompileBudget:
    def test_warmup_precompiles_exact_ladder_then_traffic_adds_zero(
            self, params):
        """`warmup_compile()` lowers exactly the width ladder — one
        (interior, final) pair per pow-2 width, ≤ 2·log₂(max_pages)+2
        programs, each [chunk_rows, chunk] (the full chunks the budget
        holds, not n_slots rows: no row ladder beside the width one) —
        and a subsequent ragged traffic mix compiles NOTHING new (the
        bench's jax_compiles_delta == 0 contract)."""
        from ray_tpu.models.paged_kv import prefill_chunk_paged

        prefill_chunk_paged.clear_cache()
        eng = _engine(params, prefill_width_bucketing=True)
        assert eng.chunk_rows == 2 < eng.n_slots    # budget 32 / chunk 16
        real, shapes = eng._rt.prefill_chunk_paged, set()

        def spy(cfg, prm, toks, *rest, **kw):
            shapes.add(toks.shape)
            return real(cfg, prm, toks, *rest, **kw)

        eng._rt.prefill_chunk_paged = spy
        n = eng.warmup_compile()
        ladder = eng._width_ladder()
        assert ladder == [1, 2, 4, 8]          # max_len 128 / page 16
        assert n == 2 * len(ladder)
        budget = 2 * int(np.log2(eng.max_pages_per_slot)) + 2
        assert prefill_chunk_paged._cache_size() == n <= budget
        prompts = _ragged_prompts(np.random.default_rng(5),
                                  _LADDER_LENGTHS)
        _drive(eng, [eng.submit(p, max_tokens=8) for p in prompts])
        assert prefill_chunk_paged._cache_size() == n, (
            "traffic after warmup must not lower new chunk programs")
        assert shapes == {(eng.chunk_rows, eng.prefill_chunk)}

    def test_warmup_idempotent_and_gated(self, params):
        eng = _engine(params, prefill_width_bucketing=True)
        assert eng.warmup_compile() > 0
        assert eng.warmup_compile() == 0       # once per engine
        full = _engine(params, prefill_width_bucketing=False)
        assert full.warmup_compile() == 2      # one width: two heights, the head in both

    def test_warmup_on_start_knob(self, params):
        """`warmup=True` (llm_warmup_compile) warms at `start()`; the
        default leaves compilation lazy."""
        eng = _engine(params, prefill_width_bucketing=True, warmup=True)
        assert not eng._warmed
        eng.start()
        try:
            assert eng._warmed
        finally:
            eng.stop()
        lazy = _engine(params, prefill_width_bucketing=True)
        lazy.start()
        try:
            assert not lazy._warmed
        finally:
            lazy.stop()


class TestWarmupStorm:
    def test_warmup_ladder_does_not_trip_storm_detector(self, params):
        """Satellite pin: the bucket-ladder warmup walks well past a
        low storm threshold back-to-back, but runs inside
        `compile_watch.warmup_scope()` — a clean boot must file no
        `recompile.storm` event. The detector stays live for real
        (unmarked) compiles."""
        from ray_tpu.models.paged_kv import prefill_chunk_paged

        prefill_chunk_paged.clear_cache()
        compile_watch.install(storm_threshold=2, storm_window_s=300.0)
        try:
            eng = _engine(params, prefill_width_bucketing=True)
            assert eng.warmup_compile() >= 4   # well past threshold 2
            assert compile_watch.storm_log() == []
            # Control: the same volume of UNMARKED compiles trips it.
            for _ in range(3):
                compile_watch.record_compile("width_storm_control", 0.01)
            assert [s["fn"] for s in compile_watch.storm_log()] == [
                "width_storm_control"]
        finally:
            # Re-arm at a threshold the rest of the suite can't cross.
            compile_watch.install(storm_threshold=100000,
                                  storm_window_s=120.0)

    def test_in_warmup_scope_nesting(self):
        assert not compile_watch.in_warmup()
        with compile_watch.warmup_scope():
            assert compile_watch.in_warmup()
            with compile_watch.warmup_scope():
                assert compile_watch.in_warmup()
            assert compile_watch.in_warmup()
        assert not compile_watch.in_warmup()


class TestScheduler:
    def test_mixed_width_tick_issues_one_dispatch_per_bucket(self, params):
        """One budget window packing consecutive chunks of a long prompt
        (done 0 / 16 / 32 → widths 1 / 2 / 4) must dispatch once per
        distinct width, ascending (write-before-attend order). A window
        of one step: an idle tick's allowance is one budget."""
        eng = _engine(params, prefill_width_bucketing=True,
                      prefill_token_budget=48, decode_block=1)
        rng = np.random.default_rng(6)
        rl = eng.submit(_ragged_prompts(rng, (100,))[0], max_tokens=4)
        eng.step()                                # first budget window
        assert eng.stats["prefill_dispatches"] == 3
        assert sorted(eng._dispatch_width_counts) == [1, 2, 4]
        _drive(eng, [rl])
        m = eng.metrics()
        assert len(m["prefill_dispatch_widths"]) >= 3
        assert m["prefill_dispatch_width_max"] == 8   # tail chunks

    def test_single_bucket_tick_stays_one_dispatch(self, params):
        """Equal-width rows — here two single-page prompts in different
        slots — share one dispatch: bucketing must not shatter a
        uniform batch."""
        eng = _engine(params, prefill_width_bucketing=True)
        rng = np.random.default_rng(7)
        reqs = [eng.submit(p, max_tokens=2)
                for p in _ragged_prompts(rng, (5, 7))]
        eng.step()
        assert eng.stats["prefill_dispatches"] == 1
        assert eng._dispatch_width_counts == {1: 1}
        _drive(eng, reqs)

    def test_width_observability_surfaces(self, params):
        """metrics() p50/max + per-width counts, load_snapshot() gauges,
        and the llm_prefill_dispatch_total{width} counter all agree."""
        from ray_tpu.serve import llm as llm_mod

        def widths_counted():
            out = {}
            for key, v in llm_mod._PREFILL_DISPATCH_COUNTER.snapshot():
                out[key[1]] = out.get(key[1], 0) + v
            return out

        before = widths_counted()
        eng = _engine(params, prefill_width_bucketing=True)
        prompts = _ragged_prompts(np.random.default_rng(8), (5, 70))
        _drive(eng, [eng.submit(p, max_tokens=4) for p in prompts])
        m = eng.metrics()
        assert m["prefill_width_bucketing"] is True
        assert m["prefill_dispatch_width_p50"] <= (
            m["prefill_dispatch_width_max"])
        assert m["prefill_dispatches"] == sum(
            m["prefill_dispatch_widths"].values())
        snap = eng.load_snapshot()
        assert snap["prefill_dispatch_width_max"] == (
            m["prefill_dispatch_width_max"])
        after = widths_counted()
        for w, c in m["prefill_dispatch_widths"].items():
            assert after.get(w, 0) - before.get(w, 0) >= c
        eng.reset_stats()
        m2 = eng.metrics()
        assert "prefill_dispatch_width_p50" not in m2
        assert m2["prefill_dispatches"] == 0

    def test_dispatch_failure_drops_later_buckets_for_failed_slot(
            self, params, monkeypatch):
        """A bucket dispatch failure releases its slots; the same tick's
        LATER buckets carry that slot's follow-on chunks and must be
        skipped, not dispatched against a freed slot."""
        eng = _engine(params, prefill_width_bucketing=True,
                      prefill_token_budget=48, decode_block=1)
        rng = np.random.default_rng(9)
        doomed = eng.submit(_ragged_prompts(rng, (100,))[0], max_tokens=4)
        real = eng._dispatch_chunk_bucket
        calls = []

        def boom(batch, width, rows):
            calls.append(width)
            # Fail the way a device error surfaces: release the slots.
            for slot, req, _d, _n in batch:
                req.error = "prefill failed: injected"
                req.done.set()
                eng._release(slot)
            return {row[0] for row in batch}

        monkeypatch.setattr(eng, "_dispatch_chunk_bucket", boom)
        eng.step()  # window packs widths 1/2/4 for the one slot
        assert doomed.done.is_set() and doomed.error is not None
        assert calls == [1], (
            "follow-on buckets must be dropped after their slot failed")
        # The engine keeps serving once the fault clears.
        monkeypatch.setattr(eng, "_dispatch_chunk_bucket", real)
        ok = eng.submit(_ragged_prompts(rng, (30,))[0], max_tokens=4)
        _drive(eng, [ok])
        assert len(ok.out_ids) == 4


class TestConfig:
    def test_attn_impl_auto_resolves_by_backend(self, params):
        """`auto` resolves once at construction: gather off-TPU (this
        suite), kernel on TPU backends; metrics report the resolved
        value."""
        eng = _engine(params, attn_impl="auto")
        expect = "kernel" if jax.default_backend() == "tpu" else "gather"
        assert eng.attn_impl == expect
        assert eng.metrics()["llm_attn_impl"] == expect

    def test_attn_impl_invalid_rejected(self, params):
        with pytest.raises(ValueError, match="gather|kernel|auto"):
            _engine(params, attn_impl="vortex")

    def test_width_bucketing_env_knob(self, params, monkeypatch):
        monkeypatch.setenv("RAY_TPU_LLM_PREFILL_WIDTH_BUCKETING", "0")
        eng = _engine(params)
        assert eng.prefill_width_bucketing is False
        monkeypatch.setenv("RAY_TPU_LLM_PREFILL_WIDTH_BUCKETING", "1")
        assert _engine(params).prefill_width_bucketing is True

    def test_warmup_env_knob(self, params, monkeypatch):
        monkeypatch.setenv("RAY_TPU_LLM_WARMUP_COMPILE", "1")
        eng = _engine(params)
        assert eng._warmup_on_start is True
        assert not eng._warmed     # still lazy until start()
