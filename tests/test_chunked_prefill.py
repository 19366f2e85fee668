"""Chunked prefill + stall-free token-budget scheduler (serve engine).

Exactness first: the chunked-prefill engine must emit the plain forward's
own greedy continuation (tests/plain_reference.py) for every chunk size,
ragged prompt lengths, both attention
implementations, and under preempt-by-recompute pool pressure. Then the
scheduler contracts: the prefill token budget is a hard cap for each
decode step of a tick's window (budget 0 = pure decode ticks), a full
pool stalls prefill instead of preempting it, the chunked path lowers
within the pow-2 width-ladder budget — 2·log₂(max_pages)+2 programs
bucketed, exactly two with bucketing off — and a page-blocked queue head
no longer head-of-line-blocks admission. The prefill kernel runs under interpret=True off-TPU, like the
decode kernel (tests/test_paged_attention.py).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import plain_reference
from ray_tpu.models import gpt
from ray_tpu.serve.llm import LLMEngine

CFG = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return gpt.init_params(CFG, jax.random.key(42))


def _drive(eng, reqs, max_steps=800):
    for _ in range(max_steps):
        if all(r.done.is_set() for r in reqs):
            break
        eng.step()
    assert all(r.done.is_set() for r in reqs)
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    return [r.out_ids for r in reqs]


def _run(params, prompts, *, max_tokens=6, n_slots=4, max_len=128, **kw):
    eng = LLMEngine(CFG, params, n_slots=n_slots, max_len=max_len, **kw)
    out = _drive(eng, [eng.submit(p, max_tokens=max_tokens)
                       for p in prompts])
    return out, eng


def _assert_plain(params, prompts, outs, n):
    """Every stream is the plain forward's greedy continuation of its
    prompt, `n` tokens long."""
    plain_reference.assert_gpt_greedy(CFG, params, prompts, outs, n=n)


def _ragged_prompts(rng, lengths):
    return [list(map(int, rng.integers(1, CFG.vocab_size, n)))
            for n in lengths]


def _spy_chunks(eng, note):
    """Record `note(toks, tables, offsets, valid, kw)` of every
    `prefill_chunk_paged` call the engine makes from here on."""
    real, seen = eng._rt.prefill_chunk_paged, []

    def spy(cfg, params, toks, pool, tables, offsets, valid, **kw):
        seen.append(note(toks, tables, offsets, valid, kw))
        return real(cfg, params, toks, pool, tables, offsets, valid, **kw)

    eng._rt.prefill_chunk_paged = spy
    return seen


def _spy_chunk_shapes(eng):
    """The (tokens, tables, offsets, n_valid) shapes of every call."""
    return _spy_chunks(eng, lambda toks, tables, offsets, valid, kw: (
        toks.shape, tables.shape, offsets.shape, valid.shape))


def _spy_chunk_programs(eng):
    """The (table width, head, live rows) of every call."""
    return _spy_chunks(eng, lambda toks, tables, offsets, valid, kw: (
        tables.shape[1], bool(kw["return_logits"]),
        int((np.asarray(valid) > 0).sum())))


def _parent_rule(eng):
    """The rule before chunk_rows: every chunk dispatch as tall as the
    engine has slots, up to n_slots rows packed into one."""
    eng.chunk_heights = (eng.n_slots,)
    return eng


class TestChunkProgramOnTheFlatPool:
    """`prefill_chunk_paged` itself (no engine): rows land at
    (layer, page, offset) of the pool [L, P+1, ps, H*K], nothing else
    moves, and the kernel reads back what the gather oracle reads."""

    @pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
    def test_rows_land_at_layer_page_offset(self, params, kv_dtype):
        from ray_tpu.models.paged_kv import (init_paged_kv,
                                             prefill_chunk_paged)

        ps, C, n_pages = 8, 12, 6
        rng = np.random.default_rng(9)
        pool = init_paged_kv(CFG, n_pages, ps, kv_dtype)
        assert pool["k"].shape == (CFG.n_layers, n_pages + 1, ps,
                                   CFG.n_heads * CFG.head_dim)
        toks = jnp.asarray(rng.integers(1, CFG.vocab_size, (3, C)),
                           jnp.int32)
        tables = jnp.asarray([[1, 2, 3], [4, 5, 0], [0, 0, 0]], jnp.int32)
        offsets = jnp.asarray([5, 0, 0], jnp.int32)     # row 0: mid-page
        n_valid = jnp.asarray([C, 7, 0], jnp.int32)     # row 2: inert
        outs = {}
        for impl in ("gather", "kernel"):
            outs[impl] = prefill_chunk_paged(
                CFG, params, toks, jax.tree.map(jnp.copy, pool), tables,
                offsets, n_valid, attn_impl=impl)
        (lg_g, pool_g), (lg_k, pool_k) = outs["gather"], outs["kernel"]
        live = np.asarray(n_valid) > 0
        np.testing.assert_allclose(np.asarray(lg_k)[live],
                                   np.asarray(lg_g)[live],
                                   rtol=2e-3, atol=2e-3)
        k = np.asarray(pool_g["k"]).astype(np.float32)
        for layer in range(CFG.n_layers):
            # Row 0 wrote positions 5..16: page 1 from offset 5, all of
            # page 2, page 3's first row. Row 1 wrote 0..6 of page 4.
            written = np.abs(k[layer]).sum(axis=2) > 0       # [P+1, ps]
            assert not written[1, :5].any() and written[1, 5:].all()
            assert written[2].all()
            assert written[3, 0] and not written[3, 1:].any()
            assert written[4, :7].all() and not written[4, 7:].any()
            assert not written[5:].any()
        atol = 1e-5 if kv_dtype == "bf16" else 1     # int8: one rounding step
        for name in pool_g:
            np.testing.assert_allclose(
                np.asarray(pool_k[name]).astype(np.float32)[:, 1:],
                np.asarray(pool_g[name]).astype(np.float32)[:, 1:],
                rtol=1e-4, atol=atol, err_msg=name)


class TestExactness:
    """Chunked == the plain forward, token-for-token."""

    @pytest.mark.parametrize("chunk", [32, 64, 128])
    def test_matches_plain_forward_across_chunk_sizes(self, params, chunk):
        prompts = _ragged_prompts(
            np.random.default_rng(0), (3, 17, 33, 50, 7, 40))
        chunked, eng = _run(params, prompts, page_size=16,
                            prefill_chunk=chunk,
                            prefill_token_budget=chunk)
        _assert_plain(params, prompts, chunked, 6)
        # The default engine (the knob's chunk) emits the same streams.
        default, _ = _run(params, prompts, page_size=16)
        assert chunked == default
        m = eng.metrics()
        assert m["kv_pages_free"] == m["kv_pages_total"]
        assert m["prefill_chunks"] > 0

    def test_kernel_impl_matches(self, params):
        """The ragged prefill Pallas kernel (interpret mode off-TPU)
        produces the same greedy streams as the gather default."""
        prompts = _ragged_prompts(np.random.default_rng(1), (5, 23, 41))
        gather, _ = _run(params, prompts, page_size=16,
                         prefill_chunk=16, prefill_token_budget=32)
        kernel, eng = _run(params, prompts, page_size=16,
                           prefill_chunk=16, prefill_token_budget=32,
                           attn_impl="kernel")
        assert kernel == gather
        assert eng.metrics()["llm_attn_impl"] == "kernel"

    def test_exact_under_preemption(self, params):
        """Pool sized so concurrent slots MUST run dry mid-generation:
        chunked admission + preempt-by-recompute still reproduce the
        plain forward's streams exactly."""
        prompts = [[5, 9, 2], [17, 3], [2, 4, 6], [8, 1, 0]]
        chunked, eng = _run(params, prompts, page_size=4,
                            n_pages=7, max_tokens=10, max_len=64,
                            prefill_chunk=4, prefill_token_budget=8)
        _assert_plain(params, prompts, chunked, 10)
        m = eng.metrics()
        assert m["preemptions"] > 0
        assert m["kv_pages_free"] == m["kv_pages_total"]

    def test_decode_never_truncated_by_prefill_contention(self, params):
        """Chunked over-admission must not starve an in-flight decode:
        a long prompt admitted mid-generation grows chunk-by-chunk until
        the pool runs dry, and the decoding slot then needs a page at a
        boundary. The window fitter reclaims from the mid-prefill slot
        (recompute) instead of truncating the decode."""
        rng = np.random.default_rng(11)
        longp = list(map(int, rng.integers(1, CFG.vocab_size, 24)))
        eng = LLMEngine(CFG, params, n_slots=2, max_len=64, page_size=4,
                        n_pages=7, decode_block=1, prefill_chunk=4,
                        prefill_token_budget=4)
        a = eng.submit([5, 9, 2], max_tokens=12)
        while a.first_token_at is None:
            eng.step()
        b = eng.submit(longp, max_tokens=2)
        _drive(eng, [a, b])
        assert not a.truncated and not b.truncated
        assert eng.stats["preemptions"] > 0   # contention actually hit
        _assert_plain(params, [[5, 9, 2]], [a.out_ids], 12)
        _assert_plain(params, [longp], [b.out_ids], 2)
        m = eng.metrics()
        assert m["kv_pages_free"] == m["kv_pages_total"]

    def test_midflight_admission_exact(self, params):
        """A long prompt prefilling chunk-by-chunk must not perturb a
        request already decoding (the fused window walks every slot: the
        mid-prefill slot's table row is masked to the null page)."""
        rng = np.random.default_rng(3)
        longp = _ragged_prompts(rng, (40,))[0]
        eng = LLMEngine(CFG, params, n_slots=2, max_len=128, page_size=8,
                        prefill_chunk=8, prefill_token_budget=8,
                        decode_block=4)
        ra = eng.submit([5, 9, 2], max_tokens=20)
        for _ in range(3):
            eng.step()
        assert ra.first_token_at is not None  # A is decoding
        rb = eng.submit(longp, max_tokens=8)  # 5 chunks, interleaved
        _drive(eng, [ra, rb])
        _assert_plain(params, [[5, 9, 2]], [ra.out_ids], 20)
        _assert_plain(params, [longp], [rb.out_ids], 8)

    def test_prompt_cap_is_the_cache(self, params):
        """No bucket bounds a prompt: one longer than any chunk is
        admissible up to the cache cap (max_len - 1), and one past it is
        refused at submit."""
        rng = np.random.default_rng(4)
        prompt = _ragged_prompts(rng, (100,))[0]
        chunked, eng = _run(params, [prompt], max_tokens=4, page_size=16,
                            max_len=256, prefill_chunk=32,
                            prefill_token_budget=64)
        _assert_plain(params, [prompt], chunked, 4)
        with pytest.raises(ValueError, match="too long.*cache bound"):
            eng.submit(_ragged_prompts(rng, (256,))[0], max_tokens=4)


class TestChunkRows:
    """The chunk program is as tall as the tick's budget can fill
    (`chunk_rows`), not as the engine has slots: same programs per
    (width, head), same tokens a tick, same streams."""

    @pytest.mark.parametrize("chunk,budget,n_slots,rows", [
        (16, 0, 4, 1),          # an idle tick still advances one chunk
        (16, 16, 4, 1),
        (128, 256, 32, 2),      # the serving cell's defaults
        (128, 384, 32, 3),
        (16, 40, 4, 3),         # a non-multiple rounds up
        (16, 64, 4, 4),         # n_slots x chunk: the old program
        (16, 4096, 4, 4),       # never more rows than slots
    ])
    @pytest.mark.parametrize("decode_block", [8, 1])
    def test_chunk_rows_from_budget(self, params, chunk, budget, n_slots,
                                    rows, decode_block):
        """The program's height is what ONE budget fills, whatever the
        window's length multiplies the tick's allowance by."""
        eng = LLMEngine(CFG, params, n_slots=n_slots, max_len=256,
                        page_size=16, n_pages=20, prefill_chunk=chunk,
                        prefill_token_budget=budget,
                        decode_block=decode_block)
        assert eng.decode_block == decode_block
        assert eng.chunk_rows == rows
        assert eng.metrics()["chunk_rows"] == rows
        assert eng.load_snapshot()["chunk_rows"] == rows

    @pytest.mark.parametrize("budget", [0, 16, 32, 48])
    def test_dispatch_arrays_are_chunk_rows_tall(self, params, budget):
        """Warm-up's inert ladder and every live dispatch hand the
        program [chunk_rows, chunk] arrays, whatever the batch holds."""
        eng = LLMEngine(CFG, params, n_slots=4, max_len=128,
                        page_size=16, prefill_chunk=16,
                        prefill_token_budget=budget)
        seen = _spy_chunk_shapes(eng)
        assert eng.warmup_compile() == 2 * len(eng._width_ladder())
        prompts = _ragged_prompts(np.random.default_rng(12), (50, 3, 17))
        _drive(eng, [eng.submit(p, max_tokens=3) for p in prompts])
        rows = eng.chunk_rows
        assert len(seen) > 2 * len(eng._width_ladder())
        for toks, tables, offsets, valid in seen:
            assert toks == (rows, 16)
            assert tables[0] == rows and tables[1] in eng._width_ladder()
            assert offsets == valid == (rows,)

    @pytest.mark.parametrize("case", ["long", "short_together",
                                      "warm_beside_cold"])
    def test_streams_equal_default_and_parent_rule(self, params, case):
        """Same tokens for the same requests as the default engine (the
        knob's chunk and budget), held to the plain forward, and
        as the parent's rule (n_slots rows a dispatch), for a lone long
        prompt, n_slots short prompts at once, and a warm-prefix row
        beside a cold one."""
        rng = np.random.default_rng(13)
        kw = dict(n_slots=6, max_len=128, page_size=16)
        chunked = dict(kw, prefill_chunk=16, prefill_token_budget=32,
                       prefix_cache=(case == "warm_beside_cold"))
        first = []
        if case == "long":
            prompts = _ragged_prompts(rng, (100,))
        elif case == "short_together":
            prompts = _ragged_prompts(rng, (5, 7, 3, 9, 4, 6))
        else:
            shared = _ragged_prompts(rng, (40,))[0]
            first = [shared + _ragged_prompts(rng, (9,))[0]]
            prompts = [shared + _ragged_prompts(rng, (13,))[0],
                       _ragged_prompts(rng, (37,))[0]]

        def serve(eng):
            for p in first:         # donates the shared prefix
                _drive(eng, [eng.submit(p, max_tokens=6)])
            return _drive(eng, [eng.submit(p, max_tokens=6)
                                for p in prompts]), eng

        default, _ = serve(LLMEngine(CFG, params, **kw))
        _assert_plain(params, prompts, default, 6)
        parent, _ = serve(_parent_rule(LLMEngine(CFG, params, **chunked)))
        out, eng = serve(LLMEngine(CFG, params, **chunked))
        assert eng.chunk_rows == 2
        assert out == parent == default
        if case == "warm_beside_cold":
            assert eng.metrics()["prefix_hits"] > 0

    def test_short_prompts_take_several_dispatches_a_tick(self, params):
        """More rows than full chunks fit the budget: the tick runs the
        program several times (where the parent's rule ran one
        n_slots-row program), FCFS, and never past the budget of each
        step of its window."""
        rng = np.random.default_rng(14)
        budget = 32
        kw = dict(n_slots=6, max_len=128, page_size=16, prefill_chunk=16,
                  prefill_token_budget=budget, decode_block=1)
        eng = LLMEngine(CFG, params, **kw)
        seen = _spy_chunk_shapes(eng)
        reqs = [eng.submit(p, max_tokens=4)
                for p in _ragged_prompts(rng, (5, 7, 3, 9, 4, 6))]
        eng.step()              # idle, a window of one step: one budget
        # 5+7 | 3+9 | 4 (6 more would pass the budget): three dispatches
        # of two rows, 28 tokens; the last prompt waits for the next tick.
        assert eng.stats["prefill_dispatches"] == len(seen) == 3
        assert eng.stats["prefill_tokens"] == 28 <= budget
        assert [r.first_token_at is not None for r in reqs] == (
            [True] * 5 + [False])
        while not all(r.done.is_set() for r in reqs):
            pt = eng.stats["prefill_tokens"]
            eng.step()
            assert (eng.stats["prefill_tokens"] - pt
                    <= budget * eng.decode_block)
        firsts = [r.first_token_at for r in reqs]
        assert firsts == sorted(firsts), "first tokens left FCFS order"
        assert all(shape[0] == (2, 16) for shape in seen)
        parent = _parent_rule(LLMEngine(CFG, params, **kw))
        again = [parent.submit(list(r.prompt_ids[:r.n_prompt]),
                               max_tokens=4) for r in reqs]
        parent.step()
        assert parent.stats["prefill_dispatches"] == 1
        assert parent.stats["prefill_tokens"] == 28
        assert _drive(parent, again) == [r.out_ids for r in reqs]


class TestWindowAllowance:
    """A tick beside a decode window of k steps may place k budgets of
    prompt tokens; the program stays as tall as ONE budget fills and
    runs once per `chunk_rows` rows of one table width."""

    KW = dict(n_slots=8, max_len=128, page_size=8, prefill_chunk=8,
              prefill_token_budget=16)

    def _beside_decode(self, params, lengths, *, rng, max_tokens=4, **kw):
        """An engine with one request decoding (for the whole test) and
        `lengths` prompts submitted behind it. → (eng, reqs)."""
        eng = LLMEngine(CFG, params, **dict(self.KW, **kw))
        first = eng.submit([5, 9, 2], max_tokens=100)
        while first.first_token_at is None:
            eng.step()
        return eng, [eng.submit(p, max_tokens=max_tokens)
                     for p in _ragged_prompts(rng, lengths)]

    def test_decode_block_one_schedules_as_the_parent(self, params):
        """A window of one step carries one budget: tick by tick the
        tokens placed and the (width, head, live rows) dispatches are
        what the parent's loop gave — pack up to chunk_rows rows FCFS
        within the budget, dispatch them by ascending width, go round
        again — here replayed beside the engine."""
        eng, reqs = self._beside_decode(
            params, (40, 33, 8, 40, 16, 24), rng=np.random.default_rng(21),
            decode_block=1)
        seen = _spy_chunk_programs(eng)
        budget, chunk, cap = 16, 8, eng.chunk_rows
        assert cap == 2
        progress = {id(r): 0 for r in reqs}
        ticks = 0
        while not all(r.first_token_at is not None for r in reqs):
            # the parent's rule over the prompts still mid-prefill
            expect, spent = [], 0
            waiting = [r for r in reqs               # admitted FCFS
                       if progress[id(r)] < len(r.prompt_ids)]
            while True:
                batch, planned = [], 0
                for r in waiting:
                    done, total = progress[id(r)], len(r.prompt_ids)
                    while done < total and len(batch) < cap:
                        n = min(chunk, total - done)
                        if spent + planned + n > budget:
                            break
                        batch.append((eng._chunk_width(done, n),
                                      done + n == total))
                        planned += n
                        done += n
                        progress[id(r)] = done
                    if done < total or len(batch) >= cap:
                        break
                if not batch:
                    break
                spent += planned
                for w in sorted({w for w, _ in batch}):
                    rows = [h for ww, h in batch if ww == w]
                    expect.append((w, any(rows), len(rows)))
            n0, pt = len(seen), eng.stats["prefill_tokens"]
            eng.step()
            ticks += 1
            assert eng.stats["prefill_tokens"] - pt == spent <= budget
            assert seen[n0:] == expect
        assert ticks >= sum(len(r.prompt_ids) for r in reqs) // budget
        _drive(eng, reqs)

    def test_lone_prompt_dispatches_the_programs_of_many_in_flight(
            self, params):
        """What a warm-up by lone requests relies on: a prompt alone in
        an idle engine dispatches every (width, head) program that
        prompts of its length dispatch when several are in flight
        beside a decode window, whole-tick buckets and all."""
        rng = np.random.default_rng(22)
        lone = LLMEngine(CFG, params, **self.KW)
        seen_lone = _spy_chunk_programs(lone)
        _drive(lone, [lone.submit(_ragged_prompts(rng, (100,))[0],
                                  max_tokens=4)])
        eng, reqs = self._beside_decode(params, (100,) * 5, rng=rng)
        seen = _spy_chunk_programs(eng)
        eng.step()
        # 16 x 8 tokens a tick: more than one prompt's 13 rows
        assert eng.stats["prefill_tokens"] >= 100 + 16
        _drive(eng, reqs)
        assert {(w, h) for w, h, _ in seen} == {
            (w, h) for w, h, _ in seen_lone} == {
            (1, False), (2, False), (4, False), (8, False), (16, False),
            (16, True)}
        assert eng.metrics()["prefill_dispatch_widths"].keys() == (
            lone.metrics()["prefill_dispatch_widths"].keys())
        # rows of one width from different prompts fill a program together
        assert max(n for *_x, n in seen) == eng.chunk_rows == 2
        assert eng.metrics()["prefill_row_fill"] > (
            lone.metrics()["prefill_row_fill"])

    def test_whole_tick_buckets_ascend_and_first_tokens_stay_fcfs(
            self, params):
        """Within a tick the dispatches run in ascending table width
        (write before attend across a prompt's own rows), each bucket
        in FCFS order, so prompts of one length reach their first
        tokens in the order they were admitted."""
        eng, reqs = self._beside_decode(
            params, (60,) * 6, rng=np.random.default_rng(23))
        seen = _spy_chunk_programs(eng)
        while not all(r.first_token_at is not None for r in reqs):
            n0 = len(seen)
            eng.step()
            widths = [w for w, _h, _n in seen[n0:]]
            assert widths == sorted(widths)
        firsts = [r.first_token_at for r in reqs]
        assert firsts == sorted(firsts), "first tokens left FCFS order"
        assert len({w for w, _h, _n in seen}) > 2
        _drive(eng, reqs)

    @pytest.mark.parametrize("case", ["long", "short_together",
                                      "warm_beside_cold"])
    def test_streams_equal_default_and_one_budget_a_tick(self, params,
                                                         case):
        """Beside a decoding request, a window's worth of budgets gives
        the streams of the default engine (the knob's chunk and budget),
        held to the plain forward, and of the parent's schedule
        (one budget a tick: decode_block 1), for a long prompt, six
        short prompts at once, and a warm-prefix row beside a cold
        one: only the order of dispatches changes."""
        kw = dict(n_slots=8, max_len=128, page_size=16)
        chunked = dict(kw, prefill_chunk=16, prefill_token_budget=32,
                       prefix_cache=(case == "warm_beside_cold"))
        rng = np.random.default_rng(24)
        first = []
        if case == "long":
            prompts = _ragged_prompts(rng, (100, 90))
        elif case == "short_together":
            prompts = _ragged_prompts(rng, (5, 7, 3, 9, 4, 6))
        else:
            shared = _ragged_prompts(rng, (40,))[0]
            first = [shared + _ragged_prompts(rng, (9,))[0]]
            prompts = [shared + _ragged_prompts(rng, (13,))[0],
                       _ragged_prompts(rng, (37,))[0]]

        def serve(eng):
            for p in first:         # donates the shared prefix
                _drive(eng, [eng.submit(p, max_tokens=6)])
            decoding = eng.submit([5, 9, 2], max_tokens=40)
            while decoding.first_token_at is None:
                eng.step()
            pt = eng.stats["prefill_tokens"]
            reqs = [eng.submit(p, max_tokens=6) for p in prompts]
            eng.step()
            placed = eng.stats["prefill_tokens"] - pt
            return _drive(eng, reqs + [decoding]), placed

        default, _ = serve(LLMEngine(CFG, params, **kw))
        _assert_plain(params, prompts + [[5, 9, 2]], default, None)
        parent, placed_1 = serve(LLMEngine(CFG, params, decode_block=1,
                                           **chunked))
        out, placed_8 = serve(LLMEngine(CFG, params, decode_block=8,
                                        **chunked))
        assert out == parent == default
        assert placed_1 <= 32 < placed_8 <= 32 * 8


class TestPoolPressure:
    """Full slots need more pages than the pool has: prefill stops
    short of the pages the decoding slots are about to need, so the
    pool stalls prompts instead of preempting them."""

    KW = dict(n_slots=6, max_len=64, page_size=8, n_pages=20, decode_block=4,
              prefill_chunk=8, prefill_token_budget=8)

    def _serve(self, params, eng):
        """12 requests of 20 + 20 tokens (5 pages each at the end; six
        slots would hold 30 of the pool's 20), two arriving a tick."""
        rng = np.random.default_rng(31)
        prompts = _ragged_prompts(rng, (20,) * 12)
        reqs, worst = [], 0
        for _ in range(600):
            for p in prompts[len(reqs):len(reqs) + 2]:
                reqs.append(eng.submit(p, max_tokens=20))
            eng.step()
            acct = eng.page_accounting()
            assert acct["closure"] and acct["refs_consistent"], acct
            worst = max(worst, len(eng._decode_ready_slots()))
            if len(reqs) == len(prompts) and all(
                    r.done.is_set() for r in reqs):
                break
        assert all(r.done.is_set() and r.error is None
                   and not r.truncated and len(r.out_ids) == 20
                   for r in reqs)
        m = eng.metrics()
        assert m["kv_pages_free"] == m["kv_pages_total"]
        return [r.out_ids for r in reqs], m, worst

    def test_full_pool_stalls_prefill_and_preempts_nobody(self, params):
        ample, m_ample, _ = self._serve(
            params, LLMEngine(CFG, params, **dict(self.KW, n_pages=64)))
        assert m_ample["preemptions"] == 0
        out, m, worst = self._serve(params, LLMEngine(CFG, params,
                                                      **self.KW))
        assert out == ample
        assert m["preemptions"] == 0
        assert m["kv_pages_free_min"] <= 2, "the pool was never full"
        assert worst >= 3
        assert m["prefill_allowance_used"] < (
            m_ample["prefill_allowance_used"]), "the pool never bound"

    def test_preemptions_counts_what_does_happen(self, params):
        """The same traffic with nothing set aside: prefill grows until
        the pool is dry, the window fitter takes a mid-prefill slot's
        pages back, and `preemptions` says so; the streams still equal
        the ample pool's (preempt by recompute is exact)."""
        ample, _m, _ = self._serve(
            params, LLMEngine(CFG, params, **dict(self.KW, n_pages=64)))
        eng = LLMEngine(CFG, params, **self.KW)
        eng._next_page_needed = lambda slot, position, held: 0
        out, m, _ = self._serve(params, eng)
        assert m["preemptions"] > 0
        assert out == ample


class TestCompileCount:
    def test_chunked_path_lowers_within_width_ladder_budget(self, params):
        """The whole point of the fixed chunk shape: ragged prompt
        lengths, multi-chunk and single-chunk prompts, partial tails —
        at most one (interior, final) program pair PER pow-2 table
        width, not buckets × ladder. This geometry (max_len 128, page
        size 16 → max_pages 8) allows widths {1, 2, 4, 8}: budget
        2·log₂(8)+2 = 8. The width-bucketing-off control arm below
        keeps the original PR 4 pin of exactly two."""
        from ray_tpu.models.paged_kv import prefill_chunk_paged

        prefill_chunk_paged.clear_cache()
        prompts = _ragged_prompts(
            np.random.default_rng(5), (3, 16, 17, 33, 50, 64, 7))
        chunked, _ = _run(params, prompts, page_size=16,
                          prefill_chunk=16, prefill_token_budget=32)
        assert prefill_chunk_paged._cache_size() <= 8

    def test_fullwidth_control_arm_keeps_two_program_pin(self, params):
        """`prefill_width_bucketing=False` restores the PR 4 contract
        bit-for-bit: every dispatch at max_pages width, two programs."""
        from ray_tpu.models.paged_kv import prefill_chunk_paged

        prefill_chunk_paged.clear_cache()
        prompts = _ragged_prompts(
            np.random.default_rng(5), (3, 16, 17, 33, 50, 64, 7))
        chunked, _ = _run(params, prompts, page_size=16,
                          prefill_chunk=16, prefill_token_budget=32,
                          prefill_width_bucketing=False)
        assert prefill_chunk_paged._cache_size() <= 2


class TestScheduler:
    def test_budget_zero_is_pure_decode_tick(self, params):
        """With decode in flight and budget 0, a tick runs ZERO prefill
        tokens; the queued prompt only advances once decode drains."""
        rng = np.random.default_rng(6)
        longp = _ragged_prompts(rng, (40,))[0]
        eng = LLMEngine(CFG, params, n_slots=2, max_len=128,
                        page_size=8,
                        prefill_chunk=8, prefill_token_budget=0,
                        decode_block=1)
        ra = eng.submit([5, 9, 2], max_tokens=30)
        while ra.first_token_at is None:
            eng.step()
        base = eng.stats["prefill_tokens"]
        rb = eng.submit(longp, max_tokens=4)
        while not ra.done.is_set():
            pt = eng.stats["prefill_tokens"]
            eng.step()
            if not ra.done.is_set():
                assert eng.stats["prefill_tokens"] == pt, (
                    "budget-0 tick ran prefill while decode was active")
        assert eng.stats["prefill_tokens"] == base
        _drive(eng, [rb])  # idle ticks still make progress at budget 0
        assert len(rb.out_ids) == 4

    @pytest.mark.parametrize("decode_block", [1, 2, 8])
    def test_budget_is_a_hard_cap(self, params, decode_block):
        """Oversubscribed queue (many multi-chunk prompts + active
        decode): no tick ever exceeds one token budget for each decode
        step of the window it runs beside — the parent's cap, one
        budget a tick, at decode_block 1 — and a tick with a window of
        several steps does use more than one."""
        rng = np.random.default_rng(7)
        budget, chunk = 16, 8
        eng = LLMEngine(CFG, params, n_slots=6, max_len=128,
                        page_size=8,
                        prefill_chunk=chunk, prefill_token_budget=budget,
                        decode_block=decode_block)
        reqs = [eng.submit(p, max_tokens=12)
                for p in _ragged_prompts(rng, (40, 33, 25, 40, 17, 40))]
        # First request(s) reach decode, then every later tick must cap.
        while not any(r.first_token_at is not None for r in reqs):
            eng.step()
        most = 0
        while not all(r.done.is_set() for r in reqs):
            pt = eng.stats["prefill_tokens"]
            decoding = eng._decode_ready_slots()
            steps = eng._pick_window(decoding) if decoding else 0
            eng.step()
            spent = eng.stats["prefill_tokens"] - pt
            if decoding:
                assert steps <= decode_block
                assert spent <= budget * steps, (
                    f"tick ran {spent} prefill tokens beside a window of "
                    f"{steps} steps, budget {budget} a step")
                most = max(most, spent)
        assert all(r.error is None for r in reqs)
        assert (most == budget) if decode_block == 1 else (most > budget)
        m = eng.metrics()
        assert 0 < m["prefill_allowance_used"] <= 1
        assert m["prefill_tokens"] <= m["prefill_allowance"]
        eng.reset_stats()
        assert eng.metrics()["prefill_allowance_used"] == 0

    def test_bad_configs_rejected(self, params):
        with pytest.raises(ValueError, match="prefill_token_budget"):
            LLMEngine(CFG, params, n_slots=2, max_len=64, prefill_chunk=16,
                      prefill_token_budget=8)
        # Negative budget would silently behave like 0 (pure-decode ticks)
        # — must be rejected, not accepted as "unlimited".
        with pytest.raises(ValueError, match="prefill_token_budget"):
            LLMEngine(CFG, params, n_slots=2, max_len=64, prefill_chunk=16,
                      prefill_token_budget=-1)
        # A chunk wider than the widest admissible prompt (max_len - 1)
        # would only ever pad — rejected like the other bad knobs.
        with pytest.raises(ValueError, match="prefill_chunk"):
            LLMEngine(CFG, params, n_slots=2, max_len=64, prefill_chunk=128,
                      prefill_token_budget=128)
        # Empty prompt: it would never build a chunk row and wedge the
        # slot forever; rejected up front.
        eng = LLMEngine(CFG, params, n_slots=2, max_len=64,
                        page_size=16,
                        prefill_chunk=16, prefill_token_budget=16)
        with pytest.raises(ValueError, match="non-empty"):
            eng.submit([], max_tokens=4)


class TestAdmissionLookahead:
    def test_blocked_head_does_not_block_small_requests(self, params):
        """A queue head whose pages don't fit no longer stalls admission:
        a small request behind it is admitted (bounded lookahead), the
        head keeps its queue position and completes once pages free."""
        rng = np.random.default_rng(8)
        # Pool of 6 pages (ps=4). R1 occupies a slot and decodes slowly.
        eng = LLMEngine(CFG, params, n_slots=2, max_len=64,
                        page_size=4,
                        n_pages=6, decode_block=1)
        r1 = eng.submit([5, 9, 2], max_tokens=24)
        while r1.first_token_at is None:
            eng.step()
        # big needs 6 pages — blocked while R1 holds any.
        big = eng.submit(list(map(int, rng.integers(1, CFG.vocab_size, 20))),
                         max_tokens=4)
        small = eng.submit([7, 7], max_tokens=4)  # 1 page: fits now
        for _ in range(200):
            eng.step()
            if small.done.is_set():
                break
        assert small.done.is_set(), "small request was HOL-blocked"
        assert not big.done.is_set() or big.first_token_at is not None
        _drive(eng, [r1, big])  # no starvation: the head still completes
        assert big.error is None and len(big.out_ids) == 4

    def test_lookahead_also_in_chunked_mode(self, params):
        """Same head-of-line fix under chunked admission (head blocked on
        its FIRST CHUNK of pool headroom)."""
        rng = np.random.default_rng(9)
        eng = LLMEngine(CFG, params, n_slots=2, max_len=64,
                        page_size=4,
                        n_pages=7, decode_block=1, prefill_chunk=20,
                        prefill_token_budget=20)
        r1 = eng.submit([5, 9, 2], max_tokens=24)
        while r1.first_token_at is None:
            eng.step()
        big = eng.submit(list(map(int, rng.integers(1, CFG.vocab_size, 20))),
                         max_tokens=4)   # first chunk needs 5 pages
        small = eng.submit([7, 7], max_tokens=4)
        for _ in range(200):
            eng.step()
            if small.done.is_set():
                break
        assert small.done.is_set(), "small request was HOL-blocked"
        _drive(eng, [r1, big])
        assert big.error is None and len(big.out_ids) == 4


class TestObservability:
    def test_prefill_chunk_histogram_and_ttft_breakdown(self, params):
        from ray_tpu import profiling
        from ray_tpu.serve.llm import _PREFILL_CHUNK_HIST

        prompts = _ragged_prompts(np.random.default_rng(10), (33, 17))
        _, eng = _run(params, prompts, page_size=16,
                      prefill_chunk=16, prefill_token_budget=32)
        m = eng.metrics()
        assert m["prefill_chunk"] == 16
        assert m["prefill_token_budget"] == 32
        assert m["ttft_ms_p50"] > 0
        assert m["ttft_ms_p95"] >= m["ttft_ms_p50"]
        counts, _sums = _PREFILL_CHUNK_HIST.snapshot_hist()
        assert counts, "chunk dispatches observed no histogram samples"
        # Sampled TTFT breakdown spans (first request always emits).
        names = {e.get("name") for e in profiling.peek_events()}
        assert {"llm.ttft", "llm.ttft.queue_wait", "llm.ttft.prefill",
                "llm.ttft.first_token"} <= names
        ev = next(e for e in profiling.peek_events()
                  if e.get("name") == "llm.ttft")
        assert "trace_id" in ev.get("args", {})

    @pytest.mark.parametrize("n_prompt,fill", [(32, 1.0), (48, 1.0),
                                               (33, 33 / 48), (5, 5 / 16)])
    def test_prefill_row_fill(self, params, n_prompt, fill):
        """Prompt tokens placed over positions dispatched: 1.0 for full
        chunks at chunk_rows 1, less with a padded tail; zeroed by
        reset_stats() with the stats it reads."""
        eng = LLMEngine(CFG, params, n_slots=2, max_len=128,
                        page_size=16, prefill_chunk=16,
                        prefill_token_budget=16)
        assert eng.chunk_rows == 1
        assert eng.metrics()["prefill_row_fill"] == 0
        _drive(eng, [eng.submit(list(range(1, n_prompt + 1)),
                                max_tokens=2)])
        assert eng.metrics()["prefill_row_fill"] == pytest.approx(fill)
        eng.reset_stats()
        assert eng.metrics()["prefill_row_fill"] == 0

    def test_prefill_row_fill_counts_inert_rows(self, params):
        """At chunk_rows 2 a lone chunk in its width bucket pads with an
        inert row: a 32-token prompt's chunks sit at widths 1 and 2, two
        dispatches of 2 x 16 positions for 32 tokens."""
        _, eng = _run(params, [list(range(1, 33))], page_size=16,
                      prefill_chunk=16, prefill_token_budget=32)
        m = eng.metrics()
        assert m["chunk_rows"] == 2 and m["prefill_dispatches"] == 2
        assert m["prefill_row_fill"] == pytest.approx(0.5)

    def test_prefill_block_fill(self):
        """Live pages attended over the pages the prefill kernel's live
        kv blocks fetched, at `opt-1.3b.batch`'s geometry (page 64, chunk
        128, two rows a program, a 1,020-token prompt): the chunks attend
        2, 4, ... 16 pages = 72, at table widths 2, 4, 8, 8, 16, 16, 16,
        16 in blocks of min(4, width) pages = 2 + 4 + 8 + 8 + 12 + 12 +
        16 + 16 = 78 fetched. 0 with no prefill, and after
        reset_stats()."""
        cfg = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32,
                                 max_seq=2048)
        eng = LLMEngine(cfg, gpt.init_params(cfg, jax.random.key(1)),
                        n_slots=2, max_len=2048, page_size=64, n_pages=40,
                        prefill_chunk=128, prefill_token_budget=256)
        assert eng.chunk_rows == 2
        assert eng.metrics()["prefill_block_fill"] == 0
        rng = np.random.default_rng(3)
        _drive(eng, [eng.submit(
            list(map(int, rng.integers(1, cfg.vocab_size, 1020))),
            max_tokens=2)])
        m = eng.metrics()
        assert (m["prefill_pages_live"], m["prefill_pages_fetched"]) == (
            72, 78)
        assert m["prefill_block_fill"] == pytest.approx(72 / 78)
        eng.reset_stats()
        assert eng.metrics()["prefill_block_fill"] == 0

    def test_decode_block_fill(self):
        """Live pages the decoding slots attend over the pages of the
        decode kernel's live kv blocks, summed once a decode window, at
        `opt-1.3b.batch`'s page (64) and `decode_block_pages`' answer
        for the tiny model's pool (every column of a table one block):
        a 100-token prompt decodes over 2 live pages in a table 2 wide
        (fill 1), a 130-token one over 3 in a table 4 wide (3 / 4). 0
        before any window, and after reset_stats()."""
        from ray_tpu.ops.paged_attention import decode_block_pages

        cfg = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32,
                                 max_seq=2048)
        eng = LLMEngine(cfg, gpt.init_params(cfg, jax.random.key(1)),
                        n_slots=2, max_len=2048, page_size=64, n_pages=40,
                        prefill_chunk=128, prefill_token_budget=256)
        lanes = cfg.n_heads * cfg.head_dim
        assert [decode_block_pages(w, 64, lanes, 4, cfg.n_heads)
                for w in (1, 2, 4)] == [1, 2, 4]
        assert eng.metrics()["decode_block_fill"] == 0
        rng = np.random.default_rng(3)
        for n_prompt, live, width in ((100, 2, 2), (130, 3, 4)):
            eng.reset_stats()
            assert eng.metrics()["decode_block_fill"] == 0
            _drive(eng, [eng.submit(
                list(map(int, rng.integers(1, cfg.vocab_size, n_prompt))),
                max_tokens=4)])
            m = eng.metrics()
            windows = m["decode_windows"]
            assert windows >= 1
            assert (m["decode_pages_live"], m["decode_pages_fetched"]) == (
                live * windows, width * windows)
            assert m["decode_block_fill"] == pytest.approx(live / width)

    def test_decode_live_column_share(self):
        """The same live pages over EVERY column of the decode call's
        table (n_slots x the window's width): what the decode kernel
        fetches of a (slot, column) grid since PR 41. Two slots, one
        decoding: 2 live pages of 2 x 2 columns, then 3 of 2 x 4; 0
        before any window and after reset_stats()."""
        cfg = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32,
                                 max_seq=2048)
        eng = LLMEngine(cfg, gpt.init_params(cfg, jax.random.key(1)),
                        n_slots=2, max_len=2048, page_size=64, n_pages=40,
                        prefill_chunk=128, prefill_token_budget=256)
        assert eng.metrics()["decode_live_column_share"] == 0
        rng = np.random.default_rng(3)
        for n_prompt, live, width in ((100, 2, 2), (130, 3, 4)):
            eng.reset_stats()
            assert eng.metrics()["decode_live_column_share"] == 0
            _drive(eng, [eng.submit(
                list(map(int, rng.integers(1, cfg.vocab_size, n_prompt))),
                max_tokens=4)])
            m = eng.metrics()
            assert m["decode_columns"] == 2 * width * m["decode_windows"] > 0
            assert m["decode_live_column_share"] == pytest.approx(
                live / (2 * width))

    def test_request_chunk_timestamps(self, params):
        eng = LLMEngine(CFG, params, n_slots=2, max_len=128,
                        page_size=16, prefill_chunk=16,
                        prefill_token_budget=16)
        req = eng.submit(list(range(1, 34)), max_tokens=3)  # 3 chunks
        _drive(eng, [req])
        assert req.first_chunk_at is not None
        assert req.last_chunk_at is not None
        assert (req.submitted_at <= req.first_chunk_at
                <= req.last_chunk_at <= req.first_token_at)
