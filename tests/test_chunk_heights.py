"""One chunk program a prompt where the engine dispatches at ONE table width.

An engine with `prefill_width_bucketing` off (what the `zaya`, `laguna`
and `qwen3_next` families choose: a chunk program there is a pass over
the weights whatever it carries) holds exactly two chunk programs, half a
tick's allowance tall and a quarter of it, both with the head. Held here:
the heights that follow from budget, chunk and window; the cut of a
tick's rows into those programs; that inert rows write nothing; that the
engine's list of programs is what `warmup_compile` compiles and what
traffic dispatches; that a warm-up by lone requests at the benchmark
cells' geometry reaches every program their load dispatches, with two
programs in all; and that greedy streams equal the parent's two-row cut
token for token in every family.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import gpt
from ray_tpu.serve.llm import LLMEngine

CFG = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32)
# budget x window / (2 x chunk) = 16 x 8 / 16 = 8 rows, and 4
PAGE, CHUNK, BUDGET, SLOTS = 8, 8, 16, 8


@pytest.fixture(scope="module")
def params():
    return gpt.init_params(CFG, jax.random.key(42))


def _engine(params, **kw):
    opts = dict(n_slots=SLOTS, max_len=192, page_size=PAGE,
                prefill_chunk=CHUNK, prefill_token_budget=BUDGET,
                prefill_width_bucketing=False)
    return LLMEngine(CFG, params, **{**opts, **kw})


def _draft_options():
    """A 1-layer half-width draft model's engine options."""
    dcfg = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32,
                              n_layers=1, d_model=32, n_heads=4, d_ff=64)
    return dict(spec_draft=dcfg, spec_k=3, spec_draft_params=(
        gpt.init_params(dcfg, jax.random.key(7))))


def _prompts(rng, lengths, vocab=CFG.vocab_size):
    return [list(map(int, rng.integers(1, vocab, n))) for n in lengths]


def _drive(eng, reqs, max_steps=2000):
    for _ in range(max_steps):
        if all(r.done.is_set() for r in reqs):
            break
        eng.step()
    assert all(r.done.is_set() and r.error is None for r in reqs), (
        [r.error for r in reqs])
    return [r.out_ids for r in reqs]


def _spy_programs(eng):
    """(height, table width, head, live rows) of every chunk program the
    engine runs from here on, with the inert rows' tables and offsets
    held to zero as they pass."""
    real, seen = eng._rt.prefill_chunk_paged, []

    def spy(cfg, prm, toks, pool, tables, offsets, valid, **kw):
        if cfg is eng.cfg:             # not the draft model's mirror
            live = np.asarray(valid) > 0
            assert not np.asarray(tables)[~live].any()
            assert not np.asarray(offsets)[~live].any()
            assert not np.asarray(toks)[~live].any()
            seen.append((toks.shape[0], tables.shape[1],
                         bool(kw["return_logits"]), int(live.sum())))
        return real(cfg, prm, toks, pool, tables, offsets, valid, **kw)

    eng._rt.prefill_chunk_paged = spy
    return seen


def _two_row_cut(eng):
    """The parent's rule in this engine: every chunk program as tall as
    ONE budget fills it (two rows here), the head only where a row is
    final."""
    eng.chunk_heights, eng.chunk_heads = (2,), (False, True)
    return eng


# ------------------------------------------------------------ the heights

@pytest.mark.parametrize("chunk,budget,window,n_slots,heights", [
    (128, 256, 8, 64, (4, 8)),       # zaya1-8b.reason, laguna-s-2.1.codegen
    (128, 256, 8, 128, (4, 8)),      # qwen3-next-80b-a3b.longform
    (128, 256, 4, 64, (2, 4)),
    (128, 256, 2, 64, (2,)),         # half a tick is one budget: today's 2
    (128, 256, 1, 64, (2,)),         # neither lower than one budget's rows
    (128, 512, 8, 64, (8, 16)),
    (128, 384, 8, 64, (6, 12)),
    (128, 320, 8, 64, (5, 10)),      # a budget that is no multiple of chunks
    (16, 48, 8, 64, (6, 12)),
    (16, 16, 8, 3, (2, 3)),          # never more rows than slots; half rounds up
    (64, 256, 8, 4, (4,)),           # the slots cap both at one budget's rows
    (16, 16, 8, 1, (1,)),
    (16, 0, 8, 4, (1,)),             # budget 0: an idle tick's one chunk
])
def test_heights_follow_budget_chunk_and_window(params, chunk, budget, window,
                                                n_slots, heights):
    """H = half a tick's allowance in rows, and H/2, neither lower than
    one budget's rows nor taller than the engine has slots; both with
    the head; the ring of a family that keeps one is sized by H."""
    kw = dict(n_slots=n_slots, max_len=256, page_size=16,
              n_pages=20, prefill_chunk=chunk, prefill_token_budget=budget,
              decode_block=window)
    eng = LLMEngine(CFG, params, prefill_width_bucketing=False, **kw)
    assert eng.chunk_heights == heights and eng.chunk_heads == (True,)
    assert eng.chunk_rows == heights[-1]
    width = eng.max_pages_per_slot
    assert eng.chunk_programs() == [(h, width, True) for h in heights]
    m = eng.metrics()
    assert m["chunk_rows"] == heights[-1]
    assert m["chunk_heights"] == list(heights)
    assert eng.load_snapshot()["chunk_rows"] == heights[-1]
    # The bucketed engine's set is the parent's: one height, the full
    # chunks ONE budget holds, at every width of the ladder, both heads.
    bucketed = LLMEngine(CFG, params, prefill_width_bucketing=True, **kw)
    rows = min(n_slots, -(-max(budget, chunk) // chunk))
    assert bucketed.chunk_heights == (rows,)
    assert bucketed.chunk_programs() == [
        (rows, w, head) for w in bucketed._width_ladder()
        for head in (False, True)]


# ---------------------------------------------------------------- the cut

def _expected_cut(n):
    return ([8] * (n // 8)
            + ([] if n % 8 == 0 else [4] if n % 8 <= 4 else [8]))


@pytest.mark.parametrize("n", range(1, 17))
def test_a_ticks_rows_are_cut_into_eights_and_one_remainder(params, n):
    """4 rows → [4]; 5-8 → [8]; 9-12 → [8, 4]; 13-16 → [8, 8]: a lone
    prompt of n chunks in an idle engine (an idle tick's allowance is a
    whole tick's: 16 rows) and the same rows beside a decode window,
    from two prompts. Every program carries the head; inert rows name
    the null page and no token."""
    eng = _engine(params)
    assert eng.chunk_heights == (4, 8)
    assert eng._cut_rows(n) == _expected_cut(n)
    rng = np.random.default_rng(n)
    seen = _spy_programs(eng)
    lone = eng.submit(_prompts(rng, (n * CHUNK,))[0], max_tokens=3)
    eng.step()
    width = eng.max_pages_per_slot
    assert [(h, w, head) for h, w, head, _ in seen] == [
        (h, width, True) for h in _expected_cut(n)]
    assert sum(live for *_x, live in seen) == n
    assert lone.first_token_at is not None
    m = eng.metrics()
    assert m["prefill_dispatches"] == len(seen)
    assert m["prefill_rows_dispatched"] == sum(_expected_cut(n))
    assert m["prefill_rows_per_program"] == pytest.approx(n / len(seen))
    assert m["prefill_row_fill"] == pytest.approx(n / sum(_expected_cut(n)))
    _drive(eng, [lone])
    # Beside a decode window of 8 steps the allowance is the same 16
    # rows: two prompts whose rows sum to n are cut the same way.
    del seen[:]
    first = eng.submit([5, 9, 2], max_tokens=150)
    while first.first_token_at is None:
        eng.step()
    del seen[:]
    a = max(1, n // 2)
    lengths = [a * CHUNK] + ([(n - a) * CHUNK] if n > a else [])
    reqs = [eng.submit(p, max_tokens=3) for p in _prompts(rng, lengths)]
    eng.step()
    assert [h for h, *_x in seen] == _expected_cut(n)
    assert all(r.first_token_at is not None for r in reqs)


def test_the_bucketed_engine_cuts_as_the_parent(params):
    """One height: ceil(n / height) programs of it, whatever n."""
    eng = _engine(params, prefill_width_bucketing=True)
    assert eng.chunk_heights == (2,)
    assert [eng._cut_rows(n) for n in (1, 2, 3, 7)] == [
        [2], [2], [2, 2], [2, 2, 2, 2]]


def test_inert_rows_write_nothing(params):
    """A prompt of five chunks runs as one program of eight rows: the
    three inert rows land on the null page, and no page but the
    prompt's own (and the null page) changes by a byte, a decoding
    bystander's pages included."""
    eng = _engine(params)
    rng = np.random.default_rng(3)
    by = eng.submit(_prompts(rng, (20,))[0], max_tokens=100)
    while by.first_token_at is None:
        eng.step()
    before = {k: np.asarray(v) for k, v in eng.cache.items()}
    seen = _spy_programs(eng)
    req = eng.submit(_prompts(rng, (5 * CHUNK,))[0], max_tokens=2)
    real = eng._decode_ready_slots
    eng._decode_ready_slots = lambda: []      # this tick: prefill only
    eng.step()
    eng._decode_ready_slots = real
    assert [(h, live) for h, _w, _head, live in seen] == [(8, 5)]
    slot = next(s for s, r in enumerate(eng.slot_req) if r is req)
    own = set(eng.pool.row(slot, eng.max_pages_per_slot).tolist()) | {0}
    others = [p for p in range(eng.n_pages + 1) if p not in own]
    assert len(own) == 1 + 5 * CHUNK // PAGE
    for name in ("k", "v"):
        after = np.asarray(eng.cache[name])
        assert np.array_equal(after[:, others], before[name][:, others])
        assert not np.array_equal(after, before[name])
    _drive(eng, [by, req])


# ---------------------------------------------- the list, warm-up, traffic

@pytest.mark.parametrize("draft", [False, True], ids=["plain", "draft"])
def test_warmup_compiles_the_engines_list_then_traffic_adds_zero(params,
                                                                 draft):
    """`warmup_compile()` lowers exactly `chunk_programs()`: (4, head)
    and (8, head) at the one table width (the draft model's mirror: the
    same two heights without a head), and ragged traffic, lone and
    together, compiles nothing more and dispatches nothing else."""
    from ray_tpu.models.paged_kv import prefill_chunk_paged

    prefill_chunk_paged.clear_cache()
    eng = _engine(params, **(_draft_options() if draft else {}))
    width = eng.max_pages_per_slot
    assert eng.chunk_programs() == [(4, width, True), (8, width, True)]
    seen = _spy_programs(eng)
    n = eng.warmup_compile()
    # the draft's two mirrors and one verify program at the one width
    assert n == 2 + (3 if draft else 0)
    assert [p[:3] for p in seen] == eng.chunk_programs()
    assert all(live == 0 for *_x, live in seen)
    n_programs = prefill_chunk_paged._cache_size()
    assert n_programs == 2 + (2 if draft else 0)
    rng = np.random.default_rng(5)
    del seen[:]
    for lengths in ((3,), (33,), (64,), (100,), (5, 7, 30, 41, 9)):
        _drive(eng, [eng.submit(p, max_tokens=8)
                     for p in _prompts(rng, lengths)])
    assert prefill_chunk_paged._cache_size() == n_programs, (
        "traffic after warm-up must not lower new chunk programs")
    assert {p[:3] for p in seen} == set(eng.chunk_programs())


# ------------------------------------------------------------ reachability

# The three one-width cells as `benchmarks/configs/*.json` and
# `benchmarks/traffic/*.json` state them: page 64, chunk 128, the default
# budget of 256 tokens a step and window of 8 steps; fixed prompts;
# outputs up to `out_max`.
CELLS = {
    "zaya1-8b.reason": dict(max_len=2048, prompt=512, out_max=1528),
    "laguna-s-2.1.codegen": dict(max_len=4096, prompt=1024, out_max=3056),
    "qwen3-next-80b-a3b.longform": dict(max_len=4096, prompt=512,
                                        out_max=3576),
}


def _warm_up_lengths(cell):
    """`benchmarks/harness/serve_cell.py` `warm_up`, restated: for every
    table width the traffic's decode steps run at, the shortest prompt
    whose pages round up to it (`below(w) x page + 1`), then the mix's
    own prompt."""
    page, cap = 64, -(-cell["max_len"] // 64)
    pow2 = lambda n: 1 << max(0, (n - 1).bit_length())
    width = lambda tokens: min(pow2(-(-tokens // page)), cap)
    lo = cell["prompt"] + 1
    hi = min(cell["prompt"] + cell["out_max"] + 8, cell["max_len"])
    widths = sorted({width(n) for n in range(lo, hi + 1, page)} | {width(hi)})
    below = lambda w: (1 << ((w - 1).bit_length() - 1)) if w > 1 else 0
    return [max(2, below(w) * page + 1) for w in widths] + [cell["prompt"]]


def test_the_warm_up_rule_restated_gives_the_cells_lengths():
    assert _warm_up_lengths(CELLS["zaya1-8b.reason"]) == [513, 1025, 512]
    assert _warm_up_lengths(CELLS["laguna-s-2.1.codegen"]) == [
        1025, 2049, 1024]
    assert _warm_up_lengths(CELLS["qwen3-next-80b-a3b.longform"]) == [
        513, 1025, 2049, 512]


@pytest.mark.parametrize("name", list(CELLS))
def test_lone_warm_up_requests_reach_every_program_of_the_load(params, name):
    """What a cell's `setup_s` and `compiles_in_window` rest on: the
    warm-up's lone requests, at the cell's geometry on a tiny model,
    dispatch at most TWO (height, head) programs, and every program a
    closed loop of the cell's prompts dispatches with one, two or three
    admissions in a tick beside a full decode window is among them."""
    cell = CELLS[name]
    kw = dict(n_slots=8, max_len=cell["max_len"], page_size=64,
              prefill_chunk=128, prefill_token_budget=256,
              n_pages=8 * 24)
    rng = np.random.default_rng(11)
    warm = _engine(params, **kw)
    assert warm.chunk_heights == (4, 8)
    seen = _spy_programs(warm)
    for n in _warm_up_lengths(cell):
        _drive(warm, [warm.submit(_prompts(rng, (n,))[0], max_tokens=12)])
    reached = {(h, head) for h, _w, head, _live in seen}
    assert reached == {(4, True), (8, True)}
    for admissions in (1, 2, 3):
        eng = _engine(params, **kw)
        decoding = [eng.submit([5, 9, 2, 7], max_tokens=400)
                    for _ in range(2)]
        while not all(r.first_token_at for r in decoding):
            eng.step()
        seen = _spy_programs(eng)
        reqs = [eng.submit(p, max_tokens=4)
                for p in _prompts(rng, (cell["prompt"],) * admissions)]
        while not all(r.first_token_at for r in reqs):
            eng.step()
        assert seen and {(h, head) for h, _w, head, _l in seen} <= reached
        assert {w for _h, w, *_x in seen} == {warm.max_pages_per_slot}


# --------------------------------------------------------------- exactness

def _gpt(draft):
    return (CFG, gpt.init_params(CFG, jax.random.key(42)), CHUNK,
            _draft_options() if draft else {})


def _zaya():
    from ray_tpu.models import zaya

    cfg = zaya.ZayaConfig.tiny(dtype=jnp.float32, max_seq=512)
    return cfg, zaya.init_params(cfg, jax.random.key(1)), 8, {}


def _laguna():
    from ray_tpu.models import laguna

    # window 32 over pages of 4, eight rows of 8 tokens a dispatch: a
    # ring of 8 + 16 + 1 = 25 columns, the cell's count.
    cfg = laguna.LagunaConfig.tiny(dtype=jnp.float32, max_seq=512)
    return cfg, laguna.init_params(cfg, jax.random.key(1)), 8, dict(
        page_size=4, n_pages=8 * 48)


def _qwen3_next():
    from ray_tpu.models import qwen3_next

    cfg = qwen3_next.Qwen3NextConfig.tiny(dtype=jnp.float32, max_seq=512)
    return cfg, qwen3_next.init_params(cfg, jax.random.key(1)), 16, {}


FAMILIES = {"gpt": lambda: _gpt(False), "gpt_draft": lambda: _gpt(True),
            "zaya": _zaya, "laguna": _laguna, "qwen3_next": _qwen3_next}


@pytest.fixture(scope="module")
def family(request):
    """(cfg, params, engine options) of one family; a chunk of C tokens,
    a budget of two chunks, pages of 8 C (three a slot at most, so three
    decode programs a family) unless the family says otherwise."""
    cfg, prm, chunk, kw = FAMILIES[request.param]()
    opts = dict(n_slots=SLOTS, max_len=24 * chunk, page_size=8 * chunk,
                prefill_chunk=chunk,
                prefill_token_budget=2 * chunk, attn_impl="gather")
    if request.param.startswith("gpt"):
        opts["prefill_width_bucketing"] = False
    yield cfg, prm, {**opts, **kw}
    jax.clear_caches()


@pytest.mark.parametrize("together", [False, True], ids=["alone", "two"])
@pytest.mark.parametrize("n_chunks", [1, 4, 5, 8, 9, 17])
@pytest.mark.parametrize("family", list(FAMILIES), indirect=True)
def test_streams_equal_the_two_row_cut(family, n_chunks, together):
    """Greedy streams under the two heights equal the parent's two-row
    cut token for token: a prompt of n chunks alone in the engine, and
    two of them admitted in one tick behind a request that already
    decodes in slot 0 (which every inert row names). Rows of one prompt
    chained at 4 and at 8 a dispatch: `zaya`'s boundary reads,
    `qwen3_next`'s state chain, `laguna`'s ring at 25 columns."""
    cfg, prm, opts = family
    chunk = opts["prefill_chunk"]
    rng = np.random.default_rng(100 * n_chunks + together)
    first = _prompts(rng, (chunk + 3,), cfg.vocab_size)[0]
    # ragged tails: the last chunk of the second prompt is not full
    prompts = _prompts(rng, ((n_chunks * chunk,)
                             + ((n_chunks * chunk - 3,) if together else ())),
                       cfg.vocab_size)

    def serve(eng):
        seen = _spy_programs(eng)
        reqs = []
        if together:
            reqs.append(eng.submit(first, max_tokens=40))
            while reqs[0].first_token_at is None:
                eng.step()
        reqs += [eng.submit(p, max_tokens=6) for p in prompts]
        return _drive(eng, reqs), seen

    with jax.default_matmul_precision("highest"):
        want, parent_seen = serve(_two_row_cut(LLMEngine(cfg, prm, **opts)))
        eng = LLMEngine(cfg, prm, **opts)
        got, seen = serve(eng)
    assert eng.chunk_heights == (4, 8) and eng.chunk_heads == (True,)
    assert got == want
    assert {h for h, *_x in parent_seen} == {2}
    assert {h for h, *_x in seen} <= {4, 8}
    if "k_win" in eng.cache:
        assert eng.cache["ring_rows"].shape == (SLOTS + 1, 25)
    # One program a prompt where the parent ran one a budget (a
    # speculative tick beside decoding slots carries ONE budget: its
    # two rows go in the lower program, as many as the parent ran).
    assert len(seen) <= len(parent_seen)
    if n_chunks > 2 and not (together and "spec_draft" in opts):
        assert len(seen) < len(parent_seen)
