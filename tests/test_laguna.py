"""The `laguna` family on the CPU at `LagunaConfig.tiny` (dense layer 0,
window x 3, full; 2 KV heads under 4 and 6 query heads; a window of two
pages; 8 experts top-3 with 4 held), seeded random weights with every
leaf moved off its initial value: `forward`, the paged programs through
both cache kinds and the engine against the plain reference
benchmarks/harness/reference/laguna_ref.py, in LOGITS.

Tolerances, each with its reason:
  ATOL_F32 = 3e-5   program and reference both compute in float32 at
      "highest" matmul precision; they differ by reassociation only
      (blockwise softmax in ring order, rsqrt for 1/sqrt, the grouped
      matmul's sums). Logits here are O(1).
  FAULT_MIN = 1e-3  each fault below must move some logit by more.
"""

import collections
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import laguna
from ray_tpu.ops.moe import token_choice_experts
from ray_tpu.serve.llm import LLMEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:          # the harness imports itself as `harness`
    sys.path.insert(0, BENCH)

from harness.reference import laguna_ref  # noqa: E402

ATOL_F32 = 3e-5
FAULT_MIN = 1e-3

CFG = laguna.LagunaConfig.tiny(dtype=jnp.float32)
RefConfig = collections.namedtuple(
    "RefConfig", "layer_types dense_layers heads_full heads_window "
    "n_kv_heads window top_k routed_scale first_expert norm_eps "
    "theta_window theta_full rotary_dim yarn_factor yarn_orig beta_fast "
    "beta_slow attention_factor")


def _rc(cfg):
    return RefConfig(
        cfg.kinds, cfg.dense_layers, cfg.n_heads, cfg.n_heads_window,
        cfg.n_kv_heads, cfg.window, cfg.top_k, cfg.routed_scale,
        cfg.first_expert, cfg.norm_eps, cfg.rope_theta_window, cfg.rope_theta,
        cfg.rotary_dim, cfg.yarn_factor, cfg.yarn_orig, cfg.beta_fast,
        cfg.beta_slow, cfg.attention_factor)


RC = _rc(CFG)
# A window of two pages; a dispatch of two 16-token chunk rows; so a ring
# of 2 + 2 + 1 = 5 pages a slot.
PAGE, N_PAGES, N_SLOTS, CHUNK, ROWS = 16, 24, 3, 16, 2


def _params(cfg=CFG, seed=0):
    """Seeded weights with EVERY leaf moved off its initial value: norm
    scales by a tenth, matmul planes by 0.02; the output projections are
    8x their initial size so that attention, the dense MLP, the shared
    expert and the routed experts all move the logits."""
    p = laguna.init_params(cfg, jax.random.key(seed))
    keys = jax.random.split(jax.random.key(seed + 1), len(p))
    out = {}
    for key, (name, v) in zip(keys, sorted(p.items())):
        size = 0.1 if name.endswith("_scale") else 0.02
        grow = 8.0 if name.endswith(("wo", "_down")) else 1.0
        out[name] = grow * v + size * jax.random.normal(key, v.shape, v.dtype)
    return out


@pytest.fixture(scope="module")
def params():
    return _params()


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, n).astype(np.int32)


def _ref_logits(params, seq, rc=RC):
    return np.asarray(laguna_ref.logits(params, jnp.asarray(seq), rc))


def test_forward_matches_the_reference_in_logits(params):
    seqs = np.stack([_tokens(3 * CFG.window, s) for s in (1, 2)])
    with jax.default_matmul_precision("highest"):
        got = np.asarray(laguna.forward(CFG, params, jnp.asarray(seqs)))
    want = np.stack([_ref_logits(params, s) for s in seqs])
    assert np.abs(want).max() > 0.5                 # not a flat model
    np.testing.assert_allclose(got, want, atol=ATOL_F32, rtol=0)


def test_yarn_moves_the_frequencies_the_config_says():
    """At the published numbers: 32 frequencies; the fastest are plain,
    the slowest divided by the factor, a ramp between (low 9, high 18:
    floor and ceil of 64 ln(8192 / (beta 2 pi)) / (2 ln 500000) = 9.04
    at beta 32 and 17.49 at beta 1)."""
    cfg = laguna.LagunaConfig()
    got = laguna.yarn_inv_freq(cfg)
    plain = cfg.rope_theta ** (-np.arange(0, 64, 2) / 64)
    assert got.shape == (32,)
    np.testing.assert_allclose(got[:10], plain[:10], rtol=1e-12)
    np.testing.assert_allclose(got[18:], plain[18:] / 128, rtol=1e-12)
    mid = got[10:18] / plain[10:18]
    assert np.all(np.diff(mid) < 0) and 1 / 128 < mid[-1] < mid[0] < 1
    np.testing.assert_array_equal(got, laguna_ref.yarn_inv_freq(_rc(cfg)))


def test_the_shares_add_up(params):
    """Two chips' routed parts (experts 0-3 and 4-7 of 8) plus the shared
    expert counted ONCE are the uncut reference's layer."""
    whole = laguna.LagunaConfig.tiny(dtype=jnp.float32, n_experts=8)
    full = _params(whole, seed=3)
    u = jax.random.normal(jax.random.key(7), (40, CFG.d_model), jnp.float32)
    j = 1                                           # a sparse layer's stack
    w = {n: full[n][j] for n in ("router", "s_gate", "s_up", "s_down",
                                 "w_gate", "w_up", "w_down")}
    with jax.default_matmul_precision("highest"):
        want = laguna_ref._sparse_mlp(
            u, w, _rc(whole), lambda e: (
                w["w_gate"][e], w["w_up"][e], w["w_down"][e]), 8)
        chosen, gates = laguna._route(whole, w["router"], u)
        parts, held = [], 0
        for first in (0, 4):
            half = slice(first, first + 4)
            y, counts = token_choice_experts(
                u, chosen, gates, w["w_gate"][half], w["w_up"][half],
                w["w_down"][half], first_expert=first)
            parts.append(y)
            held += int(counts.sum())
        shared = laguna.gated_mlp(u, w["s_gate"], w["s_up"], w["s_down"])
    assert held == u.shape[0] * whole.top_k         # every choice, once
    assert float(jnp.abs(parts[0]).max()) > 1e-3 < float(
        jnp.abs(parts[1]).max())
    np.testing.assert_allclose(parts[0] + parts[1] + shared, want,
                               atol=1e-5, rtol=0)


class Pager:
    """The engine's device side by hand: a pool of both kinds, a page
    table a slot for the full kind, and the two paged programs called as
    `LLMEngine` calls them."""

    def __init__(self, cfg, params, attn_impl="gather", rows=ROWS):
        self.cfg, self.params, self.impl = cfg, params, attn_impl
        self.pool = laguna.init_paged_kv(cfg, N_PAGES, PAGE, N_SLOTS,
                                         dispatch_tokens=rows * CHUNK)
        self.width = N_PAGES // N_SLOTS
        self.tables = np.zeros((N_SLOTS, self.width), np.int32)
        self.next_page = 1

    def grow(self, slot, n_tokens):
        for j in range(-(-n_tokens // PAGE)):
            if self.tables[slot, j] == 0:
                self.tables[slot, j] = self.next_page
                self.next_page += 1

    def chunks(self, rows, head=True, height=None):
        """rows: [(slot, tokens, offset)] -> last-valid logits, one
        dispatch of `height` rows (the rest inert)."""
        N = height or len(rows)
        toks = np.zeros((N, CHUNK), np.int32)
        offs, valid, slots = (np.zeros(N, np.int32) for _ in range(3))
        for i, (slot, t, off) in enumerate(rows):
            toks[i, :len(t)], offs[i], valid[i], slots[i] = t, off, len(t), slot
            self.grow(slot, off + len(t))
        out, self.pool = laguna.prefill_chunk_paged(
            self.cfg, self.params, jnp.asarray(toks), self.pool,
            jnp.asarray(self.tables[slots]), jnp.asarray(offs),
            jnp.asarray(valid), slots=jnp.asarray(slots),
            return_logits=head, attn_impl=self.impl)
        return None if out is None else np.asarray(out)

    def prefill(self, slot, prompt, rows=ROWS):
        """A whole prompt, `rows` chunk rows a dispatch -> its last
        token's logits."""
        cuts = [(slot, prompt[i:i + CHUNK], i)
                for i in range(0, len(prompt), CHUNK)]
        for i in range(0, len(cuts), rows):
            out = self.chunks(cuts[i:i + rows], height=rows)
        return out[len(cuts[i:i + rows]) - 1]

    def decode(self, tokens, positions, active):
        """One step for every slot (row b IS slot b) -> logits [B, V]."""
        for slot in active:
            self.grow(slot, int(positions[slot]) + 1)
        tables = np.where(np.isin(np.arange(N_SLOTS), active)[:, None],
                          self.tables, 0)
        out, self.pool = laguna.decode_step_paged(
            self.cfg, self.params, jnp.asarray(tokens, jnp.int32), self.pool,
            jnp.asarray(positions, jnp.int32), jnp.asarray(tables),
            attn_impl=self.impl)
        return np.asarray(out)


def _serve_logits(pager, prompt, follow, slot=1):
    """Chunked prefill of `prompt` in `slot`, then teacher-forced decode
    of `follow` through both cache kinds (another slot mid-prefill beside
    it) -> logits at positions len(prompt)-1 .. end-1."""
    rows = [pager.prefill(slot, prompt)]
    # A bystander: slot 0 holds HALF a prompt while slot 1 decodes; its
    # ring must survive the decode steps it takes no part in.
    other = _tokens(2 * CHUNK, seed=9)
    pager.chunks([(0, other[:CHUNK], 0)], head=False, height=ROWS)
    tokens = np.zeros(N_SLOTS, np.int32)
    positions = np.zeros(N_SLOTS, np.int32)
    for i, tok in enumerate(follow):
        tokens[slot], positions[slot] = tok, len(prompt) + i
        rows.append(pager.decode(tokens, positions, [slot])[slot])
    bystander = pager.chunks([(0, other[CHUNK:], CHUNK)], height=ROWS)[0]
    return np.stack(rows), other, bystander


# 2.3 windows of prompt, then decode across a page boundary of the ring.
PROMPT, FOLLOW = _tokens(75, 1), _tokens(13, 2)


@pytest.mark.parametrize("attn_impl", ["gather", "kernel"])
def test_paged_programs_match_the_reference_in_logits(params, attn_impl):
    with jax.default_matmul_precision("highest"):
        got, other, bystander = _serve_logits(
            Pager(CFG, params, attn_impl), PROMPT, FOLLOW)
    seq = np.concatenate([PROMPT, FOLLOW])
    want = _ref_logits(params, seq)[len(PROMPT) - 1:]
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=ATOL_F32, rtol=0)
    np.testing.assert_allclose(bystander, _ref_logits(params, other)[-1],
                               atol=ATOL_F32, rtol=0)


def _route_with(score_dtype=jnp.float32, renormalise=True):
    def route(cfg, w_router, u):
        s = jax.nn.sigmoid(u.astype(score_dtype) @ w_router.astype(score_dtype))
        top, chosen = jax.lax.top_k(s.astype(jnp.float32), cfg.top_k)
        norm = jnp.sum(top, axis=-1, keepdims=True) if renormalise else 1.0
        return chosen.astype(jnp.int32), cfg.routed_scale * top / norm
    return route


def _no_gate(true_inputs):
    def inputs(cfg, params, l, x, pos):
        q, k, v, gate = true_inputs(cfg, params, l, x, pos)
        return q, k, v, jnp.ones_like(gate)
    return inputs


def _page_off_by_one(true_view):
    def view(pool, slots, lengths, page_size):
        table, col_page = true_view(pool, slots, lengths, page_size)
        return table, jnp.where(col_page > 0, col_page - 1, col_page)
    return view


FAULTS = ["window_off_by_one", "page_off_by_one", "shared_dropped",
          "gates_not_renormalised", "gates_not_scaled", "head_gate_left_out",
          "plain_rope_for_yarn", "bf16_router"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails_the_tolerance(params, fault, monkeypatch):
    """What the tolerance is for: each of these serves logits that the
    comparison above would refuse."""
    # A configuration of its own, so that no trace of the true block is
    # found in the jit cache.
    kw = {"max_seq": 257 + FAULTS.index(fault)}
    served = dict(params)
    if fault == "window_off_by_one":
        kw["window"] = CFG.window + 1
    elif fault == "page_off_by_one":
        monkeypatch.setattr(laguna, "_ring_view",
                            _page_off_by_one(laguna._ring_view))
    elif fault == "shared_dropped":
        served["s_down"] = jnp.zeros_like(params["s_down"])
    elif fault == "gates_not_renormalised":
        monkeypatch.setattr(laguna, "_route", _route_with(renormalise=False))
    elif fault == "gates_not_scaled":
        kw["routed_scale"] = 1.0
    elif fault == "head_gate_left_out":
        monkeypatch.setattr(laguna, "_attn_inputs",
                            _no_gate(laguna._attn_inputs))
    elif fault == "plain_rope_for_yarn":
        monkeypatch.setattr(
            laguna, "yarn_inv_freq", lambda cfg: cfg.rope_theta ** (
                -np.arange(0, cfg.rotary_dim, 2) / cfg.rotary_dim))
    elif fault == "bf16_router":
        monkeypatch.setattr(laguna, "_route", _route_with(jnp.bfloat16))
    cfg = laguna.LagunaConfig.tiny(dtype=jnp.float32, **kw)
    with jax.default_matmul_precision("highest"):
        got, _other, _b = _serve_logits(Pager(cfg, served), PROMPT, FOLLOW)
    want = _ref_logits(params, np.concatenate([PROMPT, FOLLOW]))
    assert np.abs(got - want[len(PROMPT) - 1:]).max() > FAULT_MIN


@pytest.mark.parametrize("rows", [1, 2, 3])
@pytest.mark.parametrize("attn_impl", ["gather", "kernel"])
def test_a_prompt_over_any_number_of_dispatches_gives_the_unsplit_logits(
        params, rows, attn_impl):
    """The ring's hazard: chunk programs write before they attend, so a
    later row of a dispatch must not land on pages an earlier row still
    reads. A prompt of three windows, six chunks, one, two or three rows
    a dispatch (six, three, two dispatches), each with the ring that
    height asks for, ends in the reference's logits."""
    prompt = _tokens(3 * CFG.window, 3)
    with jax.default_matmul_precision("highest"):
        got = Pager(CFG, params, attn_impl, rows=rows).prefill(
            2, prompt, rows=rows)
    np.testing.assert_allclose(got, _ref_logits(params, prompt)[-1],
                               atol=ATOL_F32, rtol=0)


def test_a_dispatch_taller_than_the_ring_allows_is_refused(params):
    pager = Pager(CFG, params, rows=1)              # a ring of 4 pages
    prompt = _tokens(2 * CHUNK, 3)
    with pytest.raises(ValueError, match="needs a ring of 5 pages"):
        pager.chunks([(0, prompt[:CHUNK], 0), (0, prompt[CHUNK:], CHUNK)])


@pytest.mark.parametrize("height", [4, 8])
def test_inert_rows_of_a_tall_program_leave_every_ring_untouched(params,
                                                                 height):
    """One-width engines run programs of 4 and 8 rows, most of them
    inert behind a short prompt, and an inert row names slot 0: slot 0's
    ring and pages (a finished prompt's) and slot 2's (empty) keep every
    byte; the live row's prompt reads the reference's logits."""
    pager = Pager(CFG, params, rows=8)
    held, prompt = _tokens(40, 5), _tokens(CHUNK + 5, 6)
    with jax.default_matmul_precision("highest"):
        pager.prefill(0, held, rows=8)
        before = {k: np.asarray(v) for k, v in pager.pool.items()}
        pager.chunks([(1, prompt[:CHUNK], 0)], head=False, height=height)
        got = pager.chunks([(1, prompt[CHUNK:], CHUNK)], height=height)[0]
    ring = np.asarray(pager.pool["ring_rows"])
    assert ring.shape[1] == laguna.ring_pages(CFG.window, PAGE, 8 * CHUNK)
    for name in ("k_win", "v_win"):
        after = np.asarray(pager.pool[name])
        for slot in (0, 2):
            assert np.array_equal(after[:, ring[slot]],
                                  before[name][:, ring[slot]])
        assert not np.array_equal(after[:, ring[1]], before[name][:, ring[1]])
    theirs = [p for p in range(1, N_PAGES + 1)
              if p not in pager.tables[1].tolist()]
    for name in ("k", "v"):
        assert np.array_equal(np.asarray(pager.pool[name])[:, theirs],
                              before[name][:, theirs])
    np.testing.assert_allclose(got, _ref_logits(params, prompt)[-1],
                               atol=ATOL_F32, rtol=0)


@pytest.mark.parametrize("cut", [1, 15, 16, 17, 31, 33, 47, 63])
def test_a_prompt_split_anywhere_gives_the_unsplit_logits(params, cut):
    """Chunk rows need not be whole chunks: a 70-token prompt whose first
    dispatch ends at `cut` (mid-page, at a page's edge, past a window)
    ends in the logits of the prompt chunked evenly."""
    prompt = _tokens(70, 4)
    rows, done = [], 0
    for end in [cut] + list(range(cut + CHUNK, len(prompt), CHUNK)) + [
            len(prompt)]:
        while done < end:
            n = min(CHUNK, end - done)
            rows.append((2, prompt[done:done + n], done))
            done += n
    with jax.default_matmul_precision("highest"):
        pager = Pager(CFG, params)
        for i in range(0, len(rows), ROWS):
            out = pager.chunks(rows[i:i + ROWS], height=ROWS)
        got = out[len(rows[i:i + ROWS]) - 1]
    np.testing.assert_allclose(got, _ref_logits(params, prompt)[-1],
                               atol=ATOL_F32, rtol=0)


def _unreached_columns_hold_page_zero(true_view):
    def view(pool, slots, lengths, page_size):
        table, col_page = true_view(pool, slots, lengths, page_size)
        return table, jnp.maximum(col_page, 0)
    return view


@pytest.mark.parametrize("fault", [False, True])
def test_a_reused_slot_does_not_read_its_predecessors_ring(params, fault,
                                                           monkeypatch):
    """Slot 1 serves a long prompt and decodes, filling its ring; the
    next, shorter prompt in the same slot (new pages, offset 0) reads
    only what it wrote itself. The fault: columns the new request has
    not reached counted as page 0, as a null table entry would be."""
    first, second = _tokens(90, 4), _tokens(19, 5)
    cfg = CFG
    if fault:
        monkeypatch.setattr(
            laguna, "_ring_view",
            _unreached_columns_hold_page_zero(laguna._ring_view))
        cfg = laguna.LagunaConfig.tiny(dtype=jnp.float32, max_seq=300)
    with jax.default_matmul_precision("highest"):
        used = Pager(cfg, params)
        _serve_logits(used, first, _tokens(5, 6))
        ring = used.pool["k_win"][:, used.pool["ring_rows"][1]]
        # every row of slot 1's ring holds the first request's keys
        assert float(jnp.abs(ring).max(axis=(0, 2, 3)).min()) > 0.01
        used.tables[1] = 0                          # released: new pages
        again = used.prefill(1, second)
    want = _ref_logits(params, second)[-1]
    if fault:
        assert np.abs(again - want).max() > FAULT_MIN
    else:
        np.testing.assert_allclose(again, want, atol=ATOL_F32, rtol=0)


def test_a_ring_is_consecutive_rows_and_the_decode_call_is_owed_that():
    """The window decode call fetches a slot's window as a run of the
    plane's rows: `ring_pool` lays every ring (the null slot's too) in R
    consecutive rows, the view `_ring_view` hands the call passes its
    check, and a table whose columns are not consecutive is refused."""
    from ray_tpu.ops.paged_attention import paged_attention

    pool = laguna.init_paged_kv(CFG, N_PAGES, PAGE, N_SLOTS,
                                dispatch_tokens=ROWS * CHUNK)
    rows = np.asarray(pool["ring_rows"])
    R = laguna.ring_pages(CFG.window, PAGE, ROWS * CHUNK)
    assert rows.shape == (N_SLOTS + 1, R)
    np.testing.assert_array_equal(rows, rows[:, :1] + np.arange(R)[None])
    np.testing.assert_array_equal(rows[:, 0], R * np.arange(N_SLOTS + 1))
    lengths = jnp.asarray([1, PAGE * (R + 2) + 3, 0], jnp.int32)
    table, col_page = laguna._ring_view(pool, jnp.arange(N_SLOTS), lengths,
                                        PAGE)
    q = jnp.ones((N_SLOTS, CFG.heads("window"), CFG.head_dim), jnp.float32)
    kw = dict(window=CFG.window, col_page=col_page)
    out = paged_attention(q, pool["k_win"], pool["v_win"], jnp.int32(0),
                          table, lengths, **kw)
    assert out.shape == q.shape and not np.asarray(out).any()   # a zero pool
    with pytest.raises(ValueError, match="R consecutive page ids"):
        paged_attention(q, pool["k_win"], pool["v_win"], jnp.int32(0),
                        table[:, ::-1], lengths, **kw)


# ------------------------------------------------------- through LLMEngine

def _engine(params, **kw):
    opts = dict(n_slots=N_SLOTS, max_len=128, page_size=PAGE,
                n_pages=N_PAGES, prefill_chunk=CHUNK, attn_impl="gather",
                prefill_token_budget=ROWS * CHUNK)
    return LLMEngine(CFG, params, **{**opts, **kw})


def _run(eng, reqs):
    for _ in range(900):
        if all(r.done.is_set() for r in reqs):
            break
        eng.step()
    assert all(r.done.is_set() and r.error is None for r in reqs)


def _deficits(params, r):
    seq = np.asarray(r.prompt_ids[:r.n_prompt] + r.out_ids, np.int32)
    rows = _ref_logits(params, seq)[r.n_prompt - 1:len(seq) - 1]
    return rows.max(axis=1) - rows[np.arange(len(r.out_ids)), r.out_ids]


def test_engine_serves_the_references_tokens_and_counts(params):
    """Normal entry points, scheduler, PagePool, tick: four requests over
    three slots (so one slot is reused by a shorter request), contexts of
    up to three windows, every emitted token the float32 reference's best
    at its position (deficit under ATOL_F32)."""
    eng = _engine(params)
    # One table width: half a tick's allowance (8 rows) capped at the
    # three slots, and half of that rounded up; the head in both.
    assert eng.chunk_heights == (2, 3) and eng.chunk_heads == (True,)
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(1, CFG.vocab_size, n).tolist(),
                       max_tokens=m)
            for n, m in ((75, 21), (40, 30), (5, 50), (33, 9))]
    with jax.default_matmul_precision("highest"):
        _run(eng, reqs)
    for r in reqs:
        assert _deficits(params, r).max() <= ATOL_F32
    m = eng.metrics()
    assert m["preemptions"] == 0 and m["slot_state_bytes"] == 0
    page_bytes = PAGE * CFG.n_kv_heads * CFG.head_dim * 4
    ring = laguna.ring_pages(CFG.window, PAGE, eng.chunk_rows * CHUNK)
    assert m["window_kv_bytes"] == (
        2 * CFG.count("window") * (N_SLOTS + 1) * ring * page_bytes)
    assert m["kv_pool_bytes"] == m["window_kv_bytes"] + (
        2 * CFG.count("full") * (N_PAGES + 1) * page_bytes)
    # Every (sparse layer, step) of a decode window routed top_k choices
    # a live row, and about half of them landed on the held half.
    assert m["moe_layer_steps"] % CFG.count("sparse") == 0
    assert m["moe_rows_routed"] % CFG.top_k == 0
    assert 0 < m["moe_rows_held"] < m["moe_rows_routed"]
    assert 0.3 < m["moe_rows_held"] / m["moe_rows_routed"] < 0.7
    assert m["moe_rows_held"] >= m["moe_rows_max_sum"]
    assert 1.0 <= m["moe_experts_touched"] <= CFG.n_experts
    assert m["moe_rows_max"] >= 1.0
    eng.reset_stats()
    after = eng.metrics()
    assert after["moe_rows_routed"] == after["moe_rows_held"] == 0


def test_engine_recomputes_a_preempted_request_to_the_same_tokens(params):
    """A pool too small for both requests: one is evicted by recompute
    and re-prefilled from offset 0 into the ring it had used; both
    streams stay the reference's."""
    eng = _engine(params, n_slots=2, n_pages=9, max_len=112)
    rng = np.random.default_rng(1)
    reqs = [eng.submit(rng.integers(1, CFG.vocab_size, 40).tolist(),
                       max_tokens=50) for _ in range(2)]
    with jax.default_matmul_precision("highest"):
        _run(eng, reqs)
    assert eng.metrics()["preemptions"] >= 1
    for r in reqs:
        assert len(r.out_ids) == 50
        assert _deficits(params, r).max() <= ATOL_F32


REFUSED = [
    ("prefix_cache", True, "pages can be shared"),
    ("spec_draft", "tiny", "cannot be rewound"),
    ("kv_transfer", True, "page set would have to carry"),
    ("tp", 2, "expert-parallel exchange"),
    ("weight_dtype", "int8", "no int8 form"),
    ("kv_dtype", "int8", "scale planes"),
    ("prefill_width_bucketing", True, "packs rows of several widths"),
    ("pool_role", "prefill", "page set would have to carry"),
]


@pytest.mark.parametrize("option,value,names", REFUSED)
def test_options_the_family_cannot_carry_are_refused(params, option, value,
                                                     names):
    """At construction, each with what would have to be built."""
    with pytest.raises(ValueError, match=names):
        _engine(params, **{option: value})


def test_the_fleet_knobs_soft_disable_for_the_family(params, monkeypatch):
    monkeypatch.setenv("RAY_TPU_LLM_PREFIX_CACHE", "1")
    monkeypatch.setenv("RAY_TPU_LLM_KV_DTYPE", "int8")
    eng = LLMEngine(CFG, params, n_slots=2, max_len=128, page_size=PAGE,
                    n_pages=40, attn_impl="gather")     # knobs for the rest
    assert eng.prefill_chunk == 128
    assert eng.prefix_cache is None and eng.kv_dtype == "bf16"
    assert eng.tp == 1 and not eng.kv_transfer
    assert not eng.prefill_width_bucketing      # the knob's default is on


def test_the_other_families_get_exactly_todays_programs():
    """The ring is this family's: a gpt and a zaya are handed the pool
    builder and programs they had, and their kernels take no window."""
    from ray_tpu.models import gpt, paged_kv, serving, zaya

    fam = serving.family_of(gpt.GPTConfig.tiny())
    assert fam.name == "gpt" and not fam.slot_ring and not fam.unsupported
    for name in serving._PAGED:
        assert fam.programs(1, None)[name] is getattr(paged_kv, name)
    fam = serving.family_of(zaya.ZayaConfig.tiny())
    assert fam.name == "zaya" and fam.slot_state and not fam.slot_ring
    assert fam.init_pool is zaya.init_paged_kv
    fam = serving.family_of(CFG)
    assert fam.name == "laguna" and fam.slot_ring and not fam.slot_state
