"""The `mimo_v2` family on the CPU at `MiMoV2Config.tiny` (dense layer 0
full, window x 2, full, window; 8 query heads of 24 over 2 (full) and 4
(window) KV heads, V heads of 16; a window of two pages with a learned
sink a head; 8 experts top-3 with 4 held, chosen by a biased score),
seeded random weights with every leaf moved off its initial value:
`forward`, the paged programs through both cache kinds and the engine
against the plain reference benchmarks/harness/reference/mimo_v2_ref.py,
in LOGITS.

Tolerances, each with its reason:
  ATOL_F32 = 3e-5   program and reference both compute in float32 at
      "highest" matmul precision; they differ by reassociation only
      (blockwise softmax in ring order with the sink as its first term,
      rsqrt for 1/sqrt, the grouped matmul's sums). Logits here are O(1).
  FAULT_MIN = 1e-3  each fault below must move some logit by more.
"""

import collections
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import laguna, mimo_v2
from ray_tpu.ops.moe import token_choice_experts
from ray_tpu.serve.llm import LLMEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:          # the harness imports itself as `harness`
    sys.path.insert(0, BENCH)

from harness.reference import mimo_v2_ref  # noqa: E402

ATOL_F32 = 3e-5
FAULT_MIN = 1e-3

CFG = mimo_v2.MiMoV2Config.tiny(dtype=jnp.float32)
RefConfig = collections.namedtuple(
    "RefConfig", "layer_types dense_layers n_heads kv_heads_full "
    "kv_heads_window head_dim v_head_dim value_scale window sink_kinds "
    "top_k first_expert norm_eps theta_full theta_window rotary_dim")


def _rc(cfg):
    return RefConfig(
        cfg.kinds, cfg.dense_layers, cfg.n_heads, cfg.n_kv_heads,
        cfg.n_kv_heads_window, cfg.head_dim, cfg.v_head_dim, cfg.value_scale,
        cfg.window, cfg.sink_kinds, cfg.top_k, cfg.first_expert,
        cfg.norm_eps, cfg.rope_theta, cfg.rope_theta_window, cfg.rotary_dim)


RC = _rc(CFG)
# A window of two pages (as the published 128 over pages of 64); a
# dispatch of two 16-token chunk rows; so a ring of 2 + 2 + 1 = 5 pages.
PAGE, N_PAGES, N_SLOTS, CHUNK, ROWS = 16, 24, 3, 16, 2


def _params(cfg=CFG, seed=0):
    """Seeded weights with EVERY leaf moved off its initial value: norm
    scales by a tenth, matmul planes, sinks and the router's bias by
    0.02; the output projections are 8x their initial size so that
    attention, the dense MLP and the routed experts all move the
    logits."""
    p = mimo_v2.init_params(cfg, jax.random.key(seed))
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
    return np.asarray(mimo_v2_ref.logits(params, jnp.asarray(seq), rc))


def test_forward_matches_the_reference_in_logits(params):
    seqs = np.stack([_tokens(3 * CFG.window, s) for s in (1, 2)])
    with jax.default_matmul_precision("highest"):
        got = np.asarray(mimo_v2.forward(CFG, params, jnp.asarray(seqs)))
    want = np.stack([_ref_logits(params, s) for s in seqs])
    assert np.abs(want).max() > 0.5                 # not a flat model
    np.testing.assert_allclose(got, want, atol=ATOL_F32, rtol=0)


def test_the_published_pattern_and_widths():
    """The defaults are config.json's: full layers at 0, 5, 11, ... 47
    (9 of 48), 4 and 8 KV heads, K 192 / V 128, rotary on 64 dims."""
    cfg = mimo_v2.MiMoV2Config()
    full = [l for l, k in enumerate(cfg.kinds) if k == "full"]
    assert full == [0, 5, 11, 17, 23, 29, 35, 41, 47]
    assert (cfg.count("full"), cfg.count("window")) == (9, 39)
    assert (cfg.count("dense"), cfg.count("sparse")) == (1, 47)
    assert (cfg.kv_heads("full"), cfg.kv_heads("window")) == (4, 8)
    assert int(0.334 * cfg.head_dim) == cfg.rotary_dim == 64
    specs = mimo_v2.param_specs(cfg)
    assert specs["f_wk"]["shape"] == (9, 4096, 768)
    assert specs["f_wv"]["shape"] == (9, 4096, 512)
    assert specs["w_wk"]["shape"] == (39, 4096, 1536)
    assert specs["w_wv"]["shape"] == (39, 4096, 1024)
    assert specs["w_wo"]["shape"] == (39, 64 * 128, 4096)
    assert specs["w_sink"]["shape"] == (39, 64) and "f_sink" not in specs
    assert specs["router_bias"]["shape"] == (47, 256)


def test_the_shares_add_up(params):
    """Four chips' routed parts (experts 0-3, 4-7, 8-11, 12-15 of 16) ARE
    the uncut reference's layer: every choice lands on exactly one
    share, chosen by s + b and gated by s over all the choices."""
    whole = mimo_v2.MiMoV2Config.tiny(dtype=jnp.float32, n_experts=16,
                                      n_experts_routed=16)
    full = _params(whole, seed=3)
    u = jax.random.normal(jax.random.key(7), (40, CFG.d_model), jnp.float32)
    j = 1                                           # a sparse layer's stack
    w = {n: full[n][j] for n in ("router", "router_bias", "w_gate", "w_up",
                                 "w_down")}
    with jax.default_matmul_precision("highest"):
        want = mimo_v2_ref._sparse_mlp(
            u, w, _rc(whole), lambda e: (
                w["w_gate"][e], w["w_up"][e], w["w_down"][e]), 16)
        chosen, gates, moved = mimo_v2._route(whole, w["router"],
                                              w["router_bias"], u)
        parts, held = [], 0
        for first in (0, 4, 8, 12):
            share = slice(first, first + 4)
            y, counts = token_choice_experts(
                u, chosen, gates, w["w_gate"][share], w["w_up"][share],
                w["w_down"][share], first_expert=first)
            parts.append(y)
            held += int(counts.sum())
    assert held == u.shape[0] * whole.top_k         # every choice, once
    assert all(float(jnp.abs(p).max()) > 1e-3 for p in parts)
    assert 0 < int(moved.sum()) < held              # the bias moves some
    np.testing.assert_allclose(sum(parts), want, atol=1e-5, rtol=0)


def test_the_bias_chooses_and_the_unbiased_score_gates():
    """`_route` by hand: a bias that lifts expert 1 over expert 3 moves
    one choice, and the gates are the chosen experts' own sigmoid scores
    over their sum."""
    cfg = mimo_v2.MiMoV2Config.tiny(top_k=2, n_experts_routed=4)
    u = jnp.eye(4, cfg.d_model)[:1]
    logit = jnp.asarray([2.0, 1.0, -1.0, 1.9])
    w = jnp.zeros((cfg.d_model, 4)).at[0].set(logit)
    s = jax.nn.sigmoid(logit)
    chosen, gates, moved = mimo_v2._route(cfg, w, jnp.zeros(4), u)
    assert chosen.tolist() == [[0, 3]] and moved.tolist() == [0]
    bias = jnp.asarray([0.0, 0.2, 0.0, 0.0])
    chosen, gates, moved = mimo_v2._route(cfg, w, bias, u)
    assert sorted(chosen[0].tolist()) == [0, 1] and moved.tolist() == [1]
    want = {0: s[0] / (s[0] + s[1]), 1: s[1] / (s[0] + s[1])}
    for e, g in zip(chosen[0].tolist(), gates[0].tolist()):
        assert abs(g - float(want[e])) < 1e-6


class Pager:
    """The engine's device side by hand: a pool of both kinds, a page
    table a slot for the full kind, and the two paged programs called as
    `LLMEngine` calls them."""

    def __init__(self, cfg, params, attn_impl="gather", rows=ROWS):
        self.cfg, self.params, self.impl = cfg, params, attn_impl
        self.pool = mimo_v2.init_paged_kv(cfg, N_PAGES, PAGE, N_SLOTS,
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
        out, self.pool = mimo_v2.prefill_chunk_paged(
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
        out, self.pool = mimo_v2.decode_step_paged(
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


def _route_with(score_dtype=jnp.float32, renormalise=True, choose_by="s+b",
                gate_by="s"):
    def route(cfg, w_router, bias, u):
        s = jax.nn.sigmoid(u.astype(score_dtype)
                           @ w_router.astype(score_dtype)).astype(jnp.float32)
        scores = {"s": s, "s+b": s + bias.astype(jnp.float32)}
        _, chosen = jax.lax.top_k(scores[choose_by], cfg.top_k)
        own = jnp.take_along_axis(scores[gate_by], chosen, axis=-1)
        norm = jnp.sum(own, axis=-1, keepdims=True) if renormalise else 1.0
        return (chosen.astype(jnp.int32), own / norm,
                jnp.zeros(u.shape[0], jnp.int32))
    return route


def _kv_heads_mapped_as_the_full_kind(true_inputs):
    """Window layers: query head h reads KV head h // (H / G_full), the
    other kind's map, instead of h // (H / G_window)."""
    def inputs(cfg, params, l, x, pos):
        q, k, v = true_inputs(cfg, params, l, x, pos)
        if cfg.kinds[l] == "window":
            ratio = cfg.n_kv_heads_window // cfg.n_kv_heads
            other = jnp.arange(cfg.n_kv_heads_window) // ratio
            k, v = k[:, :, other], v[:, :, other]
        return q, k, v
    return inputs


FAULTS = ["sink_dropped", "sink_on_full_layers_too", "value_scale_dropped",
          "window_off_by_one", "kv_head_map_of_the_other_kind",
          "rope_on_every_dim", "thetas_swapped", "gates_not_renormalised",
          "gate_from_the_biased_score", "choice_from_the_unbiased_score",
          "bf16_router"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails_the_tolerance(params, fault, monkeypatch):
    """What the tolerance is for: each of these serves logits that the
    comparison above would refuse."""
    # A configuration of its own, so that no trace of the true block is
    # found in the jit cache.
    kw = {"max_seq": 257 + FAULTS.index(fault)}
    served = dict(params)
    if fault == "sink_dropped":
        kw["sink_kinds"] = ()
    elif fault == "sink_on_full_layers_too":
        kw["sink_kinds"] = ("full", "window")
        served["f_sink"] = jax.random.normal(
            jax.random.key(5), (CFG.count("full"), CFG.n_heads))
    elif fault == "value_scale_dropped":
        kw["value_scale"] = 1.0
    elif fault == "window_off_by_one":
        kw["window"] = CFG.window + 1
    elif fault == "kv_head_map_of_the_other_kind":
        monkeypatch.setattr(
            mimo_v2, "_attn_inputs",
            _kv_heads_mapped_as_the_full_kind(mimo_v2._attn_inputs))
    elif fault == "rope_on_every_dim":
        kw["rotary_dim"] = CFG.head_dim
    elif fault == "thetas_swapped":
        kw.update(rope_theta=CFG.rope_theta_window,
                  rope_theta_window=CFG.rope_theta)
    elif fault == "gates_not_renormalised":
        monkeypatch.setattr(mimo_v2, "_route", _route_with(renormalise=False))
    elif fault == "gate_from_the_biased_score":
        monkeypatch.setattr(mimo_v2, "_route", _route_with(gate_by="s+b"))
    elif fault == "choice_from_the_unbiased_score":
        monkeypatch.setattr(mimo_v2, "_route", _route_with(choose_by="s"))
    elif fault == "bf16_router":
        monkeypatch.setattr(mimo_v2, "_route", _route_with(jnp.bfloat16))
    cfg = mimo_v2.MiMoV2Config.tiny(dtype=jnp.float32, **kw)
    with jax.default_matmul_precision("highest"):
        got, _other, _b = _serve_logits(Pager(cfg, served), PROMPT, FOLLOW)
    want = _ref_logits(params, np.concatenate([PROMPT, FOLLOW]))
    # A faulty block's three programs are nobody else's: dropped here
    # (tests/conftest.py clears at a module's end only).
    jax.clear_caches()
    assert np.abs(got - want[len(PROMPT) - 1:]).max() > FAULT_MIN


def test_the_true_route_passes_where_its_twin_stands_in(params, monkeypatch):
    """The faults' stand-in router with no fault set IS the true one: the
    faults above fail by what they change, not by the stand-in."""
    monkeypatch.setattr(mimo_v2, "_route", _route_with())
    cfg = mimo_v2.MiMoV2Config.tiny(dtype=jnp.float32, max_seq=300)
    with jax.default_matmul_precision("highest"):
        got, _other, _b = _serve_logits(Pager(cfg, params), PROMPT, FOLLOW)
    want = _ref_logits(params, np.concatenate([PROMPT, FOLLOW]))
    np.testing.assert_allclose(got, want[len(PROMPT) - 1:], atol=ATOL_F32,
                               rtol=0)


@pytest.mark.parametrize("rows", [1, 2, 3])
@pytest.mark.parametrize("attn_impl", ["gather", "kernel"])
def test_a_prompt_over_any_number_of_dispatches_gives_the_unsplit_logits(
        params, rows, attn_impl):
    """The ring's hazard (models/laguna.py): a prompt of three windows,
    six chunks, one, two or three rows a dispatch, each with the ring
    that height asks for, ends in the reference's logits."""
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


def test_the_pool_holds_four_planes_of_four_widths(params):
    """K and V of each kind at its own minor width, the ring sized by
    laguna's rule (one rule for both families)."""
    pool = Pager(CFG, params, rows=8).pool
    R = laguna.ring_pages(CFG.window, PAGE, 8 * CHUNK)
    nf, nw = CFG.count("full"), CFG.count("window")
    assert pool["k"].shape == (nf, N_PAGES + 1, PAGE, 2 * 24)
    assert pool["v"].shape == (nf, N_PAGES + 1, PAGE, 2 * 16)
    assert pool["k_win"].shape == (nw, (N_SLOTS + 1) * R, PAGE, 4 * 24)
    assert pool["v_win"].shape == (nw, (N_SLOTS + 1) * R, PAGE, 4 * 16)
    assert pool["ring_rows"].shape == (N_SLOTS + 1, R)
    assert pool["moe_counters"].shape == (len(mimo_v2.COUNTERS),)
    assert mimo_v2.COUNTERS[-1] == "rows_bias_moved"


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
        cfg = mimo_v2.MiMoV2Config.tiny(dtype=jnp.float32, max_seq=301)
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
    at its position (deficit under ATOL_F32); the pool's bytes by kind
    and the counters, the bias's among them."""
    from ray_tpu.models import serving

    fam = serving.family_of(CFG)
    assert fam.name == "mimo_v2" and fam.slot_ring and fam.expert_counters
    assert fam.model is mimo_v2 and not fam.slot_state
    eng = _engine(params)
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
    ring = laguna.ring_pages(CFG.window, PAGE, eng.chunk_rows * CHUNK)
    token = lambda G: G * (CFG.head_dim + CFG.v_head_dim) * 4   # K + V, f32
    assert m["kv_bytes_window"] == m["window_kv_bytes"] == (
        CFG.count("window") * (N_SLOTS + 1) * ring * PAGE * token(4))
    assert m["kv_bytes_full"] == (
        CFG.count("full") * (N_PAGES + 1) * PAGE * token(2))
    assert m["kv_pool_bytes"] == m["kv_bytes_full"] + m["kv_bytes_window"]
    assert m["kv_bytes_window_live"] == (
        CFG.count("window") * N_SLOTS * CFG.window * token(4))
    assert m["kv_bytes_window_live"] < m["kv_bytes_window"]
    # Every (sparse layer, step) of a decode window routed top_k choices
    # a live row, about half of them on the held half, and the bias moved
    # some of them but not most.
    assert m["moe_layer_steps"] % CFG.count("sparse") == 0
    assert m["moe_rows_routed"] % CFG.top_k == 0
    assert 0.3 < m["moe_rows_held"] / m["moe_rows_routed"] < 0.7
    assert 0 < m["moe_rows_bias_moved"] < 0.5 * m["moe_rows_routed"]
    assert 1.0 <= m["moe_experts_touched"] <= CFG.n_experts
    eng.reset_stats()
    after = eng.metrics()
    assert after["moe_rows_routed"] == after["moe_rows_bias_moved"] == 0


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
