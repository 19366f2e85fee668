"""The `qwen3_next` family on the CPU at `Qwen3NextConfig.tiny` (two
periods of 3 linear : 1 full; 2 value heads a key head; 16 query heads
over 2 KV heads; rope on a quarter of a head; 8 experts top-3 with 4
held), seeded random weights with every leaf moved off its initial value
and the WIDE decay the benchmark seeds (`g_dt_bias` ~ N(0, 4), `g_A_log`
~ N(0, 1): time constants from under a token to hundreds), and once with
the model's own start: `forward`, the paged programs and the engine
against the plain reference
benchmarks/harness/reference/qwen3_next_ref.py, in LOGITS.

Tolerances, each with its reason:
  ATOL_F32 = 3e-5   program and reference both compute in float32 at
      "highest" matmul precision; they differ by reassociation only (the
      chunked scan for the recurrence, blockwise softmax, rsqrt for
      1/sqrt, the grouped matmul's sums). Logits here are O(1).
  FAULT_MIN = 1e-3  each fault below must move some logit by more.
"""

import collections
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import qwen3_next as qn
from ray_tpu.ops.moe import token_choice_experts
from ray_tpu.serve.llm import LLMEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:          # the harness imports itself as `harness`
    sys.path.insert(0, BENCH)

from harness.reference import qwen3_next_ref  # noqa: E402

ATOL_F32 = 3e-5
FAULT_MIN = 1e-3

CFG = qn.Qwen3NextConfig.tiny(dtype=jnp.float32)
RefConfig = collections.namedtuple(
    "RefConfig", "n_layers full_interval n_heads n_kv_heads lin_k_heads "
    "lin_v_heads top_k first_expert norm_eps rope_theta rotary_dim")


def _rc(cfg):
    return RefConfig(cfg.n_layers, cfg.full_interval, cfg.n_heads,
                     cfg.n_kv_heads, cfg.lin_k_heads, cfg.lin_v_heads,
                     cfg.top_k, cfg.first_expert, cfg.norm_eps,
                     cfg.rope_theta, cfg.rotary_dim)


RC = _rc(CFG)
# Chunk rows of 32 tokens hold two scan blocks of 16; two rows a dispatch.
PAGE, N_PAGES, N_SLOTS, CHUNK, ROWS = 16, 24, 3, 32, 2


def _params(cfg=CFG, seed=0, wide=True):
    """Seeded weights with EVERY leaf moved off its initial value: norm
    weights by a tenth, matmul planes by 0.02; the output projections
    are 8x their initial size so that both mixers, the shared expert and
    the routed experts all move the logits. `wide`: the decay as the
    benchmark seeds it; else the model's own start, moved a little."""
    p = qn.init_params(cfg, jax.random.key(seed))
    keys = jax.random.split(jax.random.key(seed + 1), len(p))
    out = {}
    for key, (name, v) in zip(keys, sorted(p.items())):
        size = 0.1 if name.endswith(("_scale", "norm")) else 0.02
        grow = 8.0 if name.endswith(("_wo", "_down", "g_out")) else 1.0
        out[name] = grow * v + size * jax.random.normal(key, v.shape, v.dtype)
    if wide:
        for name, scale in (("g_dt_bias", 4.0), ("g_A_log", 1.0)):
            out[name] = scale * jax.random.normal(
                jax.random.key(seed + 2), p[name].shape, p[name].dtype)
    return out


@pytest.fixture(scope="module")
def params():
    return _params()


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, n).astype(np.int32)


def _ref_logits(params, seq, rc=RC):
    return np.asarray(qwen3_next_ref.logits(params, jnp.asarray(seq), rc))


@pytest.mark.parametrize("wide", [True, False])
def test_forward_matches_the_reference_in_logits(wide):
    params = _params(wide=wide)
    seqs = np.stack([_tokens(75, s) for s in (1, 2)])
    with jax.default_matmul_precision("highest"):
        got = np.asarray(qn.forward(CFG, params, jnp.asarray(seqs)))
    want = np.stack([_ref_logits(params, s) for s in seqs])
    assert np.abs(want).max() > 0.5                 # not a flat model
    np.testing.assert_allclose(got, want, atol=ATOL_F32, rtol=0)


def test_the_wide_decay_spreads_the_time_constants(params):
    """What the seeded `g_dt_bias` and `g_A_log` are for: over a prompt,
    some heads forget within a token (|g| > 1) and some remember for
    tens (|g| < 0.05), so a fault in carrying the state shows."""
    seen = []
    true = qn._gdn_inputs

    def spy(*a, **kw):
        out = true(*a, **kw)
        seen.append(out[4])
        return out

    qn._gdn_inputs, keep = spy, qn._gdn_inputs
    try:
        qn.forward(qn.Qwen3NextConfig.tiny(dtype=jnp.float32, max_seq=300),
                   params, jnp.asarray(_tokens(64, 3))[None])
    finally:
        qn._gdn_inputs = keep
    g = -np.concatenate([np.asarray(t).reshape(-1) for t in seen])
    assert (g > 1).mean() > 0.2 and (g < 0.05).mean() > 0.1


def test_the_shares_add_up(params):
    """Four chips' routed parts (experts 0-1 ... 6-7 of 8) plus the
    gated shared expert counted ONCE are the uncut reference's layer."""
    whole = qn.Qwen3NextConfig.tiny(dtype=jnp.float32, n_experts=8)
    full = _params(whole, seed=3)
    u = jax.random.normal(jax.random.key(7), (40, CFG.d_model), jnp.float32)
    l = 2
    w = {n: full[n][l] for n in ("router", "s_gate", "s_up", "s_down",
                                 "s_gate_w", "w_gate", "w_up", "w_down")}
    with jax.default_matmul_precision("highest"):
        want = qwen3_next_ref._sparse_mlp(
            u, w, _rc(whole), lambda e: (
                w["w_gate"][e], w["w_up"][e], w["w_down"][e]), 8)
        chosen, gates = qn._route(whole, w["router"], u)
        parts, held = [], 0
        for first in (0, 2, 4, 6):
            share = slice(first, first + 2)
            y, counts = token_choice_experts(
                u, chosen, gates, w["w_gate"][share], w["w_up"][share],
                w["w_down"][share], first_expert=first)
            parts.append(y)
            held += int(counts.sum())
        shared = (qn._shared_gate(u, w["s_gate_w"])
                  * qn.gated_mlp(u, w["s_gate"], w["s_up"], w["s_down"]))
    assert held == u.shape[0] * whole.top_k         # every choice, once
    assert all(float(jnp.abs(y).max()) > 1e-3 for y in parts)
    np.testing.assert_allclose(sum(parts) + shared, want, atol=1e-5, rtol=0)


class Pager:
    """The engine's device side by hand: a pool of pages and of slot
    states, a page table a slot, and the two paged programs called as
    `LLMEngine` calls them."""

    def __init__(self, cfg, params, attn_impl="gather"):
        self.cfg, self.params, self.impl = cfg, params, attn_impl
        self.pool = qn.init_paged_kv(cfg, N_PAGES, PAGE, N_SLOTS)
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
        out, self.pool = qn.prefill_chunk_paged(
            self.cfg, self.params, jnp.asarray(toks), self.pool,
            jnp.asarray(self.tables[slots]), jnp.asarray(offs),
            jnp.asarray(valid), slots=jnp.asarray(slots),
            return_logits=head, attn_impl=self.impl)
        return None if out is None else np.asarray(out)

    def prefill(self, slot, prompt, rows=ROWS, between=None):
        """A whole prompt, `rows` chunk rows a dispatch (`between()`
        runs between dispatches) -> its last token's logits."""
        cuts = [(slot, prompt[i:i + CHUNK], i)
                for i in range(0, len(prompt), CHUNK)]
        for i in range(0, len(cuts), rows):
            if i and between is not None:
                between()
            out = self.chunks(cuts[i:i + rows], height=rows)
        return out[len(cuts[i:i + rows]) - 1]

    def decode(self, tokens, positions, active):
        """One step for every slot (row b IS slot b) -> logits [B, V]."""
        for slot in active:
            self.grow(slot, int(positions[slot]) + 1)
        tables = np.where(np.isin(np.arange(N_SLOTS), active)[:, None],
                          self.tables, 0)
        out, self.pool = qn.decode_step_paged(
            self.cfg, self.params, jnp.asarray(tokens, jnp.int32), self.pool,
            jnp.asarray(positions, jnp.int32), jnp.asarray(tables),
            attn_impl=self.impl)
        return np.asarray(out)


def _serve_logits(pager, prompt, follow, slot=1):
    """Chunked prefill of `prompt` in `slot`, then teacher-forced decode
    of `follow` (another slot mid-prefill beside it) -> logits at
    positions len(prompt)-1 .. end-1."""
    rows = [pager.prefill(slot, prompt)]
    # A bystander: slot 0 holds HALF a prompt while slot 1 decodes; its
    # state and tail must survive the decode steps it takes no part in.
    other = _tokens(2 * CHUNK - 5, seed=9)
    pager.chunks([(0, other[:CHUNK], 0)], head=False, height=ROWS)
    tokens = np.zeros(N_SLOTS, np.int32)
    positions = np.zeros(N_SLOTS, np.int32)
    for i, tok in enumerate(follow):
        tokens[slot], positions[slot] = tok, len(prompt) + i
        rows.append(pager.decode(tokens, positions, [slot])[slot])
    bystander = pager.chunks([(0, other[CHUNK:], CHUNK)], height=ROWS)[0]
    return np.stack(rows), other, bystander


# Three chunk rows (two dispatches), the last of 11 tokens: not a
# multiple of the scan block, the page or the chunk.
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


def test_the_laid_out_tree_serves_the_stacks_logits_bit_for_bit(params):
    """`lay_out` changes where a plane lives, not what is computed: the
    chunk programs (with the head and without) and the decode step over
    a leaf a layer give the logits of the stacks, every bit."""
    with jax.default_matmul_precision("highest"):
        want, _other, want_b = _serve_logits(Pager(CFG, params), PROMPT,
                                             FOLLOW)
        got, _other, got_b = _serve_logits(
            Pager(CFG, qn.lay_out(CFG, params)), PROMPT, FOLLOW)
    assert np.array_equal(got, want) and np.array_equal(got_b, want_b)


def test_lay_out_cuts_the_dense_planes_and_nothing_else(params):
    """A tuple of per-layer arrays for every dense matrix a layer (each
    a stack of matrices with a side of d_model), the stack's own rows;
    the experts' stacks, the convolution's taps and every vector come
    back as they went in; laid out twice is laid out once."""
    out = qn.lay_out(CFG, params)
    assert out.keys() == params.keys()
    planes = {name for name, a in params.items()
              if a.ndim == 3 and CFG.d_model in a.shape[1:]}
    assert planes == set(qn._PLANE_KEYS)
    for name, a in params.items():
        if name in planes:
            assert isinstance(out[name], tuple) and len(out[name]) == len(a)
            assert all(np.array_equal(leaf, a[i])
                       for i, leaf in enumerate(out[name]))
        else:
            assert out[name] is a
    again = qn.lay_out(CFG, out)
    assert all(again[name] is out[name] for name in out)
    nbytes = lambda tree: sum(int(a.nbytes) for a in jax.tree.leaves(tree))
    assert nbytes(out) == nbytes(params)


def test_the_models_own_start_serves_the_references_logits():
    params = _params(wide=False)
    with jax.default_matmul_precision("highest"):
        got, _other, _b = _serve_logits(Pager(CFG, params), PROMPT, FOLLOW)
    want = _ref_logits(params, np.concatenate([PROMPT, FOLLOW]))
    np.testing.assert_allclose(got, want[len(PROMPT) - 1:], atol=ATOL_F32,
                               rtol=0)


@pytest.mark.parametrize("n_prompt", [31, 64, 97, 128])
@pytest.mark.parametrize("how", ["one_dispatch", "a_row_a_dispatch",
                                 "between_decode_steps"])
def test_a_prompt_dispatched_any_way_gives_the_same_logits(params, n_prompt,
                                                           how):
    """A recurrence cannot read a chained row's boundary in parallel: a
    prompt whose chunks go in ONE dispatch (every row but the first
    starts from the row above), a row a dispatch (every row from the
    slot's state), or with another slot's decode steps between its
    dispatches ends in the reference's logits."""
    prompt = _tokens(n_prompt, 3)
    pager = Pager(CFG, params)
    with jax.default_matmul_precision("highest"):
        if how == "between_decode_steps":
            pager.prefill(0, _tokens(40, 8))
            state = {"pos": 40}

            def between():
                toks, pos = np.zeros(N_SLOTS, np.int32), np.zeros(
                    N_SLOTS, np.int32)
                toks[0], pos[0] = 7, state["pos"]
                pager.decode(toks, pos, [0])
                state["pos"] += 1

            got = pager.prefill(2, prompt, rows=1, between=between)
        else:
            rows = 4 if how == "one_dispatch" else 1
            got = pager.prefill(2, prompt, rows=rows)
    np.testing.assert_allclose(got, _ref_logits(params, prompt)[-1],
                               atol=ATOL_F32, rtol=0)


def test_a_reused_slot_reads_nothing_of_its_predecessor(params):
    """Slot 1 serves a long prompt and decodes, leaving a state and a
    tail; the next prompt in the same slot (new pages, offset 0) starts
    from zeros."""
    first, second = _tokens(90, 4), _tokens(19, 5)
    with jax.default_matmul_precision("highest"):
        used = Pager(CFG, params)
        _serve_logits(used, first, _tokens(5, 6))
        assert float(jnp.abs(used.pool["gdn_state"][:, 1]).max()) > 0.01
        assert float(jnp.abs(used.pool["gdn_conv"][:, 1]).max()) > 0.01
        used.tables[1] = 0                          # released: new pages
        again = used.prefill(1, second)
    np.testing.assert_allclose(again, _ref_logits(params, second)[-1],
                               atol=ATOL_F32, rtol=0)


def _gdn_inputs_with(true, change):
    def inputs(cfg, params, l, x, valid, boundary):
        return change(*true(cfg, params, l, x, valid, boundary), x=x,
                      rerun=lambda b: true(cfg, params, l, x, valid, b))
    return inputs


def _route_with(score_dtype=jnp.float32, renormalise=True):
    def route(cfg, w_router, u):
        p = jax.nn.softmax(u.astype(score_dtype) @ w_router.astype(score_dtype),
                           axis=-1)
        top, chosen = jax.lax.top_k(p.astype(jnp.float32), cfg.top_k)
        norm = jnp.sum(top, axis=-1, keepdims=True) if renormalise else 1.0
        return chosen.astype(jnp.int32), top / norm
    return route


FAULTS = ["state_in_bf16", "decay_dropped", "beta_one", "tail_not_carried",
          "state_zeroed_at_a_chunk", "qk_not_normalised",
          "attention_gate_dropped", "shared_gate_dropped", "rope_on_all_dims",
          "gates_not_renormalised", "bf16_router"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails_the_tolerance(params, fault, monkeypatch):
    """What the tolerance is for: each of these serves logits that the
    comparison above would refuse."""
    # A configuration of its own, so that no trace of the true block is
    # found in the jit cache.
    kw = {"max_seq": 257 + FAULTS.index(fault)}
    true = qn._gdn_inputs
    if fault == "state_in_bf16":
        step = qn.reference_gdn_decode_step

        def rounded(*a, **k):
            o, state = step(*a, **k)
            return o, state.astype(jnp.bfloat16).astype(jnp.float32)

        monkeypatch.setattr(qn, "reference_gdn_decode_step", rounded)
    elif fault == "decay_dropped":
        monkeypatch.setattr(qn, "_gdn_inputs", _gdn_inputs_with(
            true, lambda q, k, v, z, g, beta, ext, **_: (
                q, k, v, z, jnp.zeros_like(g), beta, ext)))
    elif fault == "beta_one":
        monkeypatch.setattr(qn, "_gdn_inputs", _gdn_inputs_with(
            true, lambda q, k, v, z, g, beta, ext, **_: (
                q, k, v, z, g, jnp.where(beta > 0, 1.0, 0.0), ext)))
    elif fault == "tail_not_carried":
        # A chunk row (not a decode step) starts its convolution from
        # zeros whatever came before it.
        monkeypatch.setattr(qn, "_gdn_inputs", _gdn_inputs_with(
            true, lambda *out, x, rerun: out if x.shape[1] == 1 else rerun(
                lambda mixed: jnp.zeros(
                    (mixed.shape[0], CFG.conv_taps - 1, mixed.shape[2]),
                    mixed.dtype))))
    elif fault == "state_zeroed_at_a_chunk":
        scan = qn.gdn_chunk_scan
        monkeypatch.setattr(
            qn, "gdn_chunk_scan",
            lambda q, k, v, g, beta, state, chain, fresh, **k2: scan(
                q, k, v, g, beta, state, chain, jnp.ones_like(fresh), **k2))
    elif fault == "qk_not_normalised":
        monkeypatch.setattr(qn, "_unit", lambda x: x)
    elif fault == "attention_gate_dropped":
        attn = qn._attn_inputs

        def no_gate(*a):
            q, k, v, gate = attn(*a)
            return q, k, v, jnp.ones_like(gate)

        monkeypatch.setattr(qn, "_attn_inputs", no_gate)
    elif fault == "shared_gate_dropped":
        monkeypatch.setattr(qn, "_shared_gate",
                            lambda u, w: jnp.ones((u.shape[0], 1)))
    elif fault == "rope_on_all_dims":
        kw["rotary_dim"] = CFG.head_dim
    elif fault == "gates_not_renormalised":
        monkeypatch.setattr(qn, "_route", _route_with(renormalise=False))
    elif fault == "bf16_router":
        monkeypatch.setattr(qn, "_route", _route_with(jnp.bfloat16))
    cfg = qn.Qwen3NextConfig.tiny(dtype=jnp.float32, **kw)
    with jax.default_matmul_precision("highest"):
        got, _other, _b = _serve_logits(Pager(cfg, params), PROMPT, FOLLOW)
    want = _ref_logits(params, np.concatenate([PROMPT, FOLLOW]))
    # A faulty block's three programs (eight layers walked in Python) are
    # nobody else's: dropped here, or this module alone holds more memory
    # mappings than a process may (tests/conftest.py clears at a
    # module's end only).
    jax.clear_caches()
    assert np.abs(got - want[len(PROMPT) - 1:]).max() > FAULT_MIN


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
    three slots (so one slot is reused by a shorter request), prompts of
    one to three chunk rows, every emitted token the float32 reference's
    best at its position (deficit under ATOL_F32)."""
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
    nl = CFG.count("linear")
    assert m["preemptions"] == 0 and m["window_kv_bytes"] == 0
    # The operator's third memory account: both leaves, null slot and all.
    assert m["slot_state_bytes"] == nl * (N_SLOTS + 1) * 4 * (
        CFG.lin_v_heads * CFG.lin_k_dim * CFG.lin_v_dim
        + (CFG.conv_taps - 1) * CFG.conv_channels)
    assert m["slot_state_bytes"] == sum(
        int(eng.cache[n].nbytes) for n in ("gdn_state", "gdn_conv"))
    assert m["kv_pool_bytes"] == (
        2 * CFG.count("full") * (N_PAGES + 1) * PAGE
        * CFG.n_kv_heads * CFG.head_dim * 4)
    # Every (layer, step) of a decode window routed top_k choices a live
    # row, and about half of them landed on the held half.
    assert m["moe_layer_steps"] % CFG.n_layers == 0
    assert m["moe_rows_routed"] % CFG.top_k == 0
    assert 0 < m["moe_rows_held"] < m["moe_rows_routed"]
    assert 0.3 < m["moe_rows_held"] / m["moe_rows_routed"] < 0.7
    assert 1.0 <= m["moe_experts_touched"] <= CFG.n_experts


def test_the_engine_holds_the_laid_out_tree(params):
    """The engine applies the family's `lay_out` once at load and keeps
    the result only: tuples where the programs index a plane, the
    operator's weight account unchanged, the caller's tree untouched."""
    eng = _engine(params)
    for name, a in params.items():
        assert isinstance(eng.params[name], tuple) == (name in qn._PLANE_KEYS)
        assert not isinstance(a, tuple)
    assert eng.metrics()["weight_bytes"] == sum(
        int(a.nbytes) for a in params.values())


def test_engine_recomputes_a_preempted_request_to_the_same_tokens(params):
    """A pool too small for both requests: one is evicted by recompute
    and re-prefilled from offset 0 into the slot it had used (zeros, not
    the state it left); both streams stay the reference's."""
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
    ("prefix_cache", True, "snapshot of the linear layers' recurrent state"),
    ("spec_draft", "tiny", "cannot be run backwards"),
    ("kv_transfer", True, "page set would have to carry"),
    ("tp", 2, "expert-parallel exchange"),
    ("weight_dtype", "int8", "no int8 form"),
    ("kv_dtype", "int8", "float32 by the model's own definition"),
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


def test_the_family_is_found_by_its_configuration():
    from ray_tpu.models import serving

    fam = serving.family_of(CFG)
    assert fam.name == "qwen3_next" and fam.init_pool is qn.init_paged_kv
    assert fam.slot_state == ("gdn_state", "gdn_conv")
    assert fam.expert_counters and not fam.slot_ring
    assert fam.lay_out is qn.lay_out


@pytest.mark.parametrize("name", ["gpt", "zaya", "laguna", "mimo_v2"])
def test_no_other_family_lays_its_weights_out(name):
    """Their decode programs hold no hoisted slice pass
    (tests/test_chip_compile.py): their trees reach the programs as
    they came."""
    from ray_tpu.models import serving

    assert serving._family(name).lay_out is None
