"""The `olmo_hybrid` family on the CPU at `OlmoHybridConfig.tiny` (two
periods of 3 linear : 1 full; 6 heads of each kind; keys 32 wide, values
64: half a lane tile too many, so the pool's state packs two heads side
by side as it does at 192; multi-head attention with a norm over q's and
k's whole width and no positions; every sublayer's output normed; a
dense MLP), seeded random weights with every leaf moved off its initial
value and the WIDE decay the benchmark seeds (`g_dt_bias` ~ N(0, 4),
`g_A_log` ~ N(0, 1)), and once with the model's own start: `forward`,
the paged programs and the engine against the plain reference
benchmarks/harness/reference/olmo_hybrid_ref.py, in LOGITS.

Tolerances, each with its reason:
  ATOL_F32 = 5e-4   program and reference both compute in float32 at
      "highest" matmul precision; they differ by reassociation only (the
      chunked scan for the recurrence, blockwise softmax, rsqrt for
      1/sqrt, one matmul against four projections side by side for four
      matmuls). Logits here are O(1). Seven of eight layers deep they
      agree to 2e-5 and at most positions of the eighth to 4e-5 (as
      qwen3_next's pre-norm block does, to 3e-5), but this block
      renormalises every sublayer's OUTPUT to unit size, so a position
      where a sublayer's output is small carries its rounding to the
      stream at full size: the REFERENCE ITSELF moves a logit by 1.1e-4
      to 2.5e-4 at such a position when every weight is moved by 1e-7 of
      itself (three draws; the model's own start, position 18 of the
      prompt below), and the program's worst reading there is 2.4e-4.
  FAULT_MIN = 1e-2  each fault below must move some logit by more; the
      least of them (the state rounded to bfloat16 after every decode
      step) moves one by 3.6e-2, the others by 0.3 to 1.4.
"""

import collections
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import olmo_hybrid as oh
from ray_tpu.serve.llm import LLMEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:          # the harness imports itself as `harness`
    sys.path.insert(0, BENCH)

from harness.reference import olmo_hybrid_ref  # noqa: E402

ATOL_F32 = 5e-4
FAULT_MIN = 1e-2

CFG = oh.OlmoHybridConfig.tiny(dtype=jnp.float32)
RefConfig = collections.namedtuple(
    "RefConfig", "n_layers full_interval n_heads n_kv_heads lin_heads "
    "lin_k_dim neg_eigval norm_eps")


def _rc(cfg):
    return RefConfig(cfg.n_layers, cfg.full_interval, cfg.n_heads,
                     cfg.n_kv_heads, cfg.lin_v_heads, cfg.lin_k_dim,
                     cfg.allow_neg_eigval, cfg.norm_eps)


RC = _rc(CFG)
# Chunk rows of 32 tokens hold two scan blocks of 16; two rows a dispatch.
PAGE, N_PAGES, N_SLOTS, CHUNK, ROWS = 16, 24, 3, 32, 2


def _params(cfg=CFG, seed=0, wide=True):
    """Seeded weights with EVERY leaf moved off its initial value: norm
    weights by a tenth, matmul planes by 0.02 (the output projections'
    own size does not matter here: each passes a norm). `wide`: the
    decay as the benchmark seeds it; else the model's own start, moved a
    little."""
    p = oh.init_params(cfg, jax.random.key(seed))
    keys = jax.random.split(jax.random.key(seed + 1), len(p))
    out = {}
    for key, (name, v) in zip(keys, sorted(p.items())):
        size = 0.1 if name.endswith(("_scale", "norm")) else 0.02
        out[name] = v + size * jax.random.normal(key, v.shape, v.dtype)
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
    return np.asarray(olmo_hybrid_ref.logits(params, jnp.asarray(seq), rc))


@pytest.mark.parametrize("wide", [True, False])
def test_forward_matches_the_reference_in_logits(wide):
    params = _params(wide=wide)
    seqs = np.stack([_tokens(75, s) for s in (1, 2)])
    with jax.default_matmul_precision("highest"):
        got = np.asarray(oh.forward(CFG, params, jnp.asarray(seqs)))
    want = np.stack([_ref_logits(params, s) for s in seqs])
    assert np.abs(want).max() > 0.5                 # not a flat model
    np.testing.assert_allclose(got, want, atol=ATOL_F32, rtol=0)


def test_the_write_strength_passes_one_and_the_decay_is_spread(params):
    """What the cell's weights are seeded for: beta = 2 sigmoid(b) lies
    in (0, 2) and passes 1 on a good share of tokens (the reflecting
    range no other family reaches), some heads forget within a token
    (|g| > 1) and some remember for tens (|g| < 0.05)."""
    seen = []
    true = oh._gdn_inputs

    def spy(*a, **kw):
        out = true(*a, **kw)
        seen.append((out[4], out[5]))
        return out

    oh._gdn_inputs, keep = spy, oh._gdn_inputs
    try:
        oh.forward(oh.OlmoHybridConfig.tiny(dtype=jnp.float32, max_seq=300),
                   params, jnp.asarray(_tokens(64, 3))[None])
    finally:
        oh._gdn_inputs = keep
    g = -np.concatenate([np.asarray(t).reshape(-1) for t, _ in seen])
    beta = np.concatenate([np.asarray(b).reshape(-1) for _, b in seen])
    assert (g > 1).mean() > 0.2 and (g < 0.05).mean() > 0.1
    assert 0 < beta.min() and beta.max() < 2 and (beta > 1).mean() > 0.3


def test_the_pool_packs_two_heads_side_by_side():
    """The state's leaf as the chip wants it: [Hv / 2, dk, 2 dv] where
    dv is half a lane tile over a whole one (64 here, 192 published),
    the plain [Hv, dk, dv] where dv fills tiles (qwen3-next's 128)."""
    pool = oh.init_paged_kv(CFG, N_PAGES, PAGE, N_SLOTS)
    assert CFG.state_pack == 2
    assert pool["gdn_state"].shape == (6, N_SLOTS + 1, 3, 32, 128)
    assert pool["gdn_state"].dtype == jnp.float32
    published = oh.OlmoHybridConfig()
    assert published.state_pack == 2
    assert jax.eval_shape(lambda: oh.init_paged_kv(
        published, 8, 64, 2))["gdn_state"].shape == (24, 3, 15, 96, 384)
    assert oh.OlmoHybridConfig.tiny(lin_v_dim=128).state_pack == 1


class Pager:
    """The engine's device side by hand: a pool of pages and of slot
    states, a page table a slot, and the two paged programs called as
    `LLMEngine` calls them."""

    def __init__(self, cfg, params, attn_impl="gather"):
        self.cfg, self.params, self.impl = cfg, params, attn_impl
        self.pool = oh.init_paged_kv(cfg, N_PAGES, PAGE, N_SLOTS)
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
        out, self.pool = oh.prefill_chunk_paged(
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
        out, self.pool = oh.decode_step_paged(
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
    slot's packed state), or with another slot's decode steps between
    its dispatches ends in the reference's logits."""
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
    def inputs(cfg, params, i, x, valid, boundary):
        return change(*true(cfg, params, i, x, valid, boundary), x=x,
                      rerun=lambda b: true(cfg, params, i, x, valid, b))
    return inputs


def _norm_a_head(cfg, true):
    """`rms_norm` (`true`) with q's and k's whole-width norm made a
    head's."""
    def norm(x, scale, eps):
        if x.shape[-1] != cfg.n_heads * cfg.head_dim:
            return true(x, scale, eps)
        heads = x.shape[:-1] + (cfg.n_heads, cfg.head_dim)
        return true(x.reshape(heads), scale.reshape(heads[-2:]),
                    eps).reshape(x.shape)
    return norm


def _rotated(x):
    """x [N, C, H, K] turned by its column's position (rotate-half,
    theta 1e4): the rotary position the model does NOT have."""
    C, K = x.shape[1], x.shape[-1]
    freq = 1e4 ** (-jnp.arange(0, K, 2) / K)
    ang = jnp.arange(C)[:, None, None] * freq
    x1, x2 = x[..., :K // 2], x[..., K // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], axis=-1)


FAULTS = ["state_in_bf16", "beta_not_doubled", "decay_dropped",
          "tail_not_carried", "state_zeroed_at_a_chunk", "qk_not_normalised",
          "whole_width_norm_made_a_heads", "output_norms_moved_to_inputs",
          "rope_added"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails_the_tolerance(params, fault, monkeypatch):
    """What the tolerance is for: each of these serves logits that the
    comparison above would refuse."""
    # A configuration of its own, so that no trace of the true block is
    # found in the jit cache.
    kw = {"max_seq": 257 + FAULTS.index(fault)}
    true = oh._gdn_inputs
    if fault == "state_in_bf16":
        step = oh.reference_gdn_decode_step

        def rounded(*a, **k):
            o, state = step(*a, **k)
            return o, state.astype(jnp.bfloat16).astype(jnp.float32)

        monkeypatch.setattr(oh, "reference_gdn_decode_step", rounded)
    elif fault == "beta_not_doubled":
        kw["allow_neg_eigval"] = False
    elif fault == "decay_dropped":
        monkeypatch.setattr(oh, "_gdn_inputs", _gdn_inputs_with(
            true, lambda q, k, v, z, g, beta, ext, **_: (
                q, k, v, z, jnp.zeros_like(g), beta, ext)))
    elif fault == "tail_not_carried":
        # A chunk row (not a decode step) starts its convolution from
        # zeros whatever came before it.
        monkeypatch.setattr(oh, "_gdn_inputs", _gdn_inputs_with(
            true, lambda *out, x, rerun: out if x.shape[1] == 1 else rerun(
                lambda mixed: jnp.zeros(
                    (mixed.shape[0], CFG.conv_taps - 1, mixed.shape[2]),
                    mixed.dtype))))
    elif fault == "state_zeroed_at_a_chunk":
        scan = oh.gdn_chunk_scan
        monkeypatch.setattr(
            oh, "gdn_chunk_scan",
            lambda q, k, v, g, beta, state, chain, fresh, **k2: scan(
                q, k, v, g, beta, state, chain, jnp.ones_like(fresh), **k2))
    elif fault == "qk_not_normalised":
        monkeypatch.setattr(oh, "_unit", lambda x: x)
    elif fault == "whole_width_norm_made_a_heads":
        monkeypatch.setattr(oh, "rms_norm", _norm_a_head(CFG, oh.rms_norm))
    elif fault == "output_norms_moved_to_inputs":
        # The pre-norm block every other family has: a sublayer reads
        # norm(x) and its output joins the stream as it is.
        norm = oh.rms_norm
        monkeypatch.setattr(
            oh, "rms_norm", lambda x, scale, eps: x
            if x.shape[-1] == CFG.d_model else norm(x, scale, eps))
        # (The i-th linear layer is layer i + i // 3, the i-th full
        # layer 4 i + 3: the inputs' functions are told i alone.)
        pre = lambda which, l, x, p: norm(x, p[which][l], CFG.norm_eps)
        attn, mlp = oh._attn_inputs, oh._mlp
        monkeypatch.setattr(
            oh, "_gdn_inputs", lambda cfg, p, i, x, valid, boundary: true(
                cfg, p, i, pre("ln1_scale", i + i // 3, x, p), valid,
                boundary))
        monkeypatch.setattr(
            oh, "_attn_inputs", lambda cfg, p, i, x: attn(
                cfg, p, i, pre("ln1_scale", 4 * i + 3, x, p)))

        def pre_mlp(cfg, p, l, x):
            u = pre("ln2_scale", l, x, p)
            return x + (mlp(cfg, p, l, u) - u)

        monkeypatch.setattr(oh, "_mlp", pre_mlp)
    cfg = oh.OlmoHybridConfig.tiny(dtype=jnp.float32, **kw)
    seq = np.concatenate([PROMPT, FOLLOW])
    want = _ref_logits(params, seq)
    with jax.default_matmul_precision("highest"):
        if fault == "rope_added":
            # Through `forward` (a row's column IS its position there).
            attn = oh._attn_inputs

            def turned(*a):
                q, k, v = attn(*a)
                return _rotated(q), _rotated(k), v

            monkeypatch.setattr(oh, "_attn_inputs", turned)
            got = np.asarray(oh.forward(cfg, params, jnp.asarray(seq)[None]))[
                0, len(PROMPT) - 1:]
        else:
            got, _other, _b = _serve_logits(Pager(cfg, params), PROMPT,
                                            FOLLOW)
    # A faulty block's three programs (eight layers walked in Python) are
    # nobody else's: dropped here (tests/conftest.py clears at a module's
    # end only).
    jax.clear_caches()
    moved = float(np.abs(got - want[len(PROMPT) - 1:]).max())
    assert moved > FAULT_MIN, moved


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
    # The operator's third memory account: both leaves, null slot and
    # all; packing two heads side by side moves no byte.
    assert m["slot_state_bytes"] == nl * (N_SLOTS + 1) * 4 * (
        CFG.lin_v_heads * CFG.lin_k_dim * CFG.lin_v_dim
        + (CFG.conv_taps - 1) * CFG.conv_channels)
    assert m["slot_state_bytes"] == sum(
        int(eng.cache[n].nbytes) for n in ("gdn_state", "gdn_conv"))
    assert m["kv_pool_bytes"] == (
        2 * CFG.count("full") * (N_PAGES + 1) * PAGE
        * CFG.n_kv_heads * CFG.head_dim * 4)
    # No `lay_out`: the engine serves the tree it was handed.
    assert all(eng.params[name] is a for name, a in params.items())


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
    ("tp", 2, "pipeline stages"),
    ("weight_dtype", "int8", "int8 form of this family's tree"),
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
    assert fam.name == "olmo_hybrid" and fam.init_pool is oh.init_paged_kv
    assert fam.lay_out is None and fam.slot_state == oh.SLOT_STATE_LEAVES
    assert fam.expert_counters == ()
