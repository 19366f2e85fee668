"""The `jamba` family on the CPU at `JambaConfig.tiny` (two periods of 3
mamba : 1 attention layer with the attention layer mid-period; 4 query
heads over ONE KV head and no positions; channels twice the width, a
state of 8 values a channel), seeded random weights with every leaf
moved off its initial value and the WIDE decay the benchmark seeds
(`m_dt_b` ~ N(0, 4), `m_A_log` ~ N(0, 1): time constants from under a
token to hundreds), and once with the model's own start: `forward`, the
paged programs and the engine against the plain reference
benchmarks/harness/reference/jamba_ref.py, in LOGITS.

Tolerances, each with its reason:
  ATOL_F32 = 3e-5   program and reference both compute in float32 at
      "highest" matmul precision; they differ by reassociation only (the
      state's sum over its values in the kernel's order, blockwise
      softmax, rsqrt for 1/sqrt). Logits here are O(1).
  FAULT_MIN = 1e-3  each fault below must move some logit by more.
"""

import collections
import itertools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import jamba as jm
from ray_tpu.serve.llm import LLMEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:          # the harness imports itself as `harness`
    sys.path.insert(0, BENCH)

from harness.reference import jamba_ref  # noqa: E402

ATOL_F32 = 3e-5
FAULT_MIN = 1e-3

CFG = jm.JambaConfig.tiny(dtype=jnp.float32)
RefConfig = collections.namedtuple(
    "RefConfig", "n_layers attn_period attn_offset n_heads n_kv_heads "
    "norm_eps")


def _rc(cfg):
    return RefConfig(cfg.n_layers, cfg.attn_period, cfg.attn_offset,
                     cfg.n_heads, cfg.n_kv_heads, cfg.norm_eps)


RC = _rc(CFG)
# Chunk rows of 32 tokens over pages of 16; two rows a dispatch.
PAGE, N_PAGES, N_SLOTS, CHUNK, ROWS = 16, 24, 3, 32, 2


def _params(cfg=CFG, seed=0, wide=True):
    """Seeded weights with EVERY leaf moved off its initial value: norm
    weights by a tenth, everything else by 0.02; the output projections
    are 8x their initial size so that both mixers and the MLP all move
    the logits. `wide`: the decay as the benchmark seeds it; else the
    model's own start (A = 1..S a channel, steps of 1e-3 to 1e-1),
    moved a little."""
    p = jm.init_params(cfg, jax.random.key(seed))
    keys = jax.random.split(jax.random.key(seed + 1), len(p))
    out = {}
    for key, (name, v) in zip(keys, sorted(p.items())):
        size = 0.1 if name.endswith(("_scale", "_norm")) else 0.02
        grow = 8.0 if name.startswith(("a_wo", "w_down", "m_out")) else 1.0
        out[name] = grow * v + size * jax.random.normal(key, v.shape, v.dtype)
    if wide:
        for name, scale in (("m_dt_b", 4.0), ("m_A_log", 1.0)):
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
    return np.asarray(jamba_ref.logits(params, jnp.asarray(seq), rc))


def test_the_layer_order_and_the_tree():
    """Layers 7 and 21 of 28 attend (l % 14 == 7); every leaf of the
    tree is a stack over the layers of its kind and the published sizes
    give 3.03 B parameters, the tied table counted once."""
    cfg = jm.JambaConfig()
    assert [l for l, k in enumerate(cfg.kinds) if k == "attn"] == [7, 21]
    assert (cfg.count("mamba"), cfg.d_inner) == (26, 5120)
    assert CFG.kinds == ("mamba", "mamba", "attn", "mamba") * 2
    assert cfg.runs == (("mamba", 0, 0, 7), ("attn", 7, 0, 1),
                        ("mamba", 8, 7, 13), ("attn", 21, 1, 1),
                        ("mamba", 22, 20, 6))
    specs = jm.param_specs(cfg)
    stacked = {n: s["shape"][0] for n, s in specs.items()
               if n not in ("wte", "ln_f_scale")}
    assert all(n == {"m": 26, "a": 2}.get(name[0], 28)
               for name, n in stacked.items()), stacked
    assert len(specs) == 23
    count = sum(int(np.prod(s["shape"])) for s in specs.values())
    assert 3.02e9 < count < 3.04e9
    assert "lm_head" not in specs


@pytest.mark.parametrize("wide", [True, False])
def test_forward_matches_the_reference_in_logits(wide):
    params = _params(wide=wide)
    seqs = np.stack([_tokens(75, s) for s in (1, 2)])
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jm.forward(CFG, params, jnp.asarray(seqs)))
    want = np.stack([_ref_logits(params, s) for s in seqs])
    assert np.abs(want).max() > 0.5                 # not a flat model
    np.testing.assert_allclose(got, want, atol=ATOL_F32, rtol=0)


def test_the_models_own_start_is_the_papers():
    """`init_params`: A = 1..S down a channel's state, D ones, and a
    bias whose softplus is a step between 1e-3 and 1e-1."""
    p = jm.init_params(CFG, jax.random.key(0))
    A = np.exp(np.asarray(p["m_A_log"]))
    assert A.shape == (6, CFG.d_state, CFG.d_inner)
    np.testing.assert_allclose(
        A, np.broadcast_to(np.arange(1, CFG.d_state + 1)[:, None], A.shape),
        rtol=1e-6)
    assert np.all(np.asarray(p["m_D"]) == 1)
    step = np.asarray(jax.nn.softplus(p["m_dt_b"]))
    assert 1e-3 * 0.99 < step.min() and step.max() < 1e-1 * 1.01


def test_the_wide_decay_spreads_the_time_constants(params):
    """What the seeded `m_dt_b` and `m_A_log` are for: over a prompt,
    some (channel, state) pairs forget within a token (dt A < -1) and
    some remember for tens (dt A > -0.05), so a fault in carrying the
    state shows."""
    seen = []
    true = jm._ssm_inputs

    def spy(cfg, params, l, i, *a, **kw):
        # (inside a run's loop: the values leave through a callback)
        out = true(cfg, params, l, i, *a, **kw)
        A, _D = jm._decay(params, i)
        jax.debug.callback(lambda g: seen.append(np.asarray(g)),
                           out[2][..., None, :] * A)
        return out

    jm._ssm_inputs, keep = spy, jm._ssm_inputs
    try:
        jm.forward(jm.JambaConfig.tiny(dtype=jnp.float32, max_seq=300),
                   params, jnp.asarray(_tokens(64, 3))[None])
    finally:
        jm._ssm_inputs = keep
    jax.effects_barrier()
    assert len(seen) == 6
    g = -np.concatenate([t.reshape(-1) for t in seen])
    assert (g > 1).mean() > 0.2 and (g < 0.05).mean() > 0.1


class Pager:
    """The engine's device side by hand: a pool of pages and of slot
    states, a page table a slot, and the two paged programs called as
    `LLMEngine` calls them."""

    def __init__(self, cfg, params, attn_impl="gather"):
        self.cfg, self.params, self.impl = cfg, params, attn_impl
        self.pool = jm.init_paged_kv(cfg, N_PAGES, PAGE, N_SLOTS)
        self.width = N_PAGES // N_SLOTS
        self.tables = np.zeros((N_SLOTS, self.width), np.int32)
        self.next_page = 1

    def grow(self, slot, n_tokens):
        for j in range(-(-n_tokens // PAGE)):
            if self.tables[slot, j] == 0:
                self.tables[slot, j] = self.next_page
                self.next_page += 1

    def chunks(self, rows, head=True, height=None, chunk=CHUNK):
        """rows: [(slot, tokens, offset)] -> last-valid logits, one
        dispatch of `height` rows (the rest inert)."""
        N = height or len(rows)
        toks = np.zeros((N, chunk), np.int32)
        offs, valid, slots = (np.zeros(N, np.int32) for _ in range(3))
        for i, (slot, t, off) in enumerate(rows):
            toks[i, :len(t)], offs[i], valid[i], slots[i] = t, off, len(t), slot
            self.grow(slot, off + len(t))
        out, self.pool = jm.prefill_chunk_paged(
            self.cfg, self.params, jnp.asarray(toks), self.pool,
            jnp.asarray(self.tables[slots]), jnp.asarray(offs),
            jnp.asarray(valid), slots=jnp.asarray(slots),
            return_logits=head, attn_impl=self.impl)
        return None if out is None else np.asarray(out)

    def prefill(self, slot, prompt, rows=ROWS, between=None, chunk=CHUNK):
        """A whole prompt, `rows` chunk rows a dispatch (`between()`
        runs between dispatches) -> its last token's logits."""
        cuts = [(slot, prompt[i:i + chunk], i)
                for i in range(0, len(prompt), chunk)]
        for i in range(0, len(cuts), rows):
            if i and between is not None:
                between()
            out = self.chunks(cuts[i:i + rows], height=rows, chunk=chunk)
        return out[len(cuts[i:i + rows]) - 1]

    def decode(self, tokens, positions, active):
        """One step for every slot (row b IS slot b) -> logits [B, V]."""
        for slot in active:
            self.grow(slot, int(positions[slot]) + 1)
        tables = np.where(np.isin(np.arange(N_SLOTS), active)[:, None],
                          self.tables, 0)
        out, self.pool = jm.decode_step_paged(
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


# Three chunk rows (two dispatches: the first two chained in one), the
# last of 11 tokens: not a multiple of the page or the chunk.
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


def test_a_chunk_boundary_off_a_page_boundary(params):
    """Chunk rows of 24 tokens over pages of 16: every second boundary
    between rows falls mid-page, and the rows chain in one dispatch."""
    prompt = _tokens(67, 6)
    with jax.default_matmul_precision("highest"):
        got = Pager(CFG, params, "kernel").prefill(1, prompt, rows=3,
                                                   chunk=24)
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
        assert float(jnp.abs(used.pool["ssm_state"][:, 1]).max()) > 0.01
        assert float(jnp.abs(used.pool["ssm_conv"][:, :, 1]).max()) > 0.01
        used.tables[1] = 0                          # released: new pages
        again = used.prefill(1, second)
    np.testing.assert_allclose(again, _ref_logits(params, second)[-1],
                               atol=ATOL_F32, rtol=0)


def _ssm_inputs_with(true, change):
    def inputs(cfg, params, l, i, x, valid, conv):
        return change(*true(cfg, params, l, i, x, valid, conv), x=x,
                      rerun=lambda boundary: true(
                          cfg, params, l, i, x, valid,
                          jm._causal_conv(boundary, cfg.d_conv)))
    return inputs


def _without(params, name, value):
    """The tree with one stack of vectors replaced by a constant."""
    return {**params, name: jnp.full_like(params[name], value)}


def _rope(t):
    """Rotate-half rope at theta 10,000 by the position in the row."""
    half = t.shape[-1] // 2
    inv = 10_000.0 ** (-np.arange(half) / half)
    ang = jnp.arange(t.shape[1])[None, :, None, None] * jnp.asarray(
        inv, jnp.float32)
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * jnp.cos(ang) - t2 * jnp.sin(ang),
                            t2 * jnp.cos(ang) + t1 * jnp.sin(ang)], axis=-1)


FAULTS = ["state_in_bf16", "state_zeroed_at_a_chunk", "tail_not_carried",
          "dt_norm_dropped", "b_norm_dropped", "c_norm_dropped",
          "conv_bias_dropped", "dt_bias_dropped", "skip_dropped",
          "gate_dropped", "rope_added", "kv_head_misassigned"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails_the_tolerance(params, fault, monkeypatch):
    """What the tolerance is for: each of these serves logits that the
    comparison above would refuse."""
    # A configuration of its own, so that no trace of the true block is
    # found in the jit cache.
    cfg = jm.JambaConfig.tiny(dtype=jnp.float32,
                              max_seq=257 + FAULTS.index(fault))
    served = params
    true = jm._ssm_inputs
    if fault == "state_in_bf16":
        step = jm.reference_ssm_decode_step

        def rounded(*a, **k):
            y, state = step(*a, **k)
            return y, state.astype(jnp.bfloat16).astype(jnp.float32)

        monkeypatch.setattr(jm, "reference_ssm_decode_step", rounded)
    elif fault == "state_zeroed_at_a_chunk":
        scan = jm.reference_ssm_chunk_scan
        monkeypatch.setattr(
            jm, "reference_ssm_chunk_scan",
            lambda xs, dt, B, C, A, D, state, chain, fresh: scan(
                xs, dt, B, C, A, D, state, chain, jnp.ones_like(fresh)))
    elif fault == "tail_not_carried":
        # A chunk row (not a decode step, whose planes have no token
        # axis) starts its convolution from zeros whatever came before it.
        monkeypatch.setattr(jm, "_ssm_inputs", _ssm_inputs_with(
            true, lambda *out, x, rerun: out if x.ndim == 2 else rerun(
                lambda xs: (jnp.zeros((xs.shape[0], xs.shape[2]), xs.dtype),)
                * (CFG.d_conv - 1))))
    elif fault.endswith("_norm_dropped"):
        # The inner norm of the step's, B's or C's projection: left out,
        # the raw projection goes on (times the norm's weight).
        which = {"dt": 0, "b": 1, "c": 2}[fault.split("_")[0]]
        true_rms, calls = jm._unit_rms, itertools.count()

        def unit_rms(x, w, eps):         # called for r, B, C in turn
            if next(calls) % 3 == which:
                return x * w.astype(jnp.float32)
            return true_rms(x, w, eps)

        monkeypatch.setattr(jm, "_unit_rms", unit_rms)
    elif fault == "conv_bias_dropped":
        served = _without(params, "m_conv_b", 0.0)
    elif fault == "dt_bias_dropped":
        served = _without(params, "m_dt_b", 0.0)
    elif fault == "skip_dropped":
        served = _without(params, "m_D", 0.0)
    elif fault == "gate_dropped":
        out = jm._ssm_output
        monkeypatch.setattr(
            jm, "_ssm_output", lambda cfg, params, l, x, y, z: out(
                cfg, params, l, x, y / jax.nn.silu(z.astype(jnp.float32)), z))
    elif fault == "rope_added":
        attn = jm._attn_inputs

        def roped(*a):
            q, k, v = attn(*a)
            return _rope(q), _rope(k), v

        monkeypatch.setattr(jm, "_attn_inputs", roped)
    elif fault == "kv_head_misassigned":
        # Query head h reads the one KV head's lanes h places on, as if
        # the head's 16 dims were dealt out among the query heads.
        attn = jm._attn_inputs

        def dealt(*a):
            q, k, v = attn(*a)
            q = jnp.stack([jnp.roll(q[:, :, h], h, axis=-1)
                           for h in range(q.shape[2])], axis=2)
            return q, k, v

        monkeypatch.setattr(jm, "_attn_inputs", dealt)
    with jax.default_matmul_precision("highest"):
        if fault == "rope_added":
            # The full-sequence forward, where a row's position is its
            # index; the paged programs hand the block no position.
            seq = np.concatenate([PROMPT, FOLLOW])
            got = np.asarray(jm.forward(cfg, served, jnp.asarray(seq)[None]))[
                0, len(PROMPT) - 1:]
        else:
            got, _other, _b = _serve_logits(Pager(cfg, served), PROMPT,
                                            FOLLOW)
    want = _ref_logits(params, np.concatenate([PROMPT, FOLLOW]))
    # A faulty block's programs are nobody else's: dropped here
    # (tests/conftest.py clears at a module's end only).
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


def test_engine_serves_the_references_tokens(params):
    """Normal entry points, scheduler, PagePool, tick: four requests over
    three slots (so one slot is reused by a shorter request), prompts of
    one to three chunk rows, every emitted token the float32 reference's
    best at its position (deficit under ATOL_F32)."""
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
    nm = CFG.count("mamba")
    assert m["preemptions"] == 0 and m["window_kv_bytes"] == 0
    # The operator's third memory account: both leaves, null slot and all.
    assert m["slot_state_bytes"] == nm * (N_SLOTS + 1) * 4 * CFG.d_inner * (
        CFG.d_state + CFG.d_conv - 1)
    assert m["slot_state_bytes"] == sum(
        int(eng.cache[n].nbytes) for n in ("ssm_state", "ssm_conv"))
    assert m["kv_pool_bytes"] == (
        2 * CFG.count("attn") * (N_PAGES + 1) * PAGE
        * CFG.n_kv_heads * CFG.head_dim * 4)
    # A dense family: nothing routes, nothing is counted.
    assert m["moe_layer_steps"] == m["moe_rows_routed"] == 0
    assert "moe_counters" not in eng.cache
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
    ("prefix_cache", True, "snapshot of the mamba layers' state-space state"),
    ("spec_draft", "tiny", "cannot be run backwards"),
    ("kv_transfer", True, "page set would have to carry"),
    ("tp", 2, "one KV head cannot shard"),
    ("weight_dtype", "int8", "an int8 form of this family's tree"),
    ("kv_dtype", "int8", "accumulates thousands of steps"),
    ("prefill_width_bucketing", True, "every layer is dense"),
    ("pool_role", "prefill", "page set would have to carry"),
]


@pytest.mark.parametrize("option,value,names", REFUSED)
def test_options_the_family_cannot_carry_are_refused(params, option, value,
                                                     names):
    """At construction, each with what would have to be built, and not a
    word of experts: the family has none."""
    with pytest.raises(ValueError, match=names) as refusal:
        _engine(params, **{option: value})
    assert "expert" not in str(refusal.value)


def test_the_refusal_table_says_nothing_of_experts_for_a_dense_family():
    from ray_tpu.models import serving

    dense = serving._family("jamba").unsupported
    assert len(dense) == len(serving._REFUSALS) == 7
    assert not [u.option for u in dense if "expert" in u.why]
    # The families that have experts say so where they did.
    for name in ("zaya", "laguna", "qwen3_next", "mimo_v2"):
        why = {u.option: u.why for u in serving._family(name).unsupported}
        assert "every held expert's weights" in why["prefill_width_bucketing"]
        assert "grouped matmul (ops/moe.py) has no int8 form" in why[
            "weight_dtype"]
        assert "expert" in why["tp"]


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
    assert fam.name == "jamba" and fam.init_pool is jm.init_paged_kv
    assert fam.slot_state == ("ssm_state", "ssm_conv")
    assert not fam.expert_counters and not fam.slot_ring
    # The loops read each layer's planes out of the stacks where they
    # lie: nothing to cut at load.
    assert fam.lay_out is None


def test_the_engine_does_not_name_the_family():
    with open(os.path.join(REPO, "ray_tpu", "serve", "llm.py")) as f:
        text = f.read().lower()
    assert "jamba" not in text and "mamba" not in text
