"""The `zaya` family on the CPU at `ZayaConfig.tiny`, seeded random
weights (every leaf perturbed, so that no bias, scale or temperature
sits at its neutral value): the paged programs and the engine against the
plain reference benchmarks/harness/reference/zaya_ref.py, in LOGITS.

Tolerances, each with its reason:
  ATOL_F32 = 3e-5   program and reference both compute in float32 at
      "highest" matmul precision; they differ by reassociation only
      (blockwise softmax, rsqrt for 1/sqrt, sums in another order). Logits
      here are O(1); 3e-5 is ~30x the largest difference seen and ~1/100
      of what any fault below moves.
  FAULT_MIN = 1e-3  a dropped conv tap, a dropped value shift or a router
      computed in bfloat16 must move some logit by more than this.
"""

import collections
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import zaya
from ray_tpu.serve.llm import LLMEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:          # the harness imports itself as `harness`
    sys.path.insert(0, BENCH)

from harness.reference import zaya_ref  # noqa: E402

ATOL_F32 = 3e-5
FAULT_MIN = 1e-3

CFG = zaya.ZayaConfig.tiny(dtype=jnp.float32)
RefConfig = collections.namedtuple(
    "RefConfig", "n_heads n_kv_heads rotary_dim rope_theta norm_eps")
RC = RefConfig(CFG.n_heads, CFG.n_kv_heads, CFG.rotary_dim, CFG.rope_theta,
               CFG.norm_eps)
PAGE, N_PAGES, N_SLOTS, CHUNK = 8, 48, 3, 16     # table width 16


def _params(cfg=CFG, seed=0):
    """Seeded weights with EVERY leaf moved off its initial value, so that
    no bias, scale or temperature is neutral: scales by a tenth; biases by
    0.02 (the size of the embedding: a larger residual bias would drown
    the tokens); matmul planes by 0.02; the balancing bias beta by 0.001
    (softmax outputs of 4 experts differ by hundredths, and beta must not
    decide every choice). The two output projections are 8x their
    initial size, so that attention and experts both move the logits."""
    p = zaya.init_params(cfg, jax.random.key(seed))
    keys = jax.random.split(jax.random.key(seed + 1), len(p))
    out = {}
    for key, (name, v) in zip(keys, sorted(p.items())):
        size = (0.001 if name == "r_beta"
                else 0.1 if v.ndim == 2 and name.endswith(
                    ("_scale", "_a", "_c", "_gamma", "_norm", "_temp"))
                else 0.02)
        grow = 8.0 if name in ("wo", "w_down") else 1.0
        out[name] = grow * v + size * jax.random.normal(key, v.shape, v.dtype)
    return out


@pytest.fixture(scope="module")
def params():
    return _params()


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, CFG.vocab_size, n).astype(np.int32)


def _ref_logits(params, seq):
    return np.asarray(zaya_ref.logits(params, jnp.asarray(seq), RC))


class Pager:
    """The engine's device side by hand: a pool, a page table a slot, and
    the two paged programs called as `LLMEngine` calls them."""

    def __init__(self, cfg, params, attn_impl="gather"):
        self.cfg, self.params, self.impl = cfg, params, attn_impl
        self.pool = zaya.init_paged_kv(cfg, N_PAGES, PAGE, N_SLOTS)
        self.width = N_PAGES // N_SLOTS
        self.tables = np.zeros((N_SLOTS, self.width), np.int32)
        self.next_page = 1

    def grow(self, slot, n_tokens):
        for j in range(-(-n_tokens // PAGE)):
            if self.tables[slot, j] == 0:
                self.tables[slot, j] = self.next_page
                self.next_page += 1

    def chunks(self, rows, chunk=CHUNK, head=True):
        """rows: [(slot, tokens, offset)] -> last-valid logits [len(rows), V]
        (one dispatch; consecutive chunks of a prompt may share it)."""
        N = len(rows)
        toks = np.zeros((N, chunk), np.int32)
        offs, valid, slots = (np.zeros(N, np.int32) for _ in range(3))
        for i, (slot, t, off) in enumerate(rows):
            toks[i, :len(t)], offs[i], valid[i], slots[i] = t, off, len(t), slot
            self.grow(slot, off + len(t))
        out, self.pool = zaya.prefill_chunk_paged(
            self.cfg, self.params, jnp.asarray(toks), self.pool,
            jnp.asarray(self.tables[slots]), jnp.asarray(offs),
            jnp.asarray(valid), slots=jnp.asarray(slots),
            return_logits=head, attn_impl=self.impl)
        return None if out is None else np.asarray(out)

    def prefill(self, slot, prompt, chunk=CHUNK):
        """A whole prompt, two rows a dispatch -> its last token's logits."""
        rows = [(slot, prompt[i:i + chunk], i)
                for i in range(0, len(prompt), chunk)]
        for i in range(0, len(rows), 2):
            out = self.chunks(rows[i:i + 2], chunk)
        return out[len(rows[i:i + 2]) - 1]

    def decode(self, tokens, positions, active):
        """One step for every slot (row b IS slot b) -> logits [B, V]."""
        for slot in active:
            self.grow(slot, int(positions[slot]) + 1)
        tables = np.where(np.isin(np.arange(N_SLOTS), active)[:, None],
                          self.tables, 0)
        out, self.pool = zaya.decode_step_paged(
            self.cfg, self.params, jnp.asarray(tokens, jnp.int32), self.pool,
            jnp.asarray(positions, jnp.int32), jnp.asarray(tables),
            attn_impl=self.impl)
        return np.asarray(out)


def _serve_logits(pager, prompt, follow, slot=1, wipe_state=False):
    """Chunked prefill of `prompt` in `slot`, then teacher-forced decode
    of `follow` through pages and slot state (another slot mid-prefill
    beside it) -> logits at positions len(prompt)-1 .. end-1.
    `wipe_state`: the fault of a decode that starts from zeros."""
    rows = [pager.prefill(slot, prompt)]
    if wipe_state:
        pager.pool["slot_state"] = jnp.zeros_like(pager.pool["slot_state"])
    # A bystander: slot 0 holds HALF a prompt while slot 1 decodes; its
    # state must survive the decode steps it takes no part in.
    other = _tokens(2 * CHUNK, seed=9)
    pager.chunks([(0, other[:CHUNK], 0)], head=False)
    tokens = np.zeros(N_SLOTS, np.int32)
    positions = np.zeros(N_SLOTS, np.int32)
    for i, tok in enumerate(follow):
        tokens[slot], positions[slot] = tok, len(prompt) + i
        rows.append(pager.decode(tokens, positions, [slot])[slot])
    bystander = pager.chunks([(0, other[CHUNK:], CHUNK)])[0]
    return np.stack(rows), other, bystander


@pytest.mark.parametrize("attn_impl", ["gather", "kernel"])
def test_paged_programs_match_the_reference_in_logits(params, attn_impl):
    prompt, follow = _tokens(37, 1), _tokens(11, 2)
    with jax.default_matmul_precision("highest"):
        got, other, bystander = _serve_logits(
            Pager(CFG, params, attn_impl), prompt, follow)
    seq = np.concatenate([prompt, follow])
    want = _ref_logits(params, seq)[len(prompt) - 1:]
    assert np.abs(want).max() > 0.5                 # not a flat model
    np.testing.assert_allclose(got, want, atol=ATOL_F32, rtol=0)
    # The bystander's second chunk read the state its first chunk left,
    # across eleven decode steps of another slot.
    np.testing.assert_allclose(bystander, _ref_logits(params, other)[-1],
                               atol=ATOL_F32, rtol=0)


def _bf16_route(cfg, layer, u, r):
    """`zaya._route` with its arithmetic in bfloat16: the fault the
    float32 router exists to prevent (near-ties of p flip the top-1)."""
    bf = jnp.bfloat16
    w = lambda name: layer[name].astype(bf)
    r = u.astype(bf) @ w("r_down") + w("r_down_b") + w("r_gamma") * r.astype(bf)
    h = zaya._rms_norm(r, w("r_norm"), cfg.norm_eps)
    h = jax.nn.gelu(h @ w("r_w1") + w("r_b1"), approximate=False)
    h = jax.nn.gelu(h @ w("r_w2") + w("r_b2"), approximate=False)
    p = jax.nn.softmax(h @ w("r_w3"), axis=-1)
    expert = jnp.argmax(p + w("r_beta"), axis=-1).astype(jnp.int32)
    gate = jnp.take_along_axis(p, expert[:, None], axis=-1)[:, 0]
    return expert, gate.astype(jnp.float32), r.astype(jnp.float32)


FAULTS = ["conv0_tap", "conv1_tap", "value_shift", "state_carry",
          "bf16_router"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails_the_tolerance(params, fault, monkeypatch):
    """What the tolerance is for: each of these serves logits that the
    comparison above would refuse."""
    # A configuration of its own, so that no trace of the true block is
    # found in the jit cache.
    cfg = zaya.ZayaConfig.tiny(dtype=jnp.float32,
                               max_seq=129 + FAULTS.index(fault))
    served = dict(params)
    if fault == "conv0_tap":
        served["conv0_w"] = params["conv0_w"].at[:, 1].set(0.0)
    elif fault == "conv1_tap":
        served["conv1_w"] = params["conv1_w"].at[:, 1].set(0.0)
    elif fault == "value_shift":
        shift, width = zaya._shift, cfg.v_shift_heads * cfg.head_dim
        monkeypatch.setattr(
            zaya, "_shift", lambda full, first:
            full if full.shape[-1] == width else shift(full, first))
    elif fault == "bf16_router":
        monkeypatch.setattr(zaya, "_route", _bf16_route)
    prompt, follow = _tokens(37, 1), _tokens(11, 2)
    with jax.default_matmul_precision("highest"):
        got, _other, _b = _serve_logits(Pager(cfg, served), prompt, follow,
                                        wipe_state=fault == "state_carry")
    want = _ref_logits(params, np.concatenate([prompt, follow]))
    assert np.abs(got - want[len(prompt) - 1:]).max() > FAULT_MIN


@pytest.mark.parametrize("same_dispatch", [True, False])
def test_a_prompt_split_anywhere_gives_the_unsplit_logits(params,
                                                          same_dispatch):
    """The state carry: a 24-token prompt cut at EVERY boundary 1..23 into
    two chunk rows — chained inside one dispatch, or in two dispatches
    through the slot state — ends in the logits of the prompt in one row."""
    prompt, C = _tokens(24, 3), 24
    with jax.default_matmul_precision("highest"):
        whole = Pager(CFG, params).chunks([(2, prompt, 0)], chunk=C)[0]
        np.testing.assert_allclose(whole, _ref_logits(params, prompt)[-1],
                                   atol=ATOL_F32, rtol=0)
        for cut in range(1, len(prompt)):
            pager = Pager(CFG, params)
            rows = [(2, prompt[:cut], 0), (2, prompt[cut:], cut)]
            if same_dispatch:
                out = pager.chunks(rows, chunk=C)[1]
            else:
                pager.chunks([rows[0], (0, prompt[:0], 0)], chunk=C,
                             head=False)
                out = pager.chunks([(0, prompt[:0], 0), rows[1]], chunk=C)[1]
            np.testing.assert_allclose(out, whole, atol=ATOL_F32, rtol=0,
                                       err_msg=f"cut at {cut}")


def test_a_reused_slot_starts_from_zero_state(params):
    """Slot 1 serves a prompt and decodes; the next prompt in the same
    slot (new pages, offset 0) reads zeros, not what was left there."""
    first, second = _tokens(21, 4), _tokens(19, 5)
    with jax.default_matmul_precision("highest"):
        used = Pager(CFG, params)
        _serve_logits(used, first, _tokens(5, 6))
        assert float(jnp.abs(used.pool["slot_state"][:, 1]).max()) > 0.1
        used.tables[1] = 0                          # released: new pages
        again = used.prefill(1, second)
        fresh = Pager(CFG, params).prefill(1, second)
    np.testing.assert_array_equal(again, fresh)
    np.testing.assert_allclose(fresh, _ref_logits(params, second)[-1],
                               atol=ATOL_F32, rtol=0)


# ------------------------------------------------------- through LLMEngine

def _engine(params, **kw):
    opts = dict(n_slots=N_SLOTS, max_len=96, page_size=PAGE,
                n_pages=N_PAGES, prefill_chunk=CHUNK, attn_impl="gather")
    return LLMEngine(CFG, params, **{**opts, **kw})


def _run(eng, reqs):
    for _ in range(600):
        if all(r.done.is_set() for r in reqs):
            break
        eng.step()
    assert all(r.done.is_set() and r.error is None for r in reqs)


def test_engine_serves_the_references_tokens_and_counts(params):
    """Normal entry points, scheduler, PagePool, tick: four requests over
    three slots (so one slot is reused), every emitted token the float32
    reference's best at its position (deficit under ATOL_F32)."""
    eng = _engine(params)
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(1, CFG.vocab_size, n).tolist(),
                       max_tokens=m)
            for n, m in ((37, 12), (16, 20), (5, 9), (50, 7))]
    with jax.default_matmul_precision("highest"):
        _run(eng, reqs)
    for r in reqs:
        seq = np.asarray(r.prompt_ids[:r.n_prompt] + r.out_ids, np.int32)
        rows = _ref_logits(params, seq)[r.n_prompt - 1:len(seq) - 1]
        deficit = rows.max(axis=1) - rows[np.arange(len(r.out_ids)),
                                          r.out_ids]
        assert deficit.max() <= ATOL_F32
    m = eng.metrics()
    assert m["preemptions"] == 0
    assert m["slot_state_bytes"] == (
        CFG.n_layers * (N_SLOTS + 1) * CFG.state_width * 4)
    assert m["kv_pool_bytes"] == (
        2 * CFG.n_layers * (N_PAGES + 1) * PAGE
        * CFG.n_kv_heads * CFG.head_dim * 4)
    # Every (layer, step) of a decode window routed its live rows, none
    # dropped: a window's steps for every slot it holds, at every layer
    # (a window of one step leaves its share for the next pull).
    assert m["moe_layer_steps"] % CFG.n_layers == 0
    assert 0 < m["moe_rows_routed"] <= CFG.n_layers * m["slot_cap_sum"]
    assert m["moe_rows_routed"] >= m["moe_rows_max_sum"]
    assert 1.0 <= m["moe_experts_touched"] <= CFG.n_experts
    assert m["moe_rows_max"] >= 1.0
    eng.reset_stats()
    after = eng.metrics()
    assert after["moe_rows_routed"] == 0


def test_engine_recomputes_the_state_of_a_preempted_request(params):
    """A pool too small for both requests: one is evicted by recompute
    and re-prefilled from offset 0, state and all; both streams stay the
    reference's."""
    eng = _engine(params, n_slots=2, n_pages=9, max_len=64)
    rng = np.random.default_rng(1)
    reqs = [eng.submit(rng.integers(1, CFG.vocab_size, 20).tolist(),
                       max_tokens=30) for _ in range(2)]
    with jax.default_matmul_precision("highest"):
        _run(eng, reqs)
    assert eng.metrics()["preemptions"] >= 1
    for r in reqs:
        assert len(r.out_ids) == 30
        seq = np.asarray(r.prompt_ids[:r.n_prompt] + r.out_ids, np.int32)
        rows = _ref_logits(params, seq)[r.n_prompt - 1:len(seq) - 1]
        deficit = rows.max(axis=1) - rows[np.arange(30), r.out_ids]
        assert deficit.max() <= ATOL_F32


REFUSED = [
    ("prefix_cache", True, "snapshot"),
    ("spec_draft", "tiny", "verify program"),
    ("kv_transfer", True, "page set would have to carry"),
    ("tp", 2, "expert-parallel dispatch"),
    ("weight_dtype", "int8", "no int8 form"),
    ("kv_dtype", "int8", "scale planes"),
    ("prefill_width_bucketing", True, "packs rows of several widths"),
    ("pool_role", "prefill", "page set would have to carry"),
]


@pytest.mark.parametrize("option,value,names", REFUSED)
def test_options_the_family_cannot_carry_are_refused(params, option, value,
                                                     names):
    """At construction, each with what would have to be built; the same
    value from the fleet-wide knob is turned off, not obeyed."""
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


def test_a_gpt_gets_exactly_todays_programs():
    from ray_tpu.models import gpt, paged_kv, serving

    fam = serving.family_of(gpt.GPTConfig.tiny())
    assert fam.name == "gpt" and not fam.unsupported
    assert not fam.slot_state and not fam.expert_counters
    programs = fam.programs(1, None)
    assert set(programs) == set(serving._PAGED)
    for name in serving._PAGED:
        assert programs[name] is getattr(paged_kv, name)
    assert serving.family_of(CFG).name == "zaya"
