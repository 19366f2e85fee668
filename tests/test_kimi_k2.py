"""The `kimi_k2` family on the CPU at `KimiK2Config.tiny` (a dense layer
and two expert layers; 4 heads of 32 + 16 over a latent row of 128 + 16
values in 256 lanes; 8 experts top-3 with 4 held, chosen by a biased
score, gated 2.827 x, beside a shared expert), seeded random weights
with every leaf moved off its initial value: `forward` (the PLAIN form),
the paged programs (the ABSORBED form over the latent pool, both
`attn_impl`s) and the engine against the plain reference
benchmarks/harness/reference/kimi_k2_ref.py, in LOGITS.

Tolerances, each with its reason:
  ATOL_F32 = 3e-5   program and reference both compute in float32 at
      "highest" matmul precision; they differ by reassociation only
      (q_nope W_UK . c for q_nope . (W_UK c), blockwise softmax, rsqrt
      for 1/sqrt, the grouped matmul's sums). Logits here are O(1).
  FAULT_MIN = 1e-3  each fault below must move some logit by more.
"""

import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import kimi_k2, serving
from ray_tpu.ops.moe import token_choice_experts
from ray_tpu.serve.llm import LLMEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:          # the harness imports itself as `harness`
    sys.path.insert(0, BENCH)

from harness import configs  # noqa: E402
from harness.reference import kimi_k2_ref  # noqa: E402

ATOL_F32 = 3e-5
FAULT_MIN = 1e-3

CFG = kimi_k2.KimiK2Config.tiny(dtype=jnp.float32)
FAMILY = configs.load_module(
    os.path.join(BENCH, "families", "kimi_k2.py"), "test_family_")
PROBE = configs.load_module(
    os.path.join(BENCH, "tools", "probe_kimi_k2.py"), "test_probe_")


def _rc(cfg):
    return FAMILY.RefConfig(
        cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
        cfg.v_head_dim, cfg.first_k_dense, cfg.top_k, cfg.routed_scale,
        cfg.first_expert, cfg.norm_eps, cfg.rope_theta, cfg.yarn_factor,
        cfg.yarn_orig, cfg.beta_fast, cfg.beta_slow, cfg.mscale,
        cfg.mscale_all_dim)


RC = _rc(CFG)
PAGE, N_PAGES, N_SLOTS, CHUNK, ROWS = 16, 24, 3, 16, 2


def _params(cfg=CFG, seed=0):
    """Seeded weights with EVERY leaf moved off its initial value: norm
    scales by a tenth, matmul planes and the router's bias by 0.02; the
    output projections are 8x their initial size so that attention, the
    dense MLP, the shared expert and the routed experts all move the
    logits."""
    p = kimi_k2.init_params(cfg, jax.random.key(seed))
    keys = jax.random.split(jax.random.key(seed + 1), len(p))
    out = {}
    for key, (name, v) in zip(keys, sorted(p.items())):
        size = 0.1 if name.endswith(("_scale", "_norm")) else 0.02
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
    return np.asarray(kimi_k2_ref.logits(params, jnp.asarray(seq), rc))


def test_forward_matches_the_reference_in_logits(params):
    seqs = np.stack([_tokens(96, s) for s in (1, 2)])
    with jax.default_matmul_precision("highest"):
        got = np.asarray(kimi_k2.forward(CFG, params, jnp.asarray(seqs)))
    want = np.stack([_ref_logits(params, s) for s in seqs])
    assert np.abs(want).max() > 0.5                 # not a flat model
    np.testing.assert_allclose(got, want, atol=ATOL_F32, rtol=0)


def test_the_published_widths_scale_and_frequencies():
    """The defaults are the public config.json's; the scale is 192^-1/2
    x 1.4159^2; YaRN leaves the fast dims alone and divides the slow
    ones by 64; a row of 576 values lies in 640 lanes."""
    cfg = kimi_k2.KimiK2Config()
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.n_experts_routed, cfg.top_k, cfg.d_ff,
            cfg.routed_scale) == (7168, 61, 64, 1536, 512, 128, 64, 128,
                                  384, 8, 2048, 2.827)
    assert cfg.head_dim == 640 and cfg.count("dense") == 1
    assert cfg.index(0) == ("dense", 0) and cfg.index(4) == ("sparse", 3)
    assert abs(kimi_k2.softmax_scale(cfg) - 0.14468) < 1e-5
    assert kimi_k2._rope_factor(cfg) == 1.0
    from ray_tpu.models.blocks import yarn_inv_freq
    f = yarn_inv_freq(cfg)
    plain = 50000.0 ** (-np.arange(0, 64, 2) / 64)
    assert f.shape == (32,) and f[0] == plain[0] == 1.0
    np.testing.assert_allclose(f[-1], plain[-1] / 64)
    np.testing.assert_allclose(f, kimi_k2_ref._inv_freq(_rc(cfg)))
    specs = kimi_k2.param_specs(cfg)
    assert specs["wkv_b"]["shape"] == (61, 512, 64 * 256)
    assert specs["wkv_a"]["shape"] == (61, 7168, 576)


def test_the_absorbed_weights_are_kv_b_proj_cut_once(params):
    """`lay_out`: W_UK [H, Kn, R] and W_UV [H, R, Kv] a layer are the
    published `kv_b_proj`'s columns, `wkv_b` leaves the tree, planes are
    one array a layer, and a second call changes nothing."""
    laid = kimi_k2.lay_out(CFG, params)
    assert "wkv_b" not in laid and kimi_k2.lay_out(CFG, laid) is laid
    H, R, Kn, Kv = (CFG.n_heads, CFG.kv_lora_rank, CFG.qk_nope_head_dim,
                    CFG.v_head_dim)
    assert len(laid["w_uk"]) == len(laid["wq_a"]) == CFG.n_layers
    assert laid["w_uk"][0].shape == (H, Kn, R)
    assert laid["w_uv"][0].shape == (H, R, Kv)
    kvb = np.asarray(params["wkv_b"][1]).reshape(R, H, Kn + Kv)
    np.testing.assert_array_equal(laid["w_uk"][1][2], kvb[:, 2, :Kn].T)
    np.testing.assert_array_equal(laid["w_uv"][1][3], kvb[:, 3, Kn:])
    assert laid["w_gate"] is params["w_gate"]       # the experts stay stacks
    fam = serving.family_of(CFG)
    assert fam.lay_out is kimi_k2.lay_out


def test_the_shares_add_up(params):
    """Four chips' routed parts (experts 0-3, .. 12-15 of 16) plus the
    shared expert counted ONCE are the uncut reference's layer: every
    choice lands on exactly one share, chosen by s + b and gated by
    2.827 s over all the choices."""
    whole = kimi_k2.KimiK2Config.tiny(dtype=jnp.float32, n_experts=16,
                                      n_experts_routed=16)
    full = _params(whole, seed=3)
    u = jax.random.normal(jax.random.key(7), (40, CFG.d_model), jnp.float32)
    j = 1                                           # a sparse layer's stack
    w = {n: full[n][j] for n in ("router", "router_bias", "s_gate", "s_up",
                                 "s_down", "w_gate", "w_up", "w_down")}
    with jax.default_matmul_precision("highest"):
        want = kimi_k2_ref._sparse_mlp(
            u, w, _rc(whole), lambda e: (
                w["w_gate"][e], w["w_up"][e], w["w_down"][e]), 16)
        chosen, gates, moved = kimi_k2._route(whole, w["router"],
                                              w["router_bias"], u)
        parts, held = [], 0
        for first in (0, 4, 8, 12):
            share = slice(first, first + 4)
            y, counts = token_choice_experts(
                u, chosen, gates, w["w_gate"][share], w["w_up"][share],
                w["w_down"][share], first_expert=first)
            parts.append(y)
            held += int(counts.sum())
        shared = kimi_k2.gated_mlp(u, w["s_gate"], w["s_up"], w["s_down"])
    assert held == u.shape[0] * whole.top_k         # every choice, once
    assert all(float(jnp.abs(p).max()) > 1e-3 for p in parts)
    assert float(jnp.abs(shared).max()) > 1e-3
    assert 0 < int(moved.sum()) < held              # the bias moves some
    np.testing.assert_allclose(float(gates.sum(-1)[0]), whole.routed_scale,
                               rtol=1e-6)
    np.testing.assert_allclose(sum(parts) + shared, want, atol=2e-5, rtol=0)


class Pager:
    """The engine's device side by hand: the latent pool, a page table a
    slot, and the two paged programs called as `LLMEngine` calls them,
    with the tree its `lay_out` makes."""

    def __init__(self, cfg, params, attn_impl="gather"):
        self.cfg, self.impl = cfg, attn_impl
        self.params = kimi_k2.lay_out(cfg, params)
        self.pool = kimi_k2.init_paged_kv(cfg, N_PAGES, PAGE, N_SLOTS)
        self.width = N_PAGES // N_SLOTS
        self.tables = np.zeros((N_SLOTS, self.width), np.int32)
        self.next_page = 1

    def grow(self, slot, n_tokens):
        for j in range(-(-n_tokens // PAGE)):
            if self.tables[slot, j] == 0:
                self.tables[slot, j] = self.next_page
                self.next_page += 1

    def chunks(self, rows, head=True, height=None):
        N = height or len(rows)
        toks = np.zeros((N, CHUNK), np.int32)
        offs, valid, slots = (np.zeros(N, np.int32) for _ in range(3))
        for i, (slot, t, off) in enumerate(rows):
            toks[i, :len(t)], offs[i], valid[i], slots[i] = t, off, len(t), slot
            self.grow(slot, off + len(t))
        out, self.pool = kimi_k2.prefill_chunk_paged(
            self.cfg, self.params, jnp.asarray(toks), self.pool,
            jnp.asarray(self.tables[slots]), jnp.asarray(offs),
            jnp.asarray(valid), return_logits=head, attn_impl=self.impl)
        return None if out is None else np.asarray(out)

    def prefill(self, slot, prompt, rows=ROWS):
        cuts = [(slot, prompt[i:i + CHUNK], i)
                for i in range(0, len(prompt), CHUNK)]
        for i in range(0, len(cuts), rows):
            out = self.chunks(cuts[i:i + rows], height=rows)
        return out[len(cuts[i:i + rows]) - 1]

    def decode(self, tokens, positions, active):
        for slot in active:
            self.grow(slot, int(positions[slot]) + 1)
        tables = np.where(np.isin(np.arange(N_SLOTS), active)[:, None],
                          self.tables, 0)
        out, self.pool = kimi_k2.decode_step_paged(
            self.cfg, self.params, jnp.asarray(tokens, jnp.int32), self.pool,
            jnp.asarray(positions, jnp.int32), jnp.asarray(tables),
            attn_impl=self.impl)
        return np.asarray(out)


def _serve_logits(pager, prompt, follow, slot=1):
    """Chunked prefill of `prompt` in `slot`, then teacher-forced decode
    of `follow` through the cache (another slot idle, a third
    mid-prefill) -> logits at positions len(prompt)-1 .. end-1."""
    rows = [pager.prefill(slot, prompt)]
    other = _tokens(2 * CHUNK, seed=9)
    pager.chunks([(0, other[:CHUNK], 0)], head=False, height=ROWS)
    tokens = np.zeros(N_SLOTS, np.int32)
    positions = np.zeros(N_SLOTS, np.int32)
    for i, tok in enumerate(follow):
        tokens[slot], positions[slot] = tok, len(prompt) + i
        rows.append(pager.decode(tokens, positions, [slot])[slot])
    bystander = pager.chunks([(0, other[CHUNK:], CHUNK)], height=ROWS)[0]
    return np.stack(rows), other, bystander


# A prompt whose last chunk and last page are partly full, then decode
# across a page boundary.
PROMPT, FOLLOW = _tokens(75, 1), _tokens(13, 2)


@pytest.mark.parametrize("attn_impl", ["gather", "kernel"])
def test_paged_programs_match_the_reference_in_logits(params, attn_impl):
    """Absorbed = plain: the programs read one latent row a token where
    the reference expands a key and a value a head."""
    with jax.default_matmul_precision("highest"):
        pager = Pager(CFG, params, attn_impl)
        got, other, bystander = _serve_logits(pager, PROMPT, FOLLOW)
    seq = np.concatenate([PROMPT, FOLLOW])
    want = _ref_logits(params, seq)[len(PROMPT) - 1:]
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=ATOL_F32, rtol=0)
    np.testing.assert_allclose(bystander, _ref_logits(params, other)[-1],
                               atol=ATOL_F32, rtol=0)
    # ONE plane, a row a token and layer; the lanes past the row's 144
    # values stay zero.
    assert set(pager.pool) == {"kv", "moe_counters"}
    assert pager.pool["kv"].shape == (CFG.n_layers, N_PAGES + 1, PAGE, 256)
    written = np.asarray(pager.pool["kv"][:, 1:])
    assert np.abs(written[..., :144]).max() > 0
    assert not written[..., 144:].any()


FAULTS = [f for f in PROBE.FAULTS if f != "none"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails_the_tolerance(params, fault):
    """What the tolerance is for: each control of
    benchmarks/tools/probe_kimi_k2.py (the chip's list) serves logits
    that the comparison above would refuse."""
    saved = dict(vars(kimi_k2))
    # A configuration of its own, so that no trace of the true block is
    # found in the jit cache.
    cfg = kimi_k2.KimiK2Config.tiny(dtype=jnp.float32,
                                    max_seq=257 + FAULTS.index(fault))
    try:
        served = PROBE.FAULTS[fault](dict(params))
        with jax.default_matmul_precision("highest"):
            got, _other, _b = _serve_logits(Pager(cfg, served), PROMPT, FOLLOW)
    finally:
        for name, value in saved.items():
            setattr(kimi_k2, name, value)
        jax.clear_caches()
    want = _ref_logits(params, np.concatenate([PROMPT, FOLLOW]))
    assert np.abs(got - want[len(PROMPT) - 1:]).max() > FAULT_MIN


@pytest.mark.parametrize("cut", [1, 15, 17, 33, 63])
def test_a_prompt_split_anywhere_gives_the_unsplit_logits(params, cut):
    """Prefill in chunks of any alignment, then decoding through the
    cache: a second dispatch reads the first one's rows."""
    prompt = _tokens(64, 5)
    with jax.default_matmul_precision("highest"):
        pager = Pager(CFG, params, "kernel")
        for i in range(0, cut, CHUNK):
            pager.chunks([(1, prompt[i:min(i + CHUNK, cut)], i)], height=ROWS)
        rows = [(1, prompt[i:i + CHUNK], i)
                for i in range(cut, len(prompt), CHUNK)]
        for i in range(0, len(rows), ROWS):
            out = pager.chunks(rows[i:i + ROWS], height=ROWS)
        got = out[len(rows[i:i + ROWS]) - 1]
    np.testing.assert_allclose(got, _ref_logits(params, prompt)[-1],
                               atol=ATOL_F32, rtol=0)


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


def _deficits(params, r, rc=RC):
    seq = np.asarray(r.prompt_ids[:r.n_prompt] + r.out_ids, np.int32)
    rows = _ref_logits(params, seq, rc)[r.n_prompt - 1:len(seq) - 1]
    return rows.max(axis=1) - rows[np.arange(len(r.out_ids)), r.out_ids]


@pytest.mark.parametrize("attn_impl", ["gather", "kernel"])
def test_engine_serves_the_references_tokens_and_counts(params, attn_impl):
    """Normal entry points, scheduler, PagePool, tick, a step in flight:
    four requests over three slots (so one slot is reused), prefill in
    chunks then decoding through the latent cache, every emitted token
    the float32 reference's best at its position (deficit under
    ATOL_F32); the pool's bytes and the counters, the bias's among
    them."""
    fam = serving.family_of(CFG)
    assert fam.name == "kimi_k2" and fam.model is kimi_k2
    assert fam.slot_state == () and not fam.slot_ring
    assert fam.expert_counters == kimi_k2.COUNTERS
    eng = _engine(params, attn_impl=attn_impl)
    assert "w_uk" in eng.params and "wkv_b" not in eng.params
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
    assert m["window_kv_bytes"] == 0
    # ONE plane: tokens x layers x a row (256 lanes of float32 here).
    assert m["kv_pool_bytes"] == m["kv_bytes_full"] == (
        CFG.n_layers * (N_PAGES + 1) * PAGE * CFG.head_dim * 4)
    assert m["moe_layer_steps"] % CFG.count("sparse") == 0
    assert m["moe_rows_routed"] % CFG.top_k == 0
    assert 0.3 < m["moe_rows_held"] / m["moe_rows_routed"] < 0.7
    assert 0 < m["moe_rows_bias_moved"] < 0.5 * m["moe_rows_routed"]
    assert 1.0 <= m["moe_experts_touched"] <= CFG.n_experts
    assert 0.0 < m["decode_block_fill"] <= 1.0
    eng.reset_stats()
    after = eng.metrics()
    assert after["moe_rows_routed"] == after["moe_rows_bias_moved"] == 0


@pytest.mark.parametrize("router", ["even", "onto_held"])
def test_rows_over_counts_what_a_first_block_did_not_take(router):
    """40 slots x top-4 over 64 experts of which 4 are held: the expert
    layer's block is 128 rows (room for 124 held choices) where every
    choice a row would be 384. An even router sends a step ~10 held
    choices and `moe_rows_over` stays 0; a router whose bias puts every
    choice on the held experts sends 160, the layer takes a second turn
    and counts the 36 a step, and every emitted token is still the
    float32 reference's best."""
    from ray_tpu.ops import moe

    cfg = kimi_k2.KimiK2Config.tiny(dtype=jnp.float32, n_experts_routed=64,
                                    top_k=4)
    slots = 40
    assert moe.block_rows(slots * cfg.top_k, cfg.n_experts, 64) == 128
    assert moe._pad_rows(slots * cfg.top_k + cfg.n_experts) == 384
    params = _params(cfg)
    if router == "onto_held":       # s is a sigmoid: 2 outbids any score
        params["router_bias"] = params["router_bias"].at[
            :, :cfg.n_experts].add(2.0)
    eng = LLMEngine(cfg, params, n_slots=slots, max_len=64, page_size=PAGE,
                    n_pages=4 * slots, prefill_chunk=CHUNK,
                    attn_impl="gather", prefill_token_budget=4 * CHUNK)
    rng = np.random.default_rng(3)
    reqs = [eng.submit(rng.integers(1, cfg.vocab_size, 5).tolist(),
                       max_tokens=40) for _ in range(slots)]
    with jax.default_matmul_precision("highest"):
        _run(eng, reqs)
    m = eng.metrics()
    assert m["preemptions"] == 0 and m["moe_rows_routed"] > 0
    for r in reqs[::8]:
        assert _deficits(params, r, _rc(cfg)).max() <= ATOL_F32
    if router == "even":
        assert m["moe_rows_over"] == 0
        assert m["moe_rows_held"] < 0.2 * m["moe_rows_routed"]
    else:
        assert m["moe_rows_held"] == m["moe_rows_routed"]
        # every step of 32 or more live slots passed the block's room
        assert 0 < m["moe_rows_over"] < m["moe_rows_held"]
    eng.reset_stats()
    assert eng.metrics()["moe_rows_over"] == 0


def test_engine_recomputes_a_preempted_request_to_the_same_tokens(params):
    """A pool too small for both requests: one is evicted by recompute
    and re-prefilled from offset 0; both streams stay the reference's."""
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
    ("prefix_cache", True, "learn the latent plane"),
    ("spec_draft", "tiny", "verify program over the latent plane"),
    ("kv_transfer", True, "splits them by a head axis"),
    ("tp", 2, "no rule for a row without heads"),
    ("weight_dtype", "int8", "no int8 form"),
    ("kv_dtype", "int8", "one-plane writer"),
    ("prefill_width_bucketing", True, "packs rows of several widths"),
]


@pytest.mark.parametrize("option,value,names", REFUSED)
def test_options_the_family_cannot_carry_are_refused(params, option, value,
                                                     names):
    """At construction, each with what would have to be built."""
    with pytest.raises(ValueError, match=names):
        _engine(params, **{option: value})
