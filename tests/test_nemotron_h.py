"""The `nemotron_h` family on the CPU at `NemotronHConfig.tiny` (the
pattern "MEMEM*E": three Mamba-2, three latent-expert layers and one
attention layer, ONE sublayer a layer, "ME" twice as one loop; 8 heads of 16 over 16 states in 2 groups; 4
query heads over 2 KV heads and no positions; 16 experts top-4 with 8
held, in a latent of 32 under a model of 64), seeded random weights with
every leaf moved off its initial value and a WIDE decay (`m_dt_b` ~
N(0, 4), `m_A_log` ~ N(0, 1): time constants from under a token to
hundreds): `forward`, the paged programs and the engine against the
plain reference benchmarks/harness/reference/nemotron_h_ref.py, in
LOGITS.

Tolerances, each with its reason:
  ATOL_F32 = 3e-5   program and reference both compute in float32 at
      "highest" matmul precision; they differ by reassociation only (the
      chunk scan's matmul form against the token-by-token recurrence,
      the state's sum in the kernel's order, blockwise softmax, rsqrt
      for 1/sqrt, the experts' rows sorted). Logits here are O(1).
  FAULT_MIN = 1e-4  each fault below must move some logit by more: over
      three times the tolerance. Most move one by 1e-2 or more; the
      smallest is the state kept in bfloat16 (1.2e-4 after a prompt of
      75 tokens and 13 steps: the group norm behind the scan divides a
      rounding of 2^-9 of the state by the row's own size).
"""

import collections
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import blocks, serving
from ray_tpu.models import nemotron_h as nh
from ray_tpu.ops.moe import token_choice_experts
from ray_tpu.serve.llm import LLMEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:          # the harness imports itself as `harness`
    sys.path.insert(0, BENCH)

from harness.reference import nemotron_h_ref  # noqa: E402

ATOL_F32 = 3e-5
FAULT_MIN = 1e-4

CFG = nh.NemotronHConfig.tiny(dtype=jnp.float32)
RefConfig = collections.namedtuple(
    "RefConfig", "pattern m_heads m_groups n_heads n_kv_heads top_k "
    "routed_scale first_expert norm_eps")


def _rc(cfg):
    return RefConfig(cfg.pattern, cfg.m_heads, cfg.m_groups, cfg.n_heads,
                     cfg.n_kv_heads, cfg.top_k, cfg.routed_scale,
                     cfg.first_expert, cfg.norm_eps)


RC = _rc(CFG)
# Chunk rows of 32 tokens (two blocks of the scan's 16) over pages of 16;
# two rows a dispatch.
PAGE, N_PAGES, N_SLOTS, CHUNK, ROWS = 16, 24, 3, 32, 2


def _params(cfg=CFG, seed=0, wide=True):
    """Seeded weights with EVERY leaf moved off its initial value: norm
    weights by a tenth, everything else by 0.02; the output projections
    are 8x their initial size so that every mixer moves the logits, the
    router's bias is wide enough to move choices. `wide`: a decay spread
    from under a token to hundreds; else the model's own start, moved a
    little."""
    p = nh.init_params(cfg, jax.random.key(seed))
    keys = jax.random.split(jax.random.key(seed + 1), len(p))
    out = {}
    for key, (name, v) in zip(keys, sorted(p.items())):
        size = 0.1 if name.endswith(("_scale", "_norm")) else 0.02
        grow = 8.0 if name in ("a_wo", "w_down", "m_out", "s_down",
                               "lat_out") else 1.0
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
    return np.asarray(nemotron_h_ref.logits(params, jnp.asarray(seq), rc))


def test_the_layer_order_and_the_tree():
    """The published period is 5 Mamba-2, 5 expert and 1 attention layer
    of one sublayer each; every leaf is a stack over the layers of its
    kind, and the published widths give the arithmetic of ISSUE 62:
    109.6 M a Mamba-2 layer, 35.7 M an attention layer, 5.505 M an
    expert, a state of 4.19 MB a layer and slot, two heads side by
    side."""
    cfg = nh.NemotronHConfig()
    assert cfg.pattern == "MEMEMEM*EME" and cfg.n_layers == 11
    assert [cfg.count(k) for k in "ME*"] == [5, 5, 1]
    assert [cfg.index(l) for l in range(11)] == [0, 0, 1, 1, 2, 2, 3, 0, 3,
                                                 4, 4]
    assert (cfg.d_inner, cfg.conv_channels) == (8192, 10240)
    assert cfg.runs == (("ME", 0, 3), ("M", 6, 1), ("*", 7, 1), ("E", 8, 1),
                        ("M", 9, 1), ("E", 10, 1))
    assert CFG.pattern == "MEMEM*E"
    assert CFG.runs == (("ME", 0, 2), ("M", 4, 1), ("*", 5, 1), ("E", 6, 1))
    assert nh.NemotronHConfig(pattern="MMM*EEMEEMEE").runs == (
        ("M", 0, 3), ("*", 3, 1), ("EEM", 4, 2), ("E", 10, 2))
    specs = nh.param_specs(cfg)
    size = lambda name: int(np.prod(specs[name]["shape"][1:]))
    assert specs["m_in"]["shape"] == (5, 4096, 18560)
    assert size("m_in") + size("m_out") == 109_576_192
    assert sum(size(n) for n in ("a_wq", "a_wk", "a_wv", "a_wo")) == 35_651_584
    assert specs["w_up"]["shape"] == (5, 512, 1024, 2688)
    assert specs["w_down"]["shape"] == (5, 512, 2688, 1024)
    stacked = {n: s["shape"][0] for n, s in specs.items()
               if n not in ("wte", "lm_head", "ln_f_scale")}
    kinds = {"m": 5, "a": 1, "l": None}
    assert all(n == (11 if name == "ln_scale" else
                     kinds.get(name[0]) or 5)
               for name, n in stacked.items()), stacked
    pool = jax.eval_shape(lambda: nh.init_paged_kv(cfg, 64, 64, 7))
    assert pool["ssm_state"].shape == (5, 8, 64, 128, 128)
    assert pool["ssm_state"].dtype == jnp.float32
    assert pool["ssm_conv"].shape == (5, 3, 8, 10240)
    assert pool["k"].shape == (1, 65, 64, 256)
    with pytest.raises(ValueError, match="not M, \\* or E"):
        nh.NemotronHConfig.tiny(pattern="M-")


@pytest.mark.parametrize("wide", [True, False])
def test_forward_matches_the_reference_in_logits(wide):
    params = _params(wide=wide)
    seqs = np.stack([_tokens(75, s) for s in (1, 2)])
    with jax.default_matmul_precision("highest"):
        got = np.asarray(nh.forward(CFG, params, jnp.asarray(seqs)))
    want = np.stack([_ref_logits(params, s) for s in seqs])
    assert np.abs(want).max() > 0.5                 # not a flat model
    np.testing.assert_allclose(got, want, atol=ATOL_F32, rtol=0)


def test_the_models_own_start_is_the_papers():
    """`init_params`: a rate A in (1, 16) a head, D ones, and a bias whose
    softplus is a step between 1e-3 and 1e-1."""
    p = nh.init_params(CFG, jax.random.key(0))
    A = np.exp(np.asarray(p["m_A_log"]))
    assert A.shape == (3, CFG.m_heads) and 1 <= A.min() and A.max() <= 16
    assert np.all(np.asarray(p["m_D"]) == 1)
    step = np.asarray(jax.nn.softplus(p["m_dt_b"]))
    assert 1e-3 * 0.99 < step.min() and step.max() < 1e-1 * 1.01


def test_the_shares_add_up(params):
    """Four chips' expert-layer results (each chip's r W_lat_out over its
    own experts 0-3, .. 12-15 of 16), with the shared expert counted
    ONCE, are the uncut reference's whole layer: every choice lands on
    exactly one share, chosen by s + b and gated by 5 s over all the
    choices; the latent's projections are every chip's alike."""
    whole = nh.NemotronHConfig.tiny(dtype=jnp.float32, n_experts=16)
    full = _params(whole, seed=3)
    u = jax.random.normal(jax.random.key(7), (40, CFG.d_model), jnp.float32)
    j = 1                                           # an expert layer's stack
    w = {n: full[n][j] for n in ("router", "router_bias", "lat_in", "lat_out",
                                 "s_up", "s_down", "w_up", "w_down")}
    with jax.default_matmul_precision("highest"):
        want = nemotron_h_ref._experts(
            u, w, _rc(whole), lambda e: (w["w_up"][e], w["w_down"][e]), 16)
        chosen, gates, moved = blocks.biased_route(
            whole, w["router"], w["router_bias"], u)
        latent = u @ w["lat_in"]
        parts, held = [], 0
        for first in (0, 4, 8, 12):
            share = slice(first, first + 4)
            r, counts = token_choice_experts(
                latent, chosen, gates, w["w_up"][share], w["w_down"][share],
                first_expert=first, n_routed=16)
            assert r.shape == (40, whole.d_latent)
            parts.append(r @ w["lat_out"])
            held += int(counts.sum())
        shared = nh._relu2_mlp(u, w["s_up"], w["s_down"])
    assert held == u.shape[0] * whole.top_k         # every choice, once
    assert all(float(jnp.abs(p).max()) > 1e-3 for p in parts)
    assert float(jnp.abs(shared).max()) > 1e-3
    assert 0 < int(moved.sum()) < held              # the bias moves some
    np.testing.assert_allclose(float(gates.sum(-1)[0]), whole.routed_scale,
                               rtol=1e-6)
    np.testing.assert_allclose(sum(parts) + shared, want, atol=2e-5, rtol=0)


class Pager:
    """The engine's device side by hand: a pool of pages and of slot
    states, a page table a slot, and the two paged programs called as
    `LLMEngine` calls them."""

    def __init__(self, cfg, params, attn_impl="gather"):
        self.cfg, self.params, self.impl = cfg, params, attn_impl
        self.pool = nh.init_paged_kv(cfg, N_PAGES, PAGE, N_SLOTS)
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
        out, self.pool = nh.prefill_chunk_paged(
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
        out, self.pool = nh.decode_step_paged(
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


@pytest.mark.parametrize("n_prompt", [31, 97, 128])
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
        used = Pager(CFG, params, "kernel")
        _serve_logits(used, first, _tokens(5, 6))
        assert float(jnp.abs(used.pool["ssm_state"][:, 1]).max()) > 0.01
        assert float(jnp.abs(used.pool["ssm_conv"][:, :, 1]).max()) > 0.01
        used.tables[1] = 0                          # released: new pages
        again = used.prefill(1, second)
    np.testing.assert_allclose(again, _ref_logits(params, second)[-1],
                               atol=ATOL_F32, rtol=0)


def _without(params, name, value):
    """The tree with one stack replaced by a constant."""
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


def _ssm_inputs_with(change):
    """`_ssm_inputs` with its outputs (xs, z, dt, B, C, ext) changed."""
    true = nh._ssm_inputs

    def inputs(cfg, params, l, i, x, valid, conv):
        return change(*true(cfg, params, l, i, x, valid, conv))
    return inputs


def _loop_experts(act):
    """The held experts' part as a loop over them, with `act` for the
    squared ReLU (the true layer's signature, as the block calls it)."""
    def experts(x, ids, gates, w_up, w_down, *, first_expert, layer, valid,
                n_routed):
        del n_routed
        y = jnp.zeros(x.shape, jnp.float32)
        for e in range(w_up.shape[1]):
            gate = jnp.sum(jnp.where(ids == first_expert + e, gates, 0.0),
                           axis=-1) * valid
            y = y + gate[:, None] * (act(x @ w_up[layer, e])
                                     @ w_down[layer, e])
        return y.astype(x.dtype), jnp.ones(w_up.shape[1], jnp.int32)
    return experts


FAULTS = ["state_in_bf16", "decay_dropped", "skip_dropped",
          "gate_after_the_norm", "norm_over_all_groups", "wrong_group",
          "tail_not_carried", "state_zeroed_at_a_chunk", "relu_for_relu2",
          "gated_expert", "gates_unscaled", "bias_in_the_gates",
          "rope_added", "latent_out_dropped"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_fails_the_tolerance(params, fault, monkeypatch):
    """What the tolerance is for: each of these serves logits that the
    comparison above would refuse."""
    # A configuration of its own, so that no trace of the true block is
    # found in the jit cache.
    cfg = nh.NemotronHConfig.tiny(dtype=jnp.float32,
                                  max_seq=257 + FAULTS.index(fault))
    served = params
    if fault == "state_in_bf16":        # as a chunk and as a step leave it
        step = nh.reference_ssd_decode_step
        rounded_ = lambda out: (out[0], out[1].astype(jnp.bfloat16).astype(
            jnp.float32))

        def rounded(*a, **k):
            y, state = step(*a, **k)
            return y, state.astype(jnp.bfloat16).astype(jnp.float32)

        monkeypatch.setattr(nh, "reference_ssd_decode_step", rounded)
        scan = nh.ssd_chunk_scan
        monkeypatch.setattr(nh, "ssd_chunk_scan",
                            lambda *a, **k: rounded_(scan(*a, **k)))
    elif fault == "decay_dropped":                  # a_h = 1
        monkeypatch.setattr(nh, "_rate",
                            lambda params, i: jnp.zeros(cfg.m_heads))
    elif fault == "skip_dropped":
        served = _without(params, "m_D", 0.0)
    elif fault in ("gate_after_the_norm", "norm_over_all_groups"):
        def output(cfg, params, i, x, y, xs, z):
            y = y + params["m_D"][i][:, None] * xs
            y, gate = y.reshape(z.shape), jax.nn.silu(z)
            groups = 1 if fault == "norm_over_all_groups" else cfg.m_groups
            normed = lambda t: (lambda g: g * jax.lax.rsqrt(jnp.mean(
                g * g, axis=-1, keepdims=True) + cfg.norm_eps))(
                    t.reshape(z.shape[:-1] + (groups, -1))).reshape(z.shape)
            y = (normed(y) * gate if fault == "gate_after_the_norm"
                 else normed(y * gate))
            return x + (y * params["m_norm"][i]) @ params["m_out"][i]

        monkeypatch.setattr(nh, "_ssm_output", output)
    elif fault == "wrong_group":
        # Every head reads group 0's B and C.
        monkeypatch.setattr(nh, "_ssm_inputs", _ssm_inputs_with(
            lambda xs, z, dt, B, C, ext: (
                xs, z, dt, jnp.broadcast_to(B[..., :1, :], B.shape),
                jnp.broadcast_to(C[..., :1, :], C.shape), ext)))
    elif fault == "tail_not_carried":
        # A chunk row starts its convolution from zeros whatever came
        # before it.
        conv = nh.causal_conv
        monkeypatch.setattr(
            nh, "causal_conv", lambda boundary, taps: conv(
                lambda xs: (jnp.zeros((xs.shape[0], xs.shape[2]),
                                      xs.dtype),) * (taps - 1), taps))
    elif fault == "state_zeroed_at_a_chunk":
        scan = nh.ssd_chunk_scan
        monkeypatch.setattr(
            nh, "ssd_chunk_scan",
            lambda x, dt, A, B, C, state, chain, fresh, **kw: scan(
                x, dt, A, B, C, state, chain, jnp.ones_like(fresh), **kw))
    elif fault in ("relu_for_relu2", "gated_expert"):
        act = (jax.nn.relu if fault == "relu_for_relu2"
               else lambda h: jax.nn.silu(h) * h)
        monkeypatch.setattr(nh, "token_choice_experts", _loop_experts(act))
    elif fault == "gates_unscaled":
        cfg = nh.NemotronHConfig.tiny(dtype=jnp.float32, routed_scale=1.0)
    elif fault == "bias_in_the_gates":
        route = blocks.biased_route

        def biased(cfg, w_router, bias, u):
            chosen, gates, moved = route(cfg, w_router, bias, u)
            b = jnp.take_along_axis(
                jnp.broadcast_to(bias, (u.shape[0],) + bias.shape), chosen,
                axis=-1)
            own = gates / cfg.routed_scale      # normalised unbiased scores
            own = own + b
            return (chosen, cfg.routed_scale * own
                    / jnp.sum(own, axis=-1, keepdims=True), moved)

        monkeypatch.setattr(blocks, "biased_route", biased)
        # A bias of the scores' own size: the seeded 0.002 moves a gate
        # by less than the tolerance.
        served = {**params, "router_bias": 100 * params["router_bias"]}
    elif fault == "rope_added":
        attn = nh._attn_inputs

        def roped(*a):
            q, k, v = attn(*a)
            return _rope(q), _rope(k), v

        monkeypatch.setattr(nh, "_attn_inputs", roped)
    elif fault == "latent_out_dropped":
        served = _without(params, "lat_out", 0.0)
    reference = served if fault == "bias_in_the_gates" else params
    with jax.default_matmul_precision("highest"):
        if fault == "rope_added":
            # The full-sequence forward, where a row's position is its
            # index; the paged programs hand the block no position.
            seq = np.concatenate([PROMPT, FOLLOW])
            got = np.asarray(nh.forward(cfg, served, jnp.asarray(seq)[None]))[
                0, len(PROMPT) - 1:]
        else:
            got, _other, _b = _serve_logits(Pager(cfg, served), PROMPT,
                                            FOLLOW)
    want = _ref_logits(reference, np.concatenate([PROMPT, FOLLOW]))
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


@pytest.mark.parametrize("attn_impl", ["gather", "kernel"])
def test_engine_serves_the_references_tokens_and_counts(params, attn_impl):
    """Normal entry points, scheduler, PagePool, tick: four requests over
    three slots (so one slot is reused by a shorter request), prompts of
    one to three chunk rows, every emitted token the float32 reference's
    best at its position (deficit under ATOL_F32); the state and tail a
    slot and the experts' counters in `metrics()`."""
    eng = _engine(params, attn_impl=attn_impl)
    rng = np.random.default_rng(0)
    reqs = [eng.submit(rng.integers(1, CFG.vocab_size, n).tolist(),
                       max_tokens=m)
            for n, m in ((75, 21), (40, 30), (5, 50), (33, 9))]
    with jax.default_matmul_precision("highest"):
        _run(eng, reqs)
    for r in reqs:
        assert _deficits(params, r).max() <= ATOL_F32
    m = eng.metrics()
    nm, ne = CFG.count("M"), CFG.count("E")
    assert m["preemptions"] == 0 and m["window_kv_bytes"] == 0
    # The operator's third memory account: both leaves, null slot and all.
    assert m["slot_state_bytes"] == nm * (N_SLOTS + 1) * 4 * (
        CFG.m_heads * CFG.m_head_dim * CFG.d_state
        + (CFG.d_conv - 1) * CFG.conv_channels)
    assert m["slot_state_bytes"] == sum(
        int(eng.cache[n].nbytes) for n in nh.SLOT_STATE_LEAVES)
    assert m["kv_pool_bytes"] == (
        2 * CFG.count("*") * (N_PAGES + 1) * PAGE
        * CFG.n_kv_heads * CFG.head_dim * 4)
    # Every live row routes top_k choices a sparse layer and step; half
    # of the 16 experts are held, so about half of them land here.
    assert m["moe_layer_steps"] % ne == 0 and m["moe_layer_steps"] > 0
    assert m["moe_rows_routed"] % CFG.top_k == 0
    assert 0.2 < m["moe_rows_held"] / m["moe_rows_routed"] < 0.8
    assert 0 < m["moe_experts_touched"] <= (
        m["moe_layer_steps"] * CFG.n_experts)
    assert m["moe_rows_over"] == 0
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


@pytest.mark.parametrize("option,value,needs", [
    ("prefill_width_bucketing", True, "every held expert"),
    ("prefix_cache", True, "snapshot"),
    ("spec_draft", "tiny", "multi-token-prediction"),
    ("kv_transfer", True, "PagePool pages only"),
    ("tp", 2, "expert-parallel exchange"),
    ("weight_dtype", "int8", "no int8 form"),
    ("kv_dtype", "int8", "float32"),
])
def test_options_the_family_cannot_carry_are_refused(params, option, value,
                                                     needs):
    """At construction, each naming the family and what would have to be
    built."""
    with pytest.raises(ValueError, match=needs) as e:
        _engine(params, **{option: value})
    assert "nemotron_h" in str(e.value)


def test_the_family_is_found_by_its_configuration():
    fam = serving.family_of(CFG)
    assert fam.name == "nemotron_h" and fam.lay_out is None
    assert fam.slot_state == ("ssm_state", "ssm_conv")
    assert fam.expert_counters == blocks.COUNTERS_BIASED
