"""One decode step in flight across the window boundary (`LLMEngine._step`,
`models/paged_kv._decode_window`): a paged window of k rows ends with k + 1
steps queued and k read, and the step left over is the next window's first
row.

What is held here, on the CPU at tiny sizes: every request's tokens are the
plain `forward`'s greedy continuation with the step in flight, for gpt and
for each family's tiny configuration (zaya and qwen3_next advance a state
by the slot in every step: a dropped or doubled step shows there first); a
finished slot's row is thrown away and a live slot's never; the tick stands
down, by cause, where the issue says it must; and whatever reads a live
slot from outside a tick (`drain`, `_export_unfinished`, `_preempt`,
`stop`) finds host and device at the same position.
"""

from __future__ import annotations

import dataclasses
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import plain_reference
from plain_reference import ATOL
from ray_tpu.models import (gpt, jamba, kimi_k2, laguna, mimo_v2,
                            nemotron_h, olmo_hybrid, paged_kv, qwen3_next,
                            zaya)
from ray_tpu.serve import llm
from ray_tpu.serve.llm import LLMEngine

PAGE, CHUNK = 16, 16


@dataclasses.dataclass(frozen=True)
class Fam:
    cfg: object
    init: object
    forward: object            # (cfg, params, tokens [1, S]) -> logits [1, S, V]
    # Leaves `_serve` leaves at their initial size: a norm over q's and
    # k's whole width is a stack [layers, H K], a "matrix" by its rank,
    # and its weight times 8 is the scores times 64: one-hot attention,
    # whose arg-max two equal sums disagree on.
    as_is: tuple = ()


def _fams() -> dict:
    return {
        "gpt": Fam(
            dataclasses.replace(gpt.GPTConfig.by_name("tiny"),
                                dtype=jnp.float32),
            gpt.init_params, plain_reference.gpt_forward),
        "zaya": Fam(zaya.ZayaConfig.tiny(dtype=jnp.float32),
                    zaya.init_params, zaya.forward),
        "laguna": Fam(laguna.LagunaConfig.tiny(dtype=jnp.float32),
                      laguna.init_params, laguna.forward),
        "qwen3_next": Fam(qwen3_next.Qwen3NextConfig.tiny(dtype=jnp.float32),
                          qwen3_next.init_params, qwen3_next.forward),
        "mimo_v2": Fam(mimo_v2.MiMoV2Config.tiny(dtype=jnp.float32),
                       mimo_v2.init_params, mimo_v2.forward),
        "jamba": Fam(jamba.JambaConfig.tiny(dtype=jnp.float32),
                     jamba.init_params, jamba.forward),
        "kimi_k2": Fam(kimi_k2.KimiK2Config.tiny(dtype=jnp.float32),
                       kimi_k2.init_params, kimi_k2.forward),
        "olmo_hybrid": Fam(
            olmo_hybrid.OlmoHybridConfig.tiny(dtype=jnp.float32),
            olmo_hybrid.init_params, olmo_hybrid.forward,
            as_is=("f_qnorm", "f_knorm")),
        # (the decay's rate a head is a stack [layers, heads]: its log
        # times 8 is a rate of up to 16^8, a state that forgets at once;
        # a squared-ReLU shared expert's output has a constant part, the
        # same for every token, and at 8x it settles a greedy
        # continuation on a few tokens: benchmarks/families/nemotron_h.py)
        "nemotron_h": Fam(
            nemotron_h.NemotronHConfig.tiny(dtype=jnp.float32),
            nemotron_h.init_params, nemotron_h.forward,
            as_is=("m_A_log", "s_down")),
    }


def _serve(name):
    """(Fam, params): seeded weights with every matrix but the embedding
    8x its initial size and moved off it (`plain_reference.lively`), so
    that the mixers and experts all move the logits and a greedy
    continuation does not settle on one token."""
    fam = _fams()[name]
    return fam, plain_reference.lively(
        fam.init(fam.cfg, jax.random.key(0)), as_is=fam.as_is)


def _drop_programs():
    """Six families' programs in one process cross the mappings a
    process may hold (tests/conftest.py `_release_compiled_programs`):
    each family's go when its tests are over."""
    jax.clear_caches()
    gc.collect()


@pytest.fixture(scope="class", params=["gpt", "zaya", "laguna", "qwen3_next",
                                       "mimo_v2", "jamba", "kimi_k2",
                                       "olmo_hybrid", "nemotron_h"])
def family(request):
    """pytest runs a class's tests family by family for this fixture."""
    yield (request.param, *_serve(request.param))
    _drop_programs()


@pytest.fixture(scope="module")
def gpt_served():
    return _serve("gpt")


@pytest.fixture(scope="class")
def zaya_served():
    yield _serve("zaya")
    _drop_programs()


def _engine(fam: Fam, params, **kw):
    opts = dict(n_slots=3, max_len=128, page_size=PAGE,
                n_pages=24, prefill_chunk=CHUNK, attn_impl="gather",
                prefill_token_budget=2 * CHUNK, decode_block=8)
    return LLMEngine(fam.cfg, params, **{**opts, **kw})


def _prompt(n, seed=0, vocab=256):
    return np.random.default_rng(seed).integers(1, vocab, n).tolist()


def _run(eng, reqs, ticks=900):
    with jax.default_matmul_precision("highest"):
        for _ in range(ticks):
            if all(r.done.is_set() for r in reqs):
                break
            eng.step()
    assert all(r.done.is_set() and r.error is None for r in reqs)


def _deficits(fam: Fam, params, r, out_ids=None):
    """How far under the plain forward's best logit each emitted token
    lies, at its position (tests/plain_reference.py)."""
    return plain_reference.request_deficits(fam.forward, fam.cfg, params, r,
                                            out_ids)


def _greedy(fam: Fam, params, prompt, n):
    """The plain forward's greedy continuation of `prompt`: what an
    engine alone with it emits, HELD to the forward (every token its best
    at its position, so the whole is the forward's own continuation; one
    forward instead of one a token)."""
    eng = _engine(fam, params)
    r = eng.submit(prompt, max_tokens=n)
    _run(eng, [r])
    assert _deficits(fam, params, r).max() <= ATOL
    return list(r.out_ids)


# ------------------------------------------------- the seam: _decode_window

class _Recorder:
    """A fake `step` and a fake `jax.device_get`, in one order of events."""

    def __init__(self):
        self.events = []
        self.n = 0

    def step(self, tokens, pool, positions, key):
        self.n += 1
        self.events.append(("step", self.n))
        return (np.full(2, 100 + self.n, np.int32), positions + 1,
                {"c": pool["c"] + 1}, key + 1)

    def device_get(self, tree):
        self.events.append(("get", tree))
        return tree


def test_decode_window_queues_one_more_step_and_pulls_only_its_own(
        monkeypatch):
    rec = _Recorder()
    monkeypatch.setattr(paged_kv.jax, "device_get", rec.device_get)
    held = []
    k = 4
    toks, pool, extra = paged_kv._decode_window(
        rec.step, np.zeros(2, np.int32), {"c": np.uint32(0)},
        np.zeros(2, np.int32), k, 0, also=lambda pool: pool["c"],
        ahead=lambda tokens, key: held.append((tokens, key)))
    # k + 1 dispatches are queued before the first (and only) fetch ...
    assert [e[0] for e in rec.events] == ["step"] * (k + 1) + ["get"]
    # ... which is asked for the first k token arrays, and for the
    # counters as they stood BEFORE the step in flight took the pool.
    asked_tokens, asked_extra = rec.events[-1][1]
    assert [int(t[0]) for t in asked_tokens] == [101, 102, 103, 104]
    assert int(asked_extra) == k and int(extra) == k
    assert toks.shape == (k, 2) and toks[:, 0].tolist() == [101, 102, 103, 104]
    assert int(pool["c"]) == k + 1
    (carried, key), = held
    assert carried.tolist() == [105, 105] and key == k + 1
    # The carried values come back with the next call's, as its first row.
    rec.events.clear()
    toks, pool, _ = paged_kv._decode_window(
        rec.step, carried, pool, np.zeros(2, np.int32), k - 1, key,
        carried=carried)
    assert [e[0] for e in rec.events] == ["step"] * (k - 1) + ["get"]
    assert toks[:, 0].tolist() == [105, 106, 107, 108]


def test_decode_window_without_ahead_is_the_old_window(monkeypatch):
    rec = _Recorder()
    monkeypatch.setattr(paged_kv.jax, "device_get", rec.device_get)
    toks, pool, _ = paged_kv._decode_window(
        rec.step, np.zeros(2, np.int32), {"c": np.uint32(0)},
        np.zeros(2, np.int32), 3, 0)
    assert [e[0] for e in rec.events] == ["step"] * 3 + ["get"]
    assert toks[:, 1].tolist() == [101, 102, 103] and int(pool["c"]) == 3


def test_join_window_feeds_the_carried_token_a_position_on():
    mask = jnp.asarray([True, False, True])
    toks, pos = paged_kv.join_window(
        mask, jnp.asarray([7, 8, 9], jnp.int32),
        jnp.asarray([1, 2, 3], jnp.int32), jnp.asarray([10, 20, 30], jnp.int32))
    assert toks.tolist() == [7, 2, 9] and pos.tolist() == [11, 20, 31]
    assert toks.dtype == jnp.int32 and pos.dtype == jnp.int32


# ------------------------------------------ every family, the step in flight

def _steps_to_carry(eng, ticks=50):
    """Tick until a step is in flight. → ticks taken."""
    with jax.default_matmul_precision("highest"):
        for i in range(ticks):
            eng.step()
            if eng._carry is not None:
                return i + 1
    raise AssertionError("no window left a step in flight")


class TestEveryFamily:
    """One engine shape for all of a family's tests, so that they share
    its programs; `family` runs them family by family."""

    def test_tokens_are_the_plain_forwards_with_a_step_in_flight(
            self, family):
        """Five requests over three slots: a slot finishes while others go
        on and is refilled the next tick, the newcomer joining at the
        window's first new step; every emitted token is the plain
        forward's best at its position, so the whole is its greedy
        continuation."""
        name, fam, params = family
        eng = _engine(fam, params)
        reqs = [eng.submit(_prompt(n, seed=n), max_tokens=m)
                for n, m in ((37, 30), (16, 44), (5, 19), (50, 12), (21, 27))]
        _run(eng, reqs)
        for r in reqs:
            assert len(r.out_ids) == r.max_tokens
            assert len(set(r.out_ids)) > len(r.out_ids) // 3
            assert _deficits(fam, params, r).max() <= ATOL, name
        m = eng.metrics()
        assert m["preemptions"] == 0
        assert m["lookahead_windows"] > 0.5 * m["decode_windows"]
        # Every window either left a step in flight or says why not.
        assert (m["lookahead_windows"]
                + sum(m["lookahead_stood_down"].values())
                == m["decode_windows"])
        assert m["lookahead_share"] == pytest.approx(
            m["lookahead_windows"] / m["decode_windows"])
        # Slots finished while others went on: their rows were dropped.
        assert m["lookahead_rows_dropped"] >= 1
        assert eng._carry is None
        acc = eng.page_accounting()
        assert acc["closure"] and acc["refs_consistent"] and acc["live"] == 0

    def test_export_with_a_step_in_flight_agrees_with_the_emitted_tokens(
            self, family):
        """`_export_unfinished` (what `drain` ends in) with a step in
        flight: it is absorbed first, so the exported lengths are the
        emitted tokens' and the continuation, resumed on a fresh engine,
        goes on to the very tokens an undisturbed run emits."""
        name, fam, params = family
        prompt = _prompt(19, seed=5)
        eng = _engine(fam, params)
        r = eng.submit(prompt, max_tokens=40)
        _steps_to_carry(eng)
        n_before = len(r.out_ids)
        out = eng.drain(0.0)
        assert eng._carry is None and out["exported"] == 1
        cont, = out["continuations"]
        # The step in flight was emitted, not dropped ...
        assert len(cont["generated_ids"]) == n_before + 1
        assert cont["prompt_ids"] == prompt
        # ... every page went back ...
        acc = eng.page_accounting()
        assert acc["closure"] and acc["live"] == 0
        # ... and the continuation resumes to the undisturbed tokens.
        eng2 = _engine(fam, params)
        r2 = eng2.submit(cont["prompt_ids"] + cont["generated_ids"],
                         max_tokens=40 - len(cont["generated_ids"]))
        _run(eng2, [r2])
        whole = cont["generated_ids"] + r2.out_ids
        assert len(whole) == 40
        assert _deficits(fam, params, r, whole).max() <= ATOL

    def test_preempt_with_a_step_in_flight_recomputes_to_the_same_tokens(
            self, family):
        name, fam, params = family
        prompt, other = _prompt(11, seed=6), _prompt(26, seed=7)
        eng = _engine(fam, params)
        r = eng.submit(prompt, max_tokens=33)
        o = eng.submit(other, max_tokens=35)
        _steps_to_carry(eng)
        slot = eng.slot_req.index(r)
        assert eng._carry.mask[slot]
        n_before, pos_before = len(r.out_ids), int(eng.positions[slot])
        assert pos_before == len(prompt) + n_before - 1
        eng._preempt(slot)
        # Absorbed: the request's context holds the token that was in
        # flight, and the other slot's host state moved on with the
        # device's.
        assert eng._carry is None and len(r.out_ids) == n_before + 1
        assert r.prompt_ids == prompt + r.out_ids
        other_slot = eng.slot_req.index(o)
        assert (int(eng.positions[other_slot])
                == len(other) + len(o.out_ids) - 1)
        _run(eng, [r, o])
        assert len(r.out_ids) == 33 and len(o.out_ids) == 35
        assert _deficits(fam, params, r).max() <= ATOL
        assert _deficits(fam, params, o).max() <= ATOL
        assert eng.metrics()["preemptions"] == 1

    def test_expert_counters_sum_to_the_devices_over_a_run(self, family):
        """The stats take differences of running totals read a step
        late: over a run what they hold is what the last pull saw, never
        more than the device counted, and what no pull has read yet is
        the run's last ticks' (a one-step tick reads nothing: its share
        comes with the next window's)."""
        name, fam, params = family
        if name in ("gpt", "jamba", "olmo_hybrid"):
            pytest.skip("no experts: nothing is counted")
        eng = _engine(fam, params)
        reqs = [eng.submit(_prompt(n, seed=n), max_tokens=m)
                for n, m in ((20, 26), (9, 18), (33, 11))]
        _run(eng, reqs)
        s = eng.stats
        assert eng._carry is None and s["lookahead_windows"] >= 2
        names = {"zaya": zaya, "laguna": laguna, "qwen3_next": qwen3_next,
                 "kimi_k2": kimi_k2, "nemotron_h": nemotron_h,
                 "mimo_v2": mimo_v2}[name].COUNTERS
        device = dict(zip(names, (int(t) for t in eng.cache["moe_counters"])))
        seen = eng._moe_seen
        for stat, counter in (("moe_layer_steps", "layer_steps"),
                              ("moe_experts_touched_sum", "experts_touched"),
                              ("moe_rows_max_sum", "rows_max"),
                              ("moe_rows_routed", "rows_routed")):
            assert 0 < s[stat] == seen[counter] <= device[counter]
        per_step = device["layer_steps"] // sum(r.max_tokens for r in reqs)
        assert device["layer_steps"] - seen["layer_steps"] <= 3 * max(
            1, per_step) * 3


# ---------------------------------------------------- a stateful family: zaya

class TestStatefulFamily:

    def test_counters_of_a_window_are_its_steps(self, zaya_served):
        """One window, step by step: the totals the window's pull hands
        over are those after its own steps; the step in flight's arrive
        with the next pull."""
        fam, params = zaya_served
        eng = _engine(fam, params)
        pulls = []
        note = eng._note_device_counters
        eng._window_counters = {
            "counters": lambda t: (pulls.append(dict(t)), note(t))}
        eng.submit(_prompt(8), max_tokens=40)
        _steps_to_carry(eng)                  # window 1: 8 steps + 1 ahead
        with jax.default_matmul_precision("highest"):
            eng.step()                        # window 2: 7 steps + 1 ahead
        per_step = pulls[0]["layer_steps"] // 8   # one live slot
        assert per_step > 0
        assert [p["layer_steps"] for p in pulls] == [8 * per_step,
                                                     16 * per_step]
        assert int(eng.cache["moe_counters"][0]) == 17 * per_step

    @pytest.mark.parametrize("where", ["middle", "last", "carried"])
    def test_eos_inside_a_window_finishes_there(self, zaya_served, where):
        """An EOS in the middle of a window, at its last row, and ON the
        row the step in flight brings: the request ends with it, the rest
        of the window and the slot's row of the next step in flight are
        dropped, and the other slot's tokens are untouched."""
        fam, params = zaya_served
        prompt, other = _prompt(9, seed=3), _prompt(14, seed=4)
        want = _greedy(fam, params, prompt, 30)
        # Emitted token 0 comes from the prefill; window rows follow: the
        # first window is rows 1..8, the step it leaves in flight row 9.
        at = {"middle": 4, "last": 8, "carried": 9}[where]
        eos = want[at]
        assert eos not in want[:at]
        eng = _engine(fam, params)
        r = eng.submit(prompt, max_tokens=30, eos_id=eos)
        o = eng.submit(other, max_tokens=26)
        _run(eng, [r, o])
        assert r.out_ids == want[:at + 1]
        assert len(o.out_ids) == 26
        assert _deficits(fam, params, o).max() <= ATOL
        assert eng.metrics()["lookahead_rows_dropped"] >= 1

    def test_a_request_owed_only_the_step_in_flight_leaves_its_slot_early(
            self, zaya_served):
        """18 tokens = the prefill's, a window of 8, the step it left in
        flight and 7 more: the 18th is in flight when that window is
        read, and is the request's last whatever it is. The slot and its
        pages go back then (the next admission takes them a tick sooner
        than the token arrives, as it would without a step in flight),
        and the token follows with the next pull."""
        fam, params = zaya_served
        pa, pb, pc = _prompt(9, seed=3), _prompt(14, seed=4), _prompt(11, seed=9)
        want = _greedy(fam, params, pa, 18)
        eng = _engine(fam, params, n_slots=2)
        a = eng.submit(pa, max_tokens=18)
        b = eng.submit(pb, max_tokens=60)
        c = eng.submit(pc, max_tokens=21)
        with jax.default_matmul_precision("highest"):
            for _ in range(20):
                eng.step()
                if a not in eng.slot_req:
                    break
            assert not a.done.is_set() and a.out_ids == want[:17]
            slot, = eng._carry.owed
            assert eng._carry.owed[slot] is a and not eng._carry.mask[slot]
            assert eng.slot_req[slot] is None and c.first_token_at is None
            eng.step()          # admits c into the slot; pulls a's token
        assert a.done.is_set() and a.out_ids == want
        assert eng.slot_req[slot] is c and len(c.out_ids) >= 1
        _run(eng, [b, c])
        assert len(b.out_ids) == 60 and len(c.out_ids) == 21
        for r in (b, c):
            assert _deficits(fam, params, r).max() <= ATOL
        assert eng._carry is None
        acc = eng.page_accounting()
        assert acc["closure"] and acc["refs_consistent"] and acc["live"] == 0

    def test_a_step_in_flight_for_owed_requests_alone_is_still_read(
            self, zaya_served):
        """One request leaves ahead of its last token in the very window
        in which the only other one meets its EOS: nothing decodes the
        next tick, and that tick still reads the step."""
        fam, params = zaya_served
        pa, pb = _prompt(9, seed=3), _prompt(14, seed=4)
        want_b = _greedy(fam, params, pb, 30)
        eos = want_b[12]        # in the second window, like a's 17th
        assert eos not in want_b[:12]
        eng = _engine(fam, params, n_slots=2)
        a = eng.submit(pa, max_tokens=18)
        b = eng.submit(pb, max_tokens=60, eos_id=eos)
        with jax.default_matmul_precision("highest"):
            for _ in range(20):
                eng.step()
                if b.done.is_set():
                    break
            assert eng._carry is not None and not eng._carry.mask.any()
            assert list(eng._carry.owed.values()) == [a]
            assert not a.done.is_set() and eng.slot_req == [None, None]
            eng.step()
        assert eng._carry is None and a.done.is_set()
        assert len(a.out_ids) == 18 and b.out_ids == want_b[:13]
        assert _deficits(fam, params, a).max() <= ATOL
        assert eng.page_accounting()["live"] == 0

    def test_stop_absorbs_the_step_in_flight(self, zaya_served):
        """The engine thread leaves a step in flight whenever it is
        stopped between two windows; `stop` reads it, and the state it
        leaves (host cursor = prompt + emitted - 1 for every live slot)
        is the one a later tick continues from, to the undisturbed
        tokens."""
        fam, params = zaya_served
        prompt = _prompt(13, seed=8)
        eng = _engine(fam, params)
        r = eng.submit(prompt, max_tokens=60)
        with jax.default_matmul_precision("highest"):
            eng.start()
            for _ in range(4000):
                if len(r.out_ids) >= 10:
                    break
                r.done.wait(0.005)
            eng.stop()
        assert eng._carry is None and not r.done.is_set()
        slot = eng.slot_req.index(r)
        assert int(eng.positions[slot]) == len(prompt) + len(r.out_ids) - 1
        assert int(eng.tokens[slot]) == r.out_ids[-1]
        _run(eng, [r])
        assert len(r.out_ids) == 60
        assert _deficits(fam, params, r).max() <= ATOL


# ------------------------------------------------------------- standing down

def _stood_down(eng):
    return {c: n for c, n in eng.metrics()["lookahead_stood_down"].items()
            if n}


def test_stands_down_for_want_of_a_page(gpt_served):
    """The pool covers the window and not one position more: the window
    runs at its full size, nothing is shed, no step is left in flight."""
    fam, params = gpt_served
    # One slot, prompt of 8: the first window writes positions 8..15 (page
    # 0), the step after it would write 16 (a second page). One page.
    eng = _engine(fam, params, n_slots=1, n_pages=1, max_len=32)
    r = eng.submit(_prompt(8), max_tokens=12)
    with jax.default_matmul_precision("highest"):
        eng.step()
    assert len(r.out_ids) == 1 + 8 and eng._carry is None
    assert _stood_down(eng) == {"pages": 1}
    assert eng.metrics()["preemptions"] == 0


@pytest.mark.parametrize("max_len, cause", [(16, {"max_len": 1}), (17, {})])
def test_stands_down_at_max_len(gpt_served, max_len, cause):
    """Prompt of 8: the window writes 8..15. In max_len 17 position 16 is
    the last there is and the step in flight may take it; in max_len 16
    it may not."""
    fam, params = gpt_served
    eng = _engine(fam, params, n_slots=1, max_len=max_len, n_pages=4)
    r = eng.submit(_prompt(8), max_tokens=40)
    with jax.default_matmul_precision("highest"):
        eng.step()
    assert len(r.out_ids) == 9
    assert _stood_down(eng) == cause
    assert (eng._carry is None) == bool(cause)
    _run(eng, [r])
    assert r.truncated and _deficits(fam, params, r).max() <= ATOL


def test_stands_down_at_one_step(gpt_served):
    """decode_block 1: every tick is the one-step tick."""
    fam, params = gpt_served
    eng = _engine(fam, params, decode_block=1)
    r = eng.submit(_prompt(8), max_tokens=5)
    _run(eng, [r])
    assert _stood_down(eng) == {"k1": 4} and eng._carry is None


def test_stands_down_when_no_window_follows(gpt_served):
    """The tail of a lone request: a window after which a single token is
    owed leaves no step in flight, so that token is the one-step tick's,
    as before (and a warm-up request still loads that tick's program)."""
    fam, params = gpt_served
    eng = _engine(fam, params)
    r = eng.submit(_prompt(8), max_tokens=12)       # 1 + 8 + 2 + 1
    _run(eng, [r])
    assert eng.metrics()["lookahead_windows"] == 1
    assert _stood_down(eng) == {"budget": 1, "k1": 1}
    assert _deficits(fam, params, r).max() <= ATOL


def test_draw_share_counts_the_windows_that_were_handed_a_temperature(
        gpt_served):
    """`decode_windows_drawn` over `decode_windows`: 0.0 after greedy
    windows (their sampling steps skip the categorical draw,
    `paged_kv._sample_next`), 1.0 after windows that held a slot at
    temperature 0.8, zeroed by `reset_stats()`. The greedy request beside
    the sampling one still emits the plain forward's continuation, and
    the sampling one something else."""
    fam, params = gpt_served
    eng = _engine(fam, params)
    greedy = eng.submit(_prompt(8), max_tokens=17)          # 1 + 8 + 8
    _run(eng, [greedy])
    m = eng.metrics()
    assert m["decode_windows"] == 2 and m["decode_windows_drawn"] == 0
    assert m["decode_draw_share"] == 0.0
    eng.reset_stats()
    pair = [eng.submit(_prompt(8), max_tokens=17),
            eng.submit(_prompt(8), max_tokens=17, temperature=0.8)]
    _run(eng, pair)
    m = eng.metrics()
    assert m["decode_windows_drawn"] == m["decode_windows"] == 2
    assert m["decode_draw_share"] == 1.0
    assert pair[0].out_ids == greedy.out_ids
    assert _deficits(fam, params, pair[0]).max() <= ATOL
    assert pair[1].out_ids != greedy.out_ids
    eng.reset_stats()
    m = eng.metrics()
    assert m["decode_windows_drawn"] == 0 and m["decode_draw_share"] == 0.0


@pytest.mark.parametrize("kind", ["speculative", "one-step"])
def test_other_ticks_never_leave_a_step_in_flight(gpt_served, kind):
    """A speculative tick and the one-step tick (a window of one row,
    sampled on the host) read everything they dispatch."""
    fam, params = gpt_served
    eng = (_engine(fam, params, spec_draft=fam.cfg, spec_k=2,
                   spec_draft_params=params) if kind == "speculative" else
           _engine(fam, params, decode_block=1))
    r = eng.submit(_prompt(8), max_tokens=20)
    with jax.default_matmul_precision("highest"):
        for _ in range(200):
            if r.done.is_set():
                break
            eng.step()
            assert eng._carry is None
    assert r.done.is_set() and len(r.out_ids) == 20
    assert eng.stats["lookahead_windows"] == 0


# --------------------------------------------------- programs and their count

def test_no_program_but_the_two_small_ones_is_added(gpt_served):
    """The step, chunk and one-step programs compile as often as they do
    without a step in flight: what feeds a step behind one (`join_window`'s
    outputs, the carried key) is placed as a step's own outputs are."""
    from ray_tpu import compile_watch

    fam, params = gpt_served
    eng = _engine(fam, params)
    names = ("decode_multi_paged", "join_window", "prefill_chunk_paged",
             "decode_step_paged")
    reqs = [eng.submit(_prompt(n, seed=n), max_tokens=m)
            for n, m in ((9, 30), (12, 22))]
    _run(eng, reqs)
    before = {fn: compile_watch.compiles_total(fn) for fn in names}
    # Like traffic again, slots joining behind a step in flight.
    reqs = [eng.submit(_prompt(n, seed=n + 1), max_tokens=m)
            for n, m in ((10, 30), (12, 22), (7, 25), (11, 12))]
    _run(eng, reqs)
    assert eng.stats["lookahead_windows"] >= 4
    assert {fn: compile_watch.compiles_total(fn) for fn in names} == before


def test_no_knob_was_added():
    import inspect

    from ray_tpu.serve import llm_options

    for fn in (LLMEngine.__init__, llm_options.resolve_options):
        assert not [p for p in inspect.signature(fn).parameters
                    if "ahead" in p or "carry" in p or "flight" in p]
    assert llm._STAND_DOWN == ("pages", "max_len", "k1", "budget")
