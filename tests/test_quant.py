"""Quantized serving: int8 weights + per-page KV scale planes
(models/gpt.py quantizer, models/paged_kv.py scale planes,
serve/llm.py knobs).

Fidelity first: the rule-driven per-channel quantizer must hold a
pinned logit-MAE and eval-loss delta against the float masters (the
tolerance-twin contract the bench re-measures per round). Exactness
where the design guarantees it: greedy speculative decoding with an
int8 draft emits the TARGET's argmax at every position, so the stream
is byte-identical to the non-speculative engine regardless of draft
precision. Then the pool contracts: the int8 KV pool's scale planes
ride the existing page tables, so COW admission, donation/adoption,
chaos faults, and tp reshard must all keep page-accounting closure and
stream-level determinism with ZERO scheduler changes. Finally the knob
surface: bad values raise, and the
GLOBAL env knob soft-disables on misfit engines instead of crashing
replica boot (the llm_tp pattern)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu import chaos
from ray_tpu.models import gpt
from ray_tpu.serve.kv_objects import LocalKVStore
from ray_tpu.serve.llm import LLMEngine

CFG = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32)
DRAFT_CFG = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32,
                               n_layers=1, d_model=32, n_heads=4, d_ff=64)
CHUNK = 16
PAGE = 16

# Pinned on this exact tiny config (seed 42 masters, seed-123 eval
# batch). Measured: MAE ~7.1e-4, loss delta ~6.2e-6 — pins carry an
# order of magnitude of headroom so they fail on real regressions
# (a wrong scale axis, a skipped plane), not on BLAS jitter.
LOGIT_MAE_BOUND = 5e-3
EVAL_LOSS_DELTA_BOUND = 1e-3


@pytest.fixture(scope="module")
def params():
    return gpt.init_params(CFG, jax.random.key(42))


@pytest.fixture(scope="module")
def draft_params():
    return gpt.init_params(DRAFT_CFG, jax.random.key(7))


def _engine(params, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_len", 128)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("prefill_token_budget", 32)
    return LLMEngine(CFG, params, **kw)


def _drive(eng, reqs, max_steps=2000):
    for _ in range(max_steps):
        if all(r.done.is_set() for r in reqs):
            break
        eng.step()
    assert all(r.done.is_set() for r in reqs)
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    return [r.out_ids for r in reqs]


def _closure(eng):
    acc = eng.page_accounting()
    assert acc["closure"], acc
    assert acc["refs_consistent"], acc
    return acc


def _prompt(seed, n):
    rng = np.random.default_rng(seed)
    return list(map(int, rng.integers(1, CFG.vocab_size, n)))


def _leaves(tree, prefix=""):
    for k, v in tree.items():
        if isinstance(v, dict):
            yield from _leaves(v, prefix + k + "/")
        else:
            yield prefix + k, v


class TestQuantizer:
    """The rule-driven per-channel quantizer (gpt.QUANT_RULES)."""

    def test_planes_scales_and_float_leaves(self, params):
        qp = gpt.quantize_params(params)
        leaves = dict(_leaves(qp))
        for name in ("wq", "wk", "wv", "wo", "w_up", "w_down"):
            path = name
            assert leaves[path].dtype == jnp.int8
            scale = leaves[path + "_scale"]
            assert scale.dtype == jnp.float32
            # Per-output-channel: contraction axes collapsed to 1.
            assert scale.size < leaves[path].size
        # Norms / embeddings / head stay exactly the float masters
        # (ln*_scale are layernorm PARAMS, not quantizer scales).
        orig = dict(_leaves(params))
        for path in ("wte", "ln1_scale", "ln1_bias", "ln_f_scale"):
            assert leaves[path].dtype == orig[path].dtype
            np.testing.assert_array_equal(np.asarray(leaves[path]),
                                          np.asarray(orig[path]))

    def test_idempotent(self, params):
        qp = gpt.quantize_params(params)
        qp2 = gpt.quantize_params(qp)
        for (k1, a), (k2, b) in zip(_leaves(qp), _leaves(qp2)):
            assert k1 == k2
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_dequant_roundtrip_error_bounded(self, params):
        """Per element: |dequant(q) - w| <= scale/2 + eps (symmetric
        round-to-nearest, no clipping at absmax-derived scale)."""
        qp = gpt.quantize_params(params)
        w = np.asarray(dict(_leaves(params))["wq"])
        q = dict(_leaves(qp))["wq"]
        s = dict(_leaves(qp))["wq_scale"]
        deq = np.asarray(gpt.dequant(q, s, jnp.float32))
        bound = 0.5 * np.broadcast_to(np.asarray(s), w.shape) + 1e-7
        assert (np.abs(deq - w) <= bound).all()

    def test_logit_mae_pin(self, params):
        qp = gpt.quantize_params(params)
        rng = np.random.default_rng(123)
        toks = jnp.asarray(rng.integers(1, CFG.vocab_size, (4, 64)))
        lo = gpt.forward(params, toks, CFG)
        lq = gpt.forward(qp, toks, CFG)
        mae = float(jnp.mean(jnp.abs(lo - lq)))
        assert mae < LOGIT_MAE_BOUND, mae

    def test_eval_loss_delta_pin(self, params):
        qp = gpt.quantize_params(params)
        rng = np.random.default_rng(123)
        toks = jnp.asarray(rng.integers(1, CFG.vocab_size, (4, 65)))
        l0 = float(gpt.loss_fn(params, toks[:, :-1], toks[:, 1:], CFG))
        l1 = float(gpt.loss_fn(qp, toks[:, :-1], toks[:, 1:], CFG))
        assert abs(l1 - l0) < EVAL_LOSS_DELTA_BOUND, (l0, l1)


class TestSpecByteExact:
    """Greedy speculative decoding emits the target's argmax at every
    position — draft precision can change acceptance rates but NEVER
    the stream. The headline deployment: cheap int8 draft under a
    full-precision target."""

    def test_int8_draft_full_target(self, params, draft_params):
        prompts = [_prompt(s, n) for s, n in
                   ((1, 5), (2, 23), (3, 41), (4, 11))]
        base = _engine(params)
        ref = _drive(base, [base.submit(p, max_tokens=24)
                            for p in prompts])
        eng = _engine(params, spec_draft=DRAFT_CFG,
                      spec_draft_params=gpt.quantize_params(draft_params),
                      spec_k=4)
        out = _drive(eng, [eng.submit(p, max_tokens=24) for p in prompts])
        assert out == ref
        _closure(eng)

    def test_int8_engine_spec_matches_int8_nonspec(self, params,
                                                   draft_params):
        """Fully quantized arm: int8 weights + int8 KV on BOTH engines;
        spec must still match its own non-spec twin byte-for-byte (the
        target logits are the quantized target's — identical arms)."""
        prompts = [_prompt(s, n) for s, n in ((5, 9), (6, 30), (7, 17))]
        base = _engine(params, weight_dtype="int8", kv_dtype="int8")
        ref = _drive(base, [base.submit(p, max_tokens=16)
                            for p in prompts])
        eng = _engine(params, weight_dtype="int8", kv_dtype="int8",
                      spec_draft=DRAFT_CFG, spec_draft_params=draft_params,
                      spec_k=2)
        out = _drive(eng, [eng.submit(p, max_tokens=16) for p in prompts])
        assert out == ref
        _closure(eng)


class TestQuantPool:
    """int8 page planes + per-page scale planes under the full page
    lifecycle: COW, donation/adoption, chaos, accounting closure."""

    def _export_mid_decode(self, params, prompt, store, **kw):
        donor = _engine(params, kv_transfer=True, kv_store=store,
                        max_len=256, **kw)
        req = donor.submit(prompt, max_tokens=24, stream=True)
        while req.first_token_at is None:
            donor.step()
        donor.step()            # a second decode window, not the last
        assert not req.done.is_set()
        conts = donor._export_unfinished()
        assert len(conts) == 1
        _closure(donor)
        return donor, conts[0]

    def _resume(self, params, cont, store, **kw):
        adopter = _engine(params, kv_transfer=True, kv_store=store,
                          max_len=256, **kw)
        req = adopter.submit(
            cont["prompt_ids"], max_tokens=cont["max_tokens"],
            generated_ids=cont["generated_ids"], kv=cont.get("kv"),
            prefix_hashes=cont.get("prefix_hashes"),
            prefix_chunk=cont.get("prefix_chunk", 0))
        out = _drive(adopter, [req])[0]
        _closure(adopter)
        return adopter, out

    def test_pool_bytes_halve_plus_scale_planes(self, params):
        b = _engine(params)
        q = _engine(params, kv_dtype="int8")
        mb, mq = b.metrics(), q.metrics()
        assert mb["llm_kv_dtype"] == "bf16" and mq["llm_kv_dtype"] == "int8"
        # cfg.dtype here is f32 (4 B) → int8 planes are 1/4 the bytes,
        # plus two (L, n_pages+1) bf16 scale planes.
        n_layers = CFG.n_layers
        n_slots = b.cache["k"].shape[1]
        scale_bytes = 2 * n_layers * n_slots * 2
        assert mq["kv_pool_bytes"] == mb["kv_pool_bytes"] // 4 + scale_bytes

    def test_warm_prefix_cow_int8(self, params):
        """Warm-prefix COW with scale planes: shared pages bind
        read-only, divergence COW copies planes AND scales, both waves
        byte-identical to the cold int8 engine."""
        rng = np.random.default_rng(6)
        shared = list(map(int, rng.integers(1, CFG.vocab_size, 44)))
        prompts = [shared + list(map(int,
                                     rng.integers(1, CFG.vocab_size, 6)))
                   for _ in range(3)]
        base = _engine(params, prefill_chunk=12, page_size=8,
                       kv_dtype="int8")
        ref = _drive(base, [base.submit(p, max_tokens=8)
                            for p in prompts])
        eng = _engine(params, prefill_chunk=12, page_size=8,
                      kv_dtype="int8", prefix_cache=True)
        wave1 = _drive(eng, [eng.submit(p, max_tokens=8)
                             for p in prompts])
        wave2 = _drive(eng, [eng.submit(p, max_tokens=8)
                             for p in prompts])
        assert wave1 == ref and wave2 == ref
        m = eng.metrics()
        assert m["prefix_hits"] > 0 and m["cow_copies"] > 0
        _closure(eng)

    def test_adoption_int8_byte_identical(self, params):
        """Donor → adopter, both int8: the frozen per-page scales ride
        the transfer, so the adopted stream is byte-identical to an
        uninterrupted int8 engine."""
        prompt = _prompt(10, 50)
        cold = _engine(params, kv_dtype="int8", max_len=256)
        exp = _drive(cold, [cold.submit(prompt, max_tokens=24)])[0]
        store = LocalKVStore(budget=64)
        _donor, cont = self._export_mid_decode(params, prompt, store,
                                               kv_dtype="int8")
        adopter, out = self._resume(params, cont, store, kv_dtype="int8")
        assert out == exp
        m = adopter.metrics()
        assert m["kv_adoptions"] == 1 and m["kv_adopt_failures"] == 0

    def test_cross_dtype_adoption_blocked(self, params):
        """int8 donor, bf16 adopter: the engine fingerprint carries the
        kv dtype, so the adopter resolves nothing and re-prefills —
        byte-identical to its own cold stream, never a silent
        mixed-dtype page bind."""
        prompt = _prompt(11, 50)
        cold = _engine(params, max_len=256)
        exp = _drive(cold, [cold.submit(prompt, max_tokens=24)])[0]
        store = LocalKVStore(budget=64)
        _donor, cont = self._export_mid_decode(params, prompt, store,
                                               kv_dtype="int8")
        adopter, out = self._resume(params, cont, store)
        assert out == exp
        assert adopter.metrics()["kv_adoptions"] == 0

    def test_donation_chaos_raise_closure(self, params):
        """serve.kv.donate raise on the int8 pool: donation skipped,
        stream completes, no in-flight-donated ref leaks."""
        store = LocalKVStore(budget=64)
        chaos.install([{"site": "serve.kv.donate", "action": "raise",
                        "count": -1}])
        try:
            donor, _cont = self._export_mid_decode(
                params, _prompt(12, 50), store, kv_dtype="int8")
        finally:
            chaos.uninstall()
        acc = _closure(donor)
        assert acc["exporting"] == 0
        assert store.stats()["entries"] == 0

    def test_adopt_chaos_drop_falls_back(self, params):
        """serve.kv.adopt drop on every fetch: the transfer rung fails,
        re-prefill engages, the int8 stream is still byte-identical to
        cold, and the quantized pool closes."""
        prompt = _prompt(13, 50)
        cold = _engine(params, kv_dtype="int8", max_len=256)
        exp = _drive(cold, [cold.submit(prompt, max_tokens=24)])[0]
        store = LocalKVStore(budget=64)
        _donor, cont = self._export_mid_decode(params, prompt, store,
                                               kv_dtype="int8")
        chaos.install([{"site": "serve.kv.adopt", "action": "drop",
                        "count": -1}])
        try:
            adopter, out = self._resume(params, cont, store,
                                        kv_dtype="int8")
        finally:
            chaos.uninstall()
        assert out == exp
        m = adopter.metrics()
        assert m["kv_adoptions"] == 0 and m["kv_adopt_failures"] >= 1


@pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="tensor-parallel tests need >= 2 (virtual) devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=N)")
class TestQuantTP:
    """tp reshard with scale vectors: head-sharded planes carry their
    per-channel scales on the SAME axis split, replicated pool scale
    planes see a pmax across shards at first write."""

    def test_tp2_int8_byte_identical(self, params):
        prompts = [_prompt(s, n) for s, n in ((1, 5), (2, 23), (3, 41))]
        base = _engine(params, weight_dtype="int8", kv_dtype="int8")
        ref = _drive(base, [base.submit(p, max_tokens=16)
                            for p in prompts])
        eng = _engine(params, weight_dtype="int8", kv_dtype="int8", tp=2)
        out = _drive(eng, [eng.submit(p, max_tokens=16) for p in prompts])
        assert out == ref
        m = eng.metrics()
        assert m["llm_tp"] == 2 and m["llm_weight_dtype"] == "int8"
        assert m["kv_pages_free"] == m["kv_pages_total"]


class TestKnobs:
    """Constructor + global-config validation (the llm_tp strictness
    split: explicit args raise, env knobs soft-off)."""

    def test_bad_value_raises(self, params):
        with pytest.raises(ValueError, match="weight_dtype"):
            _engine(params, weight_dtype="fp8")
        with pytest.raises(ValueError, match="kv_dtype"):
            _engine(params, kv_dtype="int4")

    def test_global_knob_applies(self, params, monkeypatch):
        """The fleet-wide knob pins the env→Config plumb by actually
        quantizing: int8 planes + scale pool planes."""
        monkeypatch.setenv("RAY_TPU_LLM_WEIGHT_DTYPE", "int8")
        monkeypatch.setenv("RAY_TPU_LLM_KV_DTYPE", "int8")
        eng = _engine(params)
        assert eng.weight_dtype == "int8" and eng.kv_dtype == "int8"
        leaves = dict(_leaves(eng.params))
        assert leaves["wq"].dtype == jnp.int8
        assert "wq_scale" in leaves
        assert "k_scale" in eng.cache and "v_scale" in eng.cache
        out = _drive(eng, [eng.submit(_prompt(1, 20), max_tokens=8)])
        assert len(out[0]) == 8
        _closure(eng)
