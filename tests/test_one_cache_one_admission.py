"""One cache, one way in (PR 64): `LLMEngine` serves from the paged pool
alone and admits a prompt chunk by chunk alone.

What is held here: the dense cache and one-shot admission are refused by
name for every family, with one message each; the engine built with no
option at all is the paged, chunked one and serves to the plain reference
(tests/plain_reference.py); the fleet-wide `llm_prefill_chunk` beside a
cache it does not fit takes a chunk that does, where the explicit argument
raises; and no option, knob or program of the two removed paths is left.
"""

from __future__ import annotations

import dataclasses
import importlib
import inspect

import jax
import jax.numpy as jnp
import pytest

import plain_reference
from ray_tpu.models import (gpt, jamba, kimi_k2, laguna, mimo_v2,
                            nemotron_h, olmo_hybrid, qwen3_next, serving,
                            zaya)
from ray_tpu.serve.llm import LLMDeployment, LLMEngine
from ray_tpu.serve.llm_options import _KNOBS, EngineOptions

CFG = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32)

FAMILIES = {
    "gpt": lambda: CFG,
    "zaya": zaya.ZayaConfig.tiny,
    "laguna": laguna.LagunaConfig.tiny,
    "qwen3_next": qwen3_next.Qwen3NextConfig.tiny,
    "mimo_v2": mimo_v2.MiMoV2Config.tiny,
    "jamba": jamba.JambaConfig.tiny,
    "kimi_k2": kimi_k2.KimiK2Config.tiny,
    "olmo_hybrid": olmo_hybrid.OlmoHybridConfig.tiny,
    "nemotron_h": nemotron_h.NemotronHConfig.tiny,
}


@pytest.fixture(scope="module")
def params():
    return plain_reference.lively(gpt.init_params(CFG, jax.random.key(42)))


def _drive(eng, reqs, ticks=500):
    for _ in range(ticks):
        if all(r.done.is_set() for r in reqs):
            break
        eng.step()
    assert all(r.done.is_set() and r.error is None for r in reqs), [
        r.error for r in reqs]


# ------------------------------------------------ what went is refused by name

def test_every_family_is_listed():
    assert set(FAMILIES) == set(serving._FAMILIES)


@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("option,value,says", [
    ("kv_mode", "dense", "the dense KV cache was removed"),
    ("prefill_chunk", 0, "one-shot admission .* was removed"),
])
def test_the_removed_paths_are_refused_by_one_message(family, option, value,
                                                      says):
    """For every family the same sentence, at construction, before a
    weight is placed (the parameters handed in are never looked at)."""
    cfg = FAMILIES[family]()
    assert serving.family_of(cfg).name == family
    with pytest.raises(ValueError, match=says):
        LLMEngine(cfg, {}, n_slots=2, max_len=128, page_size=16,
                  **{option: value})


def test_kv_mode_paged_is_still_accepted(params):
    """benchmarks/harness/serve_cell.py passes it; it is no attribute."""
    eng = LLMEngine(CFG, params, max_len=64, kv_mode="paged")
    assert not hasattr(eng, "kv_mode") and eng.pool is not None


@pytest.mark.parametrize("chunk", [-1, -128])
def test_a_negative_chunk_is_refused_like_zero(params, chunk):
    with pytest.raises(ValueError, match="prefill_chunk must be positive"):
        LLMEngine(CFG, params, max_len=64, prefill_chunk=chunk)


# ------------------------------------------------------ the engine by default

def test_the_engine_with_no_option_is_paged_and_chunked(params):
    """`LLMEngine(cfg, params, max_len=64)`: a pool of half the slots'
    worst case, a chunk that fits the cache, and tokens that are the
    plain forward's greedy continuation."""
    eng = LLMEngine(CFG, params, max_len=64)
    assert eng.pool is not None and eng.n_pages == eng.pool.n_free > 0
    assert 0 < eng.prefill_chunk <= 64 and eng.chunk_rows >= 1
    prompts = [[5, 9, 2], list(range(1, 41)), [17, 3]]
    reqs = [eng.submit(p, max_tokens=12) for p in prompts]
    _drive(eng, reqs)
    plain_reference.assert_gpt_greedy(CFG, params, prompts,
                                      [r.out_ids for r in reqs], n=12)
    assert all(len(set(r.out_ids)) > 3 for r in reqs)
    m = eng.metrics()
    assert m["prefill_chunks"] >= 3 and m["prefill_chunk"] == 64
    assert m["kv_pages_free"] == m["kv_pages_total"]
    assert m["llm_attn_impl"] in ("gather", "kernel")


def test_the_default_deployment_has_a_pool():
    dep = LLMDeployment()
    try:
        eng = dep.engine
        assert eng.pool is not None and eng.prefill_chunk == 128
        out = dep.generate([5, 9, 2], max_tokens=4)
        assert len(out["output_ids"]) == 4
        assert dep.load_snapshot()["pool_pages_total"] == eng.n_pages
    finally:
        dep.engine.stop()


# ------------------------------------------------------------ the knob's rule

@pytest.mark.parametrize("knob,max_len,page_size,chunk", [
    ("0", 64, 16, 64),          # the removed value: a chunk that fits
    ("0", 100, 16, 96),         # ... the largest whole number of pages
    ("0", 48, 64, 48),          # ... the cache itself under one page
    ("128", 64, 64, 64),        # the default beside a short cache
    ("128", 100, 8, 96),
    ("512", 200, 64, 192),      # a knob above the cache
    ("32", 64, 16, 32),         # a knob that fits is obeyed
])
def test_the_chunk_knob_beside_a_cache_it_does_not_fit(
        params, monkeypatch, knob, max_len, page_size, chunk):
    monkeypatch.setenv("RAY_TPU_LLM_PREFILL_CHUNK", knob)
    eng = LLMEngine(CFG, params, n_slots=2, max_len=max_len,
                    page_size=page_size, n_pages=8)
    assert eng.prefill_chunk == chunk
    assert eng.metrics()["prefill_chunk"] == chunk


def test_the_explicit_chunk_is_strict_where_the_knob_is_not(params,
                                                            monkeypatch):
    monkeypatch.setenv("RAY_TPU_LLM_PREFILL_CHUNK", "0")
    with pytest.raises(ValueError, match="exceeds the KV cache"):
        LLMEngine(CFG, params, max_len=64, prefill_chunk=128)
    with pytest.raises(ValueError, match="must be positive"):
        LLMEngine(CFG, params, max_len=64, prefill_chunk=0)
    eng = LLMEngine(CFG, params, max_len=64, page_size=16)
    r = eng.submit(list(range(1, 30)), max_tokens=6)
    _drive(eng, [r])
    plain_reference.assert_gpt_greedy(CFG, params, [r.prompt_ids],
                                      [r.out_ids], n=6)


# ------------------------------------------------- a draw on every family's row

def test_a_sampled_first_token_on_a_family_without_gpt_programs():
    """A prompt's first token at temperature > 0 is drawn on the host by
    `sample_token`, which the engine binds for every family (it used to
    come with the gpt's program table alone)."""
    cfg = zaya.ZayaConfig.tiny(dtype=jnp.float32)
    eng = LLMEngine(cfg, zaya.init_params(cfg, jax.random.key(0)),
                    n_slots=2, max_len=64, page_size=16, n_pages=8,
                    prefill_chunk=16, prefill_token_budget=32,
                    attn_impl="gather", decode_block=1)
    reqs = [eng.submit([5, 9, 2, 7], max_tokens=5, temperature=0.8),
            eng.submit([3, 1], max_tokens=5)]
    _drive(eng, reqs)
    assert all(len(r.out_ids) == 5 for r in reqs)
    assert all(0 <= t < cfg.vocab_size for r in reqs for t in r.out_ids)


# ----------------------------------------------------- nothing of them is left

def test_no_option_knob_or_program_of_the_removed_paths_is_left():
    from ray_tpu.core.config import runtime_config
    from ray_tpu.models import paged_kv

    ctor = inspect.signature(LLMEngine.__init__).parameters
    assert "prefill_buckets" not in ctor and "kv_mode" in ctor
    fields = {f.name for f in dataclasses.fields(EngineOptions)}
    assert "kv_mode" not in fields and "prefill_chunk" in fields
    assert "kv_mode" not in dict(_KNOBS)
    rc = runtime_config()
    assert not hasattr(rc, "llm_kv_mode") and rc.llm_prefill_chunk == 128
    with pytest.raises(ImportError):
        importlib.import_module("ray_tpu.models.decode")
    assert not hasattr(paged_kv, "prefill_batch_paged")
    for name in ("_bucket", "_prefill_group", "_PREFILL_LADDER", "buckets"):
        assert not hasattr(LLMEngine, name), name
    assert len(serving._REFUSALS) == 7
    assert not {"kv_mode", "prefill_chunk"} & {
        option for option, *_rest in serving._REFUSALS}


def test_admit_hands_back_nothing(params):
    """`_admit` binds requests to slots; there are no groups to dispatch."""
    eng = LLMEngine(CFG, params, max_len=64)
    r = eng.submit([5, 9, 2], max_tokens=2)
    assert eng._admit() is None
    assert eng.slot_req.count(r) == 1 and eng._prefilling
    _drive(eng, [r])
