"""Tensor-parallel multi-chip decode (models/partition.py + the
paged-program shard_map twins in models/paged_kv.py + the llm_tp knob).

Exactness first, the house pattern: a tp=2 engine over a forced
host-device mesh must emit token streams byte-identical to tp=1 —
across both attention implementations, chunked prefill, warm-prefix COW
admission, speculative decoding, preempt-by-recompute, and a
drain→resume splice onto a single-shard engine — because the sharded
programs run the SAME bodies per head-shard with only the per-layer
attention-out/MLP-down psums crossing shards (fp32-reassociation-level
logit agreement; argmax/sampling consume replicated logits). Then the
rule machinery itself (regex→PartitionSpec: scalar skip,
unmatched-leaf typed error, precedence), knob validation (non-divisor
tp, tp > devices, global-knob soft-off),
the sharding-topology observability fields, and the recompile-storm
alarm attributing shard-induced recompiles to the owning program.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

import plain_reference
from ray_tpu.models import gpt, paged_kv, partition
from ray_tpu.serve.llm import LLMEngine

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="tensor-parallel tests need >= 2 (virtual) devices "
           "(XLA_FLAGS=--xla_force_host_platform_device_count=N)")

CFG = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32)   # 8 heads
DRAFT_CFG = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32,
                               n_layers=1, d_model=32, n_heads=4, d_ff=64)


@pytest.fixture(scope="module")
def params():
    return gpt.init_params(CFG, jax.random.key(42))


@pytest.fixture(scope="module")
def draft_params():
    return gpt.init_params(DRAFT_CFG, jax.random.key(7))


def _drive(eng, reqs, max_steps=2000):
    for _ in range(max_steps):
        if all(r.done.is_set() for r in reqs):
            break
        eng.step()
    assert all(r.done.is_set() for r in reqs)
    assert all(r.error is None for r in reqs), [r.error for r in reqs]
    return [r.out_ids for r in reqs]


def _engine(params, **kw):
    kw.setdefault("n_slots", 4)
    kw.setdefault("max_len", 128)
    kw.setdefault("page_size", 16)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("prefill_token_budget", 32)
    return LLMEngine(CFG, params, **kw)


def _ragged_prompts(rng, lengths):
    return [list(map(int, rng.integers(1, CFG.vocab_size, n)))
            for n in lengths]


class TestMatchPartitionRules:
    """The regex→PartitionSpec machinery (SNIPPETS.md [2][3] pattern)."""

    def test_gpt_rules_cover_every_param(self, params):
        specs = partition.match_partition_rules(
            gpt.partition_rules(), params)
        assert set(specs) == set(params)
        # The tp axis lands exactly on the head/hidden dims.
        assert specs["wq"] == PartitionSpec(None, None, "tp", None)
        assert specs["wo"] == PartitionSpec(None, "tp", None, None)
        assert specs["w_down"] == PartitionSpec(None, "tp", None)
        for name in ("wte", "ln1_scale", "ln_f_bias", "b_down"):
            assert specs[name] == PartitionSpec(), name

    def test_scalar_leaves_skip_the_table(self):
        """Scalars resolve to PartitionSpec() without consulting any
        rule — optimizer step counts etc. need no table entries."""
        tree = {"step": jnp.zeros(()), "one": jnp.ones((1,)),
                "w": jnp.zeros((4, 4))}
        specs = partition.match_partition_rules(
            ((r"^w$", PartitionSpec("tp", None)),), tree)
        assert specs["step"] == PartitionSpec()
        assert specs["one"] == PartitionSpec()
        assert specs["w"] == PartitionSpec("tp", None)

    def test_unmatched_leaf_is_typed_error(self):
        tree = {"mystery": jnp.zeros((4, 4))}
        with pytest.raises(partition.PartitionRuleError,
                           match="mystery"):
            partition.match_partition_rules(
                ((r"^w$", PartitionSpec()),), tree)

    def test_rule_precedence_is_list_order(self):
        tree = {"wq": jnp.zeros((4, 4))}
        first = ((r"^wq$", PartitionSpec("tp", None)),
                 (r"^w", PartitionSpec(None, "tp")))
        assert partition.match_partition_rules(first, tree)["wq"] == \
            PartitionSpec("tp", None)
        flipped = (first[1], first[0])
        assert partition.match_partition_rules(flipped, tree)["wq"] == \
            PartitionSpec(None, "tp")

    def test_nested_paths_join_with_slash(self):
        tree = {"opt": {"mu": {"wq": jnp.zeros((4, 4))}}}
        assert partition.tree_path_names(tree) == ["opt/mu/wq"]
        specs = partition.match_partition_rules(
            ((r"mu/wq", PartitionSpec("tp", None)),), tree)
        assert specs["opt"]["mu"]["wq"] == PartitionSpec("tp", None)

    def test_kv_pool_rules_shard_the_head_axis(self):
        pool = paged_kv.init_paged_kv(CFG, 8, 4)
        specs = partition.match_partition_rules(
            paged_kv.KV_POOL_PARTITION_RULES, pool)
        # [L, P+1, ps, H*K]: the minor axis, heads major within it.
        assert pool["k"].ndim == 4
        want = PartitionSpec(None, None, None, "tp")
        assert specs == {"k": want, "v": want}
        assert partition.KV_HEAD_AXIS == 3

    @pytest.mark.parametrize("impl", ["kernel", "gather"])
    def test_pool_shards_hold_whole_heads(self, impl):
        """Attention over each tp=2 shard of the flat pool (its half of
        the lanes, its half of the heads) equals the unsharded result:
        a shard's lanes are whole, contiguous heads."""
        from ray_tpu.ops.paged_attention import (paged_attention,
                                                 reference_paged_attention)

        attend = paged_attention if impl == "kernel" \
            else reference_paged_attention
        rng = np.random.default_rng(3)
        L, P, ps, H, K, B = 2, 5, 8, 4, 16, 2
        k_pool = jnp.asarray(rng.normal(size=(L, P, ps, H * K)), jnp.float32)
        v_pool = jnp.asarray(rng.normal(size=(L, P, ps, H * K)), jnp.float32)
        q = jnp.asarray(rng.normal(size=(B, H, K)), jnp.float32)
        tables = jnp.asarray([[1, 2], [3, 0]], jnp.int32)
        lengths = jnp.asarray([13, 5], jnp.int32)
        layer = jnp.int32(1)
        full = attend(q, k_pool, v_pool, layer, tables, lengths)
        payload = {"k": np.asarray(k_pool), "v": np.asarray(v_pool)}
        shards = partition.split_head_planes(payload, 2)
        parts = [attend(q[:, s * H // 2:(s + 1) * H // 2],
                        jnp.asarray(shards[f"k@{s}"]),
                        jnp.asarray(shards[f"v@{s}"]),
                        layer, tables, lengths) for s in range(2)]
        np.testing.assert_allclose(
            np.concatenate([np.asarray(x) for x in parts], axis=1),
            np.asarray(full), atol=2e-6)
        back = partition.concat_head_planes(shards, 2)
        assert np.array_equal(back["k"], payload["k"])

    def test_sharding_module_folded(self):
        """ONE spec-derivation implementation: parallel/sharding.py now
        re-exports models/partition.py's helpers."""
        from ray_tpu.parallel import sharding

        assert sharding.logical_to_spec is partition.logical_to_spec
        assert sharding.tree_to_shardings is partition.tree_to_shardings
        assert sharding.shard_tree is partition.shard_tree

    def test_make_tp_mesh_bounds(self):
        mesh = partition.make_tp_mesh(2)
        assert mesh.shape == {"tp": 2}
        with pytest.raises(ValueError, match="exceeds"):
            partition.make_tp_mesh(len(jax.devices()) + 1)
        with pytest.raises(ValueError, match=">= 1"):
            partition.make_tp_mesh(0)


class TestKnobValidation:
    """Typed construction-time errors, the llm_prefill_chunk pattern."""

    def test_non_divisor_tp_rejected(self, params):
        with pytest.raises(ValueError, match="divide"):
            _engine(params, tp=3)          # 8 heads % 3 != 0

    def test_tp_beyond_devices_rejected(self, params):
        with pytest.raises(ValueError, match="device"):
            _engine(params, tp=4 * len(jax.devices()))

    def test_tp_floor(self, params):
        with pytest.raises(ValueError, match="llm_tp"):
            _engine(params, tp=0)

    def test_draft_non_divisor_rejected(self, params, draft_params):
        bad = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32,
                                 n_layers=1, d_model=32, n_heads=1,
                                 d_ff=64)
        with pytest.raises(ValueError, match="DRAFT"):
            _engine(params, tp=2, spec_draft=bad,
                    spec_draft_params=gpt.init_params(
                        bad, jax.random.key(0)))

    def test_global_knob_soft_off(self, params, monkeypatch):
        """The GLOBAL llm_tp knob on an engine it fits pins the
        env→Config plumb by actually building the mesh, the default
        engine (no argument at all) included; where it does not fit it
        soft-disables to 1 (below; explicit args are strict, above)."""
        monkeypatch.setenv("RAY_TPU_LLM_TP", "2")
        eng = LLMEngine(CFG, params, max_len=64)
        assert eng.tp == 2 and eng.mesh is not None
        eng = _engine(params)
        assert eng.tp == 2
        assert eng.mesh is not None and eng.mesh.shape == {"tp": 2}

    def test_global_knob_misfit_soft_off(self, params, monkeypatch):
        """A fleet-wide RAY_TPU_LLM_TP export must not crash replica
        boot on hosts/models it doesn't fit: too few devices or a
        non-divisor tp from the GLOBAL knob serve unsharded (tp=1)
        instead of raising — only explicit constructor args are strict.
        """
        # Non-divisor: 8 heads, knob 3.
        monkeypatch.setenv("RAY_TPU_LLM_TP", "3")
        eng = _engine(params)
        assert eng.tp == 1 and eng.mesh is None
        # Too few devices: knob far past the visible count.
        monkeypatch.setenv("RAY_TPU_LLM_TP",
                           str(8 * len(jax.devices())))
        eng = _engine(params)
        assert eng.tp == 1 and eng.mesh is None


class TestExactness:
    """tp=2 == tp=1, token-for-token (the acceptance criterion)."""

    @pytest.mark.parametrize("attn_impl", ["gather", "kernel"])
    def test_tp2_byte_identical(self, params, attn_impl):
        prompts = _ragged_prompts(np.random.default_rng(1),
                                  (5, 23, 41, 11))
        base = _engine(params, attn_impl=attn_impl)
        ref = _drive(base, [base.submit(p, max_tokens=24)
                            for p in prompts])
        eng = _engine(params, attn_impl=attn_impl, tp=2)
        out = _drive(eng, [eng.submit(p, max_tokens=24) for p in prompts])
        assert out == ref
        m = eng.metrics()
        assert m["llm_tp"] == 2
        assert m["kv_pages_free"] == m["kv_pages_total"]

    def test_tp2_warm_prefix_cow(self, params):
        """Warm-prefix COW admission at tp=2: the shared pages bind
        read-only per shard, the divergence COW runs through the
        sharded copy_pages, and both waves stay byte-exact."""
        rng = np.random.default_rng(6)
        shared = list(map(int, rng.integers(1, CFG.vocab_size, 44)))
        prompts = [shared + list(map(int,
                                     rng.integers(1, CFG.vocab_size, 6)))
                   for _ in range(3)]
        base = _engine(params, prefill_chunk=12, page_size=8)
        ref = _drive(base, [base.submit(p, max_tokens=8)
                            for p in prompts])
        eng = _engine(params, prefill_chunk=12, page_size=8,
                      prefix_cache=True, tp=2)
        wave1 = _drive(eng, [eng.submit(p, max_tokens=8)
                             for p in prompts])
        wave2 = _drive(eng, [eng.submit(p, max_tokens=8)
                             for p in prompts])
        assert wave1 == ref and wave2 == ref
        m = eng.metrics()
        assert m["prefix_hits"] > 0 and m["cow_copies"] > 0
        acct = eng.page_accounting()
        assert acct["closure"] and acct["refs_consistent"]

    @pytest.mark.parametrize("k", [2, 4])
    def test_tp2_spec_decode(self, params, draft_params, k):
        """Speculative decoding at tp=2 (draft propose loop, batched
        verify, rollback — all per-shard) is still byte-identical to
        the plain tp=1 engine."""
        prompts = _ragged_prompts(np.random.default_rng(2), (9, 30, 17))
        base = _engine(params)
        ref = _drive(base, [base.submit(p, max_tokens=16)
                            for p in prompts])
        eng = _engine(params, tp=2, spec_draft=DRAFT_CFG,
                      spec_draft_params=draft_params, spec_k=k)
        out = _drive(eng, [eng.submit(p, max_tokens=16) for p in prompts])
        assert out == ref
        m = eng.metrics()
        assert m["spec_ticks"] > 0 and m["spec_proposed"] > 0
        assert m["kv_pages_free"] == m["kv_pages_total"]

    def test_tp2_spec_temperature_smoke(self, params, draft_params):
        """temperature>0 speculative decoding at tp=2 exercises the
        need_probs=True propose variant (draft q distributions come
        back replicated through the shard_map): runs to completion
        with sane acceptance bookkeeping and closed page accounting."""
        prompts = _ragged_prompts(np.random.default_rng(3), (7, 19, 12))
        eng = _engine(params, tp=2, spec_draft=DRAFT_CFG,
                      spec_draft_params=draft_params)
        out = _drive(eng, [eng.submit(p, max_tokens=12, temperature=0.9)
                           for p in prompts])
        assert all(len(o) == 12 for o in out)
        m = eng.metrics()
        assert 0 <= m["spec_accepted"] <= m["spec_proposed"]
        acct = eng.page_accounting()
        assert acct["closure"] and acct["refs_consistent"]

    def test_tp2_exact_under_preemption(self, params):
        """Pool sized so slots run dry mid-generation: preempt-by-
        recompute on the sharded engine still reproduces the plain
        forward's streams (page ids are shard-invariant, so the
        host-side allocator needs zero tp awareness)."""
        prompts = [[5, 9, 2], [17, 3], [2, 4, 6], [8, 1, 0]]
        eng = _engine(params, tp=2, max_len=64, page_size=4, n_pages=7,
                      prefill_chunk=4, prefill_token_budget=8)
        out = _drive(eng, [eng.submit(p, max_tokens=10) for p in prompts])
        plain_reference.assert_gpt_greedy(CFG, params, prompts, out, n=10)
        m = eng.metrics()
        assert m["preemptions"] > 0
        assert m["kv_pages_free"] == m["kv_pages_total"]

    def test_tp2_drain_resumes_on_tp1(self, params):
        """Drain a tp=2 engine mid-flight and resume the continuations
        on a SINGLE-shard engine: the splice is byte-identical to an
        uninterrupted run — continuations carry token ids only, so the
        sharding topology of source and destination are independent
        (failover between tp=1 and tp=2 replica generations is free)."""
        prompts = _ragged_prompts(np.random.default_rng(5), (13, 26, 8))
        base = _engine(params)
        full = _drive(base, [base.submit(p, max_tokens=20)
                             for p in prompts])
        eng = _engine(params, tp=2)
        reqs = [eng.submit(p, max_tokens=20) for p in prompts]
        for _ in range(2):   # some tokens out, none finished
            eng.step()
        assert any(r.out_ids for r in reqs)
        assert not any(r.finished_at for r in reqs)
        out = eng.drain(timeout_s=0.0)
        assert out["exported"] == len(
            [r for r in reqs if not r.finished_at])
        conts = {tuple(c["prompt_ids"]): c for c in out["continuations"]}
        resume = _engine(params)           # tp=1 destination
        resumed = []
        for i, p in enumerate(prompts):
            c = conts.get(tuple(p))
            if c is None:                  # finished before the drain
                continue
            gen = c["generated_ids"]
            assert gen == full[i][:len(gen)]
            resumed.append((i, resume.submit(
                c["prompt_ids"], max_tokens=c["max_tokens"],
                temperature=c["temperature"], eos_id=c["eos_id"],
                generated_ids=gen)))
        assert resumed
        _drive(resume, [r for _i, r in resumed])
        for i, r in resumed:
            assert r.out_ids == full[i]
        # Drained-but-alive tp engine closes its page accounting.
        acct = eng.page_accounting()
        assert acct["closure"] and acct["refs_consistent"]


class TestObservability:
    def test_metrics_and_snapshot_carry_topology(self, params):
        eng = _engine(params, tp=2)
        _drive(eng, [eng.submit([3, 1, 4, 1, 5], max_tokens=8)])
        m = eng.metrics()
        assert m["llm_tp"] == 2
        assert m["mesh_shape"] == {"tp": 2}
        assert m["kv_heads_per_shard"] == CFG.n_heads // 2
        pool_bytes = (2 * np.prod(eng.cache["k"].shape)
                      * eng.cache["k"].dtype.itemsize)
        assert m["pool_shard_bytes"] == pool_bytes // 2
        snap = eng.load_snapshot()
        assert snap["llm_tp"] == 2
        assert snap["mesh_shape"] == {"tp": 2}
        assert snap["kv_heads_per_shard"] == CFG.n_heads // 2
        assert snap["pool_shard_bytes"] == pool_bytes // 2
        assert 0 <= snap["pool_shard_bytes_used"] <= pool_bytes // 2

    def test_tp1_engine_unchanged_surface(self, params):
        """tp=1 (the default) exports llm_tp=1 and NO mesh fields —
        the single-chip snapshot surface is untouched."""
        eng = _engine(params)
        assert eng.tp == 1 and eng.mesh is None
        m = eng.metrics()
        assert m["llm_tp"] == 1 and "mesh_shape" not in m
        snap = eng.load_snapshot()
        assert "llm_tp" not in snap and "mesh_shape" not in snap


class TestRecompileStorm:
    def test_shard_induced_storm_attributes_to_program(self, params):
        """A tp=2 decode walking the page-table width ladder re-lowers
        the SHARDED decode program per width; the compile watch must
        attribute those compiles — and the storm alarm — to the owning
        program label, exactly as on a single chip."""
        from ray_tpu import compile_watch

        compile_watch.install(storm_threshold=3, storm_window_s=600.0)
        try:
            # page_size=2 → width buckets 1/2/4/8/16/32 over 58 tokens;
            # n_slots=3 keeps these program shapes unique to this test.
            eng = _engine(params, n_slots=3, max_len=64, page_size=2,
                          n_pages=40, prefill_chunk=4,
                          prefill_token_budget=8, tp=2)
            before = compile_watch.compiles_total("decode_multi_paged")
            _drive(eng, [eng.submit([5, 9, 2], max_tokens=58)])
            delta = (compile_watch.compiles_total("decode_multi_paged")
                     - before)
            assert delta >= 3, f"expected >=3 sharded recompiles: {delta}"
            storms = [s for s in compile_watch.storm_log()
                      if s["fn"] == "decode_multi_paged"]
            assert storms and storms[0]["count"] >= 3
        finally:
            # Re-arm at a quiet threshold so later modules don't inherit
            # the hair trigger.
            compile_watch.install(storm_threshold=1000)
