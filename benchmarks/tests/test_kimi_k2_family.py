"""The `kimi_k2` family through the harness (a NEW test file: the cell came
as files and entries, so its tests do too).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_kimi_k2_family.py -q

The rehearsal goes through `run.main(..., rehearsal=True)` on the CPU: no
device metric is printed or asserted. The readers of the cell's per-layer
metrics are held to hand arithmetic over a synthetic context, and to
returning nothing (not raising) over a program that lacks what they read.
"""

from __future__ import annotations

import copy
import json
import math

import pytest

import util
from harness import configs, families, readers, scope_times

CELL = "kimi-k2.6.longthink"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("latent_attn_roofline", "latent_chunk_attn_roofline",
       "latent_absorb_ms", "latent_cache_byte_share")


@pytest.fixture(scope="module")
def real():
    bench = configs.load_benchmark(util.REPO)
    config = configs.load_config(util.REPO, bench, "kimi-k2.6")
    family, reference = families.load(util.REPO, bench, config)
    return bench, config, family, reference


def test_the_configuration_is_the_public_config_with_three_cuts(real):
    """Every key of the public config.json under its own name; the three
    reduced keys state the share, `published` the source's values; the
    layers kept are the dense layer and four expert layers."""
    _bench, config, family, _ref = real
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 61,
                                   "n_routed_experts": 384,
                                   "vocab_size": 163840}
    assert config["deployment_share"] == {
        "chips_in_group": 32, "first_expert": 0, "experts_held": 12,
        "vocab_rows_held": 20480}
    cfg = family.program_config(config, max_seq=4608)
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim) == (7168, 5, 64, 1536, 512, 128, 64, 128)
    assert (cfg.d_ff_dense, cfg.first_k_dense, cfg.n_experts,
            cfg.n_experts_routed, cfg.first_expert, cfg.top_k, cfg.d_ff,
            cfg.d_ff_shared, cfg.routed_scale, cfg.vocab_size) == (
        18432, 1, 12, 384, 0, 8, 2048, 2048, 2.827, 20480)
    assert (cfg.rope_theta, cfg.yarn_factor, cfg.yarn_orig, cfg.beta_fast,
            cfg.beta_slow, cfg.mscale, cfg.mscale_all_dim, cfg.norm_eps,
            cfg.max_seq) == (50000, 64.0, 4096, 32.0, 1.0, 1.0, 1.0, 1e-5,
                             4608)
    # A row of 576 values, stored in 640 lanes.
    assert cfg.kv_lora_rank + cfg.qk_rope_head_dim == 576
    assert cfg.head_dim == 640
    from ray_tpu.models import kimi_k2
    assert kimi_k2.softmax_scale(cfg) == pytest.approx(0.14468, rel=1e-4)
    rc = family.reference_config(config)
    hash(rc)
    assert (rc.n_heads, rc.nope_dim, rc.rope_dim, rc.v_head_dim, rc.top_k,
            rc.routed_scale, rc.first_k_dense) == (64, 128, 64, 128, 8,
                                                   2.827, 1)


def test_every_number_of_the_catalog_entry_is_in_the_file(real):
    """The driver's rule: each number of the catalog entry's `config`
    under the same key, but for the keys `reduced` names; nested groups
    copied whole."""
    _bench, config, _family, _ref = real
    try:
        with open(CATALOG) as f:
            entry = next(e for e in map(json.loads, f)
                         if e["name"] == "Kimi-K2.6")
    except OSError:
        pytest.skip("no catalog beside the model-configs guide here")
    assert config["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key


def test_the_family_counts_the_cells_parameters_and_bytes(real):
    """ISSUE 56's arithmetic, from the file's own sizes (bf16)."""
    _bench, config, family, _ref = real
    per = family.layer_params(config)
    # 7,168 x 1,536 + 1,536 x 12,288 + 7,168 x 576 + 512 x 16,384
    # + 8,192 x 7,168 + the two inner norms = 101.12 M
    assert per["attention"] == (7168 * 1536 + 1536 + 1536 * 12288
                                + 7168 * 576 + 512 + 512 * 16384
                                + 8192 * 7168) == 101_124_096
    assert per["dense_mlp"] == 3 * 7168 * 18432 == 396_361_728
    assert per["router"] == 7168 * 384 + 384
    assert per["shared"] == per["expert"] == 3 * 7168 * 2048 == 44_040_192
    assert (per["n_dense"], per["n_sparse"], per["row"],
            per["latent"]) == (1, 4, 576, 512)
    # dense layer 497.5 M; an expert layer 147.93 M + 12 x 44.04 M =
    # 676.41 M; vocabulary 293.6 M: 3,496.8 M = 6.99 GB
    layer = per["attention"] + per["norms"]
    assert round((layer + per["dense_mlp"]) / 1e6, 1) == 497.5
    outside = layer + per["router"] + per["shared"]
    assert round(outside / 1e6, 2) == 147.93
    assert round((outside + 12 * per["expert"]) / 1e6, 2) == 676.41
    assert round(family.parameters(config) / 1e6, 1) == 3496.8
    specs = family.model().param_specs(family.program_config(config))
    assert sum(int(math.prod(s["shape"]))
               for s in specs.values()) == family.parameters(config)
    c = family.serve_consts(config)
    assert c["decode_bytes_per_kv_token"] == 5 * 576 * 2 == 5760
    assert c["latent_flops_per_kv_token"] == 5 * 64 * (576 + 512) * 2 \
        == 5 * 139_264
    assert c["decode_bytes_per_window_slot"] == 0.0
    assert c["decode_bytes_per_state_slot"] == 0.0
    assert c["decode_bytes_per_live_expert"] == 4 * 44_040_192 * 2
    assert c["decode_bytes_weights"] == 2 * (
        5 * 101_124_096 + 396_361_728
        + 4 * (per["router"] + 44_040_192) + 7168 * 20480)
    # all held experts streamed: ~6.70 GB of weights a step
    streamed = (c["decode_bytes_weights"]
                + 12 * c["decode_bytes_per_live_expert"])
    assert round(streamed / 1e9, 2) == 6.70
    assert family.train_consts(config, 512)["train_flops_per_token"] > 0
    # The pool: 256 slots x 72 pages of 64 (no request can be preempted),
    # a row of 576 values = 6.80 GB, as stored (640 lanes) 7.55 GB.
    geo = config["serve"]
    assert geo["n_pages"] == geo["n_slots"] * -(-geo["max_len"]
                                                // geo["page_size"])
    rows = 5 * (geo["n_pages"] + 1) * geo["page_size"]
    assert round(rows * 576 * 2 / 1e9, 2) == 6.80
    assert round(rows * 640 * 2 / 1e9, 2) == 7.55


def test_the_family_refuses_what_it_does_not_build(real):
    _bench, config, family, _ref = real
    for key, value in (("scoring_func", "softmax"), ("n_group", 8),
                       ("n_shared_experts", 2), ("moe_layer_freq", 2),
                       ("num_key_value_heads", 8)):
        with pytest.raises(SystemExit, match="kimi_k2 family builds"):
            family.program_config({**config, key: value})


def test_the_traffic_is_the_issues(real):
    """256 quantiles of uniform(1536, 3584) behind fixed prompts of 512,
    the same set for every seed, inside max_len."""
    import numpy as np

    from harness import traffic

    bench, config, _family, _ref = real
    mix = configs.load_traffic(util.REPO, bench, "longthink")
    assert (mix["kind"], mix["clients"], mix["cycle_requests"],
            mix["ramp_s"], mix["trace_s"]) == ("closed_loop", "n_slots",
                                               256, 30, 8)
    assert mix["prompt_len"] == {"dist": "fixed", "value": 512}
    assert mix["output_len"]["dist"] == "uniform"
    assert mix["output_len"]["min"] == 1536
    assert mix["output_len"]["max"] <= 3584
    a = traffic.ClosedLoopSource(mix, 1, 20480)
    b = traffic.ClosedLoopSource(mix, 2**31 + 5, 20480)
    assert sorted(a.o_len) == sorted(b.o_len)
    assert list(a.o_len) != list(b.o_len)
    assert int(np.max(a.p_len + a.o_len)) <= config["serve"]["max_len"]
    cell = configs.find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-k2.6", "longthink", 1)


TINY = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 128,
    "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
    "n_routed_experts": 4, "num_experts_per_tok": 3,
    "moe_intermediate_size": 32, "num_hidden_layers": 3, "vocab_size": 256,
    "published": {"n_routed_experts": 8},
}


def tiny_config(config: dict) -> dict:
    """The real file cut to a size the CPU serves: every width small, the
    pattern kept (a dense layer, two expert layers; 4 heads of 32 + 16
    over a latent row of 128 + 16; 4 of 8 experts held, top-3)."""
    tiny = copy.deepcopy(config)
    tiny.update(TINY, name="kimi-k2-tiny")
    tiny["rope_scaling"] = dict(tiny["rope_scaling"], factor=8,
                                original_max_position_embeddings=32)
    tiny["serve"].update(page_size=16, n_pages=24, max_len=128,
                         prefill_chunk=16, n_slots=3, reference_factor=2.0,
                         deficit_slack=0.01, ref_sample=3)
    return tiny


def test_the_family_serves_through_the_harness_at_tiny_size(real, tmp_path):
    """families/kimi_k2.py, harness/reference/kimi_k2_ref.py and the
    cell's counter readers through run.py on the CPU: the engine's stream
    (absorbed form, latent pool) is held `correct` by `paired_rows`
    (plain form), and the metrics that read the program's counters are
    in the line."""
    bench, config, _family, _ref = real
    root = util.make_root(str(tmp_path))
    counters = [m for m in configs.metrics_for_cell(bench, "per_layer", CELL)
                if m["source"] == "program_counter"]
    cell = util.add_cell(
        root, tiny_config(config), "batch", ["out_tokens_per_s"],
        [{"name": m["name"], "unit": m["unit"], "moves": "out_tokens_per_s"}
         for m in counters])
    got = util.rehearse(root, cell, seed=2**31 + 11, seconds=1.5, trace=1)
    out = got["line"]
    assert out["correct"] is True and out["failed"] == 0
    assert any("reference check over" in ln and ": ok" in ln
               for ln in got["log"])
    value = lambda n: out["metrics"]["cpu_rehearsal." + n]["value"]
    assert 1.0 <= value("experts_touched") <= 4
    assert 0.0 < value("router_bias_moved") < 50.0
    assert value("preemptions") == 0
    assert 0.0 < value("latent_cache_byte_share") < 100.0
    assert 0.0 < value("decode_block_fill") <= 100.0
    # No device plane in a CPU trace: trace-sourced metrics are left out.
    assert not any("roofline" in n or "dev_ms" in n for n in out["metrics"])


def test_the_reference_agrees_with_the_program_at_tiny_size(real):
    """The plain reference (expanded K and V, experts one at a time) and
    the program's full forward, float32: the same logits."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    _bench, config, family, reference = real
    tiny = tiny_config(config)
    cfg = family.program_config(tiny, max_seq=128, dtype=jnp.float32)
    params = family.model().init_params(cfg, jax.random.PRNGKey(3))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (48,), 0, 256)
    with jax.default_matmul_precision("highest"):
        want = reference.logits(params, tokens,
                                family.reference_config(tiny))
        got = family.model().forward(cfg, params, tokens[None])[0]
    assert float(np.max(np.abs(np.asarray(want) - np.asarray(got)))) < 2e-5


def _context(family, config) -> dict:
    """A synthetic context of a traced run: 100 decode steps of 18 ms and
    4 chunk programs of 12 ms in a traced 1.9 s, with known kernel and
    scope times."""
    decode, chunk = "jit__decode_sample_paged(1)", "jit_prefill_chunk_paged(2)"
    call = lambda name, target="tpu_custom_call": (
        f"%{name} = bf16[256,64,512] custom-call(%a, %b), "
        f'custom_call_target="{target}"')
    ops = [
        (decode, call("paged_decode_attn_latent.2"), 0.60),
        (decode, call("ragged-dot-none.7"), 0.55),
        (decode, call("ragged-dot-metadata.1"), 0.02),
        (decode, "%fusion.9 = f32[256,20480] fusion(%x), kind=kOutput", 0.58),
        (chunk, call("paged_prefill_attn_latent.1"), 0.006),
        (chunk, call("ragged-dot-none.9"), 0.030),
        (chunk, "%fusion.3 = bf16[512,7168] fusion(%x), kind=kLoop", 0.012),
    ]
    t = [10.0 + 0.25 * i for i in range(40)]            # the window's samples
    return {
        "engine": {"moe_experts_touched": 11.5, "moe_rows_max": 18.0,
                   "moe_rows_held": 320, "moe_rows_routed": 10000,
                   "moe_rows_bias_moved": 280,
                   "slot_occupancy": 0.995, "kv_pages_free_min": 4608,
                   "compiles_in_window": 0, "preemptions": 0,
                   "tick_host_share": 0.010, "engine_prefill_tok_s": 9000.0,
                   "decode_step_ms_p50": 18.5, "decode_block_fill": 0.96,
                   "decode_live_column_share": 0.61,
                   "prefill_tokens": 143_360, "prefill_dispatches": 280},
        "samples": {"t": t, "decoding_slots": [250] * 32 + [256] * 8,
                    "kv_tokens_decoding": [700_000] * 32 + [740_000] * 8},
        "trace_t0": t[32],                              # the last 8 samples
        "trace": {"ops": ops, "window_s": 1.9, "busy_s": 1.85,
                  "per_chip_busy_s": [1.85],
                  "programs": {decode: {"count": 100, "total_s": 1.8},
                               chunk: {"count": 4, "total_s": 0.048}}},
        "memory": {}, "peaks": {"hbm_bytes_per_s": 819e9,
                                "flops_bf16": 197e12},
        "consts": dict(configs.dims(config), chips=1, window_s=51.0,
                       n_pages=18432, page_size=64,
                       **family.serve_consts(config)),
    }


# What the scope reducer would make of the synthetic trace: seconds by
# scope in the two programs (harness/scope_times.scope_times' table).
_TABLE = {
    "busy_s": 1.85,
    "programs": {
        "jit__decode_sample_paged": {
            "runs": 100, "total_s": 1.8, "by_pass": {}, "unscoped_s": 0.05,
            "by_scope": {"attn.in": 0.14, "attn.absorb": 0.11,
                         "attn.out": 0.07, "attn.kernel": 0.62,
                         "moe.route": 0.06, "moe.experts": 0.55,
                         "mlp": 0.12, "head": 0.06, "sample": 0.01}},
        "jit_prefill_chunk_paged": {
            "runs": 4, "total_s": 0.048, "by_pass": {}, "unscoped_s": 0.001,
            "by_scope": {"attn.in": 0.004, "attn.absorb": 0.002,
                         "attn.kernel": 0.0064, "moe.experts": 0.030}}},
}


def test_every_metric_of_the_cell_reads_a_synthetic_context(real,
                                                            monkeypatch):
    """Held to whatever BENCHMARK.json lists for the cell (a later PR may
    add to it), with hand arithmetic for the entries this PR brought."""
    bench, config, family, _ref = real
    entries = configs.metrics_for_cell(bench, "per_layer", CELL)
    assert entries and all(CELL in m["workloads"] for m in entries)
    assert all(m["moves"] == "out_tokens_per_s" and "." not in m["name"]
               for m in entries)
    assert set(NEW) <= {m["name"] for m in entries}
    ctx = _context(family, config)
    monkeypatch.setattr(scope_times, "for_run", lambda _ctx: _TABLE)
    got = {n: v["value"] for n, v in readers.read_all(
        configs.metrics_dirs(util.REPO, bench), entries, ctx,
        {"out_tokens_per_s": 14000.0}).items()}
    assert set(got) == {m["name"] for m in entries}
    c, hbm, mxu = ctx["consts"], 819e9, 197e12
    # One cached token: 5,760 B against 5 x 139,264 operations. On this
    # chip the bytes are the larger limit (7.03 ns against 3.53 ns).
    token_s = max(5760 / hbm, 5 * 139_264 / mxu)
    assert token_s == 5760 / hbm
    t = 143_360 / 280                                   # 512 tokens
    cache = 5760 * (700_000 * 32 + 740_000 * 8) / 40
    want = {
        # samples of the TRACED interval: 740,000 tokens; 6.2 ms a step
        "latent_attn_roofline": 740_000 * token_s / 0.0062 * 100,
        # a 512-token prompt a dispatch: 131,328 pairs, 1.6 ms a program
        "latent_chunk_attn_roofline": max(
            t * 5760 / hbm, t * (t + 1) / 2 * 5 * 139_264 / mxu)
        / 0.0016 * 100,
        "latent_absorb_ms": 1.1,
        "latent_cache_byte_share": cache / (
            cache + c["decode_bytes_weights"]
            + 11.5 * c["decode_bytes_per_live_expert"]) * 100,
        "decode_attn_roofline": 740_000 * 5760 / hbm / 0.0060 * 100,
        "decode_stream_roofline": (
            c["decode_bytes_weights"]
            + 11.5 * c["decode_bytes_per_live_expert"]
            + 740_000 * 5760) / hbm / 0.018 * 100,
        "decode_program_dev_ms": 18.0,
        "prefill_program_dev_ms": 12.0,
        "moe_expert_roofline":
            11.5 * c["decode_bytes_per_live_expert"] / hbm / 0.0055 * 100,
        "decode_dense_ms": (0.14 + 0.07) / 100 * 1000,
        "head_ms": 0.7, "moe_route_ms": 0.6,
        "router_bias_moved": 2.8, "expert_rows_held_share": 3.2,
        "experts_touched": 11.5, "expert_rows_max": 18.0,
        "kv_pool_fill": 75.0, "slot_occupancy": 99.5,
        "decode_block_fill": 96.0, "decode_live_column_share": 61.0,
    }
    assert set(want) <= set(got)
    for name, value in want.items():
        assert got[name] == pytest.approx(value, rel=1e-9), name
    # The whole step: the bytes bind (8.0 ms) over the operations
    # (256 rows x 5.29 G = 6.9 ms).
    assert got["decode_stream_mfu"] == pytest.approx(
        got["decode_stream_roofline"], rel=1e-9)
    for name in got:
        if "roofline" in name or "mfu" in name:
            assert 0 < got[name] < 100, name


def test_over_a_program_without_the_new_scope_the_readers_return_nothing(
        real, monkeypatch):
    """A program that lacks what this PR added (no `attn.absorb` in the
    scopes' vocabulary: the parent) and a family that states no latent
    read: the new readers leave their metrics out and nothing raises."""
    bench, config, family, _ref = real
    entries = [m for m in configs.metrics_for_cell(bench, "per_layer", CELL)
               if m["name"] in NEW]
    dirs = configs.metrics_dirs(util.REPO, bench)
    ctx = _context(family, config)
    monkeypatch.setattr(scope_times, "for_run", lambda _ctx: _TABLE)
    monkeypatch.setattr(scope_times, "vocabulary",
                        lambda: ("attn.in", "attn.kernel", "attn.out"))
    got = readers.read_all(dirs, entries, ctx, {"out_tokens_per_s": 1.0})
    assert set(got) == {"latent_cache_byte_share"}
    monkeypatch.undo()
    for drop in ("latent_flops_per_kv_token", "decode_bytes_per_kv_token"):
        bare = dict(ctx, consts={k: v for k, v in ctx["consts"].items()
                                 if k != drop})
        monkeypatch.setattr(scope_times, "for_run", lambda _ctx: _TABLE)
        got = readers.read_all(dirs, entries, bare, {"out_tokens_per_s": 1.0})
        assert set(got) == {"latent_absorb_ms"}, drop
    ctx["trace"], ctx["samples"] = None, {}
    monkeypatch.setattr(scope_times, "for_run", lambda _ctx: None)
    assert not readers.read_all(dirs, entries, ctx,
                                {"out_tokens_per_s": 1.0})


def test_the_contract_holds_with_89_entries():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "check_contract",
        os.path.join(util.REPO, "benchmarks", "tools", "check_contract.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.errors(util.REPO) == []
    bench = configs.load_benchmark(util.REPO)
    assert len(bench["per_layer"]) >= 89
    assert len(bench["configs"]) >= 7 and len(bench["workloads"]) >= 8
