"""The `nemotron_h` family through the harness (a NEW test file: the cell
came as files and entries, so its tests do too).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_nemotron_h_family.py -q

The rehearsal goes through `run.main(..., rehearsal=True)` on the CPU: no
device metric is printed or asserted. The readers of the cell's per-layer
metrics are held to hand arithmetic over a synthetic context, and to
returning nothing (not raising) over a program that lacks what they read.
Nothing here asserts that the cell's entries are the benchmark's LAST: a
later PR appends its own.
"""

from __future__ import annotations

import copy
import dataclasses
import json
import math

import pytest

import util
from harness import configs, families, readers, scope_times

CONFIG = "nemotron-3-super-120b-a12b"
CELL = CONFIG + ".subagents"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("ssd_chunk_roofline", "moe_latent_ms")
SHARED = (
    "ssm_share", "ssm_scan_share", "ssm_proj_ms", "ssm_step_roofline",
    "moe_expert_share", "moe_expert_roofline", "moe_route_ms",
    "experts_touched", "expert_rows_max", "expert_rows_held_share",
    "router_bias_moved", "decode_stream_mfu", "state_cache_byte_share",
    "kv_cache_byte_share", "decode_attn_roofline", "attn_kernel_share",
    "decode_program_dev_ms", "prefill_program_dev_ms", "decode_step_ms",
    "prefill_tokens_per_s", "slot_occupancy", "kv_pool_fill",
    "compiles_in_window", "tick_host_share", "device_idle_share",
    "preemptions", "head_ms", "decode_dense_ms", "scope_coverage",
    "decode_block_fill", "decode_live_column_share")


@pytest.fixture(scope="module")
def real():
    bench = configs.load_benchmark(util.REPO)
    config = configs.load_config(util.REPO, bench, CONFIG)
    family, reference = families.load(util.REPO, bench, config)
    return bench, config, family, reference


def test_the_configuration_is_the_published_one_cut_three_ways(real):
    """Every width of the public config.json under its own key, the
    pattern whole; THREE keys reduced: the depth (one period), the
    experts held, the vocabulary's slice."""
    bench, config, family, _ref = real
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 88,
                                   "n_routed_experts": 512,
                                   "vocab_size": 131072}
    for key, value in {
            "num_hidden_layers": 11, "n_routed_experts": 128,
            "vocab_size": 32768, "hidden_size": 4096, "mamba_num_heads": 128,
            "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 8,
            "conv_kernel": 4, "chunk_size": 128, "expand": 2,
            "num_attention_heads": 32, "num_key_value_heads": 2,
            "head_dim": 128, "num_experts_per_tok": 22,
            "routed_scaling_factor": 5, "moe_latent_size": 1024,
            "moe_intermediate_size": 2688,
            "moe_shared_expert_intermediate_size": 5376,
            "mlp_hidden_act": "relu2", "norm_eps": 1e-5,
            "tie_word_embeddings": False}.items():
        assert config[key] == value, key
    assert len(config["hybrid_override_pattern"]) == 88   # whole, as published
    assert config["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    (entry,) = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    cfg = family.program_config(config, max_seq=2304)
    assert cfg.pattern == "MEMEMEM*EME" and cfg.max_seq == 2304
    assert [cfg.count(k) for k in "ME*"] == [5, 5, 1]
    assert (cfg.m_heads, cfg.m_head_dim, cfg.d_state, cfg.m_groups,
            cfg.d_conv, cfg.chunk_size) == (128, 64, 128, 8, 4, 128)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 2, 128)
    assert (cfg.n_experts, cfg.n_experts_routed, cfg.first_expert, cfg.top_k,
            cfg.d_latent, cfg.d_ff, cfg.d_ff_shared, cfg.routed_scale,
            cfg.vocab_size) == (128, 512, 0, 22, 1024, 2688, 5376, 5, 32768)
    rc = family.reference_config(config)
    hash(rc)
    assert (rc.pattern, rc.m_heads, rc.m_groups, rc.top_k, rc.routed_scale,
            rc.first_expert) == ("MEMEMEM*EME", 128, 8, 22, 5.0, 0)
    for text in ("assumed", "departures", "deployment", "reduced_note"):
        assert config[text]
    for said in ("positions", "mamba2", "latent_moe", "router", "state",
                 "weights"):
        assert said in config["assumed"]
    assert "multi-token-prediction" in config["departures"][0]
    assert "four tpu v5e chips share each layer" in config["deployment"].lower()
    assert "eight stages" in config["deployment"]
    assert "host's share" in config["reduced_note"]
    assert "8.25 rows" in config["reduced_note"]
    geo = config["serve"]
    assert (geo["n_slots"], geo["max_len"], geo["prefill_chunk"],
            geo["chips"], geo["tp"]) == (192, 2304, 128, 1, 1)
    # pages for every slot's whole context: no request can be preempted
    assert geo["n_pages"] * geo["page_size"] == 192 * 2304
    assert (geo["reference_factor"], geo["deficit_slack"]) == (3.0, 1e-4)
    assert geo["ref_sample"] == 8


def test_a_file_that_asks_for_what_the_family_does_not_build_is_refused(real):
    _bench, config, family, _ref = real
    with pytest.raises(SystemExit, match="mlp_hidden_act"):
        family.program_config(dict(config, mlp_hidden_act="silu"))
    with pytest.raises(SystemExit, match="n_group"):
        family.reference_config(dict(config, n_group=4))
    with pytest.raises(SystemExit, match="pattern of M, E"):
        family.program_config(dict(
            config, hybrid_override_pattern="M-" + "M" * 20))


def test_the_catalogs_numbers_are_all_there(real):
    """Where the catalog is at hand: every key of its `config` with the
    same value, but the three reduced."""
    _bench, config, _family, _ref = real
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        pytest.skip("no catalog here")
    (row,) = [r for r in rows if r["source_url"] == config["source"]]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    for key in config["reduced"]:
        assert row["config"][key] == config["published"][key]


def test_the_family_counts_the_cells_parameters_and_bytes(real):
    """ISSUE 62's arithmetic, from the file's own sizes."""
    _bench, config, family, _ref = real
    per = family.layer_params(config)
    assert per["mamba"] == 109_576_192                    # 109.6 M
    assert per["attn"] == 35_651_584                      # 35.7 M
    assert per["expert"] == 5_505_024                     # 5.505 M
    held = (per["router"] + per["latent"] + per["shared"]
            + 128 * per["expert"])
    assert round(held / 1e6, 1) == 759.2                  # an expert layer
    assert (per["n_mamba"], per["n_expert"], per["n_attn"]) == (5, 5, 1)
    assert round(family.parameters(config) / 1e6) == 4648
    c = family.serve_consts(config)
    always = (5 * per["mamba"] + per["attn"] + 5 * (held - 128 * per["expert"])
              + 4096 * 32768)
    assert c["decode_bytes_weights"] == 2 * always
    assert round(c["decode_bytes_weights"] / 1e9, 2) == 1.98
    assert c["decode_bytes_per_live_expert"] == 2 * 5 * per["expert"]
    assert round(128 * c["decode_bytes_per_live_expert"] / 1e9, 2) == 7.05
    assert c["decode_bytes_per_kv_token"] == 1024
    assert c["ssm_step_bytes_per_slot"] == 5 * 2 * 4_194_304
    slot = 5 * (4_194_304 + 3 * 10240 * 2)
    assert round(slot / 1e6, 2) == 21.28                  # state + tails
    assert c["decode_bytes_per_state_slot"] == 2 * slot
    assert c["decode_bytes_per_window_slot"] == 0.0
    assert c["decode_flops_per_row"] == 2.0 * (
        always + 5 * 5.5 * per["expert"])
    assert c["chunk_scan_bytes_per_token"] == 5 * (
        4 * (2 * 8192 + 128 + 2048) + 2 * 4_194_304 // 128)
    assert c["chunk_scan_flops_per_token"] == 5 * (
        2 * 8 * 128 * 128 + 128 * (2 * 128 * 64 + 4 * 128 * 64))
    # a step at 192 slots and ~1,000 cached tokens a slot: the state 46 %,
    # the held experts 40 %, the other weights 11 % (ISSUE 62)
    kv, state = 192 * 1000 * 1024, 192 * c["decode_bytes_per_state_slot"]
    experts = 128 * c["decode_bytes_per_live_expert"]
    total = c["decode_bytes_weights"] + kv + state + experts
    assert round(total / 1e9, 1) == 17.4
    assert [round(100 * x / total) for x in (
        state, experts, c["decode_bytes_weights"], kv)] == [47, 41, 11, 1]
    # the tree the harness fills
    cfg = family.program_config(config)
    specs = family.model().param_specs(cfg)
    n = sum(math.prod(s["shape"]) for s in specs.values())
    assert round(n / 1e6, 1) == 4648.2                    # with the vectors
    for name, scale in (("m_dt_b", 4.0), ("m_A_log", 1.0)):
        assert (specs[name]["init"], specs[name]["scale"]) == ("normal",
                                                               scale)
    assert {s["init"] for s in specs.values()} == {"normal", "ones"}
    # the pool beside them: state 4.05 GB, tails 0.06 GB, pages 0.45 GB
    import jax

    pool = jax.eval_shape(lambda: family._program().init_paged_kv(
        cfg, 6912, 64, 192))
    nbytes = lambda a: math.prod(a.shape) * a.dtype.itemsize
    assert pool["ssm_state"].shape == (5, 193, 64, 128, 128)
    assert round(nbytes(pool["ssm_state"]) / 1e9, 2) == 4.05
    assert nbytes(pool["ssm_state"]) + nbytes(pool["ssm_conv"]) == 193 * slot
    assert nbytes(pool["k"]) + nbytes(pool["v"]) == 2 * 6913 * 64 * 256 * 2
    assert set(pool) == {"k", "v", "ssm_state", "ssm_conv", "moe_counters"}


def test_the_traffic_and_the_entries_are_the_issues(real):
    bench, _config, _family, _ref = real
    from harness import traffic

    mix = configs.load_traffic(util.REPO, bench, "subagents")
    assert (mix["kind"], mix["clients"], mix["cycle_requests"]) == (
        "closed_loop", "n_slots", 192)
    assert (mix["ramp_s"], mix["trace_s"]) == (30, 8)
    src = traffic.ClosedLoopSource(mix, 2**31 + 77, 32768)
    assert set(map(int, src.p_len)) == {256}
    outs = sorted(map(int, src.o_len))
    assert (outs[0], outs[1], outs[-1], len(outs)) == (1027, 1032, 2045, 192)
    assert sum(outs) / len(outs) == 1536
    assert max(outs) + 256 <= 2304
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "subagents", 1)
    (tokens,) = [m for m in bench["end_to_end"]
                 if m["name"] == "out_tokens_per_s"]
    assert CELL in tokens["workloads"] and tokens["bound"] == 0.01
    listed = [m["name"] for m in configs.metrics_for_cell(
        bench, "per_layer", CELL)]
    assert sorted(listed) == sorted(SHARED + NEW)
    for name in ("ssm_chunk_roofline", "decode_stream_roofline",
                 "gdn_chunk_roofline"):
        assert name not in listed
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert (m["workloads"], m["moves"], m["source"]) == (
                [CELL], "out_tokens_per_s", "device_trace")


TINY = {
    "hidden_size": 64, "num_hidden_layers": 8, "mamba_num_heads": 8,
    "mamba_head_dim": 16, "ssm_state_size": 16, "n_groups": 2,
    "chunk_size": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "n_routed_experts": 8, "num_experts_per_tok": 4,
    "moe_latent_size": 32, "moe_intermediate_size": 48,
    "moe_shared_expert_intermediate_size": 96, "vocab_size": 256,
}


def tiny_config(config: dict) -> dict:
    """The real file cut to a size the CPU serves: every width small, the
    pattern's first eight layers (four Mamba-2, three expert layers, one
    attention), 8 of 16 experts held under top-4, a latent of half the
    model's width."""
    tiny = copy.deepcopy(config)
    tiny.update(TINY, name="nemotron-h-tiny")
    tiny["published"] = dict(tiny["published"], n_routed_experts=16)
    tiny["serve"].update(page_size=16, n_pages=32, max_len=128,
                         prefill_chunk=64, n_slots=4, reference_factor=3.0,
                         deficit_slack=0.01, ref_sample=3)
    return tiny


def test_the_family_serves_through_the_harness_at_tiny_size(real, tmp_path):
    """families/nemotron_h.py and harness/reference/nemotron_h_ref.py
    through run.py on the CPU: the engine's stream is held `correct` by
    `paired_rows`, and the counter metrics the cell lists are in the
    line."""
    _bench, config, _family, _ref = real
    root = util.make_root(str(tmp_path))
    counters = [("slot_occupancy", "%"), ("kv_pool_fill", "%"),
                ("compiles_in_window", "count"), ("preemptions", "count"),
                ("decode_block_fill", "%"), ("experts_touched", "count"),
                ("expert_rows_max", "count"),
                ("expert_rows_held_share", "%"), ("router_bias_moved", "%")]
    cell = util.add_cell(
        root, tiny_config(config), "batch", ["out_tokens_per_s"],
        [{"name": n, "unit": u, "moves": "out_tokens_per_s"}
         for n, u in counters]
        + [{"name": n, "unit": "%", "moves": "out_tokens_per_s"}
           for n in ("state_cache_byte_share", "kv_cache_byte_share",
                     "ssm_step_roofline", "decode_stream_mfu")]
        + [{"name": NEW[0], "unit": "%", "moves": "out_tokens_per_s"},
           {"name": NEW[1], "unit": "ms", "moves": "out_tokens_per_s"}])
    got = util.rehearse(root, cell, seed=2**31 + 62, seconds=1.5, trace=1)
    out = got["line"]
    assert out["correct"] is True and out["failed"] == 0
    assert any("reference check over" in ln and ": ok" in ln
               for ln in got["log"])
    value = lambda n: out["metrics"]["cpu_rehearsal." + n]["value"]
    assert value("preemptions") == 0 and value("slot_occupancy") > 0
    assert 0 < value("experts_touched") <= 8
    assert 20 < value("expert_rows_held_share") < 80     # 8 of 16 held
    # The two byte shares are counters' arithmetic: read on the CPU too.
    assert 0 < value("state_cache_byte_share") < 100
    assert 0 < value("kv_cache_byte_share") < 100
    # No device plane in a CPU trace: trace-sourced metrics are left out.
    assert not any("roofline" in n or "dev_ms" in n or "mfu" in n
                   or "latent_ms" in n for n in out["metrics"])


def test_the_reference_agrees_with_the_program_at_tiny_size(real):
    """The family's two halves on the harness's own seeded weights: the
    program's full-sequence forward against `nemotron_h_ref.logits`,
    float32 (1e-4: reassociation; tests/test_nemotron_h.py has the
    reason, the paged programs and the controls)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import weights

    _bench, config, family, reference = real
    tiny = tiny_config(config)
    cfg = dataclasses.replace(family.program_config(tiny, max_seq=128),
                              dtype=jnp.float32)
    assert cfg.pattern == "MEMEMEM*"
    params = weights.make_params(family.model(), cfg, 2**31 + 5, jnp.float32)
    tokens = np.random.default_rng(0).integers(1, 256, (1, 50)).astype(
        np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(family._program().forward(cfg, params,
                                                   jnp.asarray(tokens)))[0]
    want = np.asarray(reference.logits(params, jnp.asarray(tokens[0]),
                                       family.reference_config(tiny)))
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)
    # the seeded decay spreads: fast and slow heads both
    rate = (np.asarray(jax.nn.softplus(params["m_dt_b"]))
            * np.exp(np.asarray(params["m_A_log"])))
    assert (rate > 1).mean() > 0.15 and (rate < 1 / 15).mean() > 0.05


def _context(family, config) -> dict:
    """A synthetic context of a traced run: 100 decode steps of 30 ms and
    8 chunk programs of 40 ms in a traced 3.4 s."""
    decode, chunk = "jit__decode_sample_paged(1)", "jit_prefill_chunk_paged(2)"
    call = lambda name: (
        f"%{name} = bf16[192,32,128] custom-call(%a, %b), "
        'custom_call_target="tpu_custom_call"')
    ragged = ("%ragged-dot.7 = f32[2432,2688] custom-call(%a, %b, %c), "
              'custom_call_target="ragged_dot"')
    ops = [
        (decode, call("ssd_decode_step.3"), 1.20),
        (decode, call("paged_decode_attn.2"), 0.04),
        (decode, ragged, 1.00),
        (chunk, call("paged_prefill_attn.1"), 0.004),
    ]
    t = [10.0 + 0.25 * i for i in range(40)]
    return {
        "engine": {"slot_occupancy": 0.995, "decode_block_fill": 0.9,
                   "decode_live_column_share": 0.45,
                   "compiles_in_window": 0, "preemptions": 0,
                   "tick_host_share": 0.02, "engine_prefill_tok_s": 20000.0,
                   "decode_step_ms_p50": 30.5, "prefill_tokens": 2048,
                   "prefill_dispatches": 8, "prefill_rows_per_program": 2.0,
                   "kv_pages_free_min": 2000,
                   "moe_layer_steps": 500, "moe_experts_touched": 126.0,
                   "moe_rows_max": 18.0, "moe_rows_routed": 500 * 192 * 22,
                   "moe_rows_held": 500 * 192 * 22 // 4,
                   "moe_rows_bias_moved": 500 * 192},
        "metrics": {"experts_touched": 126.0},
        "samples": {"t": t, "decoding_slots": [180] * 32 + [192] * 8,
                    "kv_tokens_decoding": [150_000] * 32 + [192_000] * 8},
        "trace_t0": t[32],                              # the last 8 samples
        "trace": {"ops": ops, "window_s": 3.4, "busy_s": 3.3,
                  "per_chip_busy_s": [3.3],
                  "programs": {decode: {"count": 100, "total_s": 3.0},
                               chunk: {"count": 8, "total_s": 0.32}}},
        "memory": {}, "peaks": {"hbm_bytes_per_s": 819e9,
                                "flops_bf16": 197e12},
        "consts": dict(configs.dims(config), chips=1, window_s=51.0,
                       n_pages=6912, page_size=64,
                       **family.serve_consts(config)),
    }


_TABLE = {
    "busy_s": 3.3,
    "programs": {
        "jit__decode_sample_paged": {
            "runs": 100, "total_s": 3.0, "by_pass": {}, "unscoped_s": 0.03,
            "by_scope": {"ssm.in": 0.30, "ssm.scan": 1.20, "ssm.out": 0.10,
                         "mlp": 0.15, "moe.route": 0.12, "moe.experts": 1.0,
                         "moe.latent": 0.05, "attn.in": 0.01,
                         "attn.out": 0.01, "attn.kernel": 0.04,
                         "head": 0.05, "sample": 0.01}},
        "jit_prefill_chunk_paged": {
            "runs": 8, "total_s": 0.32, "by_pass": {}, "unscoped_s": 0.001,
            "by_scope": {"ssm.in": 0.06, "ssm.scan": 0.08, "ssm.out": 0.02,
                         "mlp": 0.03, "moe.experts": 0.10,
                         "attn.kernel": 0.004}}},
}


def test_every_metric_of_the_cell_reads_a_synthetic_context(real,
                                                            monkeypatch):
    bench, config, family, _ref = real
    entries = configs.metrics_for_cell(bench, "per_layer", CELL)
    assert len(entries) == len(SHARED + NEW)
    assert all(m["moves"] == "out_tokens_per_s" and "." not in m["name"]
               for m in entries)
    ctx = _context(family, config)
    monkeypatch.setattr(scope_times, "for_run", lambda _ctx: _TABLE)
    got = {n: v["value"] for n, v in readers.read_all(
        configs.metrics_dirs(util.REPO, bench), entries, ctx,
        {"out_tokens_per_s": 5500.0}).items()}
    assert set(got) == {m["name"] for m in entries}
    c, peak = ctx["consts"], 819e9
    # samples of the TRACED interval: 192 slots, 192,000 cached tokens
    pages = 192_000 * c["decode_bytes_per_kv_token"]
    state = 192 * c["decode_bytes_per_state_slot"]
    experts = got["experts_touched"] * c["decode_bytes_per_live_expert"]
    step_bytes = c["decode_bytes_weights"] + pages + state + experts
    want = {
        "state_cache_byte_share": state / step_bytes * 100,
        "kv_cache_byte_share": pages / step_bytes * 100,
        "ssm_step_roofline":
            192 * c["ssm_step_bytes_per_slot"] / peak / 0.012 * 100,
        "moe_expert_roofline": experts / peak / 0.010 * 100,
        "decode_attn_roofline": pages / peak / 0.0004 * 100,
        "decode_stream_mfu": step_bytes / peak / 0.030 * 100,
        "ssm_share": (0.30 + 1.20 + 0.10 + 0.06 + 0.08 + 0.02) / 3.3 * 100,
        "ssm_scan_share": (1.20 + 0.08) / 3.3 * 100,
        "ssm_proj_ms": 4.0, "moe_route_ms": 1.2, "moe_latent_ms": 0.5,
        "decode_program_dev_ms": 30.0, "prefill_program_dev_ms": 40.0,
        "head_ms": 0.6, "decode_block_fill": 90.0,     # head and sample
    }
    for name, value in want.items():
        assert got[name] == pytest.approx(value, rel=1e-9), name
    assert 40 < got["state_cache_byte_share"] < 55
    # 256 tokens a dispatch, 10 ms of scan a chunk program: the operations
    # bound it, not the bytes
    a = 256 * c["chunk_scan_bytes_per_token"] / peak
    b = 256 * c["chunk_scan_flops_per_token"] / 197e12
    assert got["ssd_chunk_roofline"] == pytest.approx(
        max(a, b) / 0.010 * 100, rel=1e-9)
    for name in got:
        if "roofline" in name or "mfu" in name:
            assert 0 < got[name] < 100, name


def test_over_a_program_without_the_scopes_the_new_readers_return_nothing(
        real, monkeypatch):
    """This PR's two reader files over a context whose family states no
    operations term, whose program has no `moe.latent` scope (the
    parent's vocabulary), or whose run was not traced: the metric is
    left out and nothing raises."""
    bench, config, family, _ref = real
    entries = [m for m in bench["per_layer"] if m["name"] in NEW]
    read = lambda ctx: {n: v["value"] for n, v in readers.read_all(
        configs.metrics_dirs(util.REPO, bench), entries, ctx, {}).items()}
    ctx = _context(family, config)
    assert read({}) == {}
    assert read(dict(ctx, trace=None)) == {}
    monkeypatch.setattr(scope_times, "for_run", lambda _ctx: _TABLE)
    assert set(read(ctx)) == set(NEW)
    flopless = copy.deepcopy(ctx)
    flopless["consts"].pop("chunk_scan_flops_per_token")
    assert set(read(flopless)) == {"moe_latent_ms"}
    old = tuple(n for n in scope_times.vocabulary() if n != "moe.latent")
    monkeypatch.setattr(scope_times, "vocabulary", lambda: old)
    assert set(read(ctx)) == {"ssd_chunk_roofline"}
    monkeypatch.setattr(scope_times, "vocabulary", lambda: ())
    assert read(ctx) == {}
