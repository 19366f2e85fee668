"""The `mimo_v2` family through the harness (a NEW test file: the cell came
as files and entries, so its tests do too).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_mimo_v2_family.py -q

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

CELL = "mimo-v2-flash.think"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def real():
    bench = configs.load_benchmark(util.REPO)
    config = configs.load_config(util.REPO, bench, "mimo-v2-flash")
    family, reference = families.load(util.REPO, bench, config)
    return bench, config, family, reference


def test_the_configuration_is_the_public_config_with_three_cuts(real):
    """Every key of the public config.json under its own name; the three
    reduced keys state the share, `published` the source's values; the
    layers kept are dense layer 0 and one whole period after it."""
    _bench, config, family, _ref = real
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "n_routed_experts": 256,
                                   "vocab_size": 152576}
    for key, value in {"hidden_size": 4096, "intermediate_size": 16384,
                       "num_attention_heads": 64, "num_key_value_heads": 4,
                       "swa_num_key_value_heads": 8, "head_dim": 192,
                       "v_head_dim": 128, "swa_head_dim": 192,
                       "swa_v_head_dim": 128, "moe_intermediate_size": 2048,
                       "num_experts_per_tok": 8, "sliding_window": 128,
                       "rope_theta": 5000000, "swa_rope_theta": 10000,
                       "partial_rotary_factor": 0.334,
                       "attention_value_scale": 0.707,
                       "num_hidden_layers": 7, "n_routed_experts": 16,
                       "vocab_size": 19072}.items():
        assert config[key] == value, key
    assert config["deployment_share"] == {
        "chips_in_group": 16, "first_expert": 0, "experts_held": 16,
        "vocab_rows_held": 19072}
    cfg = family.program_config(config, max_seq=6144)
    assert cfg.kinds == ("full", "window", "window", "window", "window",
                         "full", "window")
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.n_kv_heads_window,
            cfg.head_dim, cfg.v_head_dim) == (64, 4, 8, 192, 128)
    assert (cfg.n_experts, cfg.n_experts_routed, cfg.first_expert,
            cfg.top_k, cfg.dense_layers) == (16, 256, 0, 8, (0,))
    assert (cfg.rotary_dim, cfg.rope_theta, cfg.rope_theta_window,
            cfg.value_scale, cfg.window, cfg.norm_eps, cfg.sink_kinds) == (
        64, 5000000, 10000, 0.707, 128, 1e-5, ("window",))
    rc = family.reference_config(config)
    hash(rc)
    assert rc.layer_types == cfg.kinds and rc.sink_kinds == ("window",)
    assert (rc.kv_heads_full, rc.kv_heads_window) == (4, 8)


def test_every_number_of_the_catalog_entry_is_in_the_file(real):
    """The driver's rule: each number of the catalog entry's `config`
    under the same key, but for the keys `reduced` names; lists copied
    whole."""
    _bench, config, _family, _ref = real
    try:
        with open(CATALOG) as f:
            entry = next(e for e in map(json.loads, f)
                         if e["name"] == "MiMo-V2-Flash")
    except OSError:
        pytest.skip("no catalog beside the model-configs guide here")
    assert config["source"] == entry["source_url"]
    for key, value in entry["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key


def test_the_family_counts_the_cells_bytes(real):
    """ISSUE 48's table, from the file's own sizes (bf16)."""
    _bench, config, family, _ref = real
    per = family.layer_params(config)
    # 4,096 x (12,288 + 768 + 512) + 8,192 x 4,096 = 89.13 M
    assert per["attention_full"] == 4096 * 13568 + 8192 * 4096 == 89_128_960
    # 4,096 x (12,288 + 1,536 + 1,024) + 8,192 x 4,096 + 64 sinks = 94.37 M
    assert per["attention_window"] == 4096 * 14848 + 8192 * 4096 + 64
    assert per["dense_mlp"] == 3 * 4096 * 16384 == 201_326_592
    assert per["router"] == 4096 * 256 + 256
    assert per["expert"] == 3 * 4096 * 2048 == 25_165_824
    assert (per["n_full"], per["n_window"], per["n_dense"],
            per["n_sparse"]) == (2, 5, 1, 6)
    c = family.serve_consts(config)
    assert c["decode_bytes_per_live_expert"] == 6 * 25_165_824 * 2
    assert c["decode_bytes_per_kv_token"] == 2 * (768 + 512) * 2 == 5120
    assert c["decode_bytes_per_window_slot"] == (
        5 * 128 * (1536 + 1024) * 2) == 3_276_800
    assert c["decode_bytes_weights"] == 2 * (
        2 * 89_128_960 + 5 * per["attention_window"] + 201_326_592
        + 6 * per["router"] + 4096 * 19072)
    # weights: 3,429.9 M parameters, 6.86 GB
    n_params = sum(
        int(math.prod(s["shape"])) for s in
        family.model().param_specs(family.program_config(config)).values())
    assert 3.429e9 < n_params < 3.431e9
    assert family.train_consts(config, 512)["train_flops_per_token"] > 0


def test_the_family_refuses_what_it_does_not_build(real):
    _bench, config, family, _ref = real
    for key, value in (("scoring_func", "softmax"), ("n_group", 8),
                       ("swa_head_dim", 128), ("n_shared_experts", 1),
                       ("routed_scaling_factor", 2.5)):
        with pytest.raises(SystemExit, match="mimo_v2 family builds"):
            family.program_config({**config, key: value})


TINY = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 8,
    "swa_num_attention_heads": 8, "num_key_value_heads": 2,
    "swa_num_key_value_heads": 4, "head_dim": 24, "swa_head_dim": 24,
    "v_head_dim": 16, "swa_v_head_dim": 16, "n_routed_experts": 4,
    "num_experts_per_tok": 3, "moe_intermediate_size": 32,
    "sliding_window": 32, "sliding_window_size": 32, "vocab_size": 256,
    "published": {"n_routed_experts": 8},
}


def tiny_config(config: dict) -> dict:
    """The real file cut to a size the CPU serves: every width small, the
    pattern kept (dense layer 0 full, window x 4, full, window; 8 query
    heads of 24 over 2 and 4 KV heads, V heads of 16; 4 of 8 experts
    held, top-3)."""
    tiny = copy.deepcopy(config)
    tiny.update(TINY, name="mimo-v2-tiny")
    tiny["serve"].update(page_size=16, n_pages=24, max_len=128,
                         prefill_chunk=16, n_slots=3, reference_factor=2.0,
                         deficit_slack=0.01, ref_sample=3)
    return tiny


def test_the_family_serves_through_the_harness_at_tiny_size(real, tmp_path):
    """families/mimo_v2.py, harness/reference/mimo_v2_ref.py and the
    cell's counter readers through run.py on the CPU: the engine's stream
    is held `correct` by `paired_rows`, and the metrics that read the
    program's counters are in the line."""
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
    assert 0.0 < value("window_ring_live_share") < 100.0
    # No device plane in a CPU trace: trace-sourced metrics are left out.
    assert not any("roofline" in n or "dev_ms" in n for n in out["metrics"])


def _context(family, config) -> dict:
    """A synthetic context of a traced run: 100 decode steps of 17 ms and
    4 chunk programs of 25 ms in a traced 1.9 s, with known kernel and
    scope times."""
    decode, chunk = "jit__decode_sample_paged(1)", "jit_prefill_chunk_paged(2)"
    call = lambda name, target="tpu_custom_call": (
        f"%{name} = bf16[128,64,128] custom-call(%a, %b), "
        f'custom_call_target="{target}"')
    ops = [
        (decode, call("paged_decode_attn_window.3"), 0.10),
        (decode, call("paged_decode_attn.2"), 0.40),
        (decode, call("ragged-dot-none.7"), 0.70),
        (decode, call("ragged-dot-metadata.1"), 0.02),
        (decode, "%fusion.9 = f32[128,19072] fusion(%x), kind=kOutput", 0.48),
        (chunk, call("paged_prefill_attn_window.1"), 0.010),
        (chunk, call("paged_prefill_attn.1"), 0.015),
        (chunk, call("ragged-dot-none.9"), 0.050),
        (chunk, "%fusion.3 = bf16[512,4096] fusion(%x), kind=kLoop", 0.025),
    ]
    t = [10.0 + 0.25 * i for i in range(40)]            # the window's samples
    return {
        "engine": {"moe_experts_touched": 15.5, "moe_rows_max": 2.5,
                   "moe_rows_held": 640, "moe_rows_routed": 10000,
                   "moe_rows_bias_moved": 230,
                   "kv_bytes_window": 4_000_000, "kv_bytes_full": 4_000_000,
                   "kv_bytes_window_live": 420_000,
                   "slot_occupancy": 0.99, "kv_pages_free_min": 3072,
                   "compiles_in_window": 0, "preemptions": 0,
                   "tick_host_share": 0.012, "engine_prefill_tok_s": 9000.0,
                   "decode_step_ms_p50": 17.5},
        "samples": {"t": t, "decoding_slots": [120] * 32 + [128] * 8,
                    "kv_tokens_decoding": [400_000] * 32 + [420_000] * 8},
        "trace_t0": t[32],                              # the last 8 samples
        "trace": {"ops": ops, "window_s": 1.9, "busy_s": 1.8,
                  "per_chip_busy_s": [1.8],
                  "programs": {decode: {"count": 100, "total_s": 1.7},
                               chunk: {"count": 4, "total_s": 0.1}}},
        "memory": {}, "peaks": {"hbm_bytes_per_s": 819e9},
        "consts": dict(configs.dims(config), chips=1, window_s=51.0,
                       n_pages=12288, page_size=64,
                       **family.serve_consts(config)),
    }


# What the scope reducer would make of the synthetic trace: seconds by
# scope in the two programs (harness/scope_times.scope_times' table).
_TABLE = {
    "busy_s": 1.8,
    "programs": {
        "jit__decode_sample_paged": {
            "runs": 100, "total_s": 1.7, "by_pass": {}, "unscoped_s": 0.05,
            "by_scope": {"attn.in": 0.16, "attn.out": 0.09,
                         "attn.kernel": 0.50, "moe.route": 0.08,
                         "moe.experts": 0.70, "head": 0.10, "sample": 0.02}},
        "jit_prefill_chunk_paged": {
            "runs": 4, "total_s": 0.1, "by_pass": {}, "unscoped_s": 0.001,
            "by_scope": {"attn.in": 0.012, "attn.out": 0.008,
                         "moe.experts": 0.050}}},
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
    ctx = _context(family, config)
    monkeypatch.setattr(scope_times, "for_run", lambda _ctx: _TABLE)
    got = {n: v["value"] for n, v in readers.read_all(
        configs.metrics_dirs(util.REPO, bench), entries, ctx,
        {"out_tokens_per_s": 7000.0}).items()}
    assert set(got) <= {m["name"] for m in entries}
    c, peak = ctx["consts"], 819e9
    scoped = sum(sum(p["by_scope"].values())
                 for p in _TABLE["programs"].values())
    want = {
        "decode_program_dev_ms": 17.0,
        "slot_occupancy": 99.0,
        "preemptions": 0.0,
        "device_idle_share": (1 - 1.8 / 1.9) * 100,
        "attn_kernel_share": (0.10 + 0.40 + 0.010 + 0.015) / 1.8 * 100,
        "moe_expert_share": (0.70 + 0.02 + 0.050) / 1.8 * 100,
        "experts_touched": 15.5,
        # the kernel alone (not its metadata), a decode step
        "moe_expert_roofline":
            15.5 * c["decode_bytes_per_live_expert"] / peak / 0.0070 * 100,
        # samples of the TRACED interval: 128 slots, 420,000 tokens
        "window_attn_roofline":
            128 * c["decode_bytes_per_window_slot"] / peak / 0.0010 * 100,
        "decode_attn_roofline":
            420_000 * c["decode_bytes_per_kv_token"] / peak / 0.0040 * 100,
        "decode_stream_roofline": (
            c["decode_bytes_weights"]
            + 15.5 * c["decode_bytes_per_live_expert"]
            + 420_000 * c["decode_bytes_per_kv_token"]
            + 128 * c["decode_bytes_per_window_slot"]) / peak / 0.017 * 100,
        "decode_dense_ms": (0.16 + 0.09) / 100 * 1000,
        "router_bias_moved": 2.3,
        "window_ring_live_share": 10.5,
        # ISSUE 48's twelve, which found no room then: appended in PR 52
        "prefill_program_dev_ms": 25.0,
        "decode_step_ms": 17.5,
        "prefill_tokens_per_s": 9000.0,
        "kv_pool_fill": 75.0,
        "compiles_in_window": 0.0,
        "tick_host_share": 1.2,
        "scope_coverage": scoped / 1.8 * 100,
        "window_attn_share": (0.10 + 0.010) / 1.8 * 100,
        "expert_rows_max": 2.5,
        "expert_rows_held_share": 6.4,
        "moe_route_ms": 0.8,
        "head_ms": (0.10 + 0.02) / 100 * 1000,
    }
    assert set(want) <= set(got)
    for name, value in want.items():
        assert got[name] == pytest.approx(value, rel=1e-9), name
    for name in got:
        if "roofline" in name:
            assert 0 < got[name] < 100, name


def test_over_a_program_without_the_new_spans_the_readers_return_nothing(real):
    """A program that lacks what this PR added (no bias counter and no
    split of the pool's bytes in `metrics()`, no window call in the
    trace): the new readers leave their metrics out and nothing raises."""
    bench, config, family, _ref = real
    ctx = _context(family, config)
    ctx["engine"] = {k: v for k, v in ctx["engine"].items()
                     if k not in ("moe_rows_bias_moved", "kv_bytes_window",
                                  "kv_bytes_window_live")}
    ctx["trace"]["ops"] = [op for op in ctx["trace"]["ops"]
                           if "_window" not in op[1]]
    entries = configs.metrics_for_cell(bench, "per_layer", CELL)
    got = readers.read_all(configs.metrics_dirs(util.REPO, bench), entries,
                           ctx, {"out_tokens_per_s": 7000.0})
    assert not {"window_attn_roofline", "router_bias_moved",
                "window_ring_live_share"} & set(got)
    assert "decode_attn_roofline" in got
    ctx["trace"] = None
    got = readers.read_all(configs.metrics_dirs(util.REPO, bench), entries,
                           ctx, {"out_tokens_per_s": 7000.0})
    traced = {m["name"] for m in entries if m["source"] == "device_trace"}
    assert len(traced) >= 8 and not traced & set(got)


def test_the_traffic_is_the_issues(real):
    """128 quantiles of uniform(1536, 5376) behind fixed prompts of 512,
    the same set for every seed, inside max_len."""
    import numpy as np

    from harness import traffic

    bench, config, _family, _ref = real
    mix = configs.load_traffic(util.REPO, bench, "think")
    assert (mix["kind"], mix["clients"], mix["cycle_requests"],
            mix["ramp_s"], mix["trace_s"]) == ("closed_loop", "n_slots",
                                               128, 30, 8)
    assert mix["prompt_len"] == {"dist": "fixed", "value": 512}
    assert mix["output_len"]["dist"] == "uniform"
    assert mix["output_len"]["min"] == 1536
    sets = []
    for seed in (1, 2**31 + 5):
        src = traffic.ClosedLoopSource(mix, seed, 19072)
        assert set(src.p_len.tolist()) == {512}
        sets.append(sorted(src.o_len.tolist()))
        assert max(src.p_len + src.o_len) < config["serve"]["max_len"]
        assert max(src.next()["prompt"]) < 19072
    assert sets[0] == sets[1] and len(sets[0]) == 128
    assert np.mean(sets[0]) == pytest.approx(
        (mix["output_len"]["min"] + mix["output_len"]["max"]) / 2, abs=1)
    geo = config["serve"]
    assert geo["n_slots"] * (-(-(geo["max_len"] - 1) // geo["page_size"])) \
        == geo["n_pages"]                               # no preemption
