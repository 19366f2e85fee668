"""The `laguna` family through the harness (a NEW test file: the cell came
as files and entries, so its tests do too).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_laguna_family.py -q

The rehearsal goes through `run.main(..., rehearsal=True)` on the CPU: no
device metric is printed or asserted. The readers of the cell's per-layer
metrics are held to hand arithmetic over a synthetic context, and to
returning nothing (not raising) over a program that lacks what they read.
"""

from __future__ import annotations

import copy
import os
import sys

import pytest

import util
from harness import configs, families, readers, scope_times

CELL = "laguna-s-2.1.codegen"


@pytest.fixture(scope="module")
def real():
    bench = configs.load_benchmark(util.REPO)
    config = configs.load_config(util.REPO, bench, "laguna-s-2.1")
    family, reference = families.load(util.REPO, bench, config)
    return bench, config, family, reference


def test_the_configuration_is_the_catalog_entry_with_three_cuts(real):
    """Every number of the public config.json under its own key; the
    three reduced keys state the share, `published` the source's values;
    the layers kept are dense layer 0 and one whole period after it."""
    _bench, config, family, _ref = real
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 256, "vocab_size": 100352}
    for key, value in {"hidden_size": 3072, "intermediate_size": 12288,
                       "num_attention_heads": 48, "num_key_value_heads": 8,
                       "head_dim": 128, "moe_intermediate_size": 1024,
                       "shared_expert_intermediate_size": 1024,
                       "num_experts_per_tok": 10, "sliding_window": 512,
                       "moe_routed_scaling_factor": 2.5}.items():
        assert config[key] == value, key
    cfg = family.program_config(config, max_seq=4096)
    assert cfg.kinds == ("full", "window", "window", "window", "full")
    assert (cfg.n_heads, cfg.n_heads_window, cfg.dense_layers) == (48, 72, (0,))
    assert (cfg.n_experts, cfg.n_experts_routed, cfg.first_expert,
            cfg.top_k) == (128, 256, 0, 10)
    assert (cfg.rotary_dim, cfg.rope_theta, cfg.yarn_factor, cfg.yarn_orig,
            cfg.rope_theta_window) == (64, 500000.0, 128.0, 8192, 10000.0)
    rc = family.reference_config(config)
    hash(rc)
    assert rc.layer_types == cfg.kinds and rc.heads_window == 72


def test_the_family_counts_the_cells_bytes(real):
    """ISSUE 36's arithmetic, from the file's own sizes (bf16)."""
    _bench, config, family, _ref = real
    per = family.layer_params(config)
    assert per["attention_full"] == 44_187_648          # 44.19 M
    assert per["attention_window"] == 63_135_744        # 63.13 M
    assert per["dense_mlp"] == 113_246_208
    assert (per["router"], per["shared"], per["expert"]) == (
        786_432, 9_437_184, 9_437_184)
    c = family.serve_consts(config)
    assert c["decode_bytes_per_live_expert"] == 4 * 3 * 3072 * 1024 * 2
    assert c["decode_bytes_per_kv_token"] == 2 * 4096
    assert c["decode_bytes_per_window_slot"] == 3 * 512 * 4096
    assert c["decode_bytes_weights"] == 2 * (
        2 * 44_187_648 + 3 * 63_135_744 + 113_246_208
        + 4 * (786_432 + 9_437_184) + 3072 * 50176)
    # weights: 5,572 M parameters, 11.14 GB
    n_params = sum(
        int(__import__("math").prod(s["shape"])) for s in
        family.model().param_specs(family.program_config(config)).values())
    assert 5.571e9 < n_params < 5.573e9


TINY = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_experts": 4,
    "num_experts_per_tok": 3, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "sliding_window": 32,
    "vocab_size": 256, "published": {"num_experts": 8},
}


def tiny_config(config: dict) -> dict:
    """The real file cut to a size the CPU serves: every width small, the
    pattern kept (dense layer 0, window x 3, full; 6 heads in a window
    layer over 2 KV heads; 4 of 8 experts held, top-3)."""
    tiny = copy.deepcopy(config)
    tiny.update(TINY, name="laguna-tiny")
    tiny["num_attention_heads_per_layer"] = [
        4 if t == "full_attention" else 6 for t in tiny["layer_types"]]
    tiny["rope_parameters"]["full_attention"].update(
        factor=8, original_max_position_embeddings=32)
    tiny["serve"].update(page_size=16, n_pages=24, max_len=128,
                         prefill_chunk=16, n_slots=3, reference_factor=2.0,
                         deficit_slack=0.01, ref_sample=3)
    return tiny


def test_the_family_serves_through_the_harness_at_tiny_size(real, tmp_path):
    """families/laguna.py, harness/reference/laguna_ref.py and the cell's
    counter readers through run.py on the CPU: the engine's stream is
    held `correct` by `paired_rows`, and the metrics that read the
    program's counters are in the line."""
    _bench, config, _family, _ref = real
    root = util.make_root(str(tmp_path))
    counters = [("experts_touched", "count"),
                ("expert_rows_max", "ratio"),
                ("expert_rows_held_share", "%"),
                ("slot_occupancy", "%"),
                ("kv_pool_fill", "%"),
                ("compiles_in_window", "count"),
                ("preemptions", "count")]
    cell = util.add_cell(
        root, tiny_config(config), "batch", ["out_tokens_per_s"],
        [{"name": n, "unit": u, "moves": "out_tokens_per_s"}
         for n, u in counters])
    got = util.rehearse(root, cell, seed=2**31 + 11, seconds=1.5, trace=1)
    out = got["line"]
    assert out["correct"] is True and out["failed"] == 0
    assert any("reference check over" in ln and ": ok" in ln
               for ln in got["log"])
    value = lambda n: out["metrics"]["cpu_rehearsal." + n]["value"]
    assert 1.0 <= value("experts_touched") <= 4
    assert 20.0 < value("expert_rows_held_share") < 80.0
    assert value("preemptions") == 0
    # No device plane in a CPU trace: trace-sourced metrics are left out.
    assert not any("roofline" in n or "dev_ms" in n for n in out["metrics"])


def _context(family, config) -> dict:
    """A synthetic context of a traced run: 100 decode steps of 26 ms and
    4 chunk programs of 25 ms in a traced 2.8 s, with known kernel times."""
    decode, chunk = "jit__decode_sample_paged(1)", "jit_prefill_chunk_paged(2)"
    call = lambda name, target="tpu_custom_call": (
        f"%{name} = bf16[64,72,128] custom-call(%a, %b), "
        f'custom_call_target="{target}"')
    ops = [
        (decode, call("paged_decode_attn_window.3"), 0.30),
        (decode, call("paged_decode_attn.2"), 0.40),
        (decode, call("ragged-dot-none.7"), 1.40),
        (decode, call("ragged-dot-metadata.1"), 0.02),
        (decode, "%fusion.9 = f32[64,50176] fusion(%x), kind=kOutput", 0.28),
        (chunk, call("paged_prefill_attn_window.1"), 0.010),
        (chunk, call("paged_prefill_attn.1"), 0.015),
        (chunk, call("ragged-dot-none.9"), 0.050),
        (chunk, "%fusion.3 = bf16[256,3072] fusion(%x), kind=kLoop", 0.025),
    ]
    t = [10.0 + 0.25 * i for i in range(40)]            # the window's samples
    return {
        "engine": {"moe_experts_touched": 110.0, "moe_rows_max": 3.5,
                   "moe_rows_held": 5000, "moe_rows_routed": 10000,
                   "slot_occupancy": 0.97, "kv_pages_free_min": 1024,
                   "decode_block_fill": 0.8, "decode_live_column_share": 0.6,
                   "compiles_in_window": 0, "preemptions": 0,
                   "tick_host_share": 0.012, "engine_prefill_tok_s": 9000.0,
                   "decode_step_ms_p50": 21.5},
        "samples": {"t": t, "decoding_slots": [60] * 32 + [64] * 8,
                    "kv_tokens_decoding": [100_000] * 32 + [128_000] * 8},
        "trace_t0": t[32],                              # the last 8 samples
        "trace": {"ops": ops, "window_s": 2.8, "busy_s": 2.7,
                  "per_chip_busy_s": [2.7],
                  "programs": {decode: {"count": 100, "total_s": 2.6},
                               chunk: {"count": 4, "total_s": 0.1}}},
        "memory": {}, "peaks": {"hbm_bytes_per_s": 819e9},
        "consts": dict(configs.dims(config), chips=1, window_s=51.0,
                       n_pages=4096, page_size=64,
                       **family.serve_consts(config)),
    }


# What the scope reducer would make of the synthetic trace: seconds by
# scope in the two programs (harness/scope_times.scope_times' table).
_TABLE = {
    "busy_s": 2.7,
    "programs": {
        "jit__decode_sample_paged": {
            "runs": 100, "total_s": 2.6, "by_pass": {}, "unscoped_s": 0.004,
            "by_scope": {"attn.in": 0.09, "attn.out": 0.034,
                         "attn.kernel": 0.70, "attn.kv_write": 0.011,
                         "mlp": 0.036, "moe.route": 0.038,
                         "moe.experts": 1.40, "head": 0.041,
                         "sample": 0.009}},
        "jit_prefill_chunk_paged": {
            "runs": 4, "total_s": 0.1, "by_pass": {}, "unscoped_s": 0.001,
            "by_scope": {"attn.in": 0.010, "attn.out": 0.006,
                         "attn.kernel": 0.025, "moe.route": 0.008,
                         "moe.experts": 0.050}}},
}


def test_every_metric_of_the_cell_reads_a_synthetic_context(real,
                                                            monkeypatch):
    bench, config, family, _ref = real
    entries = configs.metrics_for_cell(bench, "per_layer", CELL)
    assert entries and all(CELL in m["workloads"] for m in entries)
    assert all(m["moves"] == "out_tokens_per_s" and "." not in m["name"]
               for m in entries)
    ctx = _context(family, config)
    monkeypatch.setattr(scope_times, "for_run", lambda _ctx: _TABLE)
    got = {n: v["value"] for n, v in readers.read_all(
        configs.metrics_dirs(util.REPO, bench), entries, ctx,
        {"out_tokens_per_s": 2000.0}).items()}
    # a metric a later PR adds to the cell may find nothing in THIS context
    # (it is left out, not 0); PR 36's twenty all read it
    assert set(got) <= {m["name"] for m in entries}
    c, peak = ctx["consts"], 819e9
    scoped = sum(sum(p["by_scope"].values())
                 for p in _TABLE["programs"].values())
    want = {
        "decode_program_dev_ms": 26.0,
        "prefill_program_dev_ms": 25.0,
        "decode_step_ms": 21.5,
        "prefill_tokens_per_s": 9000.0,
        "slot_occupancy": 97.0,
        "kv_pool_fill": 75.0,
        "compiles_in_window": 0.0,
        "tick_host_share": 1.2,
        "device_idle_share": (1 - 2.7 / 2.8) * 100,
        "preemptions": 0.0,
        "moe_expert_share": (1.40 + 0.02 + 0.050) / 2.7 * 100,
        "experts_touched": 110.0,
        "expert_rows_max": 3.5,
        "expert_rows_held_share": 50.0,
        # the kernel alone (not its metadata), a decode step
        "moe_expert_roofline":
            110.0 * c["decode_bytes_per_live_expert"] / peak / 0.0140 * 100,
        "attn_kernel_share":
            (0.30 + 0.40 + 0.010 + 0.015) / 2.7 * 100,
        "window_attn_share": (0.30 + 0.010) / 2.7 * 100,
        # samples of the TRACED interval: 64 slots, 128,000 tokens
        "window_attn_roofline":
            64 * c["decode_bytes_per_window_slot"] / peak / 0.0030 * 100,
        "decode_attn_roofline":
            128_000 * c["decode_bytes_per_kv_token"] / peak / 0.0040 * 100,
        "decode_stream_roofline": (
            c["decode_bytes_weights"]
            + 110.0 * c["decode_bytes_per_live_expert"]
            + 128_000 * c["decode_bytes_per_kv_token"]
            + 64 * c["decode_bytes_per_window_slot"]) / peak / 0.026 * 100,
        # the pairs PR 52 appended to entries the cell had been denied
        "decode_block_fill": 80.0,
        "decode_live_column_share": 60.0,
        "moe_route_ms": 0.38,
        "head_ms": (0.041 + 0.009) / 100 * 1000,
        "decode_dense_ms": (0.09 + 0.034) / 100 * 1000,
        "scope_coverage": scoped / 2.7 * 100,
    }
    assert set(want) <= set(got)
    for name, value in want.items():
        assert got[name] == pytest.approx(value, rel=1e-9), name
    for name in got:
        if "roofline" in name:
            assert 0 < got[name] < 100, name


def test_over_a_program_without_the_new_spans_the_readers_return_nothing(real):
    """The parent commit's traced run, this PR's files laid over it: no
    window call in the trace, no held-rows counter in `metrics()`. The
    new readers leave their metrics out and nothing raises."""
    bench, config, family, _ref = real
    ctx = _context(family, config)
    ctx["engine"] = {k: v for k, v in ctx["engine"].items()
                     if k != "moe_rows_held"}
    ctx["trace"]["ops"] = [op for op in ctx["trace"]["ops"]
                           if "_window" not in op[1]]
    entries = configs.metrics_for_cell(bench, "per_layer", CELL)
    got = readers.read_all(configs.metrics_dirs(util.REPO, bench), entries,
                           ctx, {"out_tokens_per_s": 2000.0})
    assert not {"window_attn_roofline",
                "expert_rows_held_share"} & set(got)
    assert got["window_attn_share"]["value"] == 0.0
    ctx["trace"] = None
    got = readers.read_all(configs.metrics_dirs(util.REPO, bench), entries,
                           ctx, {"out_tokens_per_s": 2000.0})
    traced = {m["name"] for m in entries if m["source"] == "device_trace"}
    assert len(traced) >= 10 and not traced & set(got)


def test_the_contract_check_is_clean():
    tools = os.path.join(util.BENCH_DIR, "tools")
    sys.path.insert(0, tools)
    try:
        import check_contract
    finally:
        sys.path.remove(tools)
    assert check_contract.main() == 0
