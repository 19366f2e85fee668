"""The `qwen3_next` family through the harness (a NEW test file: the cell
came as files and entries, so its tests do too).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_qwen3_next_family.py -q

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

CELL = "qwen3-next-80b-a3b.longform"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def real():
    bench = configs.load_benchmark(util.REPO)
    config = configs.load_config(util.REPO, bench, "qwen3-next-80b-a3b")
    family, reference = families.load(util.REPO, bench, config)
    return bench, config, family, reference


def test_the_configuration_is_the_published_one_with_three_cuts(real):
    """Every number of the public config.json under its own key; the
    three reduced keys state the share, `published` the source's values;
    the layers kept are two whole periods."""
    bench, config, family, _ref = real
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 512, "vocab_size": 151936}
    for key, value in {
            "hidden_size": 2048, "num_attention_heads": 16,
            "num_key_value_heads": 2, "head_dim": 256,
            "linear_num_key_heads": 16, "linear_num_value_heads": 32,
            "linear_key_head_dim": 128, "linear_value_head_dim": 128,
            "linear_conv_kernel_dim": 4, "moe_intermediate_size": 512,
            "shared_expert_intermediate_size": 512,
            "num_experts_per_tok": 10, "full_attention_interval": 4,
            "partial_rotary_factor": 0.25, "rope_theta": 10000000,
            "intermediate_size": 5120}.items():
        assert config[key] == value, key
    (entry,) = [c for c in bench["configs"]
                if c["name"] == "qwen3-next-80b-a3b"]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    cfg = family.program_config(config, max_seq=4096)
    assert cfg.kinds == ("linear", "linear", "linear", "full") * 2
    assert (cfg.n_experts, cfg.n_experts_routed, cfg.first_expert,
            cfg.top_k) == (128, 512, 0, 10)
    assert (cfg.rotary_dim, cfg.conv_channels, cfg.scan_block) == (
        64, 8192, 64)
    rc = family.reference_config(config)
    hash(rc)
    assert (rc.n_layers, rc.full_interval, rc.rotary_dim) == (8, 4, 64)
    for text in ("assumed", "departures", "deployment"):
        assert config[text]


def test_the_catalogs_numbers_are_all_there(real):
    """Where the catalog is at hand: every key of its `config`, the same
    value, but for the three that `reduced` names."""
    _bench, config, _family, _ref = real
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        pytest.skip("no catalog here")
    (row,) = [r for r in rows if r["source_url"] == config["source"]]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key


def test_the_family_counts_the_cells_parameters_and_bytes(real):
    """ISSUE 43's arithmetic, from the file's own sizes."""
    _bench, config, family, _ref = real
    per = family.layer_params(config)
    assert per["linear"] == 33_718_272                  # 33.72 M
    assert per["full"] == 27_262_976                    # 27.26 M
    assert (per["router"], per["shared"], per["expert"]) == (
        1_048_576, 3_147_776, 3_145_728)
    assert (per["n_linear"], per["n_full"]) == (6, 2)
    c = family.serve_consts(config)
    assert c["decode_bytes_per_live_expert"] == 8 * 3 * 2048 * 512 * 2
    assert c["decode_bytes_per_kv_token"] == 4096
    assert c["decode_bytes_per_state_slot"] == 6 * 2_146_304 * 2
    assert c["decode_bytes_weights"] == 2 * (
        6 * 33_718_272 + 2 * 27_262_976 + 8 * (1_048_576 + 3_147_776)
        + 2048 * 37984)
    assert c["chunk_scan_bytes_per_token"] == 6 * (
        4 * 32 * 512 + 2 * 4 * 32 * 128 * 128 // 128)
    assert c["chunk_scan_flops_per_token"] > 0
    # weights: 3,667 M parameters, 7.33 GB in bf16
    cfg = family.program_config(config)
    specs = family.model().param_specs(cfg)
    n_params = sum(math.prod(s["shape"]) for s in specs.values())
    assert n_params == 3_667_251_328
    # the decay's two leaves as the benchmark seeds them, the rest the
    # program's own
    assert (specs["g_dt_bias"]["init"], specs["g_dt_bias"]["scale"]) == (
        "normal", 4.0)
    assert (specs["g_A_log"]["init"], specs["g_A_log"]["scale"]) == (
        "normal", 1.0)
    assert specs["ln1_scale"]["init"] == "zeros"
    assert specs["g_norm"]["init"] == "ones"
    # the pool beside them: state 1.66 GB, pages 2.15 GB
    import jax

    pool = jax.eval_shape(lambda: family._program().init_paged_kv(
        cfg, 8192, 64, 128))
    nbytes = lambda a: math.prod(a.shape) * a.dtype.itemsize
    assert nbytes(pool["gdn_state"]) + nbytes(pool["gdn_conv"]) == (
        6 * 129 * 2_146_304)
    assert nbytes(pool["k"]) + nbytes(pool["v"]) == 2 * 2 * 8193 * 64 * 512 * 2


def test_the_traffic_is_the_issues(real):
    bench, _config, _family, _ref = real
    from harness import traffic

    mix = configs.load_traffic(util.REPO, bench, "longform")
    assert (mix["kind"], mix["clients"], mix["cycle_requests"]) == (
        "closed_loop", "n_slots", 128)
    src = traffic.ClosedLoopSource(mix, 2**31 + 77, 37984)
    assert set(map(int, src.p_len)) == {512}
    outs = sorted(map(int, src.o_len))
    assert (outs[0], outs[1], outs[-1], len(outs)) == (1544, 1560, 3576, 128)
    assert sum(outs) / len(outs) == 2560
    assert max(outs) + 512 <= 4088
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3-next-80b-a3b", "longform", 1)
    assert CELL in bench["end_to_end"][0]["workloads"]


TINY = {
    "hidden_size": 64, "num_attention_heads": 16, "num_key_value_heads": 2,
    "head_dim": 16, "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "num_experts": 4, "num_experts_per_tok": 3, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "vocab_size": 256,
    "published": {"num_experts": 8},
}


def tiny_config(config: dict) -> dict:
    """The real file cut to a size the CPU serves: every width small,
    the pattern and every ratio kept (two periods of 3 : 1; 2 value
    heads a key head; 8 query heads a KV head; rope on a quarter of a
    head; 4 of 8 experts held, top-3)."""
    tiny = copy.deepcopy(config)
    tiny.update(TINY, name="qwen3-next-tiny")
    tiny["serve"].update(page_size=16, n_pages=32, max_len=128,
                         prefill_chunk=64, n_slots=4, reference_factor=2.0,
                         deficit_slack=0.01, ref_sample=3)
    return tiny


def test_the_family_serves_through_the_harness_at_tiny_size(real, tmp_path):
    """families/qwen3_next.py, harness/reference/qwen3_next_ref.py and the
    cell's counter readers through run.py on the CPU: the engine's stream
    is held `correct` by `paired_rows`, and the metrics that read the
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
    got = util.rehearse(root, cell, seed=2**31 + 43, seconds=1.5, trace=1)
    out = got["line"]
    assert out["correct"] is True and out["failed"] == 0
    assert any("reference check over" in ln and ": ok" in ln
               for ln in got["log"])
    value = lambda n: out["metrics"]["cpu_rehearsal." + n]["value"]
    assert 1.0 <= value("experts_touched") <= 4
    assert 20.0 < value("expert_rows_held_share") < 80.0
    assert value("preemptions") == 0
    # No device plane in a CPU trace: trace-sourced metrics are left out.
    assert not any("roofline" in n or "dev_ms" in n or "gdn" in n
                   for n in out["metrics"])


def _context(family, config) -> dict:
    """A synthetic context of a traced run: 100 decode steps of 24 ms and
    8 chunk programs of 12 ms in a traced 2.8 s, with known kernel
    times."""
    decode, chunk = "jit__decode_sample_paged(1)", "jit_prefill_chunk_paged(2)"
    call = lambda name, target="tpu_custom_call": (
        f"%{name} = bf16[128,16,256] custom-call(%a, %b), "
        f'custom_call_target="{target}"')
    ops = [
        (decode, call("gdn_decode_step.3"), 0.60),
        (decode, call("paged_decode_attn.2"), 0.20),
        (decode, call("ragged-dot-none.7"), 1.10),
        (decode, call("ragged-dot-metadata.1"), 0.02),
        (decode, "%fusion.9 = f32[128,37984] fusion(%x), kind=kOutput", 0.12),
        (chunk, call("paged_prefill_attn.1"), 0.010),
        (chunk, call("ragged-dot-none.9"), 0.050),
        (chunk, "%fusion.3 = bf16[256,2048] fusion(%x), kind=kLoop", 0.020),
    ]
    t = [10.0 + 0.25 * i for i in range(40)]            # the window's samples
    return {
        "engine": {"moe_experts_touched": 118.0, "moe_rows_max": 3.1,
                   "moe_rows_held": 2500, "moe_rows_routed": 10000,
                   "slot_occupancy": 0.98, "kv_pages_free_min": 2048,
                   "decode_block_fill": 0.9, "decode_live_column_share": 0.55,
                   "compiles_in_window": 0, "preemptions": 0,
                   "tick_host_share": 0.011, "engine_prefill_tok_s": 20000.0,
                   "decode_step_ms_p50": 24.5, "prefill_tokens": 2048,
                   "prefill_dispatches": 8},
        "samples": {"t": t, "decoding_slots": [120] * 32 + [126] * 8,
                    "kv_tokens_decoding": [250_000] * 32 + [280_000] * 8},
        "trace_t0": t[32],                              # the last 8 samples
        "trace": {"ops": ops, "window_s": 2.8, "busy_s": 2.7,
                  "per_chip_busy_s": [2.7],
                  "programs": {decode: {"count": 100, "total_s": 2.4},
                               chunk: {"count": 8, "total_s": 0.096}}},
        "memory": {}, "peaks": {"hbm_bytes_per_s": 819e9,
                                "flops_bf16": 197e12},
        "consts": dict(configs.dims(config), chips=1, window_s=51.0,
                       n_pages=8192, page_size=64,
                       **family.serve_consts(config)),
    }


# What the scope reducer would make of the synthetic trace: seconds by
# scope in the two programs (harness/scope_times.scope_times' table).
_TABLE = {
    "busy_s": 2.7,
    "programs": {
        "jit__decode_sample_paged": {
            "runs": 100, "total_s": 2.4, "by_pass": {}, "unscoped_s": 0.05,
            "by_scope": {"gdn.in": 0.22, "gdn.scan": 0.62, "gdn.out": 0.08,
                         "moe.route": 0.15, "moe.experts": 1.10,
                         "attn.in": 0.007, "attn.out": 0.003,
                         "attn.kernel": 0.20, "head": 0.10, "sample": 0.02}},
        "jit_prefill_chunk_paged": {
            "runs": 8, "total_s": 0.096, "by_pass": {}, "unscoped_s": 0.001,
            "by_scope": {"gdn.in": 0.012, "gdn.scan": 0.016,
                         "gdn.out": 0.004, "moe.experts": 0.050}}},
}


def test_every_metric_of_the_cell_reads_a_synthetic_context(real,
                                                            monkeypatch):
    bench, config, family, _ref = real
    entries = configs.metrics_for_cell(bench, "per_layer", CELL)
    assert len(entries) >= 26 and all(CELL in m["workloads"]
                                      for m in entries)
    assert all(m["moves"] == "out_tokens_per_s" and "." not in m["name"]
               for m in entries)
    ctx = _context(family, config)
    monkeypatch.setattr(scope_times, "for_run", lambda _ctx: _TABLE)
    got = {n: v["value"] for n, v in readers.read_all(
        configs.metrics_dirs(util.REPO, bench), entries, ctx,
        {"out_tokens_per_s": 5000.0}).items()}
    assert set(got) == {m["name"] for m in entries}
    c, peak = ctx["consts"], 819e9
    scoped = sum(sum(p["by_scope"].values())
                 for p in _TABLE["programs"].values())
    want = {
        "decode_program_dev_ms": 24.0,
        "prefill_program_dev_ms": 12.0,
        "decode_step_ms": 24.5,
        "prefill_tokens_per_s": 20000.0,
        "slot_occupancy": 98.0,
        "kv_pool_fill": 75.0,
        "compiles_in_window": 0.0,
        "tick_host_share": 1.1,
        "device_idle_share": (1 - 2.7 / 2.8) * 100,
        "preemptions": 0.0,
        "moe_expert_share": (1.10 + 0.02 + 0.050) / 2.7 * 100,
        "experts_touched": 118.0,
        "expert_rows_max": 3.1,
        "expert_rows_held_share": 25.0,
        # the kernel alone (not its metadata), a decode step
        "moe_expert_roofline":
            118.0 * c["decode_bytes_per_live_expert"] / peak / 0.0110 * 100,
        "attn_kernel_share": (0.20 + 0.010) / 2.7 * 100,
        # samples of the TRACED interval: 126 slots, 280,000 tokens
        "decode_attn_roofline":
            280_000 * c["decode_bytes_per_kv_token"] / peak / 0.0020 * 100,
        "decode_stream_roofline": (
            c["decode_bytes_weights"]
            + 118.0 * c["decode_bytes_per_live_expert"]
            + 280_000 * c["decode_bytes_per_kv_token"]
            + 126 * c["decode_bytes_per_state_slot"]) / peak / 0.024 * 100,
        "moe_route_ms": 1.5,
        "head_ms": 1.2,
        "scope_coverage": scoped / 2.7 * 100,
        "gdn_share":
            (0.22 + 0.62 + 0.08 + 0.012 + 0.016 + 0.004) / 2.7 * 100,
        "gdn_scan_share": (0.62 + 0.016) / 2.7 * 100,
        "gdn_proj_ms": 3.0,
        "gdn_step_roofline":
            126 * c["decode_bytes_per_state_slot"] / peak / 0.0062 * 100,
        # 256 tokens a dispatch; the bytes bound it
        "gdn_chunk_roofline":
            256 * c["chunk_scan_bytes_per_token"] / peak / 0.002 * 100,
        # the pairs PR 52 appended to entries the cell had lacked
        "decode_block_fill": 90.0,
        "decode_live_column_share": 55.0,
        "decode_dense_ms": (0.007 + 0.003) / 100 * 1000,
    }
    assert set(want) == set(got)
    for name, value in want.items():
        assert got[name] == pytest.approx(value, rel=1e-9), name
    assert (256 * c["chunk_scan_bytes_per_token"] / peak
            > 256 * c["chunk_scan_flops_per_token"] / 197e12)
    for name in got:
        if "roofline" in name:
            assert 0 < got[name] < 100, name


def test_over_a_program_without_the_new_scopes_the_readers_return_nothing(
        real, monkeypatch):
    """This PR's files laid over a program whose vocabulary lacks the
    `gdn.*` scopes (the parent), or a run that was not traced: the new
    readers leave their metrics out and nothing raises."""
    bench, config, family, _ref = real
    entries = configs.metrics_for_cell(bench, "per_layer", CELL)
    ctx = _context(family, config)
    old = tuple(s for s in scope_times.vocabulary() if not s.startswith("gdn"))
    monkeypatch.setattr(scope_times, "vocabulary", lambda: old)
    monkeypatch.setattr(scope_times, "for_run", lambda _ctx: _TABLE)
    got = readers.read_all(configs.metrics_dirs(util.REPO, bench), entries,
                           ctx, {"out_tokens_per_s": 5000.0})
    assert not [n for n in got if n.startswith("gdn")]
    monkeypatch.undo()
    ctx["trace"] = None
    got = readers.read_all(configs.metrics_dirs(util.REPO, bench), entries,
                           ctx, {"out_tokens_per_s": 5000.0})
    traced = {m["name"] for m in entries if m["source"] == "device_trace"}
    assert len(traced) >= 16 and not traced & set(got)
