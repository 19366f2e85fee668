"""The `jamba` family through the harness (a NEW test file: the cell came
as files and entries, so its tests do too).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_jamba_family.py -q

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

CELL = "ai21-jamba2-3b.solve"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("ssm_share", "ssm_scan_share", "ssm_proj_ms", "ssm_step_roofline",
       "ssm_chunk_roofline", "decode_stream_mfu")


@pytest.fixture(scope="module")
def real():
    bench = configs.load_benchmark(util.REPO)
    config = configs.load_config(util.REPO, bench, "ai21-jamba2-3b")
    family, reference = families.load(util.REPO, bench, config)
    return bench, config, family, reference


def test_the_configuration_is_the_published_one_uncut(real):
    """Every number of the public config.json under its own key, nothing
    reduced, `published` equal to what is run; 13 mamba layers to one
    attention layer, layers 7 and 21."""
    bench, config, family, _ref = real
    assert config["reduced"] == []
    for key, value in {
            "num_hidden_layers": 28, "hidden_size": 2560,
            "num_attention_heads": 20, "num_key_value_heads": 1,
            "intermediate_size": 8192, "mamba_d_state": 16,
            "mamba_d_conv": 4, "mamba_dt_rank": 160, "mamba_expand": 2,
            "vocab_size": 65536, "tie_word_embeddings": True,
            "attn_layer_period": 14, "attn_layer_offset": 7,
            "num_experts": 1, "rms_norm_eps": 1e-6}.items():
        assert config[key] == value, key
    for key, value in config["published"].items():
        assert config[key] == value, key
    (entry,) = [c for c in bench["configs"] if c["name"] == "ai21-jamba2-3b"]
    assert entry["reduced"] == [] and entry["source"] == config["source"]
    assert entry["file"] == "benchmarks/configs/ai21-jamba2-3b.json"
    cfg = family.program_config(config, max_seq=4096)
    assert [l for l, k in enumerate(cfg.kinds) if k == "attn"] == [7, 21]
    assert (cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.dt_rank) == (
        5120, 16, 4, 160)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff) == (
        20, 1, 128, 8192)
    rc = family.reference_config(config)
    hash(rc)
    assert (rc.n_layers, rc.attn_period, rc.attn_offset) == (28, 14, 7)
    for text in ("assumed", "departures", "deployment"):
        assert config[text]
    for said in ("layer_order", "state_dtype", "inner_norms", "no_positions",
                 "seeded_leaves"):
        assert said in config["assumed"]
    geo = config["serve"]
    assert (geo["n_slots"], geo["max_len"], geo["prefill_chunk"],
            geo["chips"], geo["tp"]) == (256, 4096, 128, 1, 1)
    # pages for every slot's whole context: no request can be preempted
    assert geo["n_pages"] * geo["page_size"] == 256 * 4096
    assert (geo["reference_factor"], geo["deficit_slack"]) == (3.0, 1e-4)
    assert 4 <= geo["ref_sample"] <= 8


def test_a_file_that_asks_for_what_the_family_does_not_build_is_refused(real):
    _bench, config, family, _ref = real
    sparse = dict(config, num_experts=16)
    with pytest.raises(SystemExit, match="num_experts"):
        family.program_config(sparse)
    wide = dict(config, head_dim=64)
    with pytest.raises(SystemExit, match="d_model / n_heads"):
        family.reference_config(wide)


def test_the_catalogs_numbers_are_all_there(real):
    """Where the catalog is at hand: every key of its `config`, the same
    value, and none reduced."""
    _bench, config, _family, _ref = real
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        pytest.skip("no catalog here")
    (row,) = [r for r in rows if r["source_url"] == config["source"]]
    assert len(row["config"]) == 26
    for key, value in row["config"].items():
        assert config[key] == value, key


def test_the_family_counts_the_cells_parameters_and_bytes(real):
    """ISSUE 53's arithmetic, from the file's own sizes."""
    _bench, config, family, _ref = real
    per = family.layer_params(config)
    assert per["mamba"] == (2560 * 10240 + 5120 * 192 + 160 * 5120
                            + 5120 * 2560) == 41_123_840      # 41.1 M
    assert per["attn"] == 2 * 2560 * 2560 + 2 * 2560 * 128 == 13_762_560
    assert per["mlp"] == 62_914_560 and per["table"] == 167_772_160
    assert (per["n_mamba"], per["n_attn"]) == (26, 2)
    assert 3.02e9 < family.n_params(config) < 3.04e9            # 3.03 B
    c = family.serve_consts(config)
    assert c["decode_bytes_per_kv_token"] == 1024
    # 9.32 MB a slot, read and written
    slot = 26 * (16 * 5120 * 4 + 3 * 5120 * 2)
    assert slot == 9_318_400 and c["decode_bytes_per_state_slot"] == 2 * slot
    assert c["ssm_step_bytes_per_slot"] == 2 * 26 * 16 * 5120 * 4
    assert (c["decode_bytes_per_live_expert"],
            c["decode_bytes_per_window_slot"]) == (0.0, 0.0)
    matmul = 26 * 41_123_840 + 2 * 13_762_560 + 28 * 62_914_560 + 167_772_160
    assert c["decode_bytes_weights"] == 2 * matmul
    assert 6.0e9 < c["decode_bytes_weights"] < 6.1e9            # 6.06 GB
    assert c["decode_flops_per_row"] == 2.0 * matmul
    assert c["chunk_scan_bytes_per_token"] == 26 * (
        4 * (3 * 5120 + 32) + 2 * 4 * 16 * 5120 // 128)
    # the tree the harness fills: every parameter, the table once
    cfg = family.program_config(config)
    specs = family.model().param_specs(cfg)
    assert sum(math.prod(s["shape"]) for s in specs.values()) == (
        family.n_params(config))
    # the three stacks the benchmark seeds, the rest the program's own
    for name, scale in (("m_dt_b", 4.0), ("m_A_log", 1.0),
                        ("ln_f_scale", 1.0)):
        assert (specs[name]["init"], specs[name]["scale"]) == ("normal",
                                                               scale)
    assert specs["m_D"]["init"] == specs["m_dt_norm"]["init"] == "ones"
    assert {s["init"] for s in specs.values()} == {"normal", "ones"}
    # the pool beside them: state and tails 2.39 GB, pages 1.07 GB
    import jax

    pool = jax.eval_shape(lambda: family._program().init_paged_kv(
        cfg, 8192, 128, 256))
    nbytes = lambda a: math.prod(a.shape) * a.dtype.itemsize
    assert nbytes(pool["ssm_state"]) + nbytes(pool["ssm_conv"]) == 257 * slot
    assert nbytes(pool["k"]) + nbytes(pool["v"]) == 2 * 2 * 8193 * 128 * 128 * 2
    assert set(pool) == {"k", "v", "ssm_state", "ssm_conv"}


def test_the_traffic_is_the_issues(real):
    bench, _config, _family, _ref = real
    from harness import traffic

    mix = configs.load_traffic(util.REPO, bench, "solve")
    assert (mix["kind"], mix["clients"], mix["cycle_requests"]) == (
        "closed_loop", "n_slots", 256)
    assert (mix["ramp_s"], mix["trace_s"]) == (30, 8)
    src = traffic.ClosedLoopSource(mix, 2**31 + 77, 65536)
    assert set(map(int, src.p_len)) == {256}
    outs = sorted(map(int, src.o_len))
    # ISSUE 53's fallback range (the first, 1,536-3,584, cannot end inside
    # ramp + window at the measured step: `lengths_note`)
    assert (outs[0], outs[1], outs[-1], len(outs)) == (1027, 1033, 2557, 256)
    assert sum(outs) / len(outs) == 1792
    assert max(outs) + 256 <= 2816
    assert "85.6 s" in mix["lengths_note"]
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ai21-jamba2-3b", "solve", 1)
    assert CELL in bench["end_to_end"][0]["workloads"]
    assert bench["end_to_end"][0]["bound"] == 0.01
    assert (len(bench["workloads"]), len(bench["configs"]),
            len(bench["per_layer"])) == (7, 6, 85)


TINY = {
    "hidden_size": 64, "num_hidden_layers": 8, "attn_layer_period": 4,
    "attn_layer_offset": 2, "num_attention_heads": 4,
    "num_key_value_heads": 1, "head_dim": 16, "intermediate_size": 96,
    "mamba_d_state": 8, "mamba_dt_rank": 8, "vocab_size": 256,
}


def tiny_config(config: dict) -> dict:
    """The real file cut to a size the CPU serves: every width small,
    the pattern and every ratio kept (two periods of 3 : 1 with the
    attention layer mid-period; 4 query heads over ONE KV head; channels
    twice the width)."""
    tiny = copy.deepcopy(config)
    tiny.update(TINY, name="jamba-tiny")
    tiny["serve"].update(page_size=16, n_pages=32, max_len=128,
                         prefill_chunk=64, n_slots=4, reference_factor=2.0,
                         deficit_slack=0.01, ref_sample=3)
    return tiny


def test_the_family_serves_through_the_harness_at_tiny_size(real, tmp_path):
    """families/jamba.py and harness/reference/jamba_ref.py through
    run.py on the CPU: the engine's stream is held `correct` by
    `paired_rows`, and the counter metrics the cell lists are in the
    line."""
    _bench, config, _family, _ref = real
    root = util.make_root(str(tmp_path))
    counters = [("slot_occupancy", "%"), ("kv_pool_fill", "%"),
                ("compiles_in_window", "count"), ("preemptions", "count"),
                ("decode_block_fill", "%")]
    cell = util.add_cell(
        root, tiny_config(config), "batch", ["out_tokens_per_s"],
        [{"name": n, "unit": u, "moves": "out_tokens_per_s"}
         for n, u in counters]
        + [{"name": n, "unit": "%", "moves": "out_tokens_per_s"}
           for n in NEW])
    got = util.rehearse(root, cell, seed=2**31 + 53, seconds=1.5, trace=1)
    out = got["line"]
    assert out["correct"] is True and out["failed"] == 0
    assert any("reference check over" in ln and ": ok" in ln
               for ln in got["log"])
    value = lambda n: out["metrics"]["cpu_rehearsal." + n]["value"]
    assert value("preemptions") == 0 and value("slot_occupancy") > 0
    # No device plane in a CPU trace: trace-sourced metrics are left out.
    assert not any("roofline" in n or "dev_ms" in n or "ssm" in n
                   or "mfu" in n for n in out["metrics"])


def test_the_reference_agrees_with_the_program_at_tiny_size(real):
    """The family's two halves on the harness's own seeded weights: the
    program's full-sequence forward against `jamba_ref.logits`, float32
    (3e-5: reassociation only; tests/test_jamba.py has the paged
    programs and the controls)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import weights

    _bench, config, family, reference = real
    tiny = tiny_config(config)
    cfg = dataclasses_replace(family.program_config(tiny, max_seq=128))
    params = weights.make_params(family.model(), cfg, 2**31 + 5, jnp.float32)
    tokens = np.random.default_rng(0).integers(1, 256, (1, 50)).astype(
        np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(family._program().forward(cfg, params,
                                                   jnp.asarray(tokens)))[0]
    want = np.asarray(reference.logits(params, jnp.asarray(tokens[0]),
                                       family.reference_config(tiny)))
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=3e-5, rtol=0)
    # the seeded decay spreads: fast and slow (state, channel) pairs both
    rate = (np.asarray(jax.nn.softplus(params["m_dt_b"]))[:, None, :]
            * np.exp(np.asarray(params["m_A_log"])))
    assert (rate > 1).mean() > 0.2 and (rate < 1 / 15).mean() > 0.1


def dataclasses_replace(cfg):
    import dataclasses

    import jax.numpy as jnp

    return dataclasses.replace(cfg, dtype=jnp.float32)


def _context(family, config) -> dict:
    """A synthetic context of a traced run: 100 decode steps of 16 ms and
    8 chunk programs of 12 ms in a traced 2.0 s, with known kernel
    times."""
    decode, chunk = "jit__decode_sample_paged(1)", "jit_prefill_chunk_paged(2)"
    call = lambda name: (
        f"%{name} = bf16[256,20,128] custom-call(%a, %b), "
        'custom_call_target="tpu_custom_call"')
    ops = [
        (decode, call("ssm_decode_step.3"), 0.65),
        (decode, call("paged_decode_attn.2"), 0.08),
        (decode, "%fusion.9 = f32[256,65536] fusion(%x), kind=kOutput", 0.10),
        (chunk, call("ssm_chunk_scan.4"), 0.020),
        (chunk, call("paged_prefill_attn.1"), 0.004),
    ]
    t = [10.0 + 0.25 * i for i in range(40)]            # the window's samples
    return {
        "engine": {"slot_occupancy": 0.995, "kv_pages_free_min": 4096,
                   "decode_block_fill": 0.9, "decode_live_column_share": 0.45,
                   "compiles_in_window": 0, "preemptions": 0,
                   "tick_host_share": 0.012, "engine_prefill_tok_s": 30000.0,
                   "decode_step_ms_p50": 16.4, "prefill_tokens": 2048,
                   "prefill_dispatches": 8},
        "samples": {"t": t, "decoding_slots": [250] * 32 + [254] * 8,
                    "kv_tokens_decoding": [400_000] * 32 + [420_000] * 8},
        "trace_t0": t[32],                              # the last 8 samples
        "trace": {"ops": ops, "window_s": 2.0, "busy_s": 1.9,
                  "per_chip_busy_s": [1.9],
                  "programs": {decode: {"count": 100, "total_s": 1.6},
                               chunk: {"count": 8, "total_s": 0.096}}},
        "memory": {}, "peaks": {"hbm_bytes_per_s": 819e9,
                                "flops_bf16": 197e12},
        "consts": dict(configs.dims(config), chips=1, window_s=51.0,
                       n_pages=8192, page_size=128,
                       **family.serve_consts(config)),
    }


# What the scope reducer would make of the synthetic trace: seconds by
# scope in the two programs (harness/scope_times.scope_times' table).
_TABLE = {
    "busy_s": 1.9,
    "programs": {
        "jit__decode_sample_paged": {
            "runs": 100, "total_s": 1.6, "by_pass": {}, "unscoped_s": 0.03,
            "by_scope": {"ssm.in": 0.30, "ssm.scan": 0.65, "ssm.out": 0.10,
                         "mlp": 0.35, "attn.in": 0.006, "attn.out": 0.004,
                         "attn.kernel": 0.08, "head": 0.07, "sample": 0.01}},
        "jit_prefill_chunk_paged": {
            "runs": 8, "total_s": 0.096, "by_pass": {}, "unscoped_s": 0.001,
            "by_scope": {"ssm.in": 0.024, "ssm.scan": 0.020,
                         "ssm.out": 0.008, "mlp": 0.030,
                         "attn.kernel": 0.004}}},
}


def test_every_metric_of_the_cell_reads_a_synthetic_context(real,
                                                            monkeypatch):
    bench, config, family, _ref = real
    entries = configs.metrics_for_cell(bench, "per_layer", CELL)
    assert len(entries) == 23 and all(CELL in m["workloads"]
                                      for m in entries)
    assert all(m["moves"] == "out_tokens_per_s" and "." not in m["name"]
               for m in entries)
    assert [m["name"] for m in entries[-6:]] == list(NEW)
    assert not [m["name"] for m in entries
                if m["name"].startswith(("moe_", "expert", "window_", "gdn_"))
                or m["name"] == "decode_stream_roofline"]
    ctx = _context(family, config)
    monkeypatch.setattr(scope_times, "for_run", lambda _ctx: _TABLE)
    got = {n: v["value"] for n, v in readers.read_all(
        configs.metrics_dirs(util.REPO, bench), entries, ctx,
        {"out_tokens_per_s": 12000.0}).items()}
    assert set(got) == {m["name"] for m in entries}
    c, peak = ctx["consts"], 819e9
    scoped = sum(sum(p["by_scope"].values())
                 for p in _TABLE["programs"].values())
    # the step's bytes: weights + 420,000 cached tokens + 254 slots' state
    step_bytes = (c["decode_bytes_weights"] + 420_000 * 1024
                  + 254 * c["decode_bytes_per_state_slot"])
    want = {
        "decode_program_dev_ms": 16.0,
        "prefill_program_dev_ms": 12.0,
        "decode_step_ms": 16.4,
        "prefill_tokens_per_s": 30000.0,
        "slot_occupancy": 99.5,
        "kv_pool_fill": 50.0,
        "compiles_in_window": 0.0,
        "preemptions": 0.0,
        "tick_host_share": 1.2,
        "device_idle_share": (1 - 1.9 / 2.0) * 100,
        "attn_kernel_share": (0.08 + 0.004) / 1.9 * 100,
        # samples of the TRACED interval: 254 slots, 420,000 tokens
        "decode_attn_roofline": 420_000 * 1024 / peak / 0.0008 * 100,
        "decode_block_fill": 90.0,
        "decode_live_column_share": 45.0,
        "head_ms": 0.8,
        "decode_dense_ms": 0.1,
        "scope_coverage": scoped / 1.9 * 100,
        "ssm_share": (0.30 + 0.65 + 0.10 + 0.024 + 0.020 + 0.008) / 1.9 * 100,
        "ssm_scan_share": (0.65 + 0.020) / 1.9 * 100,
        "ssm_proj_ms": 4.0,
        "ssm_step_roofline":
            254 * c["ssm_step_bytes_per_slot"] / peak / 0.0065 * 100,
        # 256 tokens a dispatch, 2.5 ms of scan a chunk program
        "ssm_chunk_roofline":
            256 * c["chunk_scan_bytes_per_token"] / peak / 0.0025 * 100,
        # the bytes bound the step (13.7 ms against the matmuls' 7.8)
        "decode_stream_mfu": step_bytes / peak / 0.016 * 100,
    }
    assert set(want) == set(got)
    for name, value in want.items():
        assert got[name] == pytest.approx(value, rel=1e-9), name
    assert step_bytes / peak > 254 * c["decode_flops_per_row"] / 197e12
    for name in got:
        if "roofline" in name or "mfu" in name:
            assert 0 < got[name] < 100, name


def test_the_whole_steps_share_takes_the_larger_of_bytes_and_operations(
        real, monkeypatch):
    """`decode_stream_mfu` over a family that states fewer terms: a
    missing or zero term counts 0, an `experts_touched` that was read is
    counted, and with many rows a step the operations bound it."""
    bench, config, family, _ref = real
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "decode_stream_mfu"]
    read = lambda ctx, m: readers.read_all(
        configs.metrics_dirs(util.REPO, bench), [entry], ctx, m).get(
            "decode_stream_mfu", {}).get("value")
    ctx = _context(family, config)
    base = {"decode_program_dev_ms": 16.0}
    ctx["consts"] = {"chips": 1, "decode_bytes_weights": 4e9}
    assert read(ctx, base) == pytest.approx(4e9 / 819e9 / 0.016 * 100)
    ctx["consts"]["decode_bytes_per_live_expert"] = 1e7
    assert read(ctx, dict(base, experts_touched=100.0)) == pytest.approx(
        5e9 / 819e9 / 0.016 * 100)
    assert read(ctx, base) == pytest.approx(4e9 / 819e9 / 0.016 * 100)
    ctx["consts"]["decode_flops_per_row"] = 1e10      # 254 rows: 12.9 ms
    assert read(ctx, base) == pytest.approx(
        254 * 1e10 / 197e12 / 0.016 * 100)
    assert read(ctx, {}) is None                      # no step time read
    ctx["consts"].pop("decode_bytes_weights")
    assert read(ctx, base) is None


def test_over_a_program_without_the_new_scopes_the_readers_return_nothing(
        real, monkeypatch):
    """This PR's files laid over a program whose vocabulary lacks the
    `ssm.*` scopes (the parent), or a run that was not traced: the new
    readers leave their metrics out and nothing raises."""
    bench, config, family, _ref = real
    entries = configs.metrics_for_cell(bench, "per_layer", CELL)
    ctx = _context(family, config)
    old = tuple(s for s in scope_times.vocabulary() if not s.startswith("ssm"))
    monkeypatch.setattr(scope_times, "vocabulary", lambda: old)
    monkeypatch.setattr(scope_times, "for_run", lambda _ctx: _TABLE)
    got = readers.read_all(configs.metrics_dirs(util.REPO, bench), entries,
                           ctx, {"out_tokens_per_s": 12000.0})
    assert not [n for n in got if n.startswith("ssm")]
    monkeypatch.undo()
    ctx["trace"] = None
    got = readers.read_all(configs.metrics_dirs(util.REPO, bench), entries,
                           ctx, {"out_tokens_per_s": 12000.0})
    traced = {m["name"] for m in entries if m["source"] == "device_trace"}
    assert len(traced) >= 14 and not traced & set(got)


def test_the_contract_holds_with_85_entries():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "check_contract", os.path.join(util.BENCH_DIR, "tools",
                                       "check_contract.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.errors(util.REPO) == []
