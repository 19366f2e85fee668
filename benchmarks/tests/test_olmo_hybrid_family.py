"""The `olmo_hybrid` family through the harness (a NEW test file: the
cell came as files and entries, so its tests do too).

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_olmo_hybrid_family.py -q

The rehearsal goes through `run.main(..., rehearsal=True)` on the CPU: no
device metric is printed or asserted. The readers of the cell's per-layer
metrics are held to hand arithmetic over a synthetic context, and to
returning nothing (not raising) over a program that lacks what they read.
"""

from __future__ import annotations

import copy
import dataclasses
import json
import math

import pytest

import util
from harness import configs, families, readers, scope_times

CELL = "olmo-hybrid-7b.rollout"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("state_cache_byte_share", "kv_cache_byte_share")


@pytest.fixture(scope="module")
def real():
    bench = configs.load_benchmark(util.REPO)
    config = configs.load_config(util.REPO, bench, "olmo-hybrid-7b")
    family, reference = families.load(util.REPO, bench, config)
    return bench, config, family, reference


def test_the_configuration_is_the_published_one_cut_in_depth_alone(real):
    """Every width of the public config.json under its own key and the
    whole vocabulary; ONE key reduced, the depth, to two whole periods."""
    bench, config, family, _ref = real
    assert config["reduced"] == ["num_hidden_layers"]
    assert config["published"] == {"num_hidden_layers": 32}
    for key, value in {
            "num_hidden_layers": 8, "hidden_size": 3840,
            "num_attention_heads": 30, "num_key_value_heads": 30,
            "intermediate_size": 11008, "linear_num_key_heads": 30,
            "linear_num_value_heads": 30, "linear_key_head_dim": 96,
            "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
            "linear_allow_neg_eigval": True, "vocab_size": 100352,
            "tie_word_embeddings": False, "rms_norm_eps": 1e-6,
            "rope_parameters": {"rope_theta": None}}.items():
        assert config[key] == value, key
    assert len(config["layer_types"]) == 32       # kept whole, as published
    (entry,) = [c for c in bench["configs"] if c["name"] == "olmo-hybrid-7b"]
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config["source"]
    assert entry["file"] == "benchmarks/configs/olmo-hybrid-7b.json"
    cfg = family.program_config(config, max_seq=2816)
    assert cfg.kinds == ("linear", "linear", "linear", "full") * 2
    assert (cfg.lin_k_heads, cfg.lin_v_heads, cfg.lin_k_dim, cfg.lin_v_dim,
            cfg.conv_taps, cfg.allow_neg_eigval) == (30, 30, 96, 192, 4, True)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff,
            cfg.vocab_size) == (30, 30, 128, 11008, 100352)
    rc = family.reference_config(config)
    hash(rc)
    assert (rc.n_layers, rc.full_interval, rc.lin_heads, rc.lin_k_dim,
            rc.neg_eigval) == (8, 4, 30, 96, True)
    for text in ("assumed", "departures", "deployment", "reduced_note"):
        assert config[text]
    for said in ("norm_placement", "qk_norm", "no_rotary", "state_dtype",
                 "decay_weights"):
        assert said in config["assumed"]
    assert "four pipeline stages" in config["deployment"].lower()
    assert "host's share" in config["reduced_note"].lower()
    geo = config["serve"]
    assert (geo["n_slots"], geo["max_len"], geo["prefill_chunk"],
            geo["chips"], geo["tp"]) == (96, 2816, 128, 1, 1)
    # pages for every slot's whole context: no request can be preempted
    assert geo["n_pages"] * geo["page_size"] == 96 * 2816
    assert (geo["reference_factor"], geo["deficit_slack"]) == (3.0, 1e-4)
    assert geo["ref_sample"] == 8


def test_a_file_that_asks_for_what_the_family_does_not_build_is_refused(real):
    _bench, config, family, _ref = real
    with pytest.raises(SystemExit, match="rope_parameters"):
        family.program_config(dict(
            config, rope_parameters={"rope_theta": 500000.0}))
    with pytest.raises(SystemExit, match="d_model / n_heads"):
        family.reference_config(dict(config, head_dim=64))
    with pytest.raises(SystemExit, match="every n-th layer"):
        family.program_config(dict(
            config, layer_types=["full_attention"] + config["layer_types"]))


def test_the_catalogs_numbers_are_all_there(real):
    """Where the catalog is at hand: every key of its `config` with the
    same value, but the one reduced."""
    _bench, config, _family, _ref = real
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        pytest.skip("no catalog here")
    (row,) = [r for r in rows if r["source_url"] == config["source"]]
    for key, value in row["config"].items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert row["config"]["num_hidden_layers"] == (
        config["published"]["num_hidden_layers"])


def test_the_family_counts_the_cells_parameters_and_bytes(real):
    """ISSUE 60's arithmetic, from the file's own sizes."""
    _bench, config, family, _ref = real
    per = family.layer_params(config)
    mlp = 3 * 3840 * 11008
    assert per["linear"] + mlp == 215_562_240            # 215.56 M
    assert per["full"] + mlp == 185_794_560              # 185.80 M
    assert (per["n_linear"], per["n_full"]) == (6, 2)
    layers = 6 * (per["linear"] + mlp) + 2 * (per["full"] + mlp)
    assert round(layers / 1e6, 1) == 1665.0
    c = family.serve_consts(config)
    assert c["decode_bytes_weights"] == 2 * (layers + 3840 * 100352)
    assert round(c["decode_bytes_weights"] / 1e9, 2) == 4.10
    assert c["decode_bytes_per_kv_token"] == 30_720
    slot = 6 * (30 * 96 * 192 * 4 + 3 * 11520 * 2)
    assert round(slot / 1e6, 1) == 13.7                  # 13.7 MB a slot
    assert c["decode_bytes_per_state_slot"] == 2 * slot
    assert (c["decode_bytes_per_live_expert"],
            c["decode_bytes_per_window_slot"]) == (0.0, 0.0)
    assert c["decode_flops_per_row"] == float(c["decode_bytes_weights"])
    assert c["chunk_scan_bytes_per_token"] == 6 * (
        4 * 30 * (2 * 96 + 2 * 192) + 2 * 4 * 30 * 96 * 192 // 128)
    T, dk, dv = 64, 96, 192
    block = 2 * (2 * T * T * dk + 1344 * T + T * T * dv + T * T * dk
                 + 3 * T * dk * dv + T * T * dv)
    assert c["chunk_scan_flops_per_token"] == 6 * 30 * block // T
    # a step at 96 slots and ~1,200 cached tokens a slot: 40 / 35 / 26 %
    kv, state = 96 * 1200 * 30_720, 96 * c["decode_bytes_per_state_slot"]
    total = c["decode_bytes_weights"] + kv + state
    assert [round(100 * x / total) for x in (
        c["decode_bytes_weights"], kv, state)] == [40, 34, 26]
    # the tree the harness fills: every parameter, head and embedding
    cfg = family.program_config(config)
    specs = family.model().param_specs(cfg)
    n = sum(math.prod(s["shape"]) for s in specs.values())
    assert round(n / 1e6, 1) == 2435.7                   # with the vectors
    for name, scale in (("g_dt_bias", 4.0), ("g_A_log", 1.0)):
        assert (specs[name]["init"], specs[name]["scale"]) == ("normal",
                                                               scale)
    assert {s["init"] for s in specs.values()} == {"normal", "ones"}
    # the pool beside them: pages 8.31 GB, state 1.29 GB, tails 0.04 GB
    import jax

    pool = jax.eval_shape(lambda: family._program().init_paged_kv(
        cfg, 4224, 64, 96))
    nbytes = lambda a: math.prod(a.shape) * a.dtype.itemsize
    assert pool["gdn_state"].shape == (6, 97, 15, 96, 384)
    assert nbytes(pool["gdn_state"]) + nbytes(pool["gdn_conv"]) == 97 * slot
    assert nbytes(pool["k"]) + nbytes(pool["v"]) == 2 * 2 * 4225 * 64 * 3840 * 2
    assert set(pool) == {"k", "v", "gdn_state", "gdn_conv"}


def test_the_traffic_is_the_issues(real):
    bench, _config, _family, _ref = real
    from harness import traffic

    mix = configs.load_traffic(util.REPO, bench, "rollout")
    assert (mix["kind"], mix["clients"], mix["cycle_requests"]) == (
        "closed_loop", "n_slots", 96)
    assert (mix["ramp_s"], mix["trace_s"]) == (30, 8)
    src = traffic.ClosedLoopSource(mix, 2**31 + 77, 100352)
    assert set(map(int, src.p_len)) == {256}
    outs = sorted(map(int, src.o_len))
    assert (outs[0], outs[1], outs[-1], len(outs)) == (1032, 1048, 2552, 96)
    assert sum(outs) / len(outs) == 1792
    assert max(outs) + 256 <= 2816
    (cell,) = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "olmo-hybrid-7b", "rollout", 1)
    assert bench["workloads"][-1] is cell
    assert bench["end_to_end"][0]["workloads"][-1] == CELL
    assert bench["end_to_end"][0]["bound"] == 0.01
    assert [m["name"] for m in bench["per_layer"][-2:]] == list(NEW)


TINY = {
    "hidden_size": 96, "num_hidden_layers": 8, "num_attention_heads": 6,
    "num_key_value_heads": 6, "head_dim": 16, "intermediate_size": 128,
    "linear_num_key_heads": 6, "linear_num_value_heads": 6,
    "linear_key_head_dim": 32, "linear_value_head_dim": 64,
    "vocab_size": 256,
}


def tiny_config(config: dict) -> dict:
    """The real file cut to a size the CPU serves: every width small,
    the pattern and every ratio kept (two periods of 3 : 1; a key head a
    value head, values twice as wide as keys and half a lane tile over,
    so the state packs two heads side by side; multi-head attention)."""
    tiny = copy.deepcopy(config)
    tiny.update(TINY, name="olmo-hybrid-tiny")
    # (The cell's own factor: two dozen tokens of a 256-word model read
    # 0.8-2.8 x the plain bf16 forward's deficits from run to run.)
    tiny["serve"].update(page_size=16, n_pages=32, max_len=128,
                         prefill_chunk=64, n_slots=4, reference_factor=3.0,
                         deficit_slack=0.01, ref_sample=3)
    return tiny


def test_the_family_serves_through_the_harness_at_tiny_size(real, tmp_path):
    """families/olmo_hybrid.py and harness/reference/olmo_hybrid_ref.py
    through run.py on the CPU: the engine's stream is held `correct` by
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
           for n in NEW + ("gdn_step_roofline", "decode_stream_mfu")])
    got = util.rehearse(root, cell, seed=2**31 + 60, seconds=1.5, trace=1)
    out = got["line"]
    assert out["correct"] is True and out["failed"] == 0
    assert any("reference check over" in ln and ": ok" in ln
               for ln in got["log"])
    value = lambda n: out["metrics"]["cpu_rehearsal." + n]["value"]
    assert value("preemptions") == 0 and value("slot_occupancy") > 0
    # The two byte shares are counters' arithmetic: read on the CPU too.
    assert 0 < value("state_cache_byte_share") < 100
    assert 0 < value("kv_cache_byte_share") < 100
    # No device plane in a CPU trace: trace-sourced metrics are left out.
    assert not any("roofline" in n or "dev_ms" in n or "mfu" in n
                   for n in out["metrics"])


def test_the_reference_agrees_with_the_program_at_tiny_size(real):
    """The family's two halves on the harness's own seeded weights: the
    program's full-sequence forward against `olmo_hybrid_ref.logits`,
    float32 (5e-4: reassociation, carried by the output norms;
    tests/test_olmo_hybrid.py has the reason, the paged programs and the
    controls)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import weights

    _bench, config, family, reference = real
    tiny = tiny_config(config)
    cfg = dataclasses.replace(family.program_config(tiny, max_seq=128),
                              dtype=jnp.float32)
    params = weights.make_params(family.model(), cfg, 2**31 + 5, jnp.float32)
    tokens = np.random.default_rng(0).integers(1, 256, (1, 50)).astype(
        np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(family._program().forward(cfg, params,
                                                   jnp.asarray(tokens)))[0]
    want = np.asarray(reference.logits(params, jnp.asarray(tokens[0]),
                                       family.reference_config(tiny)))
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=5e-4, rtol=0)
    # the seeded decay spreads: fast and slow heads both
    rate = (np.asarray(jax.nn.softplus(params["g_dt_bias"]))
            * np.exp(np.asarray(params["g_A_log"])))
    assert (rate > 1).mean() > 0.2 and (rate < 1 / 15).mean() > 0.05


def _context(family, config) -> dict:
    """A synthetic context of a traced run: 100 decode steps of 20 ms and
    8 chunk programs of 30 ms in a traced 2.4 s."""
    decode, chunk = "jit__decode_sample_paged(1)", "jit_prefill_chunk_paged(2)"
    call = lambda name: (
        f"%{name} = bf16[96,30,128] custom-call(%a, %b), "
        'custom_call_target="tpu_custom_call"')
    ops = [
        (decode, call("gdn_decode_step.3"), 0.40),
        (decode, call("paged_decode_attn.2"), 0.55),
        (chunk, call("paged_prefill_attn.1"), 0.016),
    ]
    t = [10.0 + 0.25 * i for i in range(40)]
    return {
        "engine": {"slot_occupancy": 0.995, "decode_block_fill": 0.9,
                   "decode_live_column_share": 0.45,
                   "compiles_in_window": 0, "preemptions": 0,
                   "tick_host_share": 0.02, "engine_prefill_tok_s": 20000.0,
                   "decode_step_ms_p50": 20.5, "prefill_tokens": 2048,
                   "prefill_dispatches": 8, "prefill_rows_per_program": 2.0,
                   "kv_pages_free_min": 2000},
        "samples": {"t": t, "decoding_slots": [90] * 32 + [96] * 8,
                    "kv_tokens_decoding": [100_000] * 32 + [115_200] * 8},
        "trace_t0": t[32],                              # the last 8 samples
        "trace": {"ops": ops, "window_s": 2.4, "busy_s": 2.3,
                  "per_chip_busy_s": [2.3],
                  "programs": {decode: {"count": 100, "total_s": 2.0},
                               chunk: {"count": 8, "total_s": 0.24}}},
        "memory": {}, "peaks": {"hbm_bytes_per_s": 819e9,
                                "flops_bf16": 197e12},
        "consts": dict(configs.dims(config), chips=1, window_s=51.0,
                       n_pages=4224, page_size=64,
                       **family.serve_consts(config)),
    }


_TABLE = {
    "busy_s": 2.3,
    "programs": {
        "jit__decode_sample_paged": {
            "runs": 100, "total_s": 2.0, "by_pass": {}, "unscoped_s": 0.03,
            "by_scope": {"gdn.in": 0.25, "gdn.scan": 0.40, "gdn.out": 0.10,
                         "mlp": 0.45, "attn.in": 0.04, "attn.out": 0.02,
                         "attn.kernel": 0.55, "head": 0.12, "sample": 0.01}},
        "jit_prefill_chunk_paged": {
            "runs": 8, "total_s": 0.24, "by_pass": {}, "unscoped_s": 0.001,
            "by_scope": {"gdn.in": 0.05, "gdn.scan": 0.04, "gdn.out": 0.02,
                         "mlp": 0.09, "attn.kernel": 0.016}}},
}


def test_every_metric_of_the_cell_reads_a_synthetic_context(real,
                                                            monkeypatch):
    bench, config, family, _ref = real
    entries = configs.metrics_for_cell(bench, "per_layer", CELL)
    assert len(entries) == 26 and all(CELL in m["workloads"]
                                      for m in entries)
    assert all(m["moves"] == "out_tokens_per_s" and "." not in m["name"]
               for m in entries)
    assert [m["name"] for m in entries[-2:]] == list(NEW)
    assert not [m["name"] for m in entries
                if m["name"].startswith(("moe_", "expert", "window_", "ssm_",
                                         "latent_"))
                or m["name"] == "decode_stream_roofline"]
    ctx = _context(family, config)
    monkeypatch.setattr(scope_times, "for_run", lambda _ctx: _TABLE)
    got = {n: v["value"] for n, v in readers.read_all(
        configs.metrics_dirs(util.REPO, bench), entries, ctx,
        {"out_tokens_per_s": 4800.0}).items()}
    assert set(got) == {m["name"] for m in entries}
    c, peak = ctx["consts"], 819e9
    # samples of the TRACED interval: 96 slots, 115,200 cached tokens
    pages = 115_200 * c["decode_bytes_per_kv_token"]
    state = 96 * c["decode_bytes_per_state_slot"]
    step_bytes = c["decode_bytes_weights"] + pages + state
    want = {
        "state_cache_byte_share": state / step_bytes * 100,
        "kv_cache_byte_share": pages / step_bytes * 100,
        "gdn_step_roofline": state / peak / 0.004 * 100,
        "decode_attn_roofline": pages / peak / 0.0055 * 100,
        "decode_stream_mfu": step_bytes / peak / 0.020 * 100,
        "gdn_share": (0.25 + 0.40 + 0.10 + 0.05 + 0.04 + 0.02) / 2.3 * 100,
        "gdn_scan_share": (0.40 + 0.04) / 2.3 * 100,
        "attn_kernel_share": (0.55 + 0.016) / 2.3 * 100,
        "decode_program_dev_ms": 20.0, "prefill_program_dev_ms": 30.0,
        "head_ms": 1.3, "decode_block_fill": 90.0,
    }
    for name, value in want.items():
        assert got[name] == pytest.approx(value, rel=1e-9), name
    assert 50 < got["state_cache_byte_share"] + got["kv_cache_byte_share"] < 70
    # 256 tokens a dispatch, 5 ms of scan a chunk program
    a = 256 * c["chunk_scan_bytes_per_token"] / peak
    b = 256 * c["chunk_scan_flops_per_token"] / 197e12
    assert got["gdn_chunk_roofline"] == pytest.approx(
        max(a, b) / 0.005 * 100, rel=1e-9)
    for name in got:
        if "roofline" in name or "mfu" in name:
            assert 0 < got[name] < 100, name


def test_over_a_family_without_the_terms_the_new_readers_return_nothing(
        real):
    """This PR's two reader files over a context whose family states no
    state by the slot, no weights, or whose run was not traced: the
    metric is left out and nothing raises."""
    bench, config, family, _ref = real
    entries = [m for m in bench["per_layer"] if m["name"] in NEW]
    read = lambda ctx: {n: v["value"] for n, v in readers.read_all(
        configs.metrics_dirs(util.REPO, bench), entries, ctx, {}).items()}
    ctx = _context(family, config)
    assert set(read(ctx)) == set(NEW)
    stateless = copy.deepcopy(ctx)
    stateless["consts"].pop("decode_bytes_per_state_slot")
    assert set(read(stateless)) == {"kv_cache_byte_share"}
    for lacking in ({"consts": {}}, {"trace_t0": None}, {"samples": {}}):
        assert read(dict(ctx, **lacking)) == {}
    assert read({}) == {}
