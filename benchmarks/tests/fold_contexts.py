"""One synthetic context of a traced run for each of the four cells whose
metrics' names PR 52 moved, and every per-layer metric the tree lists
for such a cell read from it: what `test_fold.py` holds the folded
`per_layer` to, number for number.

`data/per_layer_parent.json` holds `{cell: {metric name: value}}` as the
tree BEFORE the fold read these contexts (PR 52: a `git archive` of
2050139 with this file and the three family tests it takes its contexts
from laid over it, `read_cell` over that tree's BENCHMARK.json): what
each of the 83 suffixed names that moved `out_tokens_per_s` read. The
parent's readers are gone, so the file cannot be written again: it
proves the rename once, and a later `benchmark` PR may retire it with
this module. It pins neither the size of `per_layer` nor a cell's set.
The 44 entries of `opt-1.3b.batch` and `opt-1.3b.train` kept their names
and their reader files byte for byte, so they have no context here.

The three expert cells that had a family test use its context (and its
table of seconds by scope); `zaya1-8b.reason` had none of this kind and
gets one here. Nothing in a context is a device number: the values are
made up so that every reader finds something to read and no two terms
are equal by accident.
"""

from __future__ import annotations

from unittest import mock

import util
from harness import configs, families, readers, scope_times

DECODE, CHUNK = "jit__decode_sample_paged(1)", "jit_prefill_chunk_paged(2)"
CALL = ('%{name} = bf16[64,8,128] custom-call(%a, %b), '
        'custom_call_target="tpu_custom_call"')
T = [10.0 + 0.25 * i for i in range(40)]        # the window's sampling times


def _family(bench: dict, config_name: str):
    config = configs.load_config(util.REPO, bench, config_name)
    return config, families.load(util.REPO, bench, config)[0]


def _reason(bench: dict) -> dict:
    """100 decode steps of 23.4 ms and 4 chunk programs of 31 ms in a
    traced 2.6 s; 64 slots, a pool of 2,048 pages."""
    config, family = _family(bench, "zaya1-8b")
    ops = [
        (DECODE, CALL.format(name="paged_decode_attn.2"), 0.29),
        (DECODE, CALL.format(name="ragged-dot-none.7"), 1.59),
        (DECODE, CALL.format(name="ragged-dot-metadata.1"), 0.04),
        (DECODE, "%fusion.9 = f32[64,262272] fusion(%x), kind=kOutput", 0.14),
        (CHUNK, CALL.format(name="paged_prefill_attn.1"), 0.003),
        (CHUNK, CALL.format(name="ragged-dot-none.9"), 0.085),
        (CHUNK, "%fusion.3 = bf16[512,2048] fusion(%x), kind=kLoop", 0.02),
    ]
    table = {"busy_s": 2.5, "programs": {
        "jit__decode_sample_paged": {
            "runs": 100, "total_s": 2.34, "by_pass": {}, "unscoped_s": 0.05,
            "by_scope": {"attn.in": 0.055, "attn.out": 0.019,
                         "attn.kernel": 0.29, "attn.kv_write": 0.03,
                         "moe.route": 0.083, "moe.experts": 1.59,
                         "slot_state": 0.04, "head": 0.142, "sample": 0.048,
                         "counters": 0.002}},
        "jit_prefill_chunk_paged": {
            "runs": 4, "total_s": 0.124, "by_pass": {}, "unscoped_s": 0.006,
            "by_scope": {"attn.in": 0.007, "attn.out": 0.002,
                         "attn.kernel": 0.003, "moe.route": 0.009,
                         "moe.experts": 0.085, "head": 0.006}}}}
    return {"ctx": {
        "engine": {"moe_experts_touched": 13.1, "moe_rows_max": 3.4,
                   "moe_rows_held": 6400, "moe_rows_routed": 6400,
                   "slot_occupancy": 0.993, "kv_pages_free_min": 850,
                   "decode_block_fill": 0.72,
                   "decode_live_column_share": 0.54,
                   "prefill_rows_per_program": 4.9, "compiles_in_window": 0,
                   "preemptions": 0, "tick_host_share": 0.014,
                   "engine_prefill_tok_s": 17900.0,
                   "decode_step_ms_p50": 23.3},
        "samples": {"t": T, "decoding_slots": [61] * 32 + [63] * 8,
                    "kv_tokens_decoding": [60_000] * 32 + [66_000] * 8},
        "trace_t0": T[32],
        "trace": {"ops": ops, "window_s": 2.6, "busy_s": 2.5,
                  "per_chip_busy_s": [2.5],
                  "programs": {DECODE: {"count": 100, "total_s": 2.34},
                               CHUNK: {"count": 4, "total_s": 0.124}}},
        "memory": {}, "peaks": {"hbm_bytes_per_s": 819e9},
        "consts": dict(configs.dims(config), chips=1, window_s=51.0,
                       n_pages=2048, page_size=64,
                       **family.serve_consts(config))},
        "table": table, "end_to_end": {"out_tokens_per_s": 2500.0}}


def _of_family_test(module_name: str, config_name: str, rate: float):
    """The context a family's own test reads its cell's metrics from."""
    def build(bench: dict) -> dict:
        test = __import__(module_name)
        config, family = _family(bench, config_name)
        return {"ctx": test._context(family, config), "table": test._TABLE,
                "end_to_end": {"out_tokens_per_s": rate}}
    return build


CELLS = {
    "zaya1-8b.reason": _reason,
    "laguna-s-2.1.codegen": _of_family_test("test_laguna_family",
                                            "laguna-s-2.1", 2000.0),
    "qwen3-next-80b-a3b.longform": _of_family_test(
        "test_qwen3_next_family", "qwen3-next-80b-a3b", 5000.0),
    "mimo-v2-flash.think": _of_family_test("test_mimo_v2_family",
                                           "mimo-v2-flash", 7000.0),
}


def read_cell(bench: dict, cell: str) -> dict:
    """-> {metric name: value}: every per-layer entry `bench` lists for
    `cell`, read by the tree's reader files from the cell's context. The
    scope table stands in for the trace file a run would have written."""
    made = CELLS[cell](bench)
    with mock.patch.object(scope_times, "for_run",
                           lambda _ctx: made["table"]):
        got = readers.read_all(
            configs.metrics_dirs(util.REPO, bench),
            configs.metrics_for_cell(bench, "per_layer", cell),
            made["ctx"], made["end_to_end"])
    return {name: m["value"] for name, m in got.items()}
