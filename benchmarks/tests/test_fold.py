"""One entry a metric, one file a reader (PR 52): the fold of `per_layer`
held to the parent number for number, and `tools/check_contract.py` held
to the rule it now keeps.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_fold.py -q

`data/per_layer_parent.json` holds what each of the parent's 83 names
that moved `out_tokens_per_s` read from one synthetic context a cell
(`fold_contexts.py` says how it was written, once, from the tree before
the fold). The tests hold what the fold must KEEP: a later PR that
appends a cell to an entry, or brings entries of its own, changes none of
what they assert. Nothing here is a device number.
"""

from __future__ import annotations

import json
import os
import shutil

import pytest

import fold_contexts
import util
from harness import configs

CELLS = sorted(fold_contexts.CELLS)
# Two pairs read ONE thing under two names in different cells (the same
# two scopes; the same kernel, bytes and arithmetic), so each pair is one
# entry that lists all four cells. Every other name became its stem.
RENAMED = {"attn_proj_ms": "decode_dense_ms",
           "full_attn_roofline": "decode_attn_roofline"}
# New readings, not moved ones: metrics a cell had been denied for want of
# room, appended to the entries' lists once the fold had made it. Each is
# held to hand arithmetic in its family's test (`zaya1-8b.reason`'s below).
APPENDED = {
    "mimo-v2-flash.think": {
        "prefill_program_dev_ms", "decode_step_ms", "prefill_tokens_per_s",
        "kv_pool_fill", "compiles_in_window", "tick_host_share",
        "scope_coverage", "window_attn_share", "expert_rows_max",
        "expert_rows_held_share", "moe_route_ms", "head_ms"},
    "laguna-s-2.1.codegen": {
        "decode_block_fill", "decode_live_column_share", "moe_route_ms",
        "head_ms", "decode_dense_ms", "scope_coverage"},
    "zaya1-8b.reason": {"prefill_program_dev_ms", "decode_step_ms"},
    "qwen3-next-80b-a3b.longform": {
        "decode_block_fill", "decode_live_column_share", "decode_dense_ms"},
}


@pytest.fixture(scope="module")
def bench():
    return configs.load_benchmark(util.REPO)


@pytest.fixture(scope="module")
def parent():
    with open(os.path.join(util.HERE, "data", "per_layer_parent.json")) as f:
        return json.load(f)


def test_the_fixture_holds_the_parents_moved_names(parent):
    assert sorted(parent) == CELLS
    names = [n for cell in parent.values() for n in cell]
    assert len(names) == len(set(names)) == 83
    assert all(isinstance(v, float) for c in parent.values()
               for v in c.values())
    assert all(n.endswith("." + cell.rsplit(".", 1)[1])
               for cell, held in parent.items() for n in held)


@pytest.mark.parametrize("cell", CELLS)
def test_every_moved_name_reads_the_float_its_twin_read(cell, bench, parent):
    """Everything the parent read in the cell under `<stem>.<traffic>` is
    read under the stem (or under the one name of two that read one
    thing), from the cell's context, the same float bit for bit; every
    metric the cell lists finds something to read; the pairs the cell was
    given are there. What else a later PR lists for the cell is its own."""
    was = parent[cell]
    listed = [m["name"] for m in
              configs.metrics_for_cell(bench, "per_layer", cell)]
    got = fold_contexts.read_cell(bench, cell)
    assert set(got) == set(listed) and len(listed) == len(set(listed))
    moved = {}
    for old, value in was.items():
        stem = old.split(".", 1)[0]
        moved[old] = RENAMED.get(stem, stem)
        assert got[moved[old]] == value, (old, moved[old])
    assert len(set(moved.values())) == len(moved)
    assert set(listed) - set(moved.values()) >= APPENDED.get(cell, set())
    assert not set(moved.values()) & APPENDED.get(cell, set())
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert all(by_name[n]["moves"] == "out_tokens_per_s"
               for n in moved.values())


def test_the_one_retired_name_is_gone(bench):
    """`pool_move_share.batch`: superseded by `chunk_kv_write_share.batch`
    since PR 39; what it read was weight stream and no pool move (PR 50).
    The cell's other entries kept their names and their reader files."""
    names = {m["name"] for m in bench["per_layer"]}
    assert "pool_move_share.batch" not in names
    assert "chunk_kv_write_share.batch" in names
    assert not [f for f in os.listdir(os.path.join(util.BENCH_DIR,
                                                   "layer_metrics"))
                if f.startswith("pool_move_share")]


def test_the_shared_kernel_arithmetic_gives_what_zayas_copy_gave(bench,
                                                                 parent):
    """`moe_expert_roofline.reason.py` spelt out what
    `kernel_roofline.decode_kernel_share` does for its three siblings;
    the copy went, and the one body reads what it read."""
    got = fold_contexts.read_cell(bench, "zaya1-8b.reason")
    made = fold_contexts.CELLS["zaya1-8b.reason"](bench)
    per = made["ctx"]["consts"]["decode_bytes_per_live_expert"]
    assert got["moe_expert_roofline"] == \
        parent["zaya1-8b.reason"]["moe_expert_roofline.reason"]
    assert got["moe_expert_roofline"] == pytest.approx(
        13.1 * per / 819e9 / (1.59 / 100) * 100, rel=1e-12)
    # and the pairs this cell was given: a chunk program's device time,
    # the host's clock around a decode step
    assert got["prefill_program_dev_ms"] == pytest.approx(0.124 / 4 * 1000)
    assert got["decode_step_ms"] == 23.3


def test_a_family_that_forgets_a_byte_term_reads_nothing(bench):
    """`decode_stream_roofline` is one expression over five byte terms; a
    family states the terms it has not as 0.0. One that is MISSING makes
    the share nothing, not a smaller share."""
    from harness import readers

    for name, gone in (("zaya1-8b", "decode_bytes_per_state_slot"),
                       ("laguna-s-2.1", "decode_bytes_per_state_slot"),
                       ("qwen3-next-80b-a3b", "decode_bytes_per_window_slot"),
                       ("mimo-v2-flash", "decode_bytes_per_window_slot")):
        cell = next(w["name"] for w in bench["workloads"]
                    if w["config"] == name)
        made = fold_contexts.CELLS[cell](bench)
        read = readers.load_reader(configs.metrics_dirs(util.REPO, bench),
                                   "decode_stream_roofline")
        ctx = dict(made["ctx"], metrics={
            "experts_touched": 10.0, "decode_program_dev_ms": 20.0})
        assert 0 < read(ctx) < 100
        consts = dict(ctx["consts"])
        assert consts.pop(gone) is not None
        assert read(dict(ctx, consts=consts)) is None


# ------------------------------------- tools/check_contract.py holds the rule


@pytest.fixture(scope="module")
def contract():
    return configs.load_module(os.path.join(util.BENCH_DIR, "tools",
                                            "check_contract.py"), "tool_")


def test_the_benchmark_holds_to_the_rule(contract, bench, capsys):
    assert contract.main() == 0, capsys.readouterr().out
    said = capsys.readouterr().out
    assert f"per_layer count: {len(bench['per_layer'])} of 128" in said
    assert len(bench["per_layer"]) <= contract.PER_LAYER_MAX
    files = [f for f in os.listdir(os.path.join(util.BENCH_DIR,
                                                "layer_metrics"))
             if f.endswith((".json", ".py"))]
    assert len(files) == len(bench["per_layer"])


def _doctored(tmp_path, contract, change) -> list:
    """The contract's errors over a copy of the benchmark's data with
    `change(bench, layer_metrics_dir)` applied."""
    root = str(tmp_path)
    for sub in ("configs", "layer_metrics"):
        shutil.copytree(os.path.join(util.BENCH_DIR, sub),
                        os.path.join(root, "benchmarks", sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    bench = configs.load_benchmark(util.REPO)
    change(bench, os.path.join(root, "benchmarks", "layer_metrics"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return contract.errors(root)


def _entry(bench, name):
    return next(m for m in bench["per_layer"] if m["name"] == name)


def _a_twin(bench, metrics):
    twin = dict(_entry(bench, "slot_occupancy"), name="slot_occupancy.think",
                workloads=["mimo-v2-flash.think"])
    _entry(bench, "slot_occupancy")["workloads"].remove("mimo-v2-flash.think")
    bench["per_layer"].append(twin)
    shutil.copy(os.path.join(metrics, "slot_occupancy.json"),
                os.path.join(metrics, "slot_occupancy.think.json"))


def _a_file_without_an_entry(bench, metrics):
    shutil.copy(os.path.join(metrics, "head_ms.py"),
                os.path.join(metrics, "head_ms.codegen.py"))


def _an_entry_without_a_file(bench, metrics):
    os.remove(os.path.join(metrics, "head_ms.py"))


def _two_files_an_entry(bench, metrics):
    with open(os.path.join(metrics, "head_ms.json"), "w") as f:
        json.dump({"name": "head_ms", "reader": "engine_metric:x"}, f)


def _a_reader_before_what_it_reads(bench, metrics):
    rows = bench["per_layer"]
    rows.append(rows.pop(rows.index(_entry(bench, "experts_touched"))))


def _a_reader_of_a_cells_suffix(bench, metrics):
    path = os.path.join(metrics, "moe_expert_roofline.py")
    with open(path) as f:
        text = f.read()
    with open(path, "w") as f:
        f.write(text.replace('"experts_touched"', '"experts_touched.think"'))


def _a_reader_in_a_cell_its_source_lacks(bench, metrics):
    _entry(bench, "experts_touched")["workloads"].remove("zaya1-8b.reason")


def _a_reader_through_a_name(bench, metrics):
    path = os.path.join(metrics, "moe_expert_roofline.py")
    with open(path) as f:
        text = f.read()
    with open(path, "w") as f:
        f.write(text.replace(
            'touched = (ctx.get("metrics") or {}).get("experts_touched")',
            'm = ctx.get("metrics") or {}\n'
            '    touched = m["experts_touched.think"]'))


def _two_names_for_one_reading(bench, metrics):
    other = dict(_entry(bench, "decode_dense_ms"), name="attn_proj_ms",
                 workloads=["mimo-v2-flash.think"])
    _entry(bench, "decode_dense_ms")["workloads"].remove("mimo-v2-flash.think")
    bench["per_layer"].append(other)
    with open(os.path.join(metrics, "decode_dense_ms.py")) as f:
        text = f.read()
    with open(os.path.join(metrics, "attn_proj_ms.py"), "w") as f:
        f.write(text.replace("decode_dense_ms", "attn_proj_ms", 1))


def _a_suffix_that_is_no_traffic(bench, metrics):
    _entry(bench, "slot_occupancy.batch")["name"] = "slot_occupancy.bulk"
    os.rename(os.path.join(metrics, "slot_occupancy.batch.json"),
              os.path.join(metrics, "slot_occupancy.bulk.json"))


@pytest.mark.parametrize("change,said", [
    (_a_twin, ["are one metric moving out_tokens_per_s",
               "slot_occupancy.think moves out_tokens_per_s, which 4 cells "
               "report: its name is its stem",
               "slot_occupancy.think and slot_occupancy read the same thing "
               "the same way"]),
    (_a_file_without_an_entry, ["head_ms.codegen.py: a reader file with no "
                                "entry"]),
    (_an_entry_without_a_file, ["no reader file for head_ms"]),
    (_two_files_an_entry, ["head_ms has 2 reader files"]),
    (_a_reader_before_what_it_reads, [
        "decode_stream_roofline reads 'experts_touched', which stands after",
        "moe_expert_roofline reads 'experts_touched', which stands after"]),
    (_a_reader_of_a_cells_suffix, ["moe_expert_roofline reads "
                                   "'experts_touched.think', which is no "
                                   "entry"]),
    (_a_reader_in_a_cell_its_source_lacks, [
        "moe_expert_roofline reads 'experts_touched' in a cell that does "
        "not list it: ['zaya1-8b.reason']"]),
    (_a_reader_through_a_name, ["moe_expert_roofline reads "
                                "'experts_touched.think', which is no "
                                "entry"]),
    (_two_names_for_one_reading, ["attn_proj_ms and decode_dense_ms read the "
                                  "same thing the same way and move "
                                  "out_tokens_per_s"]),
    (_a_suffix_that_is_no_traffic, ["slot_occupancy.bulk: a suffix is the "
                                    "traffic of the one cell that reports "
                                    "out_tokens_per_s.batch"]),
], ids=lambda x: x.__name__.strip("_") if callable(x) else "")
def test_the_contract_check_refuses(tmp_path, contract, change, said):
    errs = _doctored(tmp_path, contract, change)
    for want in said:
        assert any(want in e for e in errs), (want, errs)
    assert len(errs) <= len(said) + 1, errs


def test_the_doctoring_starts_from_a_clean_copy(tmp_path, contract):
    assert _doctored(tmp_path, contract, lambda bench, metrics: None) == []
