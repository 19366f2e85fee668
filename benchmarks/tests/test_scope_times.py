"""harness/scope_times.py (device time split by the scopes the programs
name their parts with), the sixteen per-layer metrics of PR 39 that read
it or split the idle gaps by phase, `tools/scope_account.py`, and the
extended BENCHMARK.json against the contract. CPU only; the recorded
trace is from the chip (tools/record_scope_trace.py)."""

import json
import os

import pytest

import util                      # noqa: F401  (puts benchmarks/ on the path)
from harness import configs, host_phases, readers, scope_times, trace_reduce

DATA = os.path.join(util.HERE, "data")
RECORDED = os.path.join(DATA, "tiny_scopes.xplane.pb")
DIRS = [os.path.join(util.BENCH_DIR, "layer_metrics")]
from ray_tpu.ops import scopes    # noqa: E402  (constants only)

# The vocabulary is the program's (ray_tpu/ops/scopes.py `ALL`): a family
# that names a new part adds a name there, and no file of the benchmark
# pins the count (a literal tuple of PR 39's fourteen FAILED from PR 43,
# which added three, until PR 47).
NAMES = scopes.ALL
PR39 = ("embed", "attn.in", "attn.kv_write", "attn.kernel", "attn.out",
        "mlp", "moe.route", "moe.experts", "slot_state", "head", "sample",
        "counters", "loss", "optimizer")
SCOPE_METRICS = [
    "decode_weights_ms.batch", "decode_sample_ms.batch",
    "chunk_dense_share.batch", "chunk_kv_write_share.batch",
    "scope_coverage.batch", "moe_route_ms", "head_ms",
    "slot_state_ms", "decode_dense_ms", "scope_coverage",
    "train_bwd_share.train", "train_remat_share.train",
    "train_optimizer_share.train", "scope_coverage.train"]
IDLE_METRICS = ["idle_prefill_dispatch_share.batch", "idle_pull_share.batch"]


def test_the_vocabulary_is_the_programs():
    assert scope_times.vocabulary() == scopes.ALL
    # names only join: every metric file that reads one of PR 39's finds it
    assert set(PR39) <= set(NAMES) and len(set(NAMES)) == len(NAMES)


@pytest.mark.parametrize("path,want", [
    ("jit(f)/jit(main)/while/body/mlp/dot_general", ("mlp", "fwd")),
    ("jit(f)/jit(main)/while/body/closed_call/attn.in/jit(_var)/reduce_sum:",
     ("attn.in", "fwd")),
    ("jit(step)/transpose(jvp(attn.in))/dot_general:", ("attn.in", "bwd")),
    ("jit(step)/jvp(loss)/reduce_sum:", ("loss", "fwd")),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/mlp/"
     "dot_general:", ("mlp", "bwd")),
    ("jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attn.in/dot_general:", ("attn.in", "remat")),
    ("jit(step)/optimizer/sub:", ("optimizer", "fwd")),
    ("jit(f)/shard_map/moe.experts/ragged_dot:", ("moe.experts", "fwd")),
    # the first vocabulary name wins; one further down is not looked for
    ("jit(f)/attn.kernel/paged_decode_attn/while/body/mlp/add",
     ("attn.kernel", "fwd")),
    # XLA:TPU's own expansion of `lax.ragged_dot` loses the program's path
    ("ragged-dot-none:", ("moe.experts", "fwd")),
    ("ragged-dot-metadata:", ("moe.route", "fwd")),
    ("jit(f)/ragged-dot-none/add:", (None, "fwd")),
    # in no scope: a scan's own slicing, a name that only resembles one
    ("jit(step)/transpose(jvp())/while/body/dynamic_update_slice:",
     (None, "bwd")),
    ("jit(f)/jit(main)/mlp_extra/attn/dot_general:", (None, "fwd")),
    ("", (None, "fwd")),
])
def test_scope_of_a_path(path, want):
    assert scope_times.scope_of(path, NAMES) == want


def test_the_wire_reader_finds_what_the_trace_carries():
    """Against the fixture PR 23 recorded, whose three fusions carry
    `jit(tiny_step)/dot_general:` and whose copies carry no `tf_op`."""
    older = os.path.join(DATA, "tiny_tpu.xplane.pb")
    paths = scope_times._op_paths(older, os.path.getmtime(older))
    assert len(paths) == 3
    assert set(paths.values()) == {"jit(tiny_step)/dot_general:"}
    # Keyed as `trace_reduce` names a program and an operation.
    assert set(paths) == {(p, o) for p, o, _t in trace_reduce.reduce_trace(
        older, 1)["ops"] if o.startswith("%fusion")}


@pytest.mark.parametrize("older", ["tiny_tpu.xplane.pb",
                                   "tiny_phases.xplane.pb"])
def test_a_trace_without_scopes_reads_as_nothing(older):
    """A device plane, `bench.window`, `tf_op` on its ops, but none of
    them in a scope of the vocabulary, as an executable from before the
    scopes (or out of a compile cache an older tree warmed): None."""
    assert scope_times.scope_times(os.path.join(DATA, older)) is None


def test_a_program_without_the_vocabulary_reads_as_nothing(monkeypatch):
    monkeypatch.setattr(scope_times, "vocabulary", lambda: ())
    assert scope_times.scope_times(RECORDED) is None
    ctx = {"trace": trace_reduce.reduce_trace(RECORDED, 1)}
    monkeypatch.setattr(host_phases, "newest_xplane", lambda: RECORDED)
    for name in SCOPE_METRICS:
        assert readers.load_reader(DIRS, name)(ctx) is None


def test_the_recorded_trace_by_scope_and_pass():
    """Three runs of a train step: a scan over two layers (`attn.in`,
    `mlp`, under jax.checkpoint), a `loss`, its grad, an `optimizer`
    update, and one matmul in no scope."""
    with open(os.path.join(DATA, "tiny_scopes.expect.json")) as f:
        expect = json.load(f)
    table = scope_times.scope_times(RECORDED)
    red = trace_reduce.reduce_trace(RECORDED, 1)
    assert list(table["programs"]) == ["jit_scoped_step"]
    p, want = table["programs"]["jit_scoped_step"], \
        expect["table"]["programs"]["jit_scoped_step"]
    assert p["runs"] == want["runs"] == expect["runs"] == 3
    for key in ("by_scope", "by_pass", "unscoped"):
        assert p[key] == pytest.approx(want[key], rel=1e-6)
    assert [op[:2] for op in p["ops"]] == [op[:2] for op in want["ops"]]
    assert sum(t for _s, _o, t in p["ops"]) == pytest.approx(
        sum(p["by_scope"].values()) + p["unscoped_s"])
    # A caller that has the accepted reduction hands it over.
    assert scope_times.scope_times(RECORDED, red) == table
    assert set(p["by_scope"]) == {"attn.in", "mlp", "loss", "optimizer"}
    assert set(p["by_pass"]) == {"fwd", "bwd", "remat"}
    # The same busy time, programs and op self times as the accepted
    # reduction: every nanosecond lands in one scope or in none.
    assert table["busy_s"] == pytest.approx(red["busy_s"], rel=1e-9)
    assert p["total_s"] == pytest.approx(
        red["programs"]["jit_scoped_step"]["total_s"], rel=1e-9)
    ops_s = sum(t for _p, _o, t in red["ops"])
    assert sum(p["by_scope"].values()) + p["unscoped_s"] == pytest.approx(
        ops_s, rel=1e-9)
    assert sum(p["unscoped"].values()) == pytest.approx(p["unscoped_s"])
    # What lies in no scope is named by its path: the matmul before the
    # scan, its transpose, and the scan's own stacking.
    assert "jit(scoped_step)/jvp()/dot_general:" in p["unscoped"]
    assert any(k.endswith("while/body/dynamic_update_slice:")
               for k in p["unscoped"])
    # The backward pass of a checkpointed layer costs more than its
    # recompute, and the layers dominate the step.
    assert p["by_pass"]["bwd"] > p["by_pass"]["remat"] > 0
    assert p["by_scope"]["attn.in"] + p["by_scope"]["mlp"] > 0.5 * ops_s


SYNTHETIC = {"busy_s": 8.0, "programs": {
    "jit__decode_sample_paged": {
        "runs": 400, "total_s": 6.0, "unscoped_s": 0.2, "unscoped": {},
        "by_pass": {"fwd": 5.8},
        "by_scope": {"attn.in": 0.4, "attn.out": 0.2, "mlp": 1.2,
                     "head": 0.16, "sample": 0.12, "attn.kernel": 3.6,
                     "attn.kv_write": 0.04, "moe.route": 0.6,
                     "moe.experts": 0.08, "slot_state": 0.24}},
    "jit_prefill_chunk_paged": {
        "runs": 250, "total_s": 1.9, "unscoped_s": 0.1, "unscoped": {},
        "by_pass": {"fwd": 1.8},
        "by_scope": {"attn.in": 0.4, "attn.out": 0.1, "mlp": 0.6,
                     "head": 0.02, "attn.kv_write": 0.48,
                     "attn.kernel": 0.2}},
    "jit_step": {
        "runs": 5, "total_s": 0.1, "unscoped_s": 0.02, "unscoped": {},
        "by_pass": {"fwd": 0.02, "bwd": 0.04, "remat": 0.016},
        "by_scope": {"mlp": 0.048, "optimizer": 0.008, "loss": 0.004}}}}
EXPECT = {
    "decode_weights_ms.batch": (0.4 + 0.2 + 1.2 + 0.16) / 400 * 1e3,
    "decode_sample_ms.batch": 0.3,
    "chunk_dense_share.batch": (0.4 + 0.1 + 0.6 + 0.02) / 8.0 * 100,
    "chunk_kv_write_share.batch": 6.0,
    "moe_route_ms": 1.5, "head_ms": 0.7,
    "slot_state_ms": 0.6, "decode_dense_ms": 1.5,
    "train_bwd_share.train": 0.5, "train_remat_share.train": 0.2,
    "train_optimizer_share.train": 0.1}


@pytest.mark.parametrize("name", SCOPE_METRICS)
def test_a_scope_metric_reads_a_synthetic_table(name, monkeypatch):
    read = readers.load_reader(DIRS, name)
    monkeypatch.setattr(scope_times, "for_run", lambda ctx: SYNTHETIC)
    scoped = sum(sum(p["by_scope"].values())
                 for p in SYNTHETIC["programs"].values())
    want = (scoped / 8.0 * 100 if name.startswith("scope_coverage")
            else EXPECT[name])
    assert read({"trace": {"window_s": 8.0}}) == pytest.approx(want)
    # Nothing to read (not traced, no scopes in the trace, a program
    # whose name matches nothing): left out, no error.
    monkeypatch.setattr(scope_times, "for_run", lambda ctx: None)
    assert read({}) is None
    monkeypatch.setattr(scope_times, "for_run",
                        lambda ctx: {"busy_s": 1.0, "programs": {}})
    assert read({"trace": {"window_s": 8.0}}) is None


def test_scope_metrics_over_the_recorded_trace(monkeypatch):
    """The whole path of a `--trace 1` run: the newest trace, the table,
    the reader files; untraced, nothing."""
    monkeypatch.setattr(host_phases, "newest_xplane", lambda: RECORDED)
    ctx = {"trace": trace_reduce.reduce_trace(RECORDED, 1)}
    table = scope_times.for_run(ctx)
    p = table["programs"]["jit_scoped_step"]
    read = lambda name: readers.load_reader(DIRS, name)(ctx)
    assert read("scope_coverage.train") == pytest.approx(
        sum(p["by_scope"].values()) / table["busy_s"] * 100)
    assert 60 < read("scope_coverage.train") < 100
    assert read("train_optimizer_share.train") == pytest.approx(
        p["by_scope"]["optimizer"] / table["busy_s"] * 100)
    assert read("train_bwd_share.train") > read("train_remat_share.train") > 0
    assert read("decode_weights_ms.batch") is None      # no such program
    assert scope_times.ms_a_run(ctx, "scoped_step", ("mlp",)) == \
        pytest.approx(p["by_scope"]["mlp"] / 3 * 1e3)
    assert scope_times.for_run({"trace": None}) is None
    assert scope_times.for_run({"trace": {"window_s": None}}) is None


@pytest.mark.parametrize("name,phases", [
    ("idle_prefill_dispatch_share.batch", ("llm.prefill.dispatch",)),
    ("idle_pull_share.batch", ("llm.prefill.pull", "llm.decode.pull"))])
def test_an_idle_phase_metric(name, phases, monkeypatch):
    split = {"window_s": 8.0, "idle_s": 0.4, "host_work_s": 0.1,
             "dispatch_s": 0.3, "events": {},
             "by_phase": {"llm.prefill.dispatch": 0.20, "llm.plan": 0.1,
                          "llm.prefill.pull": 0.06, "llm.decode.pull": 0.02,
                          "llm.decode.dispatch": 0.02}}
    monkeypatch.setattr(host_phases, "newest_xplane", lambda: RECORDED)
    monkeypatch.setattr(host_phases, "idle_split", lambda path: split)
    read = readers.load_reader(DIRS, name)
    want = sum(split["by_phase"][p] for p in phases) / 8.0 * 100
    assert read({"trace": {"window_s": 8.0}}) == pytest.approx(want)
    assert read({}) is None
    monkeypatch.setattr(host_phases, "idle_split", lambda path: None)
    assert read({"trace": {"window_s": 8.0}}) is None


def test_the_idle_phase_metrics_split_the_lump(monkeypatch):
    """Over PR 24's recorded trace: the two parts are within
    `idle_dispatch_share.batch`, which lumps every dispatch and pull."""
    recorded = os.path.join(DATA, "tiny_phases.xplane.pb")
    monkeypatch.setattr(host_phases, "newest_xplane", lambda: recorded)
    ctx = {"trace": {"window_s": host_phases.idle_split(recorded)["window_s"]}}
    read = lambda name: readers.load_reader(DIRS, name)(ctx)
    assert read("idle_prefill_dispatch_share.batch") == 0.0
    assert 0 <= read("idle_pull_share.batch") <= read(
        "idle_dispatch_share.batch")


def test_scope_account_prints_the_table(capsys):
    tool = configs.load_module(os.path.join(util.BENCH_DIR, "tools",
                                            "scope_account.py"), "tool_")
    assert tool.main(["scope_account.py", RECORDED]) == 0
    out = capsys.readouterr().out
    assert "jit_scoped_step: 3 runs" in out and "%fusion" not in out
    assert tool.main(["scope_account.py", RECORDED, "--ops"]) == 0
    assert "%multiply_subtract_fusion" in capsys.readouterr().out
    for word in ("attn.in", "mlp", "optimizer", "(no scope)", "by pass:",
                 "remat", "while/body/dynamic_update_slice"):
        assert word in out
    assert tool.main(["scope_account.py",
                      os.path.join(DATA, "tiny_tpu.xplane.pb")]) == 1
    assert "no operation carries a scope" in capsys.readouterr().err


def test_extended_benchmark_json_holds_to_the_contract(capsys):
    mod = configs.load_module(os.path.join(util.BENCH_DIR, "tools",
                                           "check_contract.py"), "tool_")
    assert mod.main() == 0, capsys.readouterr().out
    bench = configs.load_benchmark(util.REPO)
    entries = {m["name"]: m for m in bench["per_layer"]}
    new = SCOPE_METRICS + IDLE_METRICS
    assert set(new) <= set(entries)
    cells = {"batch": "opt-1.3b.batch", "train": "opt-1.3b.train"}
    moves = {"batch": "out_tokens_per_s.batch", "train": "train_tokens_per_s"}
    dirs = configs.metrics_dirs(util.REPO, bench)
    for name in new:
        e, suffix = entries[name], name.partition(".")[2]
        assert e["source"] == "device_trace"
        if suffix:      # a cell that reports a rate of its own: one cell
            assert e["workloads"] == [cells[suffix]]
            assert e["moves"] == moves[suffix]
        else:           # the expert cells' shared rate: the entry lists them
            assert "zaya1-8b.reason" in e["workloads"]
            assert e["moves"] == "out_tokens_per_s"
        assert e["layer"] == ("Engine scheduler, host" if name in IDLE_METRICS
                              else "Train step" if suffix == "train"
                              else "Programs")
        assert readers.load_reader(dirs, name) is not None
