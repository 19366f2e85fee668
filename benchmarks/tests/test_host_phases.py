"""harness/host_phases.py (the device's idle time split by the engine's
`llm.*` phases), the nine per-layer metrics of PR 24 that read the
engine's own account, and the extended BENCHMARK.json against the
contract. CPU only; the recorded trace is from the chip
(tools/record_phase_trace.py)."""

import json
import os

import pytest

import util                      # noqa: F401  (puts benchmarks/ on the path)
from harness import configs, host_phases, readers, trace_reduce

RECORDED = os.path.join(util.HERE, "data", "tiny_phases.xplane.pb")
NEW_METRICS = {
    "tick_ms.batch", "tick_host_share.batch", "tick_blocked_share.batch",
    "decode_dispatch_ms.batch", "awaiting_first_token_max.batch",
    "queue_wait_ms_p50.batch", "prefill_span_ms_p50.batch",
    "idle_host_work_share.batch", "idle_dispatch_share.batch"}


def test_overlap_with_sorted_gaps():
    gaps = [(0.0, 10.0), (20.0, 30.0), (40.0, 50.0)]
    starts = [g[0] for g in gaps]
    over = lambda s, e: host_phases._overlap_ns(gaps, starts, s, e)
    assert over(0, 50) == 30
    assert over(5, 25) == 10
    assert over(10, 20) == 0
    assert over(22, 28) == 6
    assert over(45, 90) == 5
    assert over(-5, 1) == 1


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace beside the tests")
def test_split_of_the_recorded_trace():
    """Three rounds of dispatch, pull, 10 ms of `llm.emit`, 5 ms of
    nothing: the device idles through every sleep, and the split says
    which was the engine's."""
    with open(os.path.join(util.HERE, "data", "tiny_phases.expect.json")) as f:
        expect = json.load(f)
    split = host_phases.idle_split(RECORDED)
    red = trace_reduce.reduce_trace(RECORDED, 1)
    for key in ("window_s", "idle_s", "host_work_s", "dispatch_s"):
        assert split[key] == pytest.approx(expect["split"][key], rel=1e-6)
    assert split["events"] == {"llm.decode.dispatch": 3, "llm.decode.pull": 3,
                               "llm.emit": 3}
    # The same window and the same idle time as the accepted reduction.
    assert split["window_s"] == pytest.approx(red["window_s"], rel=1e-9)
    assert split["idle_s"] == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
    rounds, emit_s, rest_s = (expect["rounds"], expect["emit_s"],
                              expect["no_phase_s"])
    assert split["by_phase"]["llm.emit"] == split["host_work_s"]
    assert rounds * emit_s <= split["host_work_s"] <= rounds * emit_s * 1.5
    in_no_phase = split["idle_s"] - split["host_work_s"] - split["dispatch_s"]
    assert in_no_phase >= rounds * rest_s * 0.9
    assert 0 <= split["dispatch_s"] < 0.005
    # The longest gaps now carry the engine's phase, not the runtime's event.
    assert red["idle_gaps"][0][0] == "window|llm.emit"


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace beside the tests")
def test_idle_share_readers_over_the_recorded_trace(monkeypatch):
    monkeypatch.setattr(host_phases, "newest_xplane", lambda: RECORDED)
    split = host_phases.idle_split(RECORDED)
    ctx = {"trace": {"window_s": split["window_s"]}}
    got = {}
    for name, part in (("idle_host_work_share.batch", "host_work_s"),
                       ("idle_dispatch_share.batch", "dispatch_s")):
        read = readers.load_reader([os.path.join(util.BENCH_DIR,
                                                 "layer_metrics")], name)
        got[name] = read(ctx)
        assert got[name] == pytest.approx(
            split[part] / split["window_s"] * 100.0)
    idle = 100.0 * split["idle_s"] / split["window_s"]
    assert sum(got.values()) <= idle
    # Not traced, or a device-less trace: nothing to read, and no error.
    assert readers.load_reader([os.path.join(util.BENCH_DIR, "layer_metrics")],
                               "idle_dispatch_share.batch")({}) is None
    assert host_phases.idle_share({"trace": {"window_s": None}},
                                  "dispatch_s") is None


def test_a_trace_without_the_phases_reads_as_nothing():
    """The older recorded trace has a device plane and `bench.window` but
    no `llm.*` event, as a program older than the phases: None, not 0."""
    older = os.path.join(util.HERE, "data", "tiny_tpu.xplane.pb")
    if not os.path.exists(older):
        pytest.skip("no recorded trace beside the tests")
    assert host_phases.idle_split(older) is None


def test_newest_xplane_is_the_latest_written(tmp_path):
    assert host_phases.newest_xplane(str(tmp_path)) is None
    for i, cell in enumerate(("a.batch", "b.train")):
        d = tmp_path / cell / "plugins" / "profile" / "2026_01_01"
        d.mkdir(parents=True)
        p = d / "host.xplane.pb"
        p.write_bytes(b"")
        os.utime(p, (1000 + i, 1000 + i))
    assert host_phases.newest_xplane(str(tmp_path)).endswith(
        os.path.join("b.train", "plugins", "profile", "2026_01_01",
                     "host.xplane.pb"))


def test_engine_account_readers():
    ctx = {"engine": {"tick_ms_mean": 1400.0, "tick_host_share": 0.004,
                      "tick_blocked_share": 0.97,
                      "decode_dispatch_ms_mean": 0.4,
                      "awaiting_first_token_max": 28},
           "requests": [{"submitted_at": 0.0, "first_chunk_at": 1.0,
                         "first_token_at": 7.0},
                        {"submitted_at": 1.0, "first_chunk_at": 4.0,
                         "first_token_at": 9.0},
                        {"submitted_at": 1.0, "first_chunk_at": None,
                         "first_token_at": None}]}
    dirs = [os.path.join(util.BENCH_DIR, "layer_metrics")]
    read = lambda name, c=ctx: readers.load_reader(dirs, name)(c)
    assert read("tick_ms.batch") == 1400.0
    assert read("tick_host_share.batch") == pytest.approx(0.4)
    assert read("tick_blocked_share.batch") == pytest.approx(97.0)
    assert read("decode_dispatch_ms.batch") == 0.4
    assert read("awaiting_first_token_max.batch") == 28
    assert read("queue_wait_ms_p50.batch") == pytest.approx(2000.0)
    assert read("prefill_span_ms_p50.batch") == pytest.approx(5500.0)
    # A program without the account (the parent): left out, no error.
    older = {"engine": {"slot_occupancy": 0.1}, "requests": []}
    assert all(read(n, older) is None for n in NEW_METRICS)


def test_extended_benchmark_json_holds_to_the_contract(capsys):
    mod = configs.load_module(os.path.join(util.BENCH_DIR, "tools",
                                           "check_contract.py"), "tool_")
    assert mod.main() == 0, capsys.readouterr().out
    bench = configs.load_benchmark(util.REPO)
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert NEW_METRICS <= set(entries)
    # Every PR appends at the end, so these nine are held to their order
    # among themselves (a `derived` reader sees the metrics before it),
    # not to being the last.
    assert [m["name"] for m in bench["per_layer"]
            if m["name"] in NEW_METRICS] == [
        "tick_ms.batch", "tick_host_share.batch", "tick_blocked_share.batch",
        "decode_dispatch_ms.batch", "awaiting_first_token_max.batch",
        "queue_wait_ms_p50.batch", "prefill_span_ms_p50.batch",
        "idle_host_work_share.batch", "idle_dispatch_share.batch"]
    dirs = configs.metrics_dirs(util.REPO, bench)
    for name in NEW_METRICS:
        assert entries[name]["workloads"] == ["opt-1.3b.batch"]
        assert entries[name]["moves"] == "out_tokens_per_s.batch"
        assert readers.load_reader(dirs, name) is not None
