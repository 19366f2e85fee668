"""CPU tests of the benchmark's own yardstick (seconds, no chip):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

Nothing here prints or asserts a device metric: the end-to-end rehearsal
goes through `run.main(..., rehearsal=True)`, whose line prefixes every
metric name with `cpu_rehearsal.`.
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

import util  # noqa: F401  (puts benchmarks/ and the repo on sys.path)
from harness import readers, stats, trace_reduce, traffic

CHAT = {"kind": "open_loop", "rate_per_s": 3.0, "ramp_s": 5, "drain_cap_s": 10,
        "prompt_len": {"dist": "lognormal", "median": 512, "sigma": 0.7,
                       "min": 64, "max": 1536},
        "output_len": {"dist": "lognormal", "median": 128, "sigma": 0.7,
                       "min": 16, "max": 512}}


# ------------------------------------------------------------- traffic


def test_open_loop_plan_is_deterministic_in_seed():
    a = traffic.open_loop_plan(CHAT, 30.0, 2**31 + 11, 50304)
    b = traffic.open_loop_plan(CHAT, 30.0, 2**31 + 11, 50304)
    assert a["requests"] == b["requests"] and a["stats"] == b["stats"]
    c = traffic.open_loop_plan(CHAT, 30.0, 12, 50304)
    assert [r["prompt"] for r in c["requests"]] != \
        [r["prompt"] for r in a["requests"]]


def test_every_seed_gets_the_same_set_of_work_in_another_order():
    plans = [traffic.open_loop_plan(CHAT, 30.0, s, 1000) for s in (1, 2, 3)]
    measured = [[r for r in p["requests"] if r["measured"]] for p in plans]
    assert all(len(m) == 90 for m in measured)            # rate x window
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_tokens"]):
        sets = [sorted(map(key, m)) for m in measured]
        assert sets[0] == sets[1] == sets[2]              # the same set
        orders = [list(map(key, m)) for m in measured]
        assert orders[0] != orders[1] != orders[2]        # another order
    gaps = [traffic.draw_gaps(3.0, 90, 30.0, np.random.default_rng(s))
            for s in (1, 2)]
    assert np.allclose(sorted(gaps[0]), sorted(gaps[1]))  # the same gaps
    assert not np.allclose(gaps[0], gaps[1])
    assert [p["stats"] for p in plans][0]["prompt_len"] == \
        plans[1]["stats"]["prompt_len"]
    st = plans[0]["stats"]
    assert st["prompt_len"]["min"] >= 64 and st["prompt_len"]["max"] <= 1536
    assert st["output_len"]["min"] >= 16 and st["output_len"]["max"] <= 512
    assert abs(st["prompt_len"]["p50"] - 512) < 30
    assert abs(st["output_len"]["p50"] - 128) < 10


def test_open_loop_arrivals_cover_ramp_window_and_drain():
    plan = traffic.open_loop_plan(CHAT, 30.0, 5, 1000)["requests"]
    dues = [r["due"] for r in plan]
    assert dues == sorted(dues)
    assert min(dues) < -4.0 and max(dues) > 39.0 and max(dues) < 40.0
    measured = [r for r in plan if r["measured"]]
    assert all(0.0 <= r["due"] < 30.0 for r in measured)
    gaps = np.diff([r["due"] for r in measured])
    assert abs(gaps.mean() - 1 / 3.0) < 0.02        # Poisson at the rate
    assert gaps.std() > 0.2                         # ... not a metronome


def test_closed_loop_source_and_train_batches():
    mix = {"cycle_requests": 16,
           "prompt_len": {"dist": "uniform", "min": 256, "max": 1919},
           "output_len": {"dist": "fixed", "value": 128}}
    a, b = (traffic.ClosedLoopSource(mix, 9, 1000) for _ in range(2))
    first = [a.next() for _ in range(20)]
    assert first == [b.next() for _ in range(20)]
    assert first[16]["index"] == first[0]["index"]   # cyclic
    assert all(r["max_tokens"] == 128 for r in first)
    assert a.stats["prompt_len"]["min"] >= 256
    assert a.stats["prompt_len"]["max"] <= 1919
    c = traffic.ClosedLoopSource(mix, 10, 1000)           # another seed:
    assert sorted(c.p_len) == sorted(a.p_len)             # the same set,
    assert list(c.p_len) != list(a.p_len)                 # another order
    t1 = traffic.TrainBatches({"batch": 2, "seq": 8}, 4, 100)
    t2 = traffic.TrainBatches({"batch": 2, "seq": 8}, 4, 100)
    x = t1.next()
    assert x.shape == (2, 8) and (x == t2.next()).all()
    assert not (t1.next() == x).all()


# --------------------------------------------------------------- stats


def test_quantiles_and_sample_count_rule():
    xs = list(range(1, 101))
    assert stats.quantile(xs, 0.5) == pytest.approx(np.quantile(xs, 0.5))
    assert stats.quantile(xs, 0.9) == pytest.approx(np.quantile(xs, 0.9))
    assert stats.quantile([3.0], 0.9) == 3.0
    # p95 of 150 requests keeps 7 beyond it, p90 keeps 15: p90 it is.
    assert stats.samples_beyond(150, 0.95) == 7
    assert stats.highest_supported_quantile(150) == 0.9
    assert stats.highest_supported_quantile(400) == 0.95
    assert stats.highest_supported_quantile(30) is None
    assert stats.iqr_share([10, 10.1, 9.9, 10.2, 9.8, 10.0]) == \
        pytest.approx((10.125 - 9.875) / 10.0)


# --------------------------------------------------------- trace_reduce


def test_union_and_self_times():
    assert trace_reduce.union_length([(0, 10), (5, 15), (20, 25)]) == 20
    ev = [("while", 0.0, 100.0), ("fusion.1", 0.0, 40.0),
          ("copy.2", 50.0, 30.0), ("tail", 120.0, 10.0)]
    got = {n: s for n, _st, s in trace_reduce.self_times(ev)}
    assert got == {"while": 30.0, "fusion.1": 40.0, "copy.2": 30.0,
                   "tail": 10.0}


RECORDED = os.path.join(util.HERE, "data", "tiny_tpu.xplane.pb")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace beside the tests")
def test_reduce_recorded_trace():
    """A small trace recorded on the chip (tools/record_tiny_trace.py):
    three runs of one jitted program inside `bench.window`, with sleeps
    between them."""
    with open(os.path.join(util.HERE, "data", "tiny_tpu.expect.json")) as f:
        expect = json.load(f)
    red = trace_reduce.reduce_trace(RECORDED, 1)
    assert red["window_s"] == pytest.approx(expect["window_s"], rel=1e-6)
    assert red["busy_s"] == pytest.approx(expect["busy_s"], rel=1e-6)
    assert 0 < red["busy_s"] < red["window_s"]
    name, prog = next((n, p) for n, p in red["programs"].items()
                      if "tiny_step" in n)
    assert prog["count"] == expect["runs"]
    assert prog["total_s"] <= red["busy_s"] * 1.001
    assert sum(t for _p, _o, t in red["ops"]) == \
        pytest.approx(red["per_chip_busy_s"][0], rel=1e-3)
    assert trace_reduce.share(red, "tiny_step", ".*") == pytest.approx(1.0, rel=1e-3)
    assert len(red["idle_gaps"]) >= expect["runs"] - 1
    assert red["idle_gaps"][0][1] >= 0.009           # the 10 ms sleeps


def test_op_regex_sees_the_instruction_not_its_operands():
    fed = ("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %all-reduce.3), "
           "kind=kLoop, calls=%fused")
    real = "%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %x), to_apply=%add"
    kernel = ('%closed_call.8 = bf16[32,32,64]{2,1,0:T(8,128)(2,1)} '
              'custom-call(%a), custom_call_target="tpu_custom_call"')
    assert trace_reduce.op_key(fed) == "%fusion.3 fusion"
    assert trace_reduce.op_key(real) == "%all-reduce.3 all-reduce"
    assert trace_reduce.op_key(kernel) == \
        "%closed_call.8 custom-call tpu_custom_call"
    red = {"per_chip_busy_s": [4.0],
           "ops": [("p", fed, 1.0), ("p", real, 1.0), ("p", kernel, 2.0)]}
    assert trace_reduce.share(red, ".*", "all-reduce") == 0.25
    assert trace_reduce.share(red, ".*", "tpu_custom_call") == 0.5


# -------------------------------------------------------------- readers


def test_metric_expressions_are_arithmetic_only():
    env = {"m": {"a.b": 4.0}, "c": {"k": 2}}
    assert readers.evaluate("m['a.b'] / c['k'] * 100", env) == 200.0
    assert readers.evaluate("m['missing'] + 1", env) is None
    assert readers.evaluate("first - second", {"first": 3, "second": None}) is None
    with pytest.raises(ValueError):
        readers.evaluate("__import__('os').system('true')", env)


def test_metric_expressions_have_no_functions():
    with pytest.raises(ValueError):                 # a cap hides a fault
        readers.evaluate("min(m['a.b'], 100)", {"m": {"a.b": 400.0}})


def test_derived_reads_samples_of_the_traced_interval():
    ctx = {"samples": {"t": [0.0, 1.0, 2.0, 3.0], "kv": [10, 20, 30, 50]},
           "trace_t0": 1.5}
    assert readers.GENERIC["derived"](ctx, "s['kv']") == 27.5
    assert readers.GENERIC["derived"](ctx, "st['kv']") == 40.0
    assert readers.GENERIC["derived"](dict(ctx, trace_t0=None), "st['kv']") is None


def test_generic_readers():
    ctx = {"engine": {"slot_occupancy": 0.5},
           "requests": [{"a": 1.0, "b": 0.5}, {"a": 3.0, "b": 1.0},
                        {"a": None, "b": 1.0}],
           "memory": {"peak_bytes_in_use": 2e9}, "trace": None}
    assert readers.GENERIC["engine_metric"](ctx, "slot_occupancy") == 0.5
    assert readers.GENERIC["request_quantile"](ctx, "a - b", "0.5") == 1.25
    assert readers.GENERIC["trace_idle"](ctx) is None
    assert readers.GENERIC["trace_share"](ctx, ".*", "x") is None


# ----------------------------------------------------------- serve cell


def test_warm_up_is_cut_to_the_traffic():
    from harness import serve_cell

    geo = {"page_size": 64, "max_len": 2048}
    # 1,020 in / 129 out: decode at 16 pages, then 32; the prompt ends at 16
    assert serve_cell.programs_needed([1020], [129], geo) == ([16, 32], [1020])
    # short chat: a 40-token prompt ends inside one page and decodes to 3
    widths, last = serve_cell.programs_needed([40, 100, 130], [16, 16, 100], geo)
    assert widths == [1, 2, 4] and last == [40, 100, 130]
    # the cap: max_len's pages even when not a power of two
    assert serve_cell.programs_needed([1900], [200], {"page_size": 64,
                                                      "max_len": 1984}) \
        == ([31], [1900])


def test_served_stream_is_held_to_the_plain_bf16_forwards_own_noise():
    """`check_streams` on a fake request whose output IS the float32
    reference's greedy choice passes; the same with every token replaced
    by the runner-up fails, whatever the slack."""
    import types

    import jax
    import jax.numpy as jnp

    import run
    from harness import serve_cell
    from ray_tpu.models import gpt

    family, gpt_ref = util.real_family()
    cfg = gpt.GPTConfig.tiny_untied(dtype=jnp.float32)
    ref_cfg = family.reference_config({"rotary_dim": cfg.rotary_dim})
    params = gpt.init_params(cfg, jax.random.key(0))
    prompt = list(range(3, 19))
    seq = np.zeros(64, np.int32)
    seq[:16] = prompt
    best, second = [], []
    for i in range(16, 40):                       # greedy, one token at a time
        lg = np.asarray(gpt_ref.logits(params, jnp.asarray(seq), ref_cfg))
        order = np.argsort(lg[i - 1])
        seq[i] = order[-1]
        best.append(int(order[-1]))
        second.append(int(order[-2]))
    config = {"serve": {"max_len": 64, "ref_sample": 4,
              "reference_factor": 2.0, "deficit_slack": 1e-6}}
    fake = lambda out: [{"index": 0, "req": types.SimpleNamespace(
        error=None, prompt_ids=prompt, n_prompt=16, out_ids=out)}]
    log = lambda _m: None
    check = lambda out: serve_cell.check_streams(
        gpt_ref, ref_cfg, params, config, fake(out), 1, log,
        run.CompileCounter())
    good = check(best)
    assert good["ok"] and good["top1_share"] == 1.0 and good["n_tokens"] == 24
    bad = check(best[:-1] + second[-1:])
    assert not bad["ok"] and bad["worst_deficit"] > 0


def test_rate_is_taken_between_the_first_and_last_emission():
    from harness import serve_cell

    win = {"window_s": 10.0, "tokens_in_window": 130,
           "emissions": [(1.0, 40), (3.0, 80), (5.0, 120), (9.0, 200)]}
    assert serve_cell.emission_rate(win) == (20.0, 8.0)
    few = dict(win, emissions=[(4.0, 40)])
    assert serve_cell.emission_rate(few) == (13.0, 10.0)


# ------------------------------------------------------------ reference


def test_reference_agrees_with_the_programs_forward():
    """gpt_ref (float32, plain jnp, no import from ray_tpu.models) against
    `gpt.forward` in float32 at tiny size."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt

    family, gpt_ref = util.real_family()
    cfg = gpt.GPTConfig.tiny_untied(dtype=jnp.float32)
    rc = family.reference_config({"rotary_dim": cfg.rotary_dim})
    params = gpt.init_params(cfg, jax.random.key(0))
    toks = jax.random.randint(jax.random.key(1), (2, 48), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        want = gpt.forward(params, toks, cfg)
    got = jnp.stack([gpt_ref.logits(params, t, rc) for t in toks])
    assert float(jnp.max(jnp.abs(got - want))) < 2e-4
    ref_loss = gpt_ref.loss(params, toks, jnp.roll(toks, -1, axis=1), rc)
    with jax.default_matmul_precision("highest"):
        prog_loss = gpt.loss_fn(params, toks, jnp.roll(toks, -1, axis=1), cfg)
    assert float(abs(ref_loss - prog_loss)) < 1e-4
    # a wrong mask or position shows: shifting the sequence moves logits
    moved = gpt_ref.logits(params, jnp.roll(toks[0], 1), rc)
    assert float(jnp.max(jnp.abs(moved - got[0]))) > 1e-2
    # the bf16 arithmetic is the same block, a rounding apart
    low = gpt_ref.logits(params, toks[0], rc, jnp.bfloat16)
    gap = float(jnp.max(jnp.abs(low - got[0])))
    assert low.dtype == jnp.float32 and 0.0 < gap < 0.1


# ---------------------------------------------- the command, end to end


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return util.make_root(str(tmp_path_factory.mktemp("bench_root")))


@pytest.mark.parametrize("workload,names", [
    ("tiny.chat", {"ttft_p90_ms", "tpot_p90_ms", "setup_s"}),
    ("tiny.batch", {"out_tokens_per_s.batch", "setup_s"}),   # a bound of its own
    ("tiny.train", {"train_tokens_per_s", "setup_s"}),
])
def test_command_end_to_end_at_tiny_size(root, workload, names):
    out = util.rehearse(root, workload, seed=2**31 + 5, seconds=1.5)["line"]
    assert out["rehearsal"] is True and out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"cpu_rehearsal." + n for n in names}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"       # and says so


@pytest.mark.parametrize("workload,names", [
    ("tiny.chat", {"queue_wait_ms_p50", "slot_occupancy.chat"}),
    ("tiny.batch", {"slot_occupancy.batch", "compiles_in_window.batch",
                    "kv_pages_free_min.batch", "kv_pool_fill.batch"}),
])
def test_traced_run_reports_per_layer_metrics_it_can_read(root, workload,
                                                          names):
    out = util.rehearse(root, workload, seconds=1.5, trace=1)["line"]
    got = set(out["metrics"])
    assert {"cpu_rehearsal." + n for n in names} <= got
    # No device plane in a CPU trace: trace-sourced metrics are left out,
    # not invented, and no busy time is claimed.
    assert not any("roofline" in n or "dev_ms" in n or "idle" in n for n in got)
    assert "busy_s" not in out["device"] and "breakdown" not in out


def test_run_refuses_without_a_tpu():
    import run

    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "opt-1.3b.train", "--seed", "1",
                  "--seconds", "1", "--trace", "0"])
    assert "TPU" in str(e.value)


def test_unknown_device_kind_is_an_error():
    from harness import peaks

    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        peaks.peaks_for("TPU v9 imaginary")


def test_new_files_are_discovered_with_no_edit(root):
    """A configuration, a traffic mix and a per-layer metric dropped in as
    NEW files, plus their BENCHMARK.json entries, are picked up."""
    base = os.path.join(root, "benchmarks")
    before = util.snapshot_mtimes(base)
    with open(os.path.join(base, "traffic", "short.json"), "w") as f:
        json.dump(dict(util.TINY_TRAFFIC["chat"], rate_per_s=6.0), f)
    with open(os.path.join(base, "layer_metrics", "finished_share.py"), "w") as f:
        f.write("def read(ctx):\n"
                "    rows = ctx['requests']\n"
                "    return 100.0 * sum(r['ok'] for r in rows) / len(rows)\n")
    with open(os.path.join(base, "layer_metrics", "ttft_p50_ms.json"), "w") as f:
        json.dump({"name": "ttft_p50_ms", "scale": 1000,
                   "reader": "request_quantile:first_token_at - due:0.5"}, f)
    util.add_cell(root, dict(util.TINY_CONFIG, name="tiny2", num_hidden_layers=1),
                  "short", ["ttft_p90_ms", "tpot_p90_ms"],
                  [{"name": n, "unit": u, "moves": "ttft_p90_ms"}
                   for n, u in (("finished_share", "%"), ("ttft_p50_ms", "ms"))])
    out = util.rehearse(root, "tiny2.short", seconds=1.5, trace=1)["line"]
    assert out["correct"] is True
    assert out["metrics"]["cpu_rehearsal.finished_share"]["value"] == 100.0
    assert out["metrics"]["cpu_rehearsal.ttft_p50_ms"]["value"] > 0
    assert all(os.path.getmtime(p) == t for p, t in before.items())
