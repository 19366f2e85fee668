"""Shared by benchmarks/tests: a throw-away benchmark root at tiny size.

The root holds its own BENCHMARK.json and a `benchmarks/` directory with
a tiny configuration and tiny traffic; the layer-metric readers, the
families and the plain reference are the real ones, copied. Tests add
files to it to show that discovery needs no edit of an existing file."""

from __future__ import annotations

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CONFIG = {
    "name": "tiny", "source": "tests only",
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 8,
    "ffn_dim": 128, "vocab_size": 256, "rotary_dim": 4,
    "family": "gpt", "reference": "benchmarks/harness/reference/gpt_ref.py",
    "program_keys": {"d_model": "hidden_size", "n_layers": "num_hidden_layers",
                     "n_heads": "num_attention_heads", "d_ff": "ffn_dim",
                     "vocab_size": "vocab_size", "rotary_dim": "rotary_dim"},
    "program_fixed": {"tie_embeddings": False},
    "serve": {"chips": 1, "tp": 1, "weight_dtype": "bf16", "kv_mode": "paged",
              "page_size": 8, "n_pages": 48, "max_len": 128,
              "prefill_chunk": 16, "attn_impl": "auto", "n_slots": 4,
              "reference_factor": 2.0, "deficit_slack": 0.01,
              "ref_sample": 3},
    "train": {"chips": 1, "remat": True, "attn_impl": "flash",
              "param_dtype": "float32", "optimizer": "adafactor",
              "learning_rate": 1e-4, "multiply_by_parameter_scale": False,
              "loss_chunk": None, "loss_rtol": 5e-3,
              "ref_sequences_at_a_time": 2},
}
TINY_TRAFFIC = {
    "chat": {"kind": "open_loop", "rate_per_s": 4.0,
             "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.5,
                            "min": 4, "max": 60},
             "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                            "min": 2, "max": 16},
             "ramp_s": 1, "drain_cap_s": 20, "trace_s": 1},
    "batch": {"kind": "closed_loop", "clients": "n_slots",
              "cycle_requests": 8,
              "prompt_len": {"dist": "uniform", "min": 8, "max": 60},
              "output_len": {"dist": "fixed", "value": 8},
              "ramp_s": 1, "trace_s": 1},
    "train": {"kind": "train_job", "batch": 2, "seq": 32, "trace_s": 1},
}


def make_root(tmp: str) -> str:
    """A benchmark root under `tmp` with the cells tiny.chat, tiny.batch
    and tiny.train. The batch and train entries are the real ones,
    re-pointed; the real benchmark has no open-loop cell yet, so
    tiny.chat brings its own end-to-end and per-layer entries and reader
    files, as a later PR's open-loop cell would."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    base = os.path.join(tmp, "benchmarks")
    os.makedirs(os.path.join(base, "configs"))
    os.makedirs(os.path.join(base, "traffic"))
    for sub in ("layer_metrics", "families",
                os.path.join("harness", "reference")):
        shutil.copytree(os.path.join(BENCH_DIR, sub), os.path.join(base, sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(base, "configs", "tiny.json"), "w") as f:
        json.dump(TINY_CONFIG, f)
    for name, mix in TINY_TRAFFIC.items():
        with open(os.path.join(base, "traffic", name + ".json"), "w") as f:
            json.dump(mix, f)
    kind_of = {c["name"]: c["traffic"] for c in real["workloads"]}

    def repoint(metric):
        m = dict(metric)
        if "workloads" in m:
            m["workloads"] = sorted({"tiny." + kind_of[w]
                                     for w in m["workloads"]})
        return m

    bench = {
        "command": real["command"], "paths": ["benchmarks"], "run_seconds": 2,
        "configs": [{"name": "tiny", "source": "tests only",
                     "file": "benchmarks/configs/tiny.json", "reduced": [],
                     "why": "tests"}],
        "workloads": [{"name": "tiny." + t, "config": "tiny", "traffic": t,
                       "chips": 1, "why": "tests"} for t in TINY_TRAFFIC],
        "end_to_end": [repoint(m) for m in real["end_to_end"]],
        "per_layer": [repoint(m) for m in real["per_layer"]],
    }
    for name in ("ttft_p90_ms", "tpot_p90_ms"):
        bench["end_to_end"].append(
            {"name": name, "unit": "ms", "better": "lower", "bound": 0.1,
             "source": "host_clock", "workloads": ["tiny.chat"]})
    for name, reader, scale in (
            ("queue_wait_ms_p50",
             "request_quantile:first_chunk_at - submitted_at:0.5", 1000),
            ("slot_occupancy.chat", "engine_metric:slot_occupancy", 100)):
        with open(os.path.join(base, "layer_metrics", name + ".json"), "w") as f:
            json.dump({"name": name, "reader": reader, "scale": scale}, f)
        bench["per_layer"].append(
            {"name": name, "unit": "ms", "better": "lower",
             "source": "program_span", "layer": "Entry and admission",
             "moves": "ttft_p90_ms", "workloads": ["tiny.chat"]})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return tmp


def real_family(config_name: str = "opt-1.3b"):
    """-> (family module, reference module) of a configuration of the
    real BENCHMARK.json, as run.py loads them."""
    from harness import configs, families

    bench = configs.load_benchmark(REPO)
    return families.load(REPO, bench, configs.load_config(REPO, bench,
                                                          config_name))


def snapshot_mtimes(base: str) -> dict:
    return {os.path.join(d, f): os.path.getmtime(os.path.join(d, f))
            for d, _s, fs in os.walk(base) for f in fs}


def add_cell(root: str, config: dict, traffic: str, end_to_end: list[str],
             per_layer: list[dict] = ()) -> str:
    """Write `config` as a NEW configuration file of the root and append
    its entries to the root's BENCHMARK.json: the configuration, the cell
    `<config>.<traffic>` (the traffic file is there already), the cell's
    name on the named end-to-end metrics, and per-layer metrics (`name`,
    `unit`, `moves`): the cell's name appended to the entry the root has
    by that name and `moves`, or a new entry whose reader file the caller
    has dropped in."""
    name = config["name"]
    cell = f"{name}.{traffic}"
    with open(os.path.join(root, "benchmarks", "configs", name + ".json"),
              "x") as f:
        json.dump(config, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": name, "source": "tests only",
                             "file": f"benchmarks/configs/{name}.json",
                             "reduced": [], "why": "tests"})
    bench["workloads"].append({"name": cell, "config": name,
                               "traffic": traffic, "chips": 1, "why": "tests"})
    for m in bench["end_to_end"]:
        if m["name"] in end_to_end:
            m["workloads"].append(cell)
    for m in per_layer:
        shared = [e for e in bench["per_layer"]
                  if (e["name"], e["moves"]) == (m["name"], m["moves"])]
        if shared:      # a metric the benchmark has: one more cell in its list
            shared[0]["workloads"].append(cell)
            continue
        bench["per_layer"].append(dict(
            {"better": "higher", "source": "program_span",
             "layer": "Entry and admission", "workloads": [cell]}, **m))
    with open(path, "w") as f:
        json.dump(bench, f)
    return cell


def rehearse(root: str, workload: str, seed: int = 3, seconds: float = 2.0,
             trace: int = 0) -> dict:
    """benchmarks/run.py end to end on the CPU. -> its last line, parsed."""
    import io
    from contextlib import redirect_stdout

    import run

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      root=root, rehearsal=True)
    assert rc == 0
    lines = [ln for ln in buf.getvalue().splitlines() if ln.strip()]
    return {"line": json.loads(lines[-1]), "log": lines[:-1]}
