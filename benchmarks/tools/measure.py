"""Run one cell several times in one call and print each metric's median
and spread (first to third quartile over the median), the figure the
bounds in BENCHMARK.json are set from. This parent never touches JAX:
each run is a child that holds the chip alone.

    python benchmarks/tools/measure.py --workload opt-1.3b.batch --seeds 101,102,103 \
        [--seconds N] [--trace 0|1] [--label set1]

Lines go to chiprun_out/measure_<workload>_<label>.jsonl.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(HERE))

from harness import stats  # noqa: E402  (no JAX in it)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--label", default="set")
    ns = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    seconds = ns.seconds or bench["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"measure_{ns.workload}_{ns.label}.jsonl")
    rows, rc_all = [], 0
    for seed in ns.seeds.split(","):
        cmd = bench["command"] + ["--workload", ns.workload, "--seed", seed,
                                  "--seconds", str(seconds),
                                  "--trace", str(ns.trace)]
        t0 = time.time()
        try:
            p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                               timeout=600)
        except subprocess.TimeoutExpired as e:
            print(f"seed {seed}: TIMED OUT after 600s\n"
                  + (e.stdout or b"").decode(errors="replace")[-3000:],
                  flush=True)
            rc_all = 1
            continue
        wall = time.time() - t0
        lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
        with open(path.replace(".jsonl", f"_{seed}.log"), "w") as f:
            f.write(p.stdout + "\n--- stderr\n" + p.stderr[-6000:])
        if p.returncode != 0 or not lines:
            print(f"seed {seed}: FAILED rc={p.returncode} in {wall:.0f}s\n"
                  + "\n".join(lines[-15:]) + "\n" + p.stderr[-3000:],
                  flush=True)
            rc_all = 1
            continue
        row = json.loads(lines[-1])
        row["seed"], row["wall_s"] = int(seed), wall
        rows.append(row)
        with open(path, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(f"seed {seed}: wall {wall:.0f}s correct {row['correct']} "
              f"attempted {row['attempted']} failed {row['failed']} "
              + " ".join(f"{k}={v['value']:.4f}"
                         for k, v in row["metrics"].items()), flush=True)
    names = sorted({k for r in rows for k in r["metrics"]})
    for name in names:
        vals = [r["metrics"][name]["value"] for r in rows
                if name in r["metrics"]]
        if len(vals) >= 2:
            med = statistics.median(vals)
            spread = stats.iqr_share(vals) if med else float("nan")
            print(f"SPREAD {ns.workload} {ns.label} {name}: n={len(vals)} "
                  f"median {med:.4f} iqr/median {spread:.4%} "
                  f"min {min(vals):.4f} max {max(vals):.4f}", flush=True)
    return rc_all


if __name__ == "__main__":
    sys.exit(main())
