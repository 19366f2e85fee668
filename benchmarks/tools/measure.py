"""Run one cell several times in one call and print each metric's median
and spread (first to third quartile over the median), the figure the
bounds in BENCHMARK.json are set from. This parent never touches JAX:
each run is a child that holds the chip alone.

    python benchmarks/tools/measure.py --workload opt-1.3b.batch --seeds 101,102,103 \
        [--seconds N] [--trace 0|1] [--label set1]

Lines go to chiprun_out/measure_<workload>_<label>.jsonl.

A run is cut at RUN_LIMIT_S, the driver's own limit for a whole run (360 s;
ledger, PR 46: `run_timed_out` on a cell the PR had not touched). Each
run's whole wall time is printed beside its metrics with the harness's
own account of its phases, and the summary flags every run over
RUN_BUDGET_S (300 s): a cell is offered only when its COLD run ends under
that (start with JAX_COMPILATION_CACHE_DIR at an empty directory to see a
cold one).
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

RUN_LIMIT_S = 360       # the driver stops a run there and refuses the PR
RUN_BUDGET_S = 300      # what a cell's whole run, cold, has to end under


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
    rows, walls, rc_all = [], {}, 0
    for seed in ns.seeds.split(","):
        cmd = bench["command"] + ["--workload", ns.workload, "--seed", seed,
                                  "--seconds", str(seconds),
                                  "--trace", str(ns.trace)]
        t0 = time.time()
        try:
            p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                               timeout=RUN_LIMIT_S)
        except subprocess.TimeoutExpired as e:
            walls[seed] = float("inf")
            print(f"seed {seed}: TIMED OUT after {RUN_LIMIT_S}s, the driver's "
                  "limit for a whole run\n"
                  + (e.stdout or b"").decode(errors="replace")[-3000:],
                  flush=True)
            rc_all = 1
            continue
        wall = walls[seed] = time.time() - t0
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
        print(f"seed {seed}: whole run {wall:.1f}s correct {row['correct']} "
              f"attempted {row['attempted']} failed {row['failed']} "
              + " ".join(f"{k}={v['value']:.4f}"
                         for k, v in row["metrics"].items()), flush=True)
        for ln in lines[:-1]:
            if "] phases, wall seconds:" in ln or "] reference programs:" in ln:
                print("    " + ln, flush=True)
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
    if walls:
        over = {s: w for s, w in walls.items() if w > RUN_BUDGET_S}
        print(f"WHOLE RUN {ns.workload} {ns.label}: n={len(walls)} shortest "
              f"{min(walls.values()):.1f}s longest {max(walls.values()):.1f}s; "
              + (f"OVER {RUN_BUDGET_S}s (the driver stops a run at "
                 f"{RUN_LIMIT_S}s): " + ", ".join(
                     f"seed {s} {w:.1f}s" for s, w in over.items())
                 if over else f"none over {RUN_BUDGET_S}s"), flush=True)
    return rc_all


if __name__ == "__main__":
    sys.exit(main())
