"""Did a run stall, and which kind of stall was it? Run one serving cell
several times in one call (each run a child that holds the chip alone,
as tools/measure.py does) and print, for every run, the engine's own
account of its worst moments beside the end-to-end rate:

    python benchmarks/tools/stall_report.py --workload laguna-s-2.1.codegen \
        --seeds 101,102,103 [--seconds N] [--trace 0|1] [--label set1]

`tick_ms_max`, `heartbeat_late_ms_max`, `emit_gap_ms_p99` and the first
entries of `metrics()["stalls"]` (phase, seconds into the window, ms, and
`late_ms`: how much of the turn the engine's heartbeat thread stood
still too) are read from `LLMEngine.metrics()` at the window's end,
through run.main's `after` hook, so an UNTRACED run reports them; the
harness's `worst oversleep` is read from its log line. A long turn with
an on-time heartbeat is the engine waiting (device, runtime, transfer); a
long turn with a heartbeat as late is the GIL held or the process
standing still: the child stamps its collections (`gc.callbacks`), so a
late turn that holds a full collection says which of the two it was.
A program without these counters (an older commit) prints `-` for them
and still reports its phases' sums, so the tool runs on both sides of a
comparison. Rows go to chiprun_out/stall_<workload>_<label>.jsonl.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

RUN_LIMIT_S = 360       # tools/measure.py: the driver's limit for a run
MARK = "STALL_REPORT "
KEPT = ("ticks", "tick_s", "tick_ms_mean", "tick_ms_p50", "tick_ms_p99",
        "tick_ms_max", "tick_host_share", "tick_blocked_share",
        "heartbeat_late_ms_max", "heartbeat_late_s", "emit_gap_ms_p50",
        "emit_gap_ms_p99", "emit_gap_ms_max", "lookahead_share",
        "decode_step_ms_p50", "decode_step_ms_p95", "compiles_in_window",
        "phase_s", "phase_n", "phase_max_s", "stalls")


_GC: list = []     # (began at, seconds, generation): gen 2, or over 5 ms


def _watch_gc() -> None:
    """Stamp the child's collections on `perf_counter`: a full collection
    holds the GIL, which a late heartbeat cannot tell from a process that
    stood still."""
    import gc

    began = []

    def stamp(phase, info):
        if phase == "start":
            began.append(time.perf_counter())
        elif began:
            t0 = began.pop()
            dt = time.perf_counter() - t0
            if info["generation"] == 2 or dt > 0.005:
                _GC.append((t0, dt, info["generation"]))

    gc.callbacks.append(stamp)


def report(result, rc) -> None:
    """run.main's `after`: the engine's account of the window, one line."""
    eng = result["ctx"]["engine"]
    row = {k: eng.get(k) for k in KEPT}
    # perf_counter stamps, the clock of the stalls' `t_start`.
    marks = dict(rc.marks)
    row["t_window"], t_end = marks["ramp"], marks["window"]
    row["trace_t0"] = result["ctx"].get("trace_t0")
    # The window's own: the harness collects once itself, after it.
    row["gc"] = [c for c in _GC
                 if c[0] + c[1] >= row["t_window"] and c[0] <= t_end]
    print(MARK + json.dumps(row), flush=True)


def child(ns) -> int:
    sys.path.insert(0, BENCH)
    import run as bench_run

    _watch_gc()
    return bench_run.main(["--workload", ns.workload, "--seed", ns.seeds,
                           "--seconds", str(ns.seconds),
                           "--trace", str(ns.trace)], after=report)


def _fmt(v, spec=".1f") -> str:
    return "-" if v is None else format(v, spec)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--label", default="set")
    ap.add_argument("--limit", type=float, default=RUN_LIMIT_S,
                    help="seconds a whole run may take (a whole-window "
                         "trace takes longer to stop and reduce)")
    ap.add_argument("--child", action="store_true")
    ns = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        ns.seconds = ns.seconds or json.load(f)["run_seconds"]
    if ns.child:
        return child(ns)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"stall_{ns.workload}_{ns.label}.jsonl")
    rc_all = 0
    for seed in ns.seeds.split(","):
        cmd = [sys.executable, os.path.abspath(__file__), "--child",
               "--workload", ns.workload, "--seeds", seed,
               "--seconds", str(ns.seconds), "--trace", str(ns.trace)]
        t0 = time.time()
        try:
            p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                               timeout=ns.limit)
        except subprocess.TimeoutExpired:
            print(f"seed {seed}: TIMED OUT after {ns.limit}s", flush=True)
            rc_all = 1
            continue
        lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
        with open(path.replace(".jsonl", f"_{seed}.log"), "w") as f:
            f.write(p.stdout + "\n--- stderr\n" + p.stderr[-6000:])
        marked = [ln for ln in lines if ln.startswith(MARK)]
        if p.returncode != 0 or not marked:
            print(f"seed {seed}: FAILED rc={p.returncode}\n"
                  + "\n".join(lines[-15:]) + "\n" + p.stderr[-3000:],
                  flush=True)
            rc_all = 1
            continue
        eng, line = json.loads(marked[-1][len(MARK):]), json.loads(lines[-1])
        over = re.search(r"worst oversleep ([0-9.]+) ms", p.stdout)
        row = {"seed": int(seed), "wall_s": time.time() - t0,
               "correct": line["correct"], "failed": line["failed"],
               "metrics": {k: v["value"] for k, v in line["metrics"].items()},
               "device": line.get("device"),
               "idle_gaps": (line.get("breakdown") or {}).get("idle_gaps"),
               "worst_oversleep_ms": float(over.group(1)) if over else None,
               "engine": eng}
        with open(path, "a") as f:
            f.write(json.dumps(row) + "\n")
        n = eng["phase_n"]
        emit_ms = (eng["phase_s"]["emit"] / n["emit"] * 1e3 if n["emit"]
                   else None)
        rate = next((v for k, v in row["metrics"].items()
                     if k.startswith("out_tokens_per_s")), None)
        print(f"seed {seed}: correct {row['correct']} "
              f"out_tokens_per_s {_fmt(rate, '.2f')} "
              f"ticks {eng['ticks']} tick ms mean/p50/p99/max "
              f"{_fmt(eng.get('tick_ms_mean'))}/{_fmt(eng['tick_ms_p50'])}/"
              f"{_fmt(eng['tick_ms_p99'])}/{_fmt(eng['tick_ms_max'])} "
              f"heartbeat late ms max {_fmt(eng['heartbeat_late_ms_max'])} "
              f"(sum {_fmt(eng['heartbeat_late_s'], '.3f')} s) "
              f"emit gap ms p50/p99/max {_fmt(eng['emit_gap_ms_p50'])}/"
              f"{_fmt(eng['emit_gap_ms_p99'])}/{_fmt(eng['emit_gap_ms_max'])} "
              f"harness worst oversleep ms {_fmt(row['worst_oversleep_ms'])} "
              f"host share {_fmt(eng.get('tick_host_share'), '.4f')} "
              f"emit turn ms {_fmt(emit_ms, '.3f')} "
              f"lookahead {_fmt(eng.get('lookahead_share'), '.4f')} "
              f"compiles {eng['compiles_in_window']}", flush=True)
        full = [c for c in eng["gc"] if c[2] == 2]
        print(f"    gc in the window: {len(full)} full collections, longest "
              f"{max((c[1] for c in full), default=0.0) * 1e3:.1f} ms; "
              f"{len(eng['gc']) - len(full)} younger ones over 5 ms",
              flush=True)
        for e in (eng["stalls"] or [])[:3]:
            t0, t1 = e["t_start"], e["t_start"] + e["ms"] / 1e3
            in_gc = sum(max(0.0, min(t1, c[0] + c[1]) - max(t0, c[0]))
                        for c in eng["gc"])
            print(f"    stall: {e['phase']} {e['ms']:.1f} ms at "
                  f"{e['t_start'] - eng['t_window']:.2f}s of the window, "
                  f"tick {e['tick']}, heartbeat late {e['late_ms']:.1f} ms, "
                  f"collecting {in_gc * 1e3:.1f} ms", flush=True)
        if row["idle_gaps"]:
            print(f"    traced: idle gaps {row['idle_gaps']}; "
                  + " ".join(f"{k}={row['metrics'][k]:.4f}" for k in sorted(
                      row["metrics"]) if re.match(
                          r"(tick_|idle_|device_idle|heartbeat|emit_gap|"
                          r"lookahead)", k)), flush=True)
    return rc_all


if __name__ == "__main__":
    sys.exit(main())
