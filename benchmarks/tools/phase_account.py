"""Print the engine's account of its own tick beside a benchmark run.

    python benchmarks/tools/phase_account.py --workload opt-1.3b.batch [--seed N] [--seconds 51] [--trace 1]

The run goes through benchmarks/run.py unchanged; before its result line
this prints what that line has no room for: `LLMEngine.metrics()`'s
`phase_s` / `phase_n` / `tick_s` over the window (seconds and entries
per phase, whole ticks only), how much of the tick time the phases
cover, and, for a traced run, `harness/host_phases.idle_split` of the
run's trace (the device's idle time per phase). Exit code 0 iff the
phases cover 98 % of the tick time and, when traced, the two idle shares
do not exceed the device's idle time. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench_run                      # noqa: E402
from harness import host_phases              # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2**31 + 11)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ns = ap.parse_args()
    ok = []

    def after(result, rc):
        eng = result["ctx"]["engine"]
        if "phase_s" not in eng:
            print("ACCOUNT this program's engine keeps no tick account",
                  flush=True)
            ok.append(False)
            return
        covered = sum(eng["phase_s"].values()) / eng["tick_s"]
        print("ACCOUNT " + json.dumps({
            k: eng.get(k) for k in (
                "ticks", "tick_s", "tick_ms_mean", "tick_host_share",
                "tick_blocked_share", "decode_dispatch_ms_mean",
                "awaiting_first_token", "awaiting_first_token_max", "queued",
                "phase_s", "phase_n", "decode_windows",
                "decode_step_ms_p50")}), flush=True)
        print(f"ACCOUNT the phases cover {covered:.4%} of {eng['tick_s']:.3f}s "
              f"in {eng['ticks']} ticks", flush=True)
        ok.append(covered >= 0.98)
        if ns.trace:
            path = host_phases.newest_xplane()
            split = host_phases.idle_split(path) if path else None
            print("ACCOUNT idle split of " + str(path) + ": "
                  + json.dumps(split), flush=True)
            ok.append(bool(split) and split["host_work_s"]
                      + split["dispatch_s"] <= split["idle_s"] * (1 + 1e-9))

    rc = bench_run.main(["--workload", ns.workload, "--seed", str(ns.seed),
                         "--seconds", str(ns.seconds),
                         "--trace", str(ns.trace)], after=after)
    return rc or (0 if ok and all(ok) else 1)


if __name__ == "__main__":
    sys.exit(main())
