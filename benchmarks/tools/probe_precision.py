"""Show that `correct` can fail: serve a cell with weights of LOWER
precision than the reference holds, and expect the check to refuse it.

    python benchmarks/tools/probe_precision.py --workload opt-1.3b.batch [--bits 6] [--seconds 10]

The engine is given every matmul weight rounded through a signed integer
of `--bits` bits (abs-max per output channel, dequantised back to bf16:
at 8 bits, what a weight-only int8 server multiplies by); the reference
keeps the true bf16 weights. On the chip (PR 23) 8 bits read 1.97 x the
plain bf16 forward's mean deficit and PASS the factor of 3; 6 bits must
fail. The
run goes through benchmarks/run.py unchanged otherwise. Exit code 0 iff
the run's line says `"correct": false` for the reference check's sake
(no failed request, no compile in the window). With `--true-weights` the
same report for the true weights, which must PASS. Not part of a
benchmark run.
"""

from __future__ import annotations

import argparse
import io
import json
import os
import sys
from contextlib import redirect_stdout

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.dirname(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

import run as bench_run                      # noqa: E402

# name -> the axes a matmul contracts over (the rest are output channels)
_CONTRACTED = {"wq": (1,), "wk": (1,), "wv": (1,), "wo": (1, 2),
               "w_up": (1,), "w_down": (1,), "lm_head": (0,)}


def round_trip(params: dict, bits: int = 8) -> dict:
    import jax
    import jax.numpy as jnp

    top = float(2 ** (bits - 1) - 1)

    def one(name, w):
        if name not in _CONTRACTED:
            return w
        w32 = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w32), axis=_CONTRACTED[name],
                        keepdims=True) / top
        q = jnp.clip(jnp.round(w32 / scale), -top, top)
        return (q * scale).astype(w.dtype)

    out = jax.jit(lambda p: {k: one(k, v) for k, v in p.items()})(params)
    return jax.block_until_ready(out)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2**31 + 7)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--bits", type=int, default=6)
    ap.add_argument("--expect", choices=("pass", "fail"), default="fail",
                    help="what the check is expected to say of the rounded "
                         "weights (8 bits pass, 7 or fewer fail)")
    ap.add_argument("--true-weights", action="store_true",
                    help="serve the true weights: the same report, and the "
                         "check is expected to PASS")
    ns = ap.parse_args()
    seen = {}

    def after(result, rc):
        notes = result["notes"]
        seen["check"] = notes["check"]
        seen["failed"] = result["failed"]
        seen["compiles"] = result["compiles_in_window"]

    buf = io.StringIO()
    with redirect_stdout(buf):
        bench_run.main(["--workload", ns.workload, "--seed", str(ns.seed),
                        "--seconds", str(ns.seconds), "--trace", "0"],
                       degrade=None if ns.true_weights else
                       (lambda p: round_trip(p, ns.bits)),
                       after=after)
    lines = [ln for ln in buf.getvalue().splitlines() if ln.strip()]
    print("\n".join(lines[:-1]), flush=True)
    line = json.loads(lines[-1])
    served = "true" if ns.true_weights else f"{ns.bits}-bit-rounded"
    print(f"PROBE {ns.workload} serving {served} weights: correct="
          f"{line['correct']} failed={seen['failed']} compiles_in_window="
          f"{seen['compiles']}; check {seen['check']}", flush=True)
    want = bool(ns.true_weights) or ns.expect == "pass"
    ok = (line["correct"] is want and seen["failed"] == 0
          and seen["compiles"] == 0 and seen["check"]["ok"] is want)
    print("PROBE " + ("as expected" if ok else "NOT as expected"), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
