"""Show that a `jamba` cell's `correct` can fail: the controls the
configuration's `check_reason` names (tools/probe_precision.py's table of
weights is the gpt tree's, tools/probe_qwen3_next.py's its family's; this
is the jamba family's own):

    python benchmarks/tools/probe_jamba.py --workload ai21-jamba2-3b.solve --fault state_zeroed
    python benchmarks/tools/probe_jamba.py --workload ai21-jamba2-3b.solve --fault int6
    python benchmarks/tools/probe_jamba.py --workload ai21-jamba2-3b.solve --fault norms_dropped
    python benchmarks/tools/probe_jamba.py --workload ai21-jamba2-3b.solve --fault tail_dropped
    python benchmarks/tools/probe_jamba.py --workload ai21-jamba2-3b.solve --fault state_bf16
    python benchmarks/tools/probe_jamba.py --workload ai21-jamba2-3b.solve --fault none

`state_zeroed`: the true weights; every chunk row starts its recurrence
from zeros, as if the state were not carried across a chunk boundary (a
256-token prompt then remembers its last 128 tokens only).
`tail_dropped`: the true weights; a decode step's convolution sees
zeros before its token (the taps of the three older inputs are zeroed),
as if the convolution's tail were not carried from step to step.
`int6` (`int8`: the same through 8 bits): the engine is given every
matmul plane (the mixers' projections, the MLPs, the tied table: all of
the 6.06 GB but the vectors) rounded through a signed 6-bit integer,
abs-max per output channel, and dequantised back to bf16, a plane at a
time. The reference keeps the true weights; so that both trees fit, the
TRUE planes wait on the host while the engine runs (the harness frees
the engine before the reference, which takes them from there).
`norms_dropped`: the true weights; the mixer's three inner norms (on the
step's, B's and C's projections) are left out, the raw projections go on
times the norms' weights.
`state_bf16`: the true weights; the state-space state rounded to
bfloat16 after every decode step (the pool keeps float32: the values in
it are bf16's). NOT expected to be refused on the chip: see the
configuration's `check_reason`.

The run goes through benchmarks/run.py unchanged otherwise. Exit code 0
iff the line's `correct` is what `--expect` says (fail for a fault, pass
for `none`) with no failed request and no compile in the window. Not
part of a benchmark run.
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


# The leaves that are a matmul's right operand, a stack over layers each.
PLANES = ("m_in", "m_x", "m_dt", "m_out", "a_wq", "a_wk", "a_wv", "a_wo",
          "w_gate", "w_up", "w_down")


def _contracted(name: str):
    """The axis a plane's matmul contracts over, or None for a leaf that
    is no matmul's operand. The tied table is read as the head: logits =
    x wte^T contracts its last axis."""
    if name == "wte":
        return -1
    return -2 if name in PLANES else None


def round_trip(params: dict, bits: int) -> dict:
    """-> the weights the engine serves. `params` (the harness's own
    dict, which the reference reads after the engine is gone) keeps the
    true values, the planes' as host arrays from here on."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    top = float(2 ** (bits - 1) - 1)

    def one(w, axis):
        w32 = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w32), axis=axis, keepdims=True) / top
        q = jnp.clip(jnp.round(w32 / scale), -top, top)
        return (q * scale).astype(w.dtype)

    rounded = jax.jit(one, static_argnums=1)
    served = dict(params)
    for name, w in params.items():
        axis = _contracted(name)
        if axis is None:
            continue
        served[name] = jax.block_until_ready(rounded(w, axis))
        params[name] = np.asarray(w)
        w.delete()
    return served


def state_zeroed(params: dict) -> dict:
    import jax.numpy as jnp

    from ray_tpu.models import jamba

    scan = jamba.ssm_chunk_scan
    jamba.ssm_chunk_scan = (
        lambda xs, dt, B, C, A, D, state, chain, fresh, **kw: scan(
            xs, dt, B, C, A, D, state, chain, jnp.ones_like(fresh), **kw))
    return params


def tail_dropped(params: dict) -> dict:
    from ray_tpu.models import jamba

    step = jamba.ssm_conv_step
    jamba.ssm_conv_step = (
        lambda tail, layer, xs, taps, bias, active, **kw: step(
            tail, layer, xs, taps.at[:-1].set(0.0), bias, active, **kw))
    return params


def norms_dropped(params: dict) -> dict:
    import jax.numpy as jnp

    from ray_tpu.models import jamba

    jamba._unit_rms = lambda x, w, eps: x * w.astype(jnp.float32)
    return params


def state_in_bf16(params: dict) -> dict:
    import jax.numpy as jnp

    from ray_tpu.models import jamba

    step = jamba.ssm_decode_step

    def rounded(*args, **kw):
        y, state = step(*args, **kw)
        return y, state.astype(jnp.bfloat16).astype(jnp.float32)

    jamba.ssm_decode_step = rounded
    return params


FAULTS = {"int8": lambda p: round_trip(p, 8),
          "int6": lambda p: round_trip(p, 6), "state_zeroed": state_zeroed,
          "tail_dropped": tail_dropped, "norms_dropped": norms_dropped,
          "state_bf16": state_in_bf16, "none": None}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS), required=True)
    ap.add_argument("--seed", type=int, default=2**31 + 7)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--expect", choices=("pass", "fail"))
    ns = ap.parse_args()
    expect = ns.expect or ("pass" if ns.fault == "none" else "fail")
    seen = {}

    def after(result, rc):
        seen["check"] = result["notes"]["check"]
        seen["failed"] = result["failed"]
        seen["compiles"] = result["compiles_in_window"]

    buf = io.StringIO()
    with redirect_stdout(buf):
        bench_run.main(["--workload", ns.workload, "--seed", str(ns.seed),
                        "--seconds", str(ns.seconds), "--trace", "0"],
                       degrade=FAULTS[ns.fault], after=after)
    lines = [ln for ln in buf.getvalue().splitlines() if ln.strip()]
    print("\n".join(lines[:-1]), flush=True)
    line = json.loads(lines[-1])
    print(f"PROBE {ns.workload} fault {ns.fault}: correct={line['correct']} "
          f"failed={seen['failed']} compiles_in_window={seen['compiles']}; "
          f"check {seen['check']}", flush=True)
    want = expect == "pass"
    ok = (line["correct"] is want and seen["failed"] == 0
          and seen["compiles"] == 0 and seen["check"]["ok"] is want)
    print("PROBE " + ("as expected" if ok else "NOT as expected"), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
