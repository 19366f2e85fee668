"""Show that a `qwen3_next` cell's `correct` can fail: the controls the
configuration's `check_reason` names (tools/probe_precision.py's table of
weights is the gpt tree's, tools/probe_zaya.py's and tools/probe_laguna.py's
their families'; this is the qwen3_next family's own):

    python benchmarks/tools/probe_qwen3_next.py --workload qwen3-next-80b-a3b.longform --fault state_bf16
    python benchmarks/tools/probe_qwen3_next.py --workload qwen3-next-80b-a3b.longform --fault state_zeroed
    python benchmarks/tools/probe_qwen3_next.py --workload qwen3-next-80b-a3b.longform --fault int6
    python benchmarks/tools/probe_qwen3_next.py --workload qwen3-next-80b-a3b.longform --fault absent_as_held
    python benchmarks/tools/probe_qwen3_next.py --workload qwen3-next-80b-a3b.longform --fault none

`state_bf16`: the true weights; the linear layers' matrix state rounded
to bfloat16 after every decode step (the pool keeps float32: the values
in it are bf16's).
`state_zeroed`: the true weights; every chunk row starts its recurrence
from zeros, as if the state were not carried across a chunk boundary (a
512-token prompt then remembers its last 128 tokens only).
`int6` (`int8`: the same through 8 bits): the engine is given every
matmul plane (both mixers' projections, embedding, head, shared and
routed experts: all of the 7.33 GB but the router, the convolution, the
decay's leaves and the norms) rounded through a signed 6-bit integer,
abs-max per output channel, and dequantised back to bf16. The reference
keeps the true weights; the TRUE expert planes wait on the host while
the engine runs (the harness frees the engine before the reference,
which then takes them from there).
`absent_as_held`: the true weights; a choice that lands on an expert of
the absent three quarters (128-511) is answered by the held expert with
the same id modulo 128, as if this chip held all 512.

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

# name -> the axis a matmul contracts over, counted from the END (the
# leaves are stacks: the leading axes are layers and experts)
_CONTRACTED = {"wte": -1, "lm_head": -2, "g_qkvz": -2, "g_ba": -2,
               "g_out": -2, "f_wq": -2, "f_wk": -2, "f_wv": -2, "f_wo": -2,
               "s_gate": -2, "s_up": -2, "s_down": -2, "w_gate": -2,
               "w_up": -2, "w_down": -2}
_EXPERTS = ("w_gate", "w_up", "w_down")


def round_trip(params: dict, bits: int) -> dict:
    """-> the weights the engine serves. `params` (the harness's own
    dict, which the reference reads after the engine is gone) keeps the
    true values, the experts' as host arrays from here on."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    top = float(2 ** (bits - 1) - 1)

    def one(w, axis):
        w32 = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w32), axis=axis, keepdims=True) / top
        q = jnp.clip(jnp.round(w32 / scale), -top, top)
        return (q * scale).astype(w.dtype)

    # A matrix at a time (the float32 copies of a stack do not fit).
    def by_matrix(w, axis):
        flat = w.reshape((-1,) + w.shape[-2:])
        return jax.lax.map(lambda x: one(x, axis), flat).reshape(w.shape)

    rounded = jax.jit(by_matrix, static_argnums=1)
    served = dict(params)
    for name, axis in _CONTRACTED.items():
        w = params[name]
        served[name] = jax.block_until_ready(rounded(w, axis))
        if name in _EXPERTS:
            params[name] = np.asarray(w)
            w.delete()
    return served


def state_in_bf16(params: dict) -> dict:
    import jax.numpy as jnp

    from ray_tpu.models import qwen3_next

    def rounded(step):
        def wrapped(*args, **kw):
            o, state = step(*args, **kw)
            return o, state.astype(jnp.bfloat16).astype(jnp.float32)
        return wrapped

    qwen3_next.gdn_decode_step = rounded(qwen3_next.gdn_decode_step)
    return params


def state_zeroed(params: dict) -> dict:
    import jax.numpy as jnp

    from ray_tpu.models import qwen3_next

    scan = qwen3_next.gdn_chunk_scan
    qwen3_next.gdn_chunk_scan = (
        lambda q, k, v, g, beta, state, chain, fresh, **kw: scan(
            q, k, v, g, beta, state, chain, jnp.ones_like(fresh), **kw))
    return params


def absent_as_held(params: dict) -> dict:
    from ray_tpu.models import qwen3_next

    true = qwen3_next._route

    def route(cfg, w_router, u):
        chosen, gates = true(cfg, w_router, u)
        return cfg.first_expert + (chosen - cfg.first_expert) % cfg.n_experts, gates

    qwen3_next._route = route
    return params


FAULTS = {"int8": lambda p: round_trip(p, 8), "int6": lambda p: round_trip(p, 6),
          "state_bf16": state_in_bf16, "state_zeroed": state_zeroed,
          "absent_as_held": absent_as_held, "none": None}


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
