"""Show that a `mimo_v2` cell's `correct` can fail: the controls the
configuration's `check_reason` names (tools/probe_laguna.py is the
laguna family's; this is the mimo_v2 family's own):

    python benchmarks/tools/probe_mimo_v2.py --workload mimo-v2-flash.think --fault sink_dropped
    ... --fault value_unscaled | window_whole | absent_as_held | int6 | choice_by_s | none

`sink_dropped`: the true weights; the window layers' softmax without its
learned sink (the kernels are handed `sink=None`).
`value_unscaled`: W_v of every layer divided by `attention_value_scale`
(0.707), so that V reaches the pool unscaled.
`window_whole`: the true weights; the window layers' mask widened to
every key the ring still holds (19 pages, 1,153-1,216 keys, where the
model attends 128).
`absent_as_held`: the true weights; a choice that lands on an absent
expert (16-255) is answered by the held expert with the same id modulo
16, as if this chip held all 256.
`int6` (`int8`: the same through 8 bits): the engine is given every
matmul plane (attention projections, embedding, head, the dense MLP, the
routed experts: all of the 6.86 GB but the router, its bias, the sinks
and the norms) rounded through a signed 6-bit integer, abs-max per output
channel, and dequantised back to bf16; the reference keeps the true
weights. The cell fills 15.1 of the chip's 16.9 GB, so no true plane can
stay beside its rounded copy: every TRUE plane waits on the host while
the engine runs (the harness frees the engine before the reference,
which then takes them from there).
`choice_by_s`: the true weights; the router chooses by the unbiased
score s where the model chooses by s + b.

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
_CONTRACTED = {"wte": -1, "lm_head": -2, "d_gate": -2, "d_up": -2,
               "d_down": -2, "w_gate": -2, "w_up": -2, "w_down": -2,
               **{p + n: -2 for p in ("f_", "w_")
                  for n in ("wq", "wk", "wv", "wo")}}


def round_trip(params: dict, bits: int) -> dict:
    """-> the weights the engine serves. `params` (the harness's own
    dict, which the reference reads after the engine is gone) keeps the
    true values, every rounded plane's as a host array from here on."""
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
        params[name] = np.asarray(w)
        w.delete()
    return served


def sink_dropped(params: dict) -> dict:
    from ray_tpu.models import mimo_v2

    mimo_v2._sink = lambda cfg, params, kind, i: None
    return params


def value_unscaled(params: dict) -> dict:
    served = dict(params)
    for name in ("f_wv", "w_wv"):
        served[name] = (params[name].astype("float32") / 0.707).astype(
            params[name].dtype)
    return served


def window_whole(params: dict) -> dict:
    from ray_tpu.models import laguna

    true = laguna._attend_fn

    def attend_fn(attn_impl, chunk):
        attend = true(attn_impl, chunk)

        def wide(*args, **kw):
            if kw.get("window") is not None:
                kw["window"] = 1 << 20
            return attend(*args, **kw)

        return wide

    laguna._attend_fn = attend_fn       # the walk both families share
    return params


def absent_as_held(params: dict) -> dict:
    from ray_tpu.models import mimo_v2

    true = mimo_v2._route

    def route(cfg, w_router, bias, u):
        chosen, gates, moved = true(cfg, w_router, bias, u)
        return (cfg.first_expert + (chosen - cfg.first_expert)
                % cfg.n_experts, gates, moved)

    mimo_v2._route = route
    return params


def choice_by_s(params: dict) -> dict:
    import jax.numpy as jnp

    from ray_tpu.models import mimo_v2

    true = mimo_v2._route
    mimo_v2._route = lambda cfg, w_router, bias, u: true(
        cfg, w_router, jnp.zeros_like(bias), u)
    return params


FAULTS = {"int8": lambda p: round_trip(p, 8), "int6": lambda p: round_trip(p, 6),
          "sink_dropped": sink_dropped, "value_unscaled": value_unscaled,
          "window_whole": window_whole, "absent_as_held": absent_as_held,
          "choice_by_s": choice_by_s, "none": None}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS), required=True)
    ap.add_argument("--seed", type=int, default=2**31 + 7)
    ap.add_argument("--seconds", type=float, default=10.0)
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
