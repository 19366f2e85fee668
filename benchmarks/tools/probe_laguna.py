"""Show that a `laguna` cell's `correct` can fail: the controls the
configuration's `check_reason` names (tools/probe_precision.py's table of
weights is the gpt tree's, tools/probe_zaya.py's the zaya family's; this
is the laguna family's own):

    python benchmarks/tools/probe_laguna.py --workload laguna-s-2.1.codegen --fault int6
    python benchmarks/tools/probe_laguna.py --workload laguna-s-2.1.codegen --fault window_whole
    python benchmarks/tools/probe_laguna.py --workload laguna-s-2.1.codegen --fault absent_as_held
    python benchmarks/tools/probe_laguna.py --workload laguna-s-2.1.codegen --fault bf16_router
    python benchmarks/tools/probe_laguna.py --workload laguna-s-2.1.codegen --fault none

`int6` (`int8`: the same through 8 bits): the engine is given every
matmul plane (attention projections and gates, embedding, head, the
dense MLP, shared and routed experts: all of the 11.14 GB but the router
and the norms) rounded through a signed 6-bit integer, abs-max per
output channel, and dequantised back to bf16. The reference keeps the
true weights; two copies of 10.2 GB of experts do not exist together on
a 16 GB chip, so the TRUE expert planes wait on the host while the
engine runs (the harness frees the engine before the reference, which
then takes them from there).
`window_whole`: the true weights; the window layers' mask widened to
every key the ring still holds (13 pages, 768-832 keys, where the model
attends 512).
`absent_as_held`: the true weights; a choice that lands on an expert of
the absent half (128-255) is answered by the held expert 128 below it,
as if this chip held all 256.
`bf16_router`: the true weights, the router's matmul and sigmoid in
bfloat16 where the program and the reference compute them in float32.

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
               "d_down": -2, "s_gate": -2, "s_up": -2, "s_down": -2,
               "w_gate": -2, "w_up": -2, "w_down": -2,
               **{p + n: -2 for p in ("f_", "w_")
                  for n in ("wq", "wk", "wv", "wg", "wo")}}
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

    laguna._attend_fn = attend_fn
    return params


def absent_as_held(params: dict) -> dict:
    from ray_tpu.models import laguna

    true = laguna._route

    def route(cfg, w_router, u):
        chosen, gates = true(cfg, w_router, u)
        return cfg.first_expert + (chosen - cfg.first_expert) % cfg.n_experts, gates

    laguna._route = route
    return params


def router_in_bf16(params: dict) -> dict:
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import laguna

    def route(cfg, w_router, u):
        bf = jnp.bfloat16
        s = jax.nn.sigmoid(u.astype(bf) @ w_router.astype(bf))
        top, chosen = jax.lax.top_k(s.astype(jnp.float32), cfg.top_k)
        return (chosen.astype(jnp.int32), cfg.routed_scale * top
                / jnp.sum(top, axis=-1, keepdims=True))

    laguna._route = route
    return params


FAULTS = {"int8": lambda p: round_trip(p, 8), "int6": lambda p: round_trip(p, 6),
          "window_whole": window_whole, "absent_as_held": absent_as_held,
          "bf16_router": router_in_bf16, "none": None}


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
