"""Show that a `zaya` cell's `correct` can fail, by the two faults the
configuration's `check_reason` names (tools/probe_precision.py's table of
weights is the gpt tree's; this is the zaya family's own):

    python benchmarks/tools/probe_zaya.py --workload zaya1-8b.reason --fault int8
    python benchmarks/tools/probe_zaya.py --workload zaya1-8b.reason --fault bf16_router
    python benchmarks/tools/probe_zaya.py --workload zaya1-8b.reason --fault none

(`int6`: as `int8`, through 6 bits.)

`int8`: the engine is given the attention projections, the tied
embedding and all 16 experts of every layer (all but 0.05 of 11.04 GB:
not the convolutions' taps, the router, norms or scales) rounded through
a signed 8-bit integer, abs-max per output channel, and dequantised back
to bf16: what a weight-only int8 server multiplies by. The reference
keeps the true weights; two copies of 9.7 GB of experts do not exist
together on a 16 GB chip, so the TRUE expert planes wait on the host
while the engine runs (the harness frees the engine before the
reference, which then takes them from there). `bf16_router`: the true
weights, with the router's arithmetic (its five matmuls, norm, GELUs,
softmax and the carried stream) in bfloat16 where the program and the
reference compute it in float32. The reference keeps the true weights
and its float32 router.
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

# name -> the axis a matmul contracts over (the rest are output channels)
_CONTRACTED = {"wq": 1, "wk": 1, "wv1": 1, "wv2": 1, "wo": 1, "wte": 1,
               "w_gate": 2, "w_up": 2, "w_down": 2}
_EXPERTS = ("w_gate", "w_up", "w_down")


def round_trip(params: dict, bits: int = 8) -> dict:
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

    # A leaf at a time, a layer at a time where there are layers (the
    # float32 copies do not fit together); the router, norms, biases and
    # scales are served as they are.
    whole = jax.jit(one, static_argnums=1)
    by_layer = jax.jit(lambda w, axis: jax.lax.map(
        lambda x: one(x, axis - 1), w), static_argnums=1)
    served = dict(params)
    for name, axis in _CONTRACTED.items():
        w = params[name]
        served[name] = jax.block_until_ready(
            (whole if name == "wte" else by_layer)(w, axis))
        if name in _EXPERTS:
            params[name] = np.asarray(w)
            w.delete()
    return served


def router_in_bf16(params: dict) -> dict:
    """The true weights; the program's router retraced in bfloat16."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import zaya

    def route(cfg, layer, u, r):
        bf = jnp.bfloat16
        w = lambda name: layer[name].astype(bf)
        r = (u.astype(bf) @ w("r_down") + w("r_down_b")
             + w("r_gamma") * r.astype(bf))
        h = zaya._rms_norm(r, w("r_norm"), cfg.norm_eps)
        h = jax.nn.gelu(h @ w("r_w1") + w("r_b1"), approximate=False)
        h = jax.nn.gelu(h @ w("r_w2") + w("r_b2"), approximate=False)
        p = jax.nn.softmax(h @ w("r_w3"), axis=-1)
        expert = jnp.argmax(p + w("r_beta"), axis=-1).astype(jnp.int32)
        gate = jnp.take_along_axis(p, expert[:, None], axis=-1)[:, 0]
        return expert, gate.astype(jnp.float32), r.astype(jnp.float32)

    zaya._route = route
    return params


FAULTS = {"int8": round_trip, "int6": lambda p: round_trip(p, 6),
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
