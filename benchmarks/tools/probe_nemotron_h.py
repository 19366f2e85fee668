"""Show that a `nemotron_h` cell's `correct` can fail: the controls the
configuration's `check_reason` names (tools/probe_jamba.py and its
siblings are their families'; this is the nemotron_h family's own):

    python benchmarks/tools/probe_nemotron_h.py --workload nemotron-3-super-120b-a12b.subagents --fault state_zeroed
    ... --fault decay_one | group_zero | relu_for_relu2 | gates_unscaled | latent_out_dropped | int6 | int8 | none

`state_zeroed`: the true weights; every chunk row starts its recurrence
from zeros, as if the state were not carried across a chunk boundary (a
256-token prompt then remembers its last 128 tokens only).
`decay_one`: the true weights; the decay taken as 1 (a_h = 1: the state
forgets nothing).
`group_zero`: the true weights; every head reads group 0's B and C.
`relu_for_relu2`: the true weights; the routed experts' relu(.)^2 made
relu(.) (the shared expert keeps its square).
`gates_unscaled`: the true weights; the gates not scaled by
`routed_scaling_factor` 5.
`latent_out_dropped`: the latent's output projection zero, so that the
routed sum never reaches the stream (the shared expert alone).
`int6` (`int8`: the same through 8 bits): the engine is given every
matmul plane (the mixers' projections, router, latent projections,
experts, shared expert, embedding and head: all of the 9.3 GB but the
convolutions, the decay's leaves and the norms) rounded through a signed
6-bit integer, abs-max per output channel, and dequantised back to bf16.
The reference keeps the true weights; the TRUE planes wait on the host
while the engine runs.

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
# leaves are stacks: the leading axes are the layer and the expert)
_CONTRACTED = {"wte": -1, "lm_head": -2, "m_in": -2, "m_out": -2,
               "a_wq": -2, "a_wk": -2, "a_wv": -2, "a_wo": -2, "router": -2,
               "lat_in": -2, "lat_out": -2, "w_up": -2, "w_down": -2,
               "s_up": -2, "s_down": -2}


def round_trip(params: dict, bits: int) -> dict:
    """-> the weights the engine serves. `params` (the harness's own
    dict, which the reference reads after the engine is gone) keeps the
    true values, the rounded planes' as HOST arrays from here on: two
    copies of the planes (18.6 GB) do not fit the chip, and the harness
    frees the engine before the reference, which then takes them from
    there."""
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


def _model():
    from ray_tpu.models import nemotron_h

    return nemotron_h


def state_zeroed(params: dict) -> dict:
    import jax.numpy as jnp

    nh = _model()
    scan = nh.ssd_chunk_scan
    nh.ssd_chunk_scan = (
        lambda x, dt, A, B, C, state, chain, fresh, **kw: scan(
            x, dt, A, B, C, state, chain, jnp.ones_like(fresh), **kw))
    return params


def decay_one(params: dict) -> dict:
    import jax.numpy as jnp

    nh = _model()
    rate = nh._rate
    nh._rate = lambda p, i: jnp.zeros_like(rate(p, i))
    return params


def group_zero(params: dict) -> dict:
    import jax.numpy as jnp

    nh = _model()
    true = nh._ssm_inputs

    def inputs(*args):
        xs, z, dt, B, C, ext = true(*args)
        first = lambda t: jnp.broadcast_to(t[..., :1, :], t.shape)
        return xs, z, dt, first(B), first(C), ext

    nh._ssm_inputs = inputs
    return params


def relu_for_relu2(params: dict) -> dict:
    """`jnp.square` switched off while the grouped matmuls' body is
    traced: its one call there is the routed experts' square."""
    import jax.numpy as jnp

    from ray_tpu.ops import moe

    true = moe._experts

    def experts(*args):
        square, jnp.square = jnp.square, (lambda t: t)
        try:
            return true(*args)
        finally:
            jnp.square = square

    moe._experts = experts
    return params


def gates_unscaled(params: dict) -> dict:
    nh = _model()
    route = nh.blocks.biased_route

    def unscaled(cfg, w_router, bias, u):
        chosen, gates, moved = route(cfg, w_router, bias, u)
        return chosen, gates / cfg.routed_scale, moved

    nh.blocks.biased_route = unscaled
    return params


def latent_out_dropped(params: dict) -> dict:
    import jax.numpy as jnp

    return {**params, "lat_out": jnp.zeros_like(params["lat_out"])}


FAULTS = {"int8": lambda p: round_trip(p, 8), "int6": lambda p: round_trip(p, 6),
          "state_zeroed": state_zeroed, "decay_one": decay_one,
          "group_zero": group_zero, "relu_for_relu2": relu_for_relu2,
          "gates_unscaled": gates_unscaled,
          "latent_out_dropped": latent_out_dropped, "none": None}


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
