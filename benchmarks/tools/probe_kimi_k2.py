"""Show that a `kimi_k2` cell's `correct` can fail: the controls the
configuration's `check_reason` names (tools/probe_mimo_v2.py is the
mimo_v2 family's; this is the kimi_k2 family's own):

    python benchmarks/tools/probe_kimi_k2.py --workload kimi-k2.6.longthink --fault rope_dropped
    ... --fault latent_norm_dropped | scale_without_m2 | routed_scale_dropped
        | shared_dropped | absent_as_held | row_int6 | choice_by_s | none

Each fault serves the true weights (but `shared_dropped`) through a block
with one thing wrong; the reference keeps the true block:
`rope_dropped`: the rotary part of the score dropped (every head's q_pe
zeroed, so a score is q~ . c alone: attention blind to position).
`latent_norm_dropped`: the latent c is cached as it leaves W_kva, without
its RMSNorm.
`scale_without_m2`: the softmax scale 192^-1/2 without YaRN's m^2 (2.005).
`routed_scale_dropped`: the gates without `routed_scaling_factor` (2.827).
`shared_dropped`: the shared expert's W_down zeroed.
`absent_as_held`: a choice that lands on an absent expert (12-383) is
answered by the held expert with the same id modulo 12, as if this chip
held all 384.
`row_int6`: every cached row rounded through a signed 6-bit integer
(abs-max a row) on its way into the pool.
`choice_by_s`: the router chooses by the unbiased score s where the model
chooses by s + b.

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


def _block():
    from ray_tpu.models import kimi_k2

    return kimi_k2


def rope_dropped(params: dict) -> dict:
    import jax.numpy as jnp

    block, true = _block(), _block()._absorb_query
    block._absorb_query = lambda cfg, w_uk, q_nope, q_pe: true(
        cfg, w_uk, q_nope, jnp.zeros_like(q_pe))
    return params


def latent_norm_dropped(params: dict) -> dict:
    block, true = _block(), _block().rms_norm
    rank = params["kv_norm"].shape[-1]      # no other norm is this wide
    block.rms_norm = lambda x, scale, eps: (
        x if scale.shape[-1] == rank else true(x, scale, eps))
    return params


def scale_without_m2(params: dict) -> dict:
    block = _block()
    block.softmax_scale = lambda cfg: (
        cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    return params


def _route_fault(change):
    """Install a router that is the true one with `change(cfg, chosen,
    gates, bias) -> (chosen, gates)` or a changed bias."""
    block, true = _block(), _block()._route

    def route(cfg, w_router, bias, u):
        chosen, gates, moved = true(cfg, w_router, bias, u)
        chosen, gates = change(cfg, chosen, gates)
        return chosen, gates, moved

    block._route = route


def routed_scale_dropped(params: dict) -> dict:
    _route_fault(lambda cfg, chosen, gates: (chosen,
                                             gates / cfg.routed_scale))
    return params


def shared_dropped(params: dict) -> dict:
    return dict(params, s_down=params["s_down"] * 0)


def absent_as_held(params: dict) -> dict:
    _route_fault(lambda cfg, chosen, gates: (
        cfg.first_expert + (chosen - cfg.first_expert) % cfg.n_experts,
        gates))
    return params


def row_int6(params: dict) -> dict:
    import jax.numpy as jnp

    block, true = _block(), _block().write_row
    top = float(2 ** 5 - 1)

    def write(pool, l, pages, offs, row, plane):
        r32 = row.astype(jnp.float32)
        scale = jnp.maximum(jnp.max(jnp.abs(r32), axis=-1, keepdims=True),
                            1e-30) / top
        rounded = jnp.clip(jnp.round(r32 / scale), -top, top) * scale
        return true(pool, l, pages, offs, rounded.astype(row.dtype), plane)

    block.write_row = write
    return params


def choice_by_s(params: dict) -> dict:
    import jax.numpy as jnp

    block, true = _block(), _block()._route
    block._route = lambda cfg, w_router, bias, u: true(
        cfg, w_router, jnp.zeros_like(bias), u)
    return params


FAULTS = {"rope_dropped": rope_dropped,
          "latent_norm_dropped": latent_norm_dropped,
          "scale_without_m2": scale_without_m2,
          "routed_scale_dropped": routed_scale_dropped,
          "shared_dropped": shared_dropped, "absent_as_held": absent_as_held,
          "row_int6": row_int6, "choice_by_s": choice_by_s, "none": None}


def main() -> int:
    import run as bench_run

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
