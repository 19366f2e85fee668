"""Show that an `olmo_hybrid` cell's `correct` can fail: the controls the
configuration's `check_reason` names (tools/probe_qwen3_next.py and its
siblings are their families'; this is the olmo_hybrid family's own):

    python benchmarks/tools/probe_olmo_hybrid.py --workload olmo-hybrid-7b.rollout --fault beta_not_doubled
    python benchmarks/tools/probe_olmo_hybrid.py --workload olmo-hybrid-7b.rollout --fault state_zeroed
    python benchmarks/tools/probe_olmo_hybrid.py --workload olmo-hybrid-7b.rollout --fault qk_norm_dropped
    python benchmarks/tools/probe_olmo_hybrid.py --workload olmo-hybrid-7b.rollout --fault norms_at_inputs
    python benchmarks/tools/probe_olmo_hybrid.py --workload olmo-hybrid-7b.rollout --fault int6
    python benchmarks/tools/probe_olmo_hybrid.py --workload olmo-hybrid-7b.rollout --fault none

`beta_not_doubled`: the true weights; the linear layers' write strength
is sigmoid(b), in (0, 1), as if `linear_allow_neg_eigval` were false.
`state_zeroed`: the true weights; every chunk row starts its recurrence
from zeros, as if the state were not carried across a chunk boundary (a
256-token prompt then remembers its last 128 tokens only).
`qk_norm_dropped`: the true weights; the full layers' q and k go to the
softmax as projected, without the norm over their whole width.
`norms_at_inputs`: the true weights; every sublayer reads norm(x) and
its output joins the residual stream as it is (the pre-norm block of
every other family), the same norm weights.
`int6` (`int8`: the same through 8 bits): the engine is given every
matmul plane (both mixers' projections, the MLPs, embedding and head: all
of the 4.87 GB but the convolutions, the decay's leaves and the norms)
rounded through a signed 6-bit integer, abs-max per output channel, and
dequantised back to bf16. The reference keeps the true weights; the TRUE
planes wait on the host while the engine runs.

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
# leaves are stacks: the leading axis is the layer)
_CONTRACTED = {"wte": -1, "lm_head": -2, "g_qkvz": -2, "g_ba": -2,
               "g_out": -2, "f_qkv": -2, "f_wo": -2, "w_gate": -2,
               "w_up": -2, "w_down": -2}


def round_trip(params: dict, bits: int) -> dict:
    """-> the weights the engine serves. `params` (the harness's own
    dict, which the reference reads after the engine is gone) keeps the
    true values, the rounded planes' as HOST arrays from here on: two
    copies of a dense model's planes (9.7 GB) do not fit the chip beside
    this pool, and the harness frees the engine before the reference,
    which then takes them from there."""
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
    from ray_tpu.models import olmo_hybrid

    return olmo_hybrid


def beta_not_doubled(params: dict) -> dict:
    import jax.numpy as jnp

    oh = _model()
    true = oh._gdn_inputs

    def halved(*args):
        q, k, v, z, g, beta, ext = true(*args)
        return q, k, v, z, g, beta * jnp.float32(0.5), ext

    oh._gdn_inputs = halved
    return params


def state_zeroed(params: dict) -> dict:
    import jax.numpy as jnp

    oh = _model()
    scan = oh.gdn_chunk_scan
    oh.gdn_chunk_scan = (
        lambda q, k, v, g, beta, state, chain, fresh, **kw: scan(
            q, k, v, g, beta, state, chain, jnp.ones_like(fresh), **kw))
    return params


def qk_norm_dropped(params: dict) -> dict:
    """`rms_norm` switched off while `_attn_inputs` runs: its two calls
    are the norms over q's and k's whole width, and nothing else."""
    oh = _model()
    norm = oh.rms_norm
    attn = oh._attn_inputs

    def inputs(cfg, p, i, x):
        oh.rms_norm = lambda t, scale, eps: t
        try:
            return attn(cfg, p, i, x)
        finally:
            oh.rms_norm = norm

    oh._attn_inputs = inputs
    return params


def norms_at_inputs(params: dict) -> dict:
    """The pre-norm block: a sublayer reads norm(x), its output joins
    the stream as it is. (The i-th linear layer is layer i + i // 3, the
    i-th full layer 4 i + 3 at `full_interval` 4.)"""
    oh = _model()
    norm = oh.rms_norm
    gdn_in, gdn_out = oh._gdn_inputs, oh._gdn_output
    attn_in, attn_out, mlp = oh._attn_inputs, oh._attn_output, oh._mlp
    pre = lambda cfg, p, which, l, x: norm(x, p[which][l], cfg.norm_eps)

    def unnormed(fn):
        """`fn` with the stream-wide norm inside it switched off (the
        whole-width q / k norm is `_attn_inputs`', untouched)."""
        def call(*args):
            oh.rms_norm = lambda t, scale, eps: t
            try:
                return fn(*args)
            finally:
                oh.rms_norm = norm
        return call

    oh._gdn_inputs = lambda cfg, p, i, x, valid, boundary: gdn_in(
        cfg, p, i, pre(cfg, p, "ln1_scale", i + i // (cfg.full_interval - 1),
                       x), valid, boundary)
    oh._attn_inputs = lambda cfg, p, i, x: attn_in(
        cfg, p, i, pre(cfg, p, "ln1_scale",
                       cfg.full_interval * i + cfg.full_interval - 1, x))
    oh._gdn_output = unnormed(gdn_out)
    oh._attn_output = unnormed(attn_out)

    def pre_mlp(cfg, p, l, x):
        u = pre(cfg, p, "ln2_scale", l, x)
        return x + (unnormed(mlp)(cfg, p, l, u) - u)

    oh._mlp = pre_mlp
    return params


FAULTS = {"int8": lambda p: round_trip(p, 8), "int6": lambda p: round_trip(p, 6),
          "beta_not_doubled": beta_not_doubled, "state_zeroed": state_zeroed,
          "qk_norm_dropped": qk_norm_dropped,
          "norms_at_inputs": norms_at_inputs, "none": None}


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
