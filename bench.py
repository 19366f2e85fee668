"""Headline benchmark: GPT-2 124M pretrain step throughput (tokens/sec/chip).

Mirrors BASELINE.json config 2 (GPT-2 124M LM pretrain) scaled to the single
available chip; the flagship metric family is Train tokens/sec/chip.
`published` in BASELINE.json is empty → vs_baseline is reported against our
own first recorded value when available (BENCH_BASELINE.json), else 1.0.

This is a chip measurement: it needs a TPU, initialises the backend in this
one process (the chip belongs to one process at a time), and exits non-zero
when the platform is not `tpu`, when the device is not in the peak table, or
when the step fails. `BENCH_SMOKE=1` is the CPU rehearsal of the same code
path at tiny size; its output is labelled as a CPU run and carries no MFU.

Prints one JSON line:
  {"metric": ..., "value": N, "unit": "tokens/sec/chip", "vs_baseline": N,
   "mfu": N, "platform": ..., "device_kind": ..., ...}
"""

import json
import os
import sys
import time

from ray_tpu.utils.platform import place_compile_cache

place_compile_cache()

# Peak dense bf16 FLOP/s per chip by TPU generation (Google Cloud TPU
# documentation, per-generation system-architecture pages). Most-specific
# keys first: matched as substrings of the normalized device_kind (e.g.
# "TPU v5 lite" → "tpuv5lite", "TPU v6 lite" → "tpuv6lite"). A device
# that is not in the table is an error, not a default.
_PEAK_FLOPS = (
    ("v5litepod", 197e12),
    ("v5lite", 197e12),
    ("v6lite", 918e12),
    ("v5e", 197e12),
    ("v6e", 918e12),
    ("v5p", 459e12),
    ("v2", 22.5e12),
    ("v3", 61.25e12),  # per chip (2 cores)
    ("v4", 275e12),
)


def _peak_flops(device) -> float:
    kind = getattr(device, "device_kind", "").lower().replace(" ", "")
    for key, val in _PEAK_FLOPS:
        if key in kind:
            return val
    raise ValueError(
        f"no peak FLOP/s known for device_kind "
        f"{getattr(device, 'device_kind', None)!r}; add it to _PEAK_FLOPS "
        "with its source")


def _gpt_train_flops_per_token(cfg) -> float:
    """~6N per token (fwd 2N + bwd 4N) + attention score/value term.

    N counts matmul params only: tied embedding/unembedding, per-layer
    qkv+proj (4*d^2) and MLP in+out (2*d*d_ff); rotary has no position table.
    """
    n_params = (
        cfg.vocab_size * cfg.d_model
        + cfg.n_layers
        * (4 * cfg.d_model * cfg.d_model + 2 * cfg.d_model * cfg.d_ff)
    )
    attn = 12 * cfg.n_layers * cfg.d_model * cfg.max_seq
    return 6.0 * n_params + attn


def main() -> None:
    _model = os.environ.get("BENCH_MODEL", "gpt2_124m")
    metric = f"{_model}_train_tokens_per_sec_per_chip"
    unit = "tokens/sec/chip"
    smoke = bool(os.environ.get("BENCH_SMOKE"))

    if smoke:
        # CPU rehearsal of the code path; never a measurement, and never
        # printed under the device metric's name.
        metric, unit = f"cpu_rehearsal_{metric}", "tokens/sec (CPU rehearsal)"
        from ray_tpu.utils.platform import force_cpu_devices

        force_cpu_devices(1)

    import jax

    devs = jax.devices()
    if not smoke and devs[0].platform != "tpu":
        sys.exit(f"bench.py: JAX platform is {devs[0].platform!r}; this is "
                 "a chip measurement and needs a TPU (BENCH_SMOKE=1 is the "
                 "CPU rehearsal)")
    peak_flops = None if smoke else _peak_flops(devs[0])  # unknown: fail now

    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_tpu.models import gpt
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.train import spmd

    n_dev = len(devs)
    platform = devs[0].platform
    mesh = make_mesh(MeshConfig(dp=1, fsdp=-1, sp=1, tp=1))

    if smoke:  # tiny model, real path
        cfg = gpt.GPTConfig.tiny()
        B, S = 2 * n_dev, 128
    else:
        # Tuned defaults (see BENCH.md ablation, measured on v5e):
        # the in-repo Pallas flash-attention kernel (bf16 MXU dots,
        # 512x512 blocks), remat ON (with the fast kernel the recompute
        # is cheaper than the HBM traffic of storing activations —
        # 83.8k tok/s vs 82.6k off), B=8/chip (B=16/32 amortize no
        # better). Every knob is env-overridable for ablations
        # (BENCH_ATTN / BENCH_REMAT / BENCH_BATCH / BENCH_SEQ /
        # BENCH_CHUNK / BENCH_MODEL).
        model_name = os.environ.get("BENCH_MODEL", "gpt2_124m")
        S = int(os.environ.get("BENCH_SEQ", "1024"))
        chunk = int(os.environ.get("BENCH_CHUNK", "0")) or None
        cfg_kw = dict(
            max_seq=S,
            remat=os.environ.get("BENCH_REMAT", "1") == "1",
            attn_impl=os.environ.get("BENCH_ATTN", "flash"),
            loss_chunk=chunk,
        )
        # Attention tiles: env overrides win; otherwise the model
        # registry's per-tier defaults apply (1024 globally, 512 for
        # 2.7B whose 1024-tile backward scratch OOMs one chip).
        if os.environ.get("BENCH_BLOCK_Q"):
            cfg_kw["attn_block_q"] = int(os.environ["BENCH_BLOCK_Q"])
        if os.environ.get("BENCH_BLOCK_KV"):
            cfg_kw["attn_block_kv"] = int(os.environ["BENCH_BLOCK_KV"])
        cfg = gpt.GPTConfig.by_name(model_name, **cfg_kw)
        B = int(os.environ.get("BENCH_BATCH", str(8 * n_dev)))
    # BENCH_OPT=adafactor for tiers whose fp32 adam moments don't fit
    # one chip; BENCH_OPT=adafactor_sr additionally keeps the MASTER
    # WEIGHTS in bf16 with stochastic-rounding updates (halves param
    # + grad residency — the 2.7B-tier enabler, train/low_precision.py;
    # see train/memory_audit.py + tests/test_sharding_audit).
    bench_opt = os.environ.get("BENCH_OPT", "adamw")
    stochastic_round = False
    if bench_opt == "adafactor_sr":
        import dataclasses

        optimizer = optax.adafactor(
            3e-4,
            multiply_by_parameter_scale=not os.environ.get(
                "BENCH_AF_NOSCALE"))
        stochastic_round = True
        cfg = dataclasses.replace(cfg, param_dtype=jnp.bfloat16)
    elif bench_opt == "adafactor":
        # BENCH_AF_NOSCALE=1 drops multiply_by_parameter_scale (its
        # param-RMS reduce + fp32 broadcast temps showed up as the
        # largest optimizer-phase allocations in the B=12 OOM dump).
        optimizer = optax.adafactor(
            3e-4,
            multiply_by_parameter_scale=not os.environ.get(
                "BENCH_AF_NOSCALE"))
    else:
        # Adam's first moment in bf16 (default; BENCH_MU=fp32 to
        # ablate) halves the mu read+write HBM traffic per step —
        # measured 83.7k → 84.7k tok/s on v5e. The second moment
        # stays fp32: its magnitudes span too many octaves for bf16.
        mu_env = os.environ.get("BENCH_MU", "bf16").strip()
        if mu_env not in ("bf16", "fp32"):
            raise ValueError(f"BENCH_MU must be bf16|fp32, got "
                             f"{mu_env!r}")
        mu_dtype = {"bf16": "bfloat16", "fp32": None}[mu_env]
        optimizer = optax.adamw(3e-4, weight_decay=0.1,
                                mu_dtype=mu_dtype)
    params, opt_state, step = spmd.build_training(
        cfg, mesh, optimizer, jax.random.key(0),
        stochastic_round=stochastic_round,
    )

    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)
    targets = jnp.roll(toks, -1, axis=1)

    # Warmup / compile (donation means we must thread state through).
    params, opt_state, loss = step(params, opt_state, (toks, targets))
    jax.block_until_ready(loss)

    n_steps = 20
    t0 = time.perf_counter()
    for _ in range(n_steps):
        params, opt_state, loss = step(params, opt_state, (toks, targets))
    jax.block_until_ready(loss)
    dt = time.perf_counter() - t0

    tokens_per_sec = B * S * n_steps / dt
    per_chip = tokens_per_sec / n_dev

    base_path = os.path.join(
        os.path.dirname(__file__), "BENCH_BASELINE.json"
    )
    vs = 1.0
    if os.path.exists(base_path):
        try:
            base = json.load(open(base_path))["value"]
            if base > 0:
                vs = per_chip / base
        except Exception:
            pass

    out = {
        "metric": metric,
        "value": round(per_chip, 1),
        "unit": unit,
        "vs_baseline": round(vs, 4),
        "platform": platform,
        "device_kind": devs[0].device_kind,
        "n_devices": n_dev,
        "step_ms": round(dt / n_steps * 1e3, 2),
    }
    if smoke:
        out["cpu_rehearsal"] = True   # not a device number
    else:
        out["mfu"] = round(_gpt_train_flops_per_token(cfg) * per_chip
                           / peak_flops, 4)
        # HBM high-water: ground truth for train/memory_audit.py's
        # arithmetic.
        peak_b = (devs[0].memory_stats() or {}).get("peak_bytes_in_use")
        if peak_b:
            out["hbm_peak_gb"] = round(peak_b / 2**30, 3)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
