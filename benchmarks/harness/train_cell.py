"""A training cell: the program's own SPMD train step
(`ray_tpu.train.spmd.build_training` over the family's model module), one
fresh seeded batch per step, for the length of the window."""

from __future__ import annotations

import math
import time

import numpy as np

from . import configs, traffic, weights

now = time.perf_counter


def _optimizer(tr: dict):
    import optax

    if tr["optimizer"] != "adafactor":
        raise SystemExit(f"unknown optimizer {tr['optimizer']!r}")
    return optax.adafactor(
        tr["learning_rate"],
        multiply_by_parameter_scale=tr["multiply_by_parameter_scale"])


def reference_loss(reference, ref_cfg, params, toks: np.ndarray,
                   tgts: np.ndarray, at_a_time: int) -> float:
    import jax
    import jax.numpy as jnp

    ref = jax.jit(reference.loss, static_argnums=(3,))
    total = 0.0
    for i in range(0, toks.shape[0], at_a_time):
        total += float(ref(params, jnp.asarray(toks[i:i + at_a_time]),
                           jnp.asarray(tgts[i:i + at_a_time]), ref_cfg)
                       ) * len(toks[i:i + at_a_time])
    return total / toks.shape[0]


def run(rc) -> dict:
    import jax
    import jax.numpy as jnp

    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.train import spmd

    config, mix, log = rc.config, rc.traffic, rc.log
    tr = config["train"]
    cfg = rc.family.program_config(
        config, max_seq=mix["seq"], remat=tr["remat"],
        attn_impl=tr["attn_impl"] if rc.platform == "tpu" else "xla",
        param_dtype=jnp.dtype(tr["param_dtype"]), loss_chunk=tr["loss_chunk"])
    mesh = make_mesh(MeshConfig(dp=1, fsdp=-1, sp=1, tp=1),
                     devices=rc.devices)
    t0 = now()
    params, opt_state, step = spmd.build_training(
        cfg, mesh, _optimizer(tr), weights.seed_key(rc.seed),
        model=rc.family.model())
    jax.block_until_ready(params)
    log(f"training state built in {now() - t0:.1f}s")
    rc.mark("state")
    batches = traffic.TrainBatches(mix, rc.seed, cfg.vocab_size)
    log(f"traffic train_job: batch {batches.batch} x seq {batches.seq}, "
        "fresh seeded tokens every step")

    def place(toks: np.ndarray):
        toks = jnp.asarray(toks)
        return toks, jnp.roll(toks, -1, axis=1)

    first = batches.next()
    t0 = now()
    ref = reference_loss(rc.reference, rc.family.reference_config(config),
                         params, first, np.roll(first, -1, axis=1),
                         tr["ref_sequences_at_a_time"])
    log(f"reference loss on the first batch {ref:.5f} in {now() - t0:.1f}s")
    rc.mark("reference")
    # Step 1 is the warm-up, and its loss (of the untouched parameters on
    # the first batch) is what the reference is compared with.
    t0 = now()
    params, opt_state, loss = step(params, opt_state, place(first))
    loss1 = float(jax.block_until_ready(loss))
    rel = abs(loss1 - ref) / abs(ref)
    log(f"step 1 (warm-up) loss {loss1:.5f} in {now() - t0:.1f}s; relative "
        f"difference from the reference {rel:.2e} (tolerance "
        f"{tr['loss_rtol']})")
    batch = place(batches.next())
    jax.block_until_ready(batch)
    # ---------------------------------------------------------- the window
    c0 = rc.compiles()
    t_window = now()
    rc.mark_setup_end(t_window)
    rc.mark("warm_up", t_window)
    t_end = t_window + rc.seconds
    losses, step_s, last = [], [], t_window
    while True:
        rc.tracer.maybe_start(now(), t_end, step_s=step_s[-1] if step_s else 1.0)
        params, opt_state, loss = step(params, opt_state, batch)
        batch = place(batches.next())     # while the step runs
        losses.append(float(jax.block_until_ready(loss)))
        t = now()
        step_s.append(t - last)
        last = t
        if t >= t_end:
            break
    window_s = last - t_window
    rc.mark("window", last)
    rc.tracer.stop()
    compiles_in_window = rc.compiles() - c0
    tokens_per_step = batches.batch * batches.seq
    tokens_per_s = tokens_per_step * len(step_s) / window_s
    log(f"{len(step_s)} steps in {window_s:.2f}s; losses first "
        f"{losses[0]:.4f} last {losses[-1]:.4f}; step ms p50 "
        f"{sorted(step_s)[len(step_s) // 2] * 1e3:.1f}")
    finite = all(math.isfinite(x) for x in losses)
    ok = finite and rel <= tr["loss_rtol"] and compiles_in_window == 0
    memory = rc.memory_stats()
    ctx = {"train": {"step_s": step_s, "tokens_per_step": tokens_per_step},
           "memory": memory, "trace_t0": rc.tracer.t_started,
           "engine": {"compiles_in_window": compiles_in_window},
           "consts": dict(configs.dims(config), chips=len(rc.devices),
                          window_s=window_s,
                          **rc.family.train_consts(config, batches.seq))}
    compared = {"step1_loss_rel": {"value": rel, "limit": tr["loss_rtol"]},
                "losses_not_finite": {
                    "value": sum(not math.isfinite(x) for x in losses),
                    "limit": 0},
                "compiles_in_window": {"value": compiles_in_window,
                                       "limit": 0}}
    return {"end_to_end": {"train_tokens_per_s": tokens_per_s},
            "attempted": len(step_s), "failed": 0 if finite else 1,
            "correct": bool(ok), "ctx": ctx, "memory": memory,
            "compared": compared,
            "compiles_in_window": compiles_in_window,
            "notes": {"ref_loss": ref, "loss1": loss1, "rel": rel}}
