"""SPMD train-step construction: sharded init + jitted update.

TPU-native replacement for the reference's DDP wrapper path
(`/root/reference/python/ray/train/torch/train_loop_utils.py` prepare_model →
DistributedDataParallel): here the *program* is partitioned — params carry
logical shardings (ZeRO-3 over `fsdp`, megatron over `tp`), the batch is
sharded over (`dp`,`fsdp`), and XLA emits the reduce-scatter/all-gather
collectives that NCCL DDP would have done by hand.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ray_tpu.ops import scopes
from ray_tpu.parallel.sharding import logical_to_spec, tree_to_shardings
from ray_tpu.parallel.mesh import DEFAULT_LOGICAL_RULES


def param_shardings(logical_tree: Any, mesh: Mesh, rules=DEFAULT_LOGICAL_RULES):
    return tree_to_shardings(logical_tree, mesh, rules)


def sharded_init(
    init_fn: Callable[[jax.Array], Any],
    logical_tree: Any,
    mesh: Mesh,
    rng: jax.Array,
    rules=DEFAULT_LOGICAL_RULES,
):
    """jit-init params directly into their shardings (never materialized
    unsharded — required for models larger than one chip's HBM)."""
    shardings = param_shardings(logical_tree, mesh, rules)
    return jax.jit(init_fn, out_shardings=shardings)(rng), shardings


def opt_state_shardings(optimizer, params, params_shardings, init_fn=None):
    """Shard optimizer state like the params it mirrors (ZeRO: the m/v moments
    inherit the param sharding; scalars replicate). `init_fn` overrides
    `optimizer.init` for callers whose state is built from a transformed
    view of the params. NOTE: the bf16-master (SR) path deliberately uses
    the PLAIN init — see the regression note in build_training; an fp32
    view adds un-donatable first-step argument bytes that OOM big tiers."""
    shapes = jax.eval_shape(init_fn or optimizer.init, params)
    flat_params, _ = jax.tree.flatten(params)
    spec_by_shape = {}
    shape_only = {}
    flat_shard, _ = jax.tree.flatten(params_shardings)
    for p, s in zip(flat_params, flat_shard):
        spec_by_shape.setdefault((p.shape, p.dtype), s)
        shape_only.setdefault(p.shape, s)
    mesh = jax.tree.leaves(params_shardings)[0].mesh

    def pick(leaf):
        # Exact (shape, dtype) match first; shape-only second — fp32
        # moments of bf16 params must still shard like the param, not
        # silently replicate.
        s = spec_by_shape.get((leaf.shape, leaf.dtype))
        if s is None:
            s = shape_only.get(leaf.shape)
        if s is not None:
            return s
        return NamedSharding(mesh, PartitionSpec())

    return jax.tree.map(pick, shapes)


def make_train_step(
    loss_fn: Callable[..., jax.Array],
    optimizer: optax.GradientTransformation,
    mesh: Mesh,
    params_shardings: Any,
    opt_shardings: Any,
    *,
    batch_spec: PartitionSpec = PartitionSpec(("dp", "fsdp"), "sp"),
    donate: bool = True,
    stochastic_round: bool = False,
):
    """Build the jitted SPMD train step.

    loss_fn(params, *batch) -> scalar. `batch` is passed to the step as one
    pytree (tuple of arrays), every leaf sharded by `batch_spec`
    ([batch, seq] by default — dp+fsdp on batch, sp on sequence).

    stochastic_round=True is the bf16-master-weights path
    (train/low_precision.py): grads are upcast to fp32 for the optimizer
    and applied with stochastic rounding; opt_state gains a uint32 step
    counter that drives the rounding PRNG, so the caller must init it as
    `(optimizer.init(params), jnp.uint32(0))` (build_training does).
    """
    batch_sharding = NamedSharding(mesh, batch_spec)
    repl = NamedSharding(mesh, PartitionSpec())

    if stochastic_round:
        from ray_tpu.train.low_precision import sr_apply_updates

        def step(params, opt_state, batch):
            inner, count = opt_state
            loss, grads = jax.value_and_grad(loss_fn)(params, *batch)
            with jax.named_scope(scopes.OPTIMIZER):
                grads = jax.tree.map(
                    lambda g: g.astype(jax.numpy.float32), grads)
                updates, inner = optimizer.update(grads, inner, params)
                params = sr_apply_updates(params, updates, count)
            return params, (inner, count + 1), loss

        opt_shardings = (opt_shardings, repl)
    else:
        def step(params, opt_state, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, *batch)
            with jax.named_scope(scopes.OPTIMIZER):
                updates, opt_state = optimizer.update(
                    grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return params, opt_state, loss

    return jax.jit(
        step,
        in_shardings=(params_shardings, opt_shardings, batch_sharding),
        out_shardings=(params_shardings, opt_shardings, repl),
        donate_argnums=(0, 1) if donate else (),
    )


def build_training(
    cfg,
    mesh: Mesh,
    optimizer: optax.GradientTransformation,
    rng: jax.Array,
    rules=DEFAULT_LOGICAL_RULES,
    model=None,
    stochastic_round: bool = False,
):
    """End-to-end: model params + opt state sharded on `mesh`, jitted step.

    `model` is a module exposing logical_axes/init_params/loss_fn (defaults
    to models.gpt; models.llama works identically — the PARAM_SPECS table
    convention makes trainers model-agnostic).
    `stochastic_round=True` enables the bf16-master-weights path (set
    cfg.param_dtype=bfloat16 with it — see train/low_precision.py).
    Returns (params, opt_state, step_fn) where
    step_fn(params, opt_state, (tokens, targets)) -> (params, opt_state, loss).
    """
    if model is None:
        from ray_tpu.models import gpt as model

    logical = model.logical_axes(cfg)
    params, p_shard = sharded_init(
        partial(model.init_params, cfg), logical, mesh, rng, rules
    )
    import jax.numpy as jnp

    o_shard = opt_state_shardings(optimizer, params, p_shard)
    opt_state = jax.jit(optimizer.init, out_shardings=o_shard)(params)
    if stochastic_round:
        # State dtypes follow the (bf16) params: optax's factored-rms
        # update casts its moments back to the param dtype each step, so
        # a bf16-init state is STABLE from step 1 (one compile, donated
        # buffers alias in-place). Do NOT init from an fp32 view — it
        # adds 4 un-donatable bytes/param of arguments to the first step
        # (measured: OOMs the 2.7B tier this path exists for) and the
        # update casts the state back down anyway.
        opt_state = (opt_state, jnp.uint32(0))

    def loss(params, tokens, targets):
        return model.loss_fn(params, tokens, targets, cfg, mesh)

    step_fn = make_train_step(loss, optimizer, mesh, p_shard, o_shard,
                              stochastic_round=stochastic_round)
    return params, opt_state, step_fn


def build_pipeline_training(
    cfg,
    mesh: Mesh,
    optimizer: optax.GradientTransformation,
    rng: jax.Array,
    *,
    n_micro: int | None = None,
):
    """Pipeline-parallel variant of build_training: the layer stack shards
    over the mesh's `pp` axis (PIPELINE_LOGICAL_RULES) and the train step
    differentiates straight through the GPipe schedule
    (parallel/pipeline.py). Composes with dp/fsdp/tp via the same logical
    rules — those axes stay under XLA's auto partitioner."""
    from ray_tpu.models import gpt
    from ray_tpu.parallel.mesh import PIPELINE_LOGICAL_RULES
    from ray_tpu.parallel.pipeline import split_microbatch_count

    pp = mesh.shape.get("pp", 1)
    if cfg.n_layers % max(pp, 1) != 0:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by pp={pp}")
    rules = PIPELINE_LOGICAL_RULES
    logical = gpt.logical_axes(cfg)
    params, p_shard = sharded_init(
        partial(gpt.init_params, cfg), logical, mesh, rng, rules
    )
    o_shard = opt_state_shardings(optimizer, params, p_shard)
    opt_state = jax.jit(optimizer.init, out_shardings=o_shard)(params)

    def loss(params, tokens, targets):
        m = n_micro or split_microbatch_count(tokens.shape[0], pp)
        return gpt.pipeline_loss_fn(params, tokens, targets, cfg, mesh, m)

    step_fn = make_train_step(
        loss, optimizer, mesh, p_shard, o_shard,
        batch_spec=PartitionSpec(("dp", "fsdp")),
    )
    return params, opt_state, step_fn
