"""Weights made on the device, in one jitted call, from --seed.

No checkpoint and nothing on the host: one program fills every leaf of
`gpt.param_specs(cfg)` in the type it is served in. At tp > 1 the leaves
are born in the tp shardings of `gpt.partition_rules()`
(`out_shardings`), so no chip ever holds the whole model and the
engine's own load-time `shard_by_rules` finds them already in place.
"""

from __future__ import annotations


def seed_key(seed: int):
    """A PRNG key for any non-negative whole-number seed (the driver's
    seeds pass 2**31, which a 32-bit key seed cannot hold)."""
    import jax

    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def make_params(cfg, seed: int, dtype, tp: int = 1):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from ray_tpu.models import gpt
    from ray_tpu.models import partition

    specs = gpt.param_specs(cfg)
    names = sorted(specs)

    def init(key):
        out = {}
        for k, name in zip(jax.random.split(key, len(names)), names):
            spec = specs[name]
            if spec["init"] == "normal":
                out[name] = (jax.random.normal(k, spec["shape"], dtype)
                             * jnp.asarray(spec["scale"], dtype))
            elif spec["init"] == "ones":
                out[name] = jnp.ones(spec["shape"], dtype)
            else:
                out[name] = jnp.zeros(spec["shape"], dtype)
        return out

    out_shardings = None
    if tp > 1:
        mesh = partition.make_tp_mesh(tp)
        shapes = jax.eval_shape(init, seed_key(0))
        pspecs = partition.match_partition_rules(gpt.partition_rules(), shapes)
        out_shardings = {n: NamedSharding(mesh, pspecs[n]) for n in names}
    params = jax.jit(init, out_shardings=out_shardings)(seed_key(seed))
    return jax.block_until_ready(params)
