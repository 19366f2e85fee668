"""Compile a configuration's programs at real size for a DESCRIBED
v5e:2x2, without the chip (on-chip-measurement guide, section 2.3).

    JAX_PLATFORMS=cpu python benchmarks/tools/compile_rehearsal.py --config opt-1.3b [--n-slots 32] [--train]

Prints each program's `memory_analysis()` (bytes per device) and whether
the Mosaic kernel is in it. Nothing runs: this says what the chip's
compiler accepts and what fits, never a time. Not part of a benchmark
run; kept so the next configuration can be rehearsed the same way.
"""

from __future__ import annotations

import argparse
import importlib
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax                       # noqa: E402
import jax.numpy as jnp          # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding  # noqa: E402

from harness import configs      # noqa: E402
from harness.reference import gpt_ref  # noqa: E402

GB = 1e9


def report(name, compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{name}: args {m.argument_size_in_bytes / GB:.2f} GB, out "
          f"{m.output_size_in_bytes / GB:.2f}, temp "
          f"{m.temp_size_in_bytes / GB:.2f}, aliased "
          f"{m.alias_size_in_bytes / GB:.2f} -> {total / GB:.2f} GB per "
          f"device; mosaic={'tpu_custom_call' in compiled.as_text()}",
          flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--n-slots", type=int)
    ap.add_argument("--n-pages", type=int)
    ap.add_argument("--train", action="store_true")
    ap.add_argument("--skip-ref", action="store_true")
    ns = ap.parse_args()
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies

    from ray_tpu.models import gpt, paged_kv, partition

    # Code that asks the backend still sees the CPU here: steer the
    # kernels to their compiled (not interpreted) form.
    for mod in ("ray_tpu.ops.attention", "ray_tpu.ops.paged_attention"):
        importlib.import_module(mod)._interpret_default = lambda: False
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    root = os.path.dirname(os.path.dirname(HERE))
    bench = configs.load_benchmark(root)
    config = configs.load_config(root, bench, ns.config)
    if ns.train:
        return rehearse_train(config, topo)
    geo = config["serve"]
    tp = geo["tp"]
    n_slots = ns.n_slots or geo["n_slots"]
    n_pages = ns.n_pages or geo["n_pages"]
    cfg = configs.gpt_config(config, max_seq=geo["max_len"])
    bf = jnp.bfloat16
    specs = gpt.param_specs(cfg)
    p_shapes = {k: jax.ShapeDtypeStruct(v["shape"], bf) for k, v in specs.items()}
    pool_shape = (cfg.n_layers, n_pages + 1, geo["page_size"], cfg.n_heads,
                  cfg.head_dim)
    pool_shapes = {"k": jax.ShapeDtypeStruct(pool_shape, bf),
                   "v": jax.ShapeDtypeStruct(pool_shape, bf)}
    if tp > 1:
        mesh = Mesh(topo.devices[:tp], (partition.TP_AXIS,))
        place = lambda tree, rules: jax.tree.map(
            lambda s, spec: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
            tree, partition.match_partition_rules(rules, tree))
        params = place(p_shapes, gpt.partition_rules())
        pool = place(pool_shapes, paged_kv.KV_POOL_PARTITION_RULES)
        rep = NamedSharding(mesh, PartitionSpec())
    else:
        mesh = None
        rep = SingleDeviceSharding(topo.devices[0])
        on = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep)
        params, pool = jax.tree.map(on, p_shapes), jax.tree.map(on, pool_shapes)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=rep)
    width = -(-geo["max_len"] // geo["page_size"])
    i32 = lambda *s: sds(s, jnp.int32)
    key = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=rep)
    kw = {"attn_impl": "kernel"}
    print(f"{ns.config}: n_slots {n_slots}, pages {n_pages} x "
          f"{geo['page_size']}, tp {tp}, table width {width}", flush=True)
    if tp > 1:
        kw["mesh"] = mesh
        decode, step, chunk = (paged_kv._decode_sample_paged_tp,
                               paged_kv.decode_step_paged_tp,
                               paged_kv.prefill_chunk_paged_tp)
    else:
        decode, step, chunk = (paged_kv._decode_sample_paged,
                               paged_kv.decode_step_paged,
                               paged_kv.prefill_chunk_paged)
    report("decode step + sample", decode.lower(
        cfg, params, i32(n_slots), pool, i32(n_slots), i32(n_slots, width),
        sds((n_slots,), jnp.float32), key, **kw).compile())
    report("decode step (logits)", step.lower(
        cfg, params, i32(n_slots), pool, i32(n_slots), i32(n_slots, width),
        **kw).compile())
    report("prefill chunk (head)", chunk.lower(
        cfg, params, i32(n_slots, geo["prefill_chunk"]), pool,
        i32(n_slots, width), i32(n_slots), i32(n_slots),
        return_logits=True, **kw).compile())
    if not ns.skip_ref:
        ref = jax.jit(gpt_ref.paired_rows, static_argnums=(2,))
        report("reference paired_rows (float32 + bf16)", ref.lower(
            params, i32(geo["max_len"]), config["rotary_dim"]).compile())


def rehearse_train(config, topo) -> None:
    from ray_tpu.models import gpt

    tr = config["train"]
    one = SingleDeviceSharding(topo.devices[0])
    cfg = configs.gpt_config(config, max_seq=1024, remat=tr["remat"],
                             attn_impl=tr["attn_impl"],
                             param_dtype=jnp.dtype(tr["param_dtype"]))
    on = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)
    params = jax.tree.map(on, jax.eval_shape(
        lambda: gpt.init_params(cfg, jax.random.key(0))))
    toks = jax.ShapeDtypeStruct((tr["ref_sequences_at_a_time"], 1024),
                                jnp.int32, sharding=one)
    ref = jax.jit(gpt_ref.loss, static_argnums=(3,))
    report("reference loss", ref.lower(params, toks, toks,
                                       config["rotary_dim"]).compile())


if __name__ == "__main__":
    main()
