"""Compile acceptance for the chip, without the chip.

The TPU compiler is installed here and compiles for a DESCRIBED v5e:2x2
device while `JAX_PLATFORMS=cpu` stays set: what Mosaic or XLA:TPU would
refuse on the chip (an unsupported matmul form, a misaligned block, too
much fast memory, a kernel the partitioner cannot split) is refused here.
Interpret mode, which every other kernel test uses, checks semantics
only and accepted a decode kernel the chip's compiler could not parse.
Nothing runs, so these say nothing about results or speed —
`chip_smoke.py` phase 1 is where the kernels produce numbers on hardware.

Rules this file keeps (see the on-chip-measurement guide, section 2):
the topology is described inside a module-scoped, non-autouse fixture —
never at import, in a skipif, in parametrize, or in conftest — because
only one process may load the TPU library and every xdist worker imports
every test file; all chip-compile tests live in this ONE file for the
same reason; the persistent compile cache is off around these compiles
(a described-device entry cannot be read back without a chip).
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.paged_attention import (paged_attention,
                                         paged_prefill_attention)

# OPT-1.3B head shapes with a deployment-sized pool (16 slots x 2048).
B, H, K, P, PS, N_PG, C = 16, 32, 64, 512, 64, 16, 128


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def chip(topo, no_persistent_cache):
    """shape/dtype → ShapeDtypeStruct placed on one described chip."""
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one)


def _compile(fn, *args, kernels=()):
    """`kernels`: the `name=` of each pallas_call, which must be the
    custom call's instruction name in the compiled program — what a
    device trace shows and `benchmarks/harness/trace_reduce.op_key`
    matches (unnamed, the decode kernel was `%closed_call.8`)."""
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, \
        "the compiled program holds no Mosaic kernel"
    for name in kernels:
        # (autodiff wraps it: `%transpose_jvp_flash_attn_bwd_dq__.1`)
        assert re.search(rf"%\w*{name}[\w.]* = [^\n]*custom-call\(", text), \
            f"no custom call named %{name}"
    return compiled


def _pool(chip, dtype):
    return chip((P, PS, H, K), dtype)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_decode_kernel_compiles(chip, kv):
    """The paged decode kernel: the einsums it shipped with ("hk,thk->ht",
    an lhs with no non-contracting dimension) were refused by Mosaic."""
    q = chip((B, H, K), jnp.bfloat16)
    tables, lengths = chip((B, N_PG), jnp.int32), chip((B,), jnp.int32)
    if kv == "bf16":
        _compile(lambda q, k, v, t, n: paged_attention(
            q, k, v, t, n, interpret=False),
            q, _pool(chip, jnp.bfloat16), _pool(chip, jnp.bfloat16),
            tables, lengths, kernels=("paged_decode_attn",))
    else:
        scale = chip((P,), jnp.float32)   # per-page scales in scalar memory
        _compile(lambda q, k, v, t, n, ks, vs: paged_attention(
            q, k, v, t, n, interpret=False, k_scale=ks, v_scale=vs),
            q, _pool(chip, jnp.int8), _pool(chip, jnp.int8),
            tables, lengths, scale, scale, kernels=("paged_decode_attn",))


@pytest.mark.parametrize("chunk", [C, 5], ids=["prefill128", "verify5"])
def test_prefill_kernel_compiles(chip, chunk):
    """Chunked prefill (C=128) and the speculative-verify row (C=k+1=5),
    which is the same kernel."""
    _compile(lambda q, k, v, t, o, n: paged_prefill_attention(
        q, k, v, t, o, n, interpret=False),
        chip((B, chunk, H, K), jnp.bfloat16), _pool(chip, jnp.bfloat16),
        _pool(chip, jnp.bfloat16), chip((B, N_PG), jnp.int32),
        chip((B,), jnp.int32), chip((B,), jnp.int32),
        kernels=("paged_prefill_attn",))


@pytest.mark.parametrize("heads,block", [(12, 512), (12, 1024), (32, 1024)],
                         ids=["gpt2-512", "gpt2-1024", "opt1.3b-1024"])
def test_flash_fwd_bwd_compiles(chip, heads, block):
    """Training flash attention, forward and backward, B=8 S=1024 K=64."""
    x = chip((8, 1024, heads, 64), jnp.bfloat16)

    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=block,
                            block_kv=block, interpret=False)
        return jnp.sum(o.astype(jnp.float32))

    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), x, x, x,
             kernels=("flash_attn_fwd", "flash_attn_bwd_dq",
                      "flash_attn_bwd_dkv"))


def test_decode_kernel_compiles_under_tp_mesh(topo, no_persistent_cache):
    """The decode kernel per shard of a 4-device ("tp",) mesh of described
    chips — heads sharded, as the tp serving engine runs it."""
    from ray_tpu.utils.jax_compat import shard_map

    mesh = Mesh(np.asarray(topo.devices[:4]), ("tp",))
    heads = PartitionSpec(None, "tp", None)
    pool = PartitionSpec(None, None, "tp", None)
    rep = PartitionSpec()

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec))

    fn = shard_map(
        functools.partial(paged_attention, interpret=False), mesh=mesh,
        in_specs=(heads, pool, pool, rep, rep), out_specs=heads,
        check_vma=False)
    compiled = _compile(
        fn, sds((B, H, K), jnp.bfloat16, heads),
        sds((P, PS, H, K), jnp.bfloat16, pool),
        sds((P, PS, H, K), jnp.bfloat16, pool),
        sds((B, N_PG), jnp.int32, rep), sds((B,), jnp.int32, rep))
    # Each device holds a quarter of the heads: q 16x8x64 bf16 in,
    # plus its pool shards.
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < 2 * P * PS * H * K * 2 / 4 * 2.5


def test_flash_training_step_partitions_over_fsdp(topo, no_persistent_cache):
    """The SPMD partitioner cannot split a Mosaic kernel: with a batch
    sharded over fsdp=4 the model must run the flash kernel per shard
    (models/gpt._attention wraps it in a shard_map). A two-layer GPT-2
    width model keeps the compile short."""
    import optax

    from ray_tpu.models import gpt
    from ray_tpu.parallel.mesh import MeshConfig, make_mesh
    from ray_tpu.train import spmd

    cfg = dataclasses.replace(
        gpt.GPTConfig.gpt2_124m(max_seq=1024, remat=True, attn_impl="flash"),
        n_layers=2)
    mesh = make_mesh(MeshConfig(dp=1, fsdp=4, sp=1, tp=1),
                     devices=topo.devices)
    opt = optax.adafactor(3e-4)
    p_shard = spmd.param_shardings(gpt.logical_axes(cfg), mesh)
    p_shape = jax.eval_shape(functools.partial(gpt.init_params, cfg),
                             jax.random.key(0))
    o_shard = spmd.opt_state_shardings(opt, p_shape, p_shard)
    o_shape = jax.eval_shape(opt.init, p_shape)
    placed = lambda tree, sh: jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        tree, sh)
    step = spmd.make_train_step(
        lambda p, t, y: gpt.loss_fn(p, t, y, cfg, mesh), opt, mesh,
        p_shard, o_shard)
    tok = jax.ShapeDtypeStruct(
        (8, 1024), jnp.int32, sharding=NamedSharding(
            mesh, PartitionSpec(("dp", "fsdp"), "sp")))
    import importlib

    attention_mod = importlib.import_module("ray_tpu.ops.attention")

    # The model asks the backend whether to interpret; the backend here
    # is the CPU, the target is the chip — steer it in the test.
    saved = attention_mod._interpret_default
    attention_mod._interpret_default = lambda: False
    try:
        compiled = step.lower(placed(p_shape, p_shard),
                              placed(o_shape, o_shard), (tok, tok)).compile()
    finally:
        attention_mod._interpret_default = saved
    assert "tpu_custom_call" in compiled.as_text()
