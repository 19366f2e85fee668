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

import contextlib
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

# OPT-1.3B head shapes with a deployment-sized pool (16 slots x 2048);
# the kernels see the WHOLE pool [L, P, page_size, H*K] and a layer index.
B, H, K, P, PS, N_PG, C = 16, 32, 64, 512, 64, 16, 128
L = 24


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


@contextlib.contextmanager
def _chips_answers(*modules):
    """The backend questions of `ray_tpu.ops.<module>` steered to the
    chip's answers: kernels compiled, not interpreted, and (`moe`) bf16
    groups into float32 through the repo's grouped matmul."""
    import importlib

    asked = []
    for name in modules:
        mod = importlib.import_module("ray_tpu.ops." + name)
        attr, answer = (("_mixed_dot_default", True) if name == "moe"
                        else ("_interpret_default", False))
        asked.append((mod, attr, getattr(mod, attr)))
        setattr(mod, attr, lambda answer=answer: answer)
    try:
        yield
    finally:
        for mod, attr, fn in asked:
            setattr(mod, attr, fn)


def _pool(chip, dtype, heads=H, head_dim=K):
    return chip((L, P, PS, heads * head_dim), dtype)


def _layer(chip):
    return chip((), jnp.int32)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_decode_kernel_compiles(chip, kv):
    """The paged decode kernel: the einsums it shipped with ("hk,thk->ht",
    an lhs with no non-contracting dimension) were refused by Mosaic."""
    q = chip((B, H, K), jnp.bfloat16)
    tables, lengths = chip((B, N_PG), jnp.int32), chip((B,), jnp.int32)
    if kv == "bf16":
        _compile(lambda q, k, v, l, t, n: paged_attention(
            q, k, v, l, t, n, interpret=False),
            q, _pool(chip, jnp.bfloat16), _pool(chip, jnp.bfloat16),
            _layer(chip), tables, lengths, kernels=("paged_decode_attn",))
    else:
        # Per-page scale planes; the layer's row goes to scalar memory.
        scale = chip((L, P), jnp.float32)
        _compile(lambda q, k, v, l, t, n, ks, vs: paged_attention(
            q, k, v, l, t, n, interpret=False, k_scale=ks, v_scale=vs),
            q, _pool(chip, jnp.int8), _pool(chip, jnp.int8), _layer(chip),
            tables, lengths, scale, scale, kernels=("paged_decode_attn",))


def test_decode_kernel_compiles_at_head_dim_256(chip):
    """GPT-J's heads (16 of 256; 4 a shard at tp=4) through the same
    kernel: one layout serves head_dim 64 and 256."""
    heads, head_dim = 4, 256
    _compile(lambda q, k, v, l, t, n: paged_attention(
        q, k, v, l, t, n, interpret=False),
        chip((B, heads, head_dim), jnp.bfloat16),
        _pool(chip, jnp.bfloat16, heads, head_dim),
        _pool(chip, jnp.bfloat16, heads, head_dim), _layer(chip),
        chip((B, N_PG), jnp.int32), chip((B,), jnp.int32),
        kernels=("paged_decode_attn",))


@pytest.mark.parametrize("chunk,rows,width", [
    *[(C, 2, width) for width in (2, 4, 8, 16, 32)], (C, B, N_PG),
    (5, B, N_PG)],
    ids=["w2", "w4", "w8", "w16", "w32", "prefill128", "verify5"])
def test_prefill_kernel_compiles(chip, chunk, rows, width):
    """Chunked prefill (C=128) at every table width of `opt-1.3b.batch`'s
    ladder, two rows a program as the cell dispatches it (the kv block is
    min(4, width) pages: 1 MB each of K and V at width >= 4,
    double-buffered), at 16 rows, and the speculative-verify row
    (C=k+1=5), which is the same kernel."""
    _compile(lambda q, k, v, l, t, o, n: paged_prefill_attention(
        q, k, v, l, t, o, n, interpret=False),
        chip((rows, chunk, H, K), jnp.bfloat16), _pool(chip, jnp.bfloat16),
        _pool(chip, jnp.bfloat16), _layer(chip),
        chip((rows, width), jnp.int32), chip((rows,), jnp.int32),
        chip((rows,), jnp.int32), kernels=("paged_prefill_attn",))


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
    pool = PartitionSpec(None, None, None, "tp")   # H*K: whole heads a shard
    rep = PartitionSpec()

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(mesh, spec))

    fn = shard_map(
        functools.partial(paged_attention, interpret=False), mesh=mesh,
        in_specs=(heads, pool, pool, rep, rep, rep), out_specs=heads,
        check_vma=False)
    n_layers = 2
    compiled = _compile(
        fn, sds((B, H, K), jnp.bfloat16, heads),
        sds((n_layers, P, PS, H * K), jnp.bfloat16, pool),
        sds((n_layers, P, PS, H * K), jnp.bfloat16, pool),
        sds((), jnp.int32, rep),
        sds((B, N_PG), jnp.int32, rep), sds((B,), jnp.int32, rep))
    # Each device holds a quarter of the heads: q 16x8x64 bf16 in,
    # plus its pool shards, with no padding (512 lanes a row).
    per_device = compiled.memory_analysis().argument_size_in_bytes
    assert per_device < 2 * n_layers * P * PS * H * K * 2 / 4 * 1.05


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


# --- the WHOLE step programs of the benchmark's serving cell -------------
# OPT-1.3B as `benchmarks/configs/opt-1.3b.json` serves it: 32 slots,
# 512 pages of 64, full table width 32, prefill chunk 128; the chunk
# program is `LLMEngine.chunk_rows` tall: the 2 full chunks the default
# budget of 256 tokens holds.
CELL_SLOTS, CELL_PAGES, CELL_WIDTH, CELL_CHUNK = 32, 512, 32, 128
CELL_CHUNK_ROWS = 2
_MOVES = re.compile(r"\b(copy|dynamic-slice|dynamic-update-slice)\b")
_RESULT = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = \(?(\w+)\[([\d,]*)\]")
_OPCODE = re.compile(r"(?:^|\s)([a-z][\w\-.]*)\(")


def _grouped_matmuls(text, a_layer=3):
    """The grouped matmuls a compiled program's text holds: calls of the
    repo's kernel, and none of the compiler's `ragged-dot` beside them.
    The kernel's walk (the grid's length and the four arrays of scalars
    it reads before `lhs`) is computed ONCE an expert layer: its
    `a_layer` calls (gate, up and down; up and down of an ungated expert)
    are handed the same five operands."""
    assert "ragged-dot" not in text
    calls = re.findall(
        r"%\w*moe_grouped_matmul[\w.]* = [^\n]*?custom-call\(([^)]*)\)", text)
    walks = {tuple(operands.split(", ")[:5]) for operands in calls}
    assert len(walks) * a_layer == len(calls), walks
    return len(calls)


def _pool_moves(text, dtype, min_elems):
    """Lines of compiled HLO `text` that are a copy, dynamic-slice or
    dynamic-update-slice — the instruction itself or a fusion named
    after one — with a result of the pool's `dtype` holding `min_elems`
    (one layer of a plane) or more. Pool-typed results only: the prefill
    head's fp32 logits [32,128,V/2] are copied too, and are nobody's
    pool."""
    moved = []
    for line in text.splitlines():
        hit = _RESULT.match(line)
        if not hit or hit.group(2) != dtype or not hit.group(3):
            continue
        opcode = _OPCODE.search(line.split(" = ", 1)[1])
        key = hit.group(1) + " " + (opcode.group(1) if opcode else "")
        if (_MOVES.search(key.replace("_", " ")) and np.prod(
                [int(d) for d in hit.group(3).split(",")]) >= min_elems):
            moved.append(line.strip()[:160])
    return moved


def test_pool_move_rule_finds_the_old_programs_ops():
    """The rule below, held to the four kinds of op that were 90 % of the
    5-D pool's decode step (the ledger's PR 24 `breakdown`)."""
    layer = 513 * 64 * 32 * 64
    old = """
  %copy.75 = bf16[1,513,64,32,64]{4,3,2,1,0:T(8,128)(2,1)} copy(%fusion.4)
  %copy.81 = bf16[1,513,64,32,64]{1,4,3,2,0:T(8,128)(2,1)} copy(%custom-call.2)
  %constant_dynamic-slice_fusion.4 = bf16[1,513,64,32,64]{1,4,3,2,0:T(8,128)(2,1)} fusion(%p.1, %p.2), kind=kLoop
  %constant_dynamic-update-slice_fusion.5 = bf16[24,513,64,32,64]{1,4,3,2,0:T(8,128)(2,1)} fusion(%p.1), kind=kLoop
  ROOT %dynamic-update-slice.9 = bf16[24,513,64,32,64]{1,4,3,2,0:T(8,128)(2,1)} dynamic-update-slice(%a, %b, %c)
  %copy.3 = bf16[32,32,64]{2,1,0:T(8,128)(2,1)} copy(%q)
  %copy.34 = f32[32,128,25216]{2,1,0:T(8,128)} copy(%mini-gather-slice.2)
  %fusion.198 = bf16[787968,2048]{1,0:T(8,128)(2,1)} fusion(%bitcast.335, %rows), kind=kCustom
"""
    assert [m.split(" = ")[0] for m in _pool_moves(old, "bf16", layer)] == [
        "%copy.75", "%copy.81", "%constant_dynamic-slice_fusion.4",
        "%constant_dynamic-update-slice_fusion.5",
        "ROOT %dynamic-update-slice.9"]


def _stacks(chip, model, cfg):
    """The model's parameter tree as its `param_specs` shape it, bf16."""
    return {name: chip(spec["shape"], jnp.bfloat16)
            for name, spec in model.param_specs(cfg).items()}


def _served(chip, cfg, stacks):
    """The tree the engine hands the family's programs: the seam's
    `lay_out` (models/serving.py) over the stacks, as shapes."""
    from ray_tpu.models.serving import family_of

    lay_out = family_of(cfg).lay_out
    if lay_out is None:
        return stacks
    return jax.tree.map(
        lambda x: chip(x.shape, x.dtype),
        jax.eval_shape(functools.partial(lay_out, cfg), stacks))


@pytest.fixture(scope="module")
def opt_serving(chip):
    """(cfg, params, pool) as shapes on one described chip."""
    from ray_tpu.models import gpt, paged_kv

    cfg = gpt.GPTConfig.opt_1_3b(vocab_size=50272, max_seq=2048)
    params = _stacks(chip, gpt, cfg)
    pool = jax.tree.map(
        lambda x: chip(x.shape, x.dtype),
        jax.eval_shape(lambda: paged_kv.init_paged_kv(cfg, CELL_PAGES, PS)))
    # The programs ask the backend whether to interpret; the backend here
    # is the CPU, the target is the chip — steer it in the test.
    with _chips_answers("paged_attention"):
        yield cfg, params, pool


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_paged_program_moves_no_pool_layer(chip, opt_serving, step_program,
                                           program):
    """`_decode_sample_paged` and `prefill_chunk_paged`, compiled whole at
    the cell's size: both kernels are in them under their names, the pool
    is lane-dense and row-major as the chip lays it out, and nothing in
    them cuts a layer out of the pool, re-lays it out or puts it back —
    the eight ops that were 90 % of a decode step (PERF.md, PR 25)."""
    from ray_tpu.models import paged_kv

    cfg, params, pool = opt_serving
    i32 = lambda *shape: chip(shape, jnp.int32)
    if program == "decode":
        compiled = step_program("gpt", "decode")
        kernel = "paged_decode_attn"
    else:
        rows = CELL_CHUNK_ROWS
        compiled = paged_kv.prefill_chunk_paged.lower(
            cfg, params, i32(rows, CELL_CHUNK), pool,
            i32(rows, CELL_WIDTH), i32(rows), i32(rows),
            return_logits=True, attn_impl="kernel").compile()
        kernel = "paged_prefill_attn"
    text = compiled.as_text()
    if program == "prefill":
        # The chunk program is as tall as the budget fills it: nothing in
        # it carries n_slots x chunk = 4,096 token rows, flat or split.
        tall = re.findall(rf"\w+\[(?:{CELL_SLOTS * CELL_CHUNK}|"
                          rf"{CELL_SLOTS},{CELL_CHUNK}),[\d,]*\]", text)
        assert not tall, f"n_slots-tall operands: {sorted(set(tall))[:8]}"
    # (a) the kernel, under the name the trace and the benchmark find it by.
    assert re.search(rf"%\w*{kernel}[\w.]* = [^\n]*custom-call\(", text), \
        f"no custom call named %{kernel}"
    # The pool's own layout: minor axis H*K = 2,048 lanes, row-major, so
    # the kernel's page block is what the chip stores.
    lanes = cfg.n_heads * cfg.head_dim
    plane = f"bf16[{cfg.n_layers},{CELL_PAGES + 1},{PS},{lanes}]"
    assert lanes % 128 == 0
    assert plane + "{3,2,1,0:" in text, "the pool is not row-major"
    assert plane.replace("bf16[", "bf16[1,") not in text   # no layer slice
    # (b) no copy, dynamic-slice or dynamic-update-slice (an instruction
    # or a fusion named after one) whose result holds a layer of the
    # pool or more.
    layer_elems = (CELL_PAGES + 1) * PS * lanes
    moved = _pool_moves(text, "bf16", layer_elems)
    assert not moved, "pool-sized moves:\n" + "\n".join(moved)
    # (c) no second pool: what the program needs beyond its arguments is
    # under one K or V plane (in fact: the head's logits in prefill, half
    # a megabyte in decode), and the donated pool is updated in place.
    mem = compiled.memory_analysis()
    plane_bytes = cfg.n_layers * layer_elems * 2
    assert mem.temp_size_in_bytes < plane_bytes
    assert mem.alias_size_in_bytes >= 2 * plane_bytes


# --- grouped-query pages and the zaya family's step programs ------------
# ZAYA1-8B as `benchmarks/configs/zaya1-8b.json` serves it: 8 query heads
# over 2 KV heads of 128 (the pool's minor axis is 2 x 128 = 256 lanes),
# 64 slots, 2,048 pages of 64, table width 32, chunk 128, 24 layers, all
# 16 experts and the whole 262,272-row tied vocabulary. An engine that
# dispatches at one table width holds two chunk programs, 4 and 8 rows
# tall at the default budget, chunk and window, both with the head
# (`LLMEngine.chunk_programs`).
Z_SLOTS, Z_PAGES, Z_H, Z_G, Z_K = 64, 2048, 8, 2, 128
ONE_WIDTH_HEIGHTS = (4, 8)
ONE_WIDTH_PROGRAMS = ["decode"] + [f"prefill-{h}" for h in ONE_WIDTH_HEIGHTS]


@pytest.fixture(scope="module")
def step_program(request, chip):
    """(family, program) → the family's step program compiled whole at
    its cell's size, once a module: "decode" is `_decode_sample_paged`
    over every slot, "prefill-<n>" a one-width family's chunk program n
    rows tall with the head. `stacks=True`: handed the stacks as
    `param_specs` shapes them, not the tree the engine serves."""
    import importlib

    from ray_tpu.models.serving import family_of

    # family → (its serving fixture, its programs' module, slots, width)
    cells = {"gpt": ("opt_serving", "paged_kv", CELL_SLOTS, CELL_WIDTH),
             "zaya": ("zaya_serving", "zaya", Z_SLOTS, 32),
             "laguna": ("laguna_serving", "laguna", G_SLOTS, 64),
             "qwen3_next": ("qwen3_next_serving", "qwen3_next", Q_SLOTS, 64),
             "mimo_v2": ("mimo_v2_serving", "mimo_v2", M_SLOTS, 96),
             "jamba": ("jamba_serving", "jamba", J_SLOTS, J_WIDTH),
             "kimi_k2": ("kimi_k2_serving", "kimi_k2", K2_SLOTS, K2_WIDTH),
             "olmo_hybrid": ("olmo_hybrid_serving", "olmo_hybrid", O_SLOTS,
                             O_WIDTH),
             "nemotron_h": ("nemotron_h_serving", "nemotron_h", N_SLOTS,
                            N_WIDTH)}
    i32 = lambda *shape: chip(shape, jnp.int32)

    @functools.cache
    def compiled(family, program, stacks=False):
        fixture, module, slots, width = cells[family]
        programs = importlib.import_module("ray_tpu.models." + module)
        cfg, params, pool = request.getfixturevalue(fixture)
        if stacks:
            params = _stacks(chip, family_of(cfg).model, cfg)
        if program == "decode":
            key = jax.eval_shape(lambda: jax.random.key(0))
            return programs._decode_sample_paged.lower(
                cfg, params, i32(slots), pool, i32(slots), i32(slots, width),
                chip((slots,), jnp.float32), chip(key.shape, key.dtype),
                attn_impl="kernel").compile()
        n = int(program.split("-")[1])
        fam = family_of(cfg)        # nothing by the slot: no `slots=`
        rows = ({"slots": i32(n)} if fam.slot_state or fam.slot_ring
                else {})
        return programs.prefill_chunk_paged.lower(
            cfg, params, i32(n, C), pool, i32(n, width), i32(n), i32(n),
            return_logits=True, attn_impl="kernel", **rows).compile()

    return compiled


def _attn_kernel(program):
    return "paged_decode_attn" if program == "decode" else "paged_prefill_attn"


def test_grouped_query_kernels_compile_at_head_size_128(chip):
    """Both paged kernels with G = 2 KV heads under H = 8 query heads of
    128: the decode kernel takes the query a head a row, the prefill
    kernel reads head h's page lanes at KV head h // 4, at the one table
    width `zaya1-8b.reason` dispatches (32: its family turns the width
    buckets off)."""
    pool = chip((L, Z_PAGES + 1, PS, Z_G * Z_K), jnp.bfloat16)
    _compile(lambda q, k, v, l, t, n: paged_attention(
        q, k, v, l, t, n, interpret=False),
        chip((Z_SLOTS, Z_H, Z_K), jnp.bfloat16), pool, pool, _layer(chip),
        chip((Z_SLOTS, 32), jnp.int32), chip((Z_SLOTS,), jnp.int32),
        kernels=("paged_decode_attn",))
    for rows in ONE_WIDTH_HEIGHTS:
        _compile(lambda q, k, v, l, t, o, n: paged_prefill_attention(
            q, k, v, l, t, o, n, interpret=False),
            chip((rows, C, Z_H, Z_K), jnp.bfloat16), pool, pool,
            _layer(chip), chip((rows, 32), jnp.int32),
            chip((rows,), jnp.int32), chip((rows,), jnp.int32),
            kernels=("paged_prefill_attn",))


@pytest.fixture(scope="module")
def zaya_serving(chip):
    """(cfg, params, pool) of the zaya cell as shapes on one described
    chip, with the backend questions steered to the chip's answers."""
    from ray_tpu.models import zaya

    cfg = zaya.ZayaConfig(n_layers=L)
    params = _stacks(chip, zaya, cfg)
    pool = jax.tree.map(
        lambda x: chip(x.shape, x.dtype),
        jax.eval_shape(lambda: zaya.init_paged_kv(cfg, Z_PAGES, PS,
                                                  Z_SLOTS)))
    with _chips_answers("paged_attention", "moe", "grouped_matmul"):
        yield cfg, params, pool


@pytest.mark.parametrize("program", ONE_WIDTH_PROGRAMS)
def test_zaya_program_fits_and_moves_no_expert_layer(zaya_serving,
                                                     step_program, program):
    """The zaya family's step programs (decode, and the chunk program at
    both of the engine's heights), compiled whole at the cell's
    size: the attention kernel and the experts' grouped matmul are in
    them under the names a trace finds them by; no layer of experts
    (16 x 2,048 x 2,048 bf16, 134 MB a matrix) and no layer of the pool is
    copied, sliced out or put back; the donated pool (pages AND slot
    state) is updated in place; weights + pool + what the program needs
    besides stay under the chip's 16 GB."""
    cfg, _params, pool = zaya_serving
    compiled = step_program("zaya", program)
    kernel = _attn_kernel(program)
    text = compiled.as_text()
    assert re.search(rf"%\w*{kernel}[\w.]* = [^\n]*custom-call\(", text)
    # gate, up and down: three grouped matmuls over the WHOLE stack of
    # 24 x 16 experts, the layer picked by its groups' sizes.
    assert _grouped_matmuls(text) == 3
    assert f"bf16[{L * cfg.n_experts},{cfg.d_model},{cfg.d_ff}]" in text
    expert_layer = cfg.n_experts * cfg.d_model * cfg.d_ff
    pool_layer = (Z_PAGES + 1) * PS * Z_G * Z_K
    moved = _pool_moves(text, "bf16", min(expert_layer, pool_layer))
    assert not moved, "layer-sized moves:\n" + "\n".join(moved)
    mem = compiled.memory_analysis()
    pool_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                     for a in pool.values())
    assert mem.alias_size_in_bytes >= pool_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 14.0e9 < total < 15.0e9


# Laguna-S-2.1's serving shapes: 8 KV heads of 128 under 48 query heads
# (full layers, the engine's page tables) and 72 (window layers, a ring
# of 25 pages a slot: the window's 8, the 16 that eight chunk rows of one
# prompt write in one dispatch, and one); the cell's cut: 5 layers, 128 of 256 experts held,
# half the vocabulary, 64 slots x 64 pages.
G_SLOTS, G_PAGES, G_G, G_K, G_RING, G_WINDOW = 64, 4096, 8, 128, 25, 512


@pytest.mark.parametrize("heads,kind", [(48, "full"), (72, "window")])
def test_many_head_grouped_kernels_compile(chip, heads, kind):
    """Both paged kernels at H = 48 and 72 over G = 8 at head size 128.
    The query block, accumulator and state of that many heads pass the
    prefill kernel's VMEM by themselves, so its grid splits by KV head
    (`prefill_kv_split`); the window kind runs over a ring table with
    `col_page` under names of its own."""
    from ray_tpu.ops.paged_attention import prefill_kv_split

    assert prefill_kv_split(G_G * G_K, C, heads * G_K, 2, heads) == G_G
    ring = kind == "window"
    width = G_RING if ring else 64
    rows = (G_SLOTS + 1) * G_RING if ring else G_PAGES + 1
    pool = chip((3, rows, PS, G_G * G_K), jnp.bfloat16)
    i32 = lambda *shape: chip(shape, jnp.int32)
    kw = lambda col: ({"window": G_WINDOW, "col_page": col} if ring else {})
    suffix = "_window" if ring else ""
    _compile(lambda q, k, v, l, t, n, col: paged_attention(
        q, k, v, l, t, n, interpret=False, **kw(col)),
        chip((G_SLOTS, heads, G_K), jnp.bfloat16), pool, pool, _layer(chip),
        i32(G_SLOTS, width), i32(G_SLOTS), i32(G_SLOTS, width),
        kernels=("paged_decode_attn" + suffix,))
    for n in ONE_WIDTH_HEIGHTS:
        _compile(lambda q, k, v, l, t, o, n, col: paged_prefill_attention(
            q, k, v, l, t, o, n, interpret=False, **kw(col)),
            chip((n, C, heads, G_K), jnp.bfloat16), pool, pool, _layer(chip),
            i32(n, width), i32(n), i32(n), i32(n, width),
            kernels=("paged_prefill_attn" + suffix,))


# The decode call as each cell's decode program makes it (slots, H, G, K,
# table width, pool dtype, ring): `opt-1.3b.batch` and its int8 pool,
# `zaya1-8b.reason`, `laguna-s-2.1.codegen`'s full and window kinds.
_DECODE_CELLS = {
    "opt-1.3b": (32, 32, 32, 64, 32, jnp.bfloat16, False),
    "opt-1.3b-int8": (32, 32, 32, 64, 32, jnp.int8, False),
    "zaya1-8b": (64, 8, 2, 128, 32, jnp.bfloat16, False),
    "laguna-full": (64, 48, 8, 128, 64, jnp.bfloat16, False),
    "laguna-window": (64, 72, 8, 128, G_RING, jnp.bfloat16, True),
}


@pytest.mark.parametrize("cell", sorted(_DECODE_CELLS))
def test_decode_kernel_at_a_cells_shapes_fits_what_its_rule_reckons(chip,
                                                                    cell):
    """The decode kernel keeps the pools in HBM and fetches live pages by
    its own DMAs into buffers of a block (PR 41): at every cell's shapes
    it compiles for the chip with one grid step for the whole batch, its
    pools are operands in no block of VMEM, and its VMEM scratch (the K
    and V buffers, the block-diagonal query, the softmax state) plus the
    score tiles is what `_decode_vmem_bytes` reckons for the rule's
    block, to the byte for a bf16 pool; with the batch's queries and
    outputs it stays inside the 16 MiB a kernel gets. The window kind
    (PR 61) fetches a slot's window as rows of its ring, a block by
    `window_block_rows`."""
    import importlib

    attn = importlib.import_module("ray_tpu.ops.paged_attention")
    slots, heads, kv_heads, head_dim, width, dtype, ring = _DECODE_CELLS[cell]
    lanes, item = kv_heads * head_dim, jnp.dtype(dtype).itemsize
    pool = chip((3, 1025, PS, lanes), dtype)
    i32 = lambda *shape: chip(shape, jnp.int32)
    scale = chip((3, 1025), jnp.float32)

    def call(q, k, v, l, t, n, col, ks, vs):
        kw = {"window": 512, "col_page": col} if ring else {}
        if item == 1:
            kw.update(k_scale=ks, v_scale=vs)
        return paged_attention(q, k, v, l, t, n, interpret=False, **kw)

    args = (chip((slots, heads, head_dim), jnp.bfloat16), pool, pool,
            _layer(chip), i32(slots, width), i32(slots), i32(slots, width),
            scale, scale)
    _compile(call, *args,
             kernels=("paged_decode_attn" + ("_window" if ring else ""),))
    (eqn,) = [e for e in jax.make_jaxpr(call)(*args).eqns
              if e.primitive.name == "pallas_call"]
    spec = eqn.params["grid_mapping"]
    assert spec.grid == (1,)
    assert [str(m.block_aval.memory_space).lower().endswith("any")
            for m in spec.block_mappings] == [False, True, True, False]
    scratch = [v.aval for v in eqn.params["jaxpr"].invars[
        -spec.num_scratch_operands:]]
    vmem = sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
               for a in scratch if "sem" not in str(a.dtype).lower())
    if ring:    # ONE block a slot: the window's 512 rows and a tile of 16
        keys, blocks = attn.window_block_rows(512, lanes, item, heads)
        assert (keys, blocks) == (528, 1)
        n, page = 1, keys
    else:
        n, page = attn.decode_block_pages(width, PS, lanes, item, heads), PS
        assert n == {"opt-1.3b": 2, "opt-1.3b-int8": 4, "zaya1-8b": 16,
                     "laguna-full": 4}[cell]
    buffers = attn._DECODE_BUFFERS * 2 * n * page * lanes * item
    assert vmem == (buffers + heads * lanes * (2 + 4)
                    + 2 * heads * 128 * 4)
    tiles = 2 * heads * n * page * 4
    dequant = 2 * n * page * lanes * 4 if item == 1 else 0
    reckoned = attn._decode_vmem_bytes(n, page, lanes, item, heads)
    assert vmem + tiles + dequant <= reckoned <= attn._DECODE_VMEM_BUDGET
    # bf16: all the rule overcounts is the block-diagonal query at 4 bytes
    assert item == 1 or reckoned - (vmem + tiles) == heads * lanes * 6
    queries = 2 * 2 * slots * heads * head_dim * 2      # q, out; two each
    assert reckoned + queries <= attn._DECODE_GROUP_BUDGET < 16 * 2**20


@pytest.fixture(scope="module")
def laguna_serving(chip):
    """(cfg, params, pool) of the laguna cell as shapes on one described
    chip, with the backend questions steered to the chip's answers."""
    from ray_tpu.models import laguna

    cfg = laguna.LagunaConfig(n_layers=5, n_experts=128, vocab_size=50176)
    params = _stacks(chip, laguna, cfg)
    pool = jax.tree.map(
        lambda x: chip(x.shape, x.dtype),
        jax.eval_shape(lambda: laguna.init_paged_kv(
            cfg, G_PAGES, PS, G_SLOTS,
            dispatch_tokens=ONE_WIDTH_HEIGHTS[-1] * C)))
    with _chips_answers("paged_attention", "moe", "grouped_matmul"):
        yield cfg, params, pool


@pytest.mark.parametrize("program", ONE_WIDTH_PROGRAMS)
def test_laguna_program_fits_and_moves_no_expert_layer(laguna_serving,
                                                       step_program, program):
    """The laguna family's step programs (decode, and the chunk program
    at both of the engine's heights), compiled whole at the
    cell's size: all four attention calls and the experts' grouped
    matmul are in them under the names a trace finds them by; no layer
    of experts (128 x 3,072 x 1,024 bf16, 805 MB a matrix) and no layer
    of either cache kind (the smaller: 65 rings of 25 pages, 213 MB) is
    copied, sliced out or put back (XLA prefetches W_o and the dense
    MLP's matrices into fast memory, `S(1)` copies of 38-75 MB: reads,
    under the rule's size); the donated pool (pages, rings, counters)
    is updated in place; weights + pool + what the program needs
    besides stay under the chip's 16 GB."""
    cfg, _params, pool = laguna_serving
    assert pool["ring_rows"].shape == (G_SLOTS + 1, G_RING)
    compiled = step_program("laguna", program)
    kernel = _attn_kernel(program)
    text = compiled.as_text()
    calls = lambda name: len(re.findall(
        rf"%\w*{name}[\w.]* = [^\n]*custom-call\(", text))
    # Two full layers and three window layers (the plain name's pattern
    # finds the window calls too).
    assert calls(kernel + "_window") == 3 and calls(kernel) == 5
    # three grouped matmuls an expert layer (four of the five layers)
    assert _grouped_matmuls(text) == 3 * 4
    n_sparse = cfg.count("sparse")
    assert (f"bf16[{n_sparse * cfg.n_experts},{cfg.d_model},{cfg.d_ff}]"
            in text)
    expert_layer = cfg.n_experts * cfg.d_model * cfg.d_ff
    ring_layer = (G_SLOTS + 1) * G_RING * PS * G_G * G_K
    moved = _pool_moves(text, "bf16", min(expert_layer, ring_layer))
    assert not moved, "layer-sized moves:\n" + "\n".join(moved)
    mem = compiled.memory_analysis()
    pool_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                     for a in pool.values())
    assert mem.alias_size_in_bytes >= pool_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 13.5e9 < total < 15.0e9


Q_SLOTS, Q_PAGES = 128, 8192


@pytest.fixture(scope="module")
def qwen3_next_serving(chip):
    """(cfg, params, pool) of the qwen3-next cell as shapes on one
    described chip, the weights as the engine serves them (the seam's
    `lay_out`: a dense plane is a leaf a layer), with the backend
    questions steered to the chip's answers."""
    from ray_tpu.models import qwen3_next

    cfg = qwen3_next.Qwen3NextConfig(n_layers=8, n_experts=128,
                                     vocab_size=37984)
    params = _served(chip, cfg, _stacks(chip, qwen3_next, cfg))
    pool = jax.tree.map(
        lambda x: chip(x.shape, x.dtype),
        jax.eval_shape(lambda: qwen3_next.init_paged_kv(
            cfg, Q_PAGES, PS, Q_SLOTS)))
    with _chips_answers("paged_attention", "gated_delta", "moe",
                        "grouped_matmul"):
        yield cfg, params, pool


@pytest.mark.parametrize("program", ONE_WIDTH_PROGRAMS)
def test_qwen3_next_program_fits_and_moves_no_state(qwen3_next_serving,
                                                    step_program, program):
    """The qwen3_next family's step programs (decode, and the chunk
    program at both of the engine's heights), compiled whole at the
    cell's size: the full layers' attention calls at head size 256, the
    recurrent step's kernel (decode) and the experts' grouped matmul are
    in them under the names a trace finds them by; no layer of the
    recurrent state (129 slots x 2 MiB float32, 271 MB) and no layer of
    experts (128 x 2,048 x 512 bf16, 268 MB a matrix) is copied, sliced
    out or put back; the donated pool (pages, state, tails, counters) is
    updated in place; weights + pool + what the program needs besides
    are 11-12 GB of the chip's 16."""
    cfg, _params, pool = qwen3_next_serving
    assert pool["gdn_state"].shape == (6, Q_SLOTS + 1, 32, 128, 128)
    assert pool["gdn_conv"].shape == (6, Q_SLOTS + 1, 3, 8192)
    compiled = step_program("qwen3_next", program)
    kernels = {_attn_kernel(program): 2}
    if program == "decode":
        kernels["gdn_decode_step"] = 6
    text = compiled.as_text()
    for name, n in kernels.items():
        assert len(re.findall(rf"%\w*{name}[\w.]* = [^\n]*custom-call\(",
                              text)) == n, name
    # three grouped matmuls an expert layer, all eight
    assert _grouped_matmuls(text) == 3 * 8
    assert (f"bf16[{cfg.n_layers * cfg.n_experts},{cfg.d_model},{cfg.d_ff}]"
            in text)
    state_layer = (Q_SLOTS + 1) * 32 * 128 * 128
    moved = _pool_moves(text, "f32", state_layer)
    assert not moved, "state-sized moves:\n" + "\n".join(moved)
    expert_layer = cfg.n_experts * cfg.d_model * cfg.d_ff
    moved = _pool_moves(text, "bf16", expert_layer)
    assert not moved, "layer-sized moves:\n" + "\n".join(moved)
    mem = compiled.memory_analysis()
    pool_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                     for a in pool.values())
    assert mem.alias_size_in_bytes >= pool_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 11.0e9 < total < 12.5e9


_PLANE = 2**21       # elements: the least a dense weight plane holds here
_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]\{([^}]*)\}")
_WEIGHT_PASS = re.compile(r"\s(?:fusion|copy)\(([^)]*%params__[^)]*)\)")


def _weight_planes_written_to_hbm(text, shapes=None):
    """{weight parameter: bytes} over the entry computation's `fusion`
    and `copy` instructions that read a weight parameter and have a bf16
    result of a plane's size outside the chip's fast memory (`S(1)` in
    the result's layout): a plane copied out of its stack and written
    back to HBM, which its matmul then reads a second time. (A prefetch
    is a `copy-start` whose `copy-done` lands in `S(1)`; a matmul fusion
    that slices its plane itself has an activation for a result; where
    a program's activations are as large as a plane, `shapes` names the
    planes' own dims, "2560,8192", and only those count.)"""
    written = {}
    for line in text[text.index("\nENTRY "):].splitlines():
        hit = _WEIGHT_PASS.search(line)
        if not hit:
            continue
        result = line[:hit.start()].partition(" = ")[2]
        elems = [int(np.prod([int(d) for d in dims.split(",")]))
                 for dtype, dims, layout in _SHAPE.findall(result)
                 if dtype == "bf16" and dims and "S(1)" not in layout
                 and (shapes is None or dims in shapes)]
        nbytes = 2 * sum(n for n in elems if n >= _PLANE)
        if nbytes:
            name = re.search(r"%params__(\w+?)__", hit.group(1)).group(1)
            written[name] = written.get(name, 0) + nbytes
    return written


@pytest.mark.parametrize("program", ONE_WIDTH_PROGRAMS)
def test_qwen3_next_reads_a_weight_plane_once(step_program, program):
    """With the tree the seam's `lay_out` returns (a dense plane a leaf a
    layer), no step program of the qwen3_next cell copies a weight plane
    out of its parameter into HBM, and its scratch is what the layer
    walk needs. Handed the stacks, the same program text starts with ONE
    fusion that slices all six `g_qkvz` planes out (302 MB read) and
    writes five back to HBM (251.7 MB), for each layer's matmul to read
    again: the rule is shown to see it."""
    laid_out = step_program("qwen3_next", program)
    assert _weight_planes_written_to_hbm(laid_out.as_text()) == {}
    temp = laid_out.memory_analysis().temp_size_in_bytes
    assert temp < (400e6 if program == "prefill-8" else 150e6)
    stacked = step_program("qwen3_next", program, stacks=True)
    written = _weight_planes_written_to_hbm(stacked.as_text())
    assert written["g_qkvz"] == 5 * 2048 * 12288 * 2
    assert stacked.memory_analysis().temp_size_in_bytes > temp + 200e6


# MiMo-V2-Flash's serving shapes: 64 query heads of 192 over 4 KV heads
# (full layers: K pages 768 lanes beside V pages 512) and 8 (window
# layers: 1,536 beside 1,024, a learned sink a query head, a ring of 19
# pages a slot: the window's 2, the 16 that eight chunk rows of one
# prompt write in one dispatch, and one); the cell's cut: 7 layers, 16 of
# 256 experts held, an eighth of the vocabulary, 128 slots x 96 pages.
M_SLOTS, M_PAGES, M_H, M_K, M_KV, M_RING, M_WINDOW = 128, 12288, 64, 192, \
    128, 19, 128
_MIMO_KINDS = {"full": (4, 96, False), "window": (8, M_RING, True)}


def _mimo_call(chip, kind, rows=None):
    """(fn, args, kernel name) of a mimo attention call of a layer kind:
    the decode call over all 128 slots, or the prefill call at `rows`
    chunk rows."""
    G, width, ring = _MIMO_KINDS[kind]
    n_rows = (M_SLOTS + 1) * M_RING if ring else M_PAGES + 1
    k_pool = chip((2, n_rows, PS, G * M_K), jnp.bfloat16)
    v_pool = chip((2, n_rows, PS, G * M_KV), jnp.bfloat16)
    i32 = lambda *shape: chip(shape, jnp.int32)
    kw = lambda col, sink: ({"window": M_WINDOW, "col_page": col,
                             "sink": sink} if ring else {})
    sink = chip((M_H,), jnp.float32)
    suffix = "_window" if ring else ""
    if rows is None:
        fn = lambda q, k, v, l, t, n, col, s: paged_attention(
            q, k, v, l, t, n, interpret=False, **kw(col, s))
        args = (chip((M_SLOTS, M_H, M_K), jnp.bfloat16), k_pool, v_pool,
                _layer(chip), i32(M_SLOTS, width), i32(M_SLOTS),
                i32(M_SLOTS, width), sink)
        return fn, args, "paged_decode_attn" + suffix
    fn = lambda q, k, v, l, t, o, n, col, s: paged_prefill_attention(
        q, k, v, l, t, o, n, interpret=False, **kw(col, s))
    args = (chip((rows, C, M_H, M_K), jnp.bfloat16), k_pool, v_pool,
            _layer(chip), i32(rows, width), i32(rows), i32(rows),
            i32(rows, width), sink)
    return fn, args, "paged_prefill_attn" + suffix


@pytest.mark.parametrize("kind", sorted(_MIMO_KINDS))
def test_kernels_compile_at_unequal_head_sizes_with_a_sink(chip, kind):
    """Both paged kernels at K heads of 192 (1.5 lane tiles: every odd
    head's lanes start mid-tile) beside V heads of 128, 64 query heads
    over 4 and over 8 KV heads, the window kind over a ring with a sink.
    The prefill grid splits into KV-head PAIRS (384 and 256 lanes: whole
    tiles), since one head's 192 lanes are no block of the pool."""
    from ray_tpu.ops.paged_attention import (prefill_block_pages,
                                             prefill_kv_split)

    G, width, _ring = _MIMO_KINDS[kind]
    shape = (G * M_K, C, M_H * M_K, 2, M_H, G * M_KV)
    assert prefill_kv_split(*shape) == G // 2
    assert prefill_block_pages(width, PS, G * M_K, 2, *shape[1:]) == 4
    fn, args, name = _mimo_call(chip, kind)
    out = _compile(fn, *args, kernels=(name,))
    assert f"bf16[{M_SLOTS},{M_H},{M_KV}]" in out.as_text()
    for rows in ONE_WIDTH_HEIGHTS:
        fn, args, name = _mimo_call(chip, kind, rows)
        _compile(fn, *args, kernels=(name,))


@pytest.mark.parametrize("kind", sorted(_MIMO_KINDS))
def test_decode_kernel_at_unequal_head_sizes_fits_what_its_rule_reckons(
        chip, kind):
    """`_decode_vmem_bytes` with K and V widths apart: the kernel's VMEM
    scratch (K buffers 192 a head, V buffers 128, the block-diagonal
    query at K's width, the accumulator at V's, the softmax state) plus
    the score tiles is what the rule reckons for its block, and with a
    slot group's queries (192 lanes pad to 256 in VMEM) and outputs it
    stays inside the 16 MiB a kernel gets: two groups of 64 slots."""
    import importlib

    attn = importlib.import_module("ray_tpu.ops.paged_attention")
    G, width, ring = _MIMO_KINDS[kind]
    fn, args, _name = _mimo_call(chip, kind)
    (eqn,) = [e for e in jax.make_jaxpr(fn)(*args).eqns
              if e.primitive.name == "pallas_call"]
    spec = eqn.params["grid_mapping"]
    assert spec.grid == (2,)
    assert [str(m.block_aval.memory_space).lower().endswith("any")
            for m in spec.block_mappings] == (
        [False] + [False] * ring + [True, True, False])
    scratch = [v.aval for v in eqn.params["jaxpr"].invars[
        -spec.num_scratch_operands:]]
    vmem = sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
               for a in scratch if "sem" not in str(a.dtype).lower())
    k_lanes, v_lanes = G * M_K, G * M_KV
    if ring:    # ONE block a slot: the window's 128 rows and a tile of 16
        keys, blocks = attn.window_block_rows(M_WINDOW, k_lanes, 2, M_H,
                                              v_lanes)
        assert (keys, blocks) == (144, 1)
        n, page = 1, keys
    else:
        n, page = attn.decode_block_pages(width, PS, k_lanes, 2, M_H,
                                          v_lanes), PS
        assert n == 4
    buffers = attn._DECODE_BUFFERS * n * page * (k_lanes + v_lanes) * 2
    assert vmem == (buffers + M_H * (k_lanes * 2 + v_lanes * 4)
                    + 2 * M_H * 128 * 4)
    tiles = 2 * M_H * n * page * 4
    reckoned = attn._decode_vmem_bytes(n, page, k_lanes, 2, M_H, v_lanes)
    assert vmem + tiles <= reckoned <= attn._DECODE_VMEM_BUDGET
    # all the rule overcounts: the block-diagonal query at 4 bytes, and
    # the accumulator's update
    assert reckoned - (vmem + tiles) == M_H * (k_lanes * 2 + v_lanes * 4)
    group = M_SLOTS // 2
    queries = 2 * group * M_H * (256 + 128) * 2         # q, out; two each
    assert reckoned + queries <= attn._DECODE_GROUP_BUDGET < 16 * 2**20


@pytest.fixture(scope="module")
def mimo_v2_serving(chip):
    """(cfg, params, pool) of the mimo-v2-flash cell as shapes on one
    described chip, with the backend questions steered to the chip's
    answers."""
    from ray_tpu.models import mimo_v2

    cfg = mimo_v2.MiMoV2Config(n_layers=7, n_experts=16, vocab_size=19072)
    params = _stacks(chip, mimo_v2, cfg)
    pool = jax.tree.map(
        lambda x: chip(x.shape, x.dtype),
        jax.eval_shape(lambda: mimo_v2.init_paged_kv(
            cfg, M_PAGES, PS, M_SLOTS,
            dispatch_tokens=ONE_WIDTH_HEIGHTS[-1] * C)))
    with _chips_answers("paged_attention", "moe", "grouped_matmul"):
        yield cfg, params, pool


@pytest.mark.parametrize("program", ONE_WIDTH_PROGRAMS)
def test_mimo_v2_program_fits_and_moves_no_expert_layer(mimo_v2_serving,
                                                        step_program,
                                                        program):
    """The mimo_v2 family's step programs (decode, and the chunk program
    at both of the engine's heights), compiled whole at the cell's size:
    all four attention calls and the experts' grouped matmul are in them
    under the names a trace finds them by; no layer of experts (16 x
    4,096 x 2,048 bf16, 268 MB a matrix) and no layer of any of the four
    planes (the smallest: the full kind's V, 12,289 pages x 64 x 512,
    805 MB) is copied, sliced out or put back; the donated pool (four
    planes of four widths, ring rows, six counters) is updated in place;
    weights + pool + what the program needs besides stay under the
    chip's 16 GB."""
    cfg, _params, pool = mimo_v2_serving
    assert pool["ring_rows"].shape == (M_SLOTS + 1, M_RING)
    assert [pool[n].shape[3] for n in ("k", "v", "k_win", "v_win")] == [
        768, 512, 1536, 1024]
    compiled = step_program("mimo_v2", program)
    kernel = _attn_kernel(program)
    text = compiled.as_text()
    calls = lambda name: len(re.findall(
        rf"%\w*{name}[\w.]* = [^\n]*custom-call\(", text))
    # Two full layers and five window layers (the plain name's pattern
    # finds the window calls too).
    assert calls(kernel + "_window") == 5 and calls(kernel) == 7
    # three grouped matmuls an expert layer (six of the seven layers)
    assert _grouped_matmuls(text) == 3 * 6
    n_sparse = cfg.count("sparse")
    assert (f"bf16[{n_sparse * cfg.n_experts},{cfg.d_model},{cfg.d_ff}]"
            in text)
    expert_layer = cfg.n_experts * cfg.d_model * cfg.d_ff
    moved = _pool_moves(text, "bf16", expert_layer)
    assert not moved, "layer-sized moves:\n" + "\n".join(moved)
    mem = compiled.memory_analysis()
    pool_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                     for a in pool.values())
    assert mem.alias_size_in_bytes >= pool_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"mimo_v2 {program}: arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB, temp "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB, total {total / 1e9:.3f} GB")
    assert 14.5e9 < total < 15.8e9


# What a family's decode program needs beyond its arguments, read here at
# its cell's size (MB: gpt 0.5, zaya 5.3, mimo_v2 22.5, laguna 43.4,
# qwen3_next 111.8 laid out, kimi_k2 81.3 over stacks), with about twice
# the room: all under one
# dense plane of theirs but qwen3_next's, whose own is the layer walk's.
_DECODE_SCRATCH = {"gpt": 1e6, "zaya": 11e6, "mimo_v2": 45e6,
                   "laguna": 87e6, "qwen3_next": 224e6, "kimi_k2": 170e6}


@pytest.mark.parametrize("family", sorted(_DECODE_SCRATCH))
def test_decode_scratch_holds_no_second_copy_of_a_weight_plane(step_program,
                                                               family):
    """Every family's decode program, compiled at its cell's size with
    the tree its engine serves: its scratch stays under the family's
    bound, so a family (or a compiler) that starts slicing the planes of
    a stack out ahead of their matmuls, as qwen3_next's stacks do
    (354.6 MB: five `g_qkvz` planes written back to HBM, 3 % of the
    cell's step), fails here and not on the chip, unseen."""
    mem = step_program(family, "decode").memory_analysis()
    assert mem.temp_size_in_bytes < _DECODE_SCRATCH[family]
    if family == "qwen3_next":
        stacked = step_program(family, "decode", stacks=True)
        assert (stacked.memory_analysis().temp_size_in_bytes
                > _DECODE_SCRATCH[family])


# --- the jamba family: a state-space state by the slot, one KV head -----
# AI21-Jamba2-3B as `benchmarks/configs/ai21-jamba2-3b.json` serves it:
# every layer and width (28 layers, 26 of them mamba: 5,120 channels of
# 16 float32 state values; 20 query heads over ONE KV head of 128 in
# layers 7 and 21; MLPs of 8,192; 65,536 tied vocabulary rows), 256
# slots, pages of 128 tokens for 256 x 4,096 (table width 32), chunk 128.
J_SLOTS, J_PS, J_PAGES, J_WIDTH, J_H, J_K = 256, 128, 8192, 32, 20, 128


@pytest.fixture(scope="module")
def jamba_serving(chip):
    """(cfg, params, pool) of the jamba cell as shapes on one described
    chip, with the backend questions steered to the chip's answers."""
    from ray_tpu.models import jamba

    cfg = jamba.JambaConfig()
    params = _served(chip, cfg, _stacks(chip, jamba, cfg))
    pool = jax.tree.map(
        lambda x: chip(x.shape, x.dtype),
        jax.eval_shape(lambda: jamba.init_paged_kv(
            cfg, J_PAGES, J_PS, J_SLOTS)))
    with _chips_answers("paged_attention", "selective_scan"):
        yield cfg, params, pool


@pytest.mark.parametrize("page", [64, 128])
def test_paged_kernels_compile_at_20_query_heads_over_one_kv_head(chip, page):
    """Both paged kernels with G = 1 KV head of 128 (the pool's minor
    axis is ONE vreg row of 128 lanes; a page of 64 tokens is 16 KB)
    under H = 20 query heads: a group of 20 that no other cell has and
    that is no multiple of the 8 sublanes."""
    width = 4096 // page
    pool = chip((2, 256 * width + 1, page, J_K), jnp.bfloat16)
    _compile(lambda q, k, v, l, t, n: paged_attention(
        q, k, v, l, t, n, interpret=False),
        chip((J_SLOTS, J_H, J_K), jnp.bfloat16), pool, pool, _layer(chip),
        chip((J_SLOTS, width), jnp.int32), chip((J_SLOTS,), jnp.int32),
        kernels=("paged_decode_attn",))
    for rows in (2,) + ONE_WIDTH_HEIGHTS:
        _compile(lambda q, k, v, l, t, o, n: paged_prefill_attention(
            q, k, v, l, t, o, n, interpret=False),
            chip((rows, C, J_H, J_K), jnp.bfloat16), pool, pool,
            _layer(chip), chip((rows, width), jnp.int32),
            chip((rows,), jnp.int32), chip((rows,), jnp.int32),
            kernels=("paged_prefill_attn",))


_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[\w.\-]+) \(.*\{$")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%[\w.\-]+ = (\w+)\[([\d,]*)\]\{[^}]*\} ([\w\-]+)\(")


def _planes_made(text, shapes):
    """Instructions OUTSIDE a fused computation (the entry's and a loop
    body's own: what they produce is a buffer) whose result is a bf16
    array of one of `shapes` ("2560,8192"; a leading 1 is the same
    plane): a weight plane cut out of its stack and written somewhere,
    for its matmul to read a second time. A prefetch into fast memory is
    a `slice-done` or `copy-done` and is not one."""
    made, fused = [], False
    for line in text.splitlines():
        comp = _COMPUTATION.match(line)
        if comp:
            fused = "fused" in comp.group(1)
            continue
        hit = None if fused else _INSTRUCTION.match(line)
        if (hit and hit.group(1) == "bf16"
                and hit.group(2).removeprefix("1,") in shapes
                and hit.group(3) not in ("parameter", "get-tuple-element",
                                         "bitcast", "slice-done", "copy-done")
                and "S(1)" not in line.split(" = ")[1].split(" ")[0]):
            made.append(line.strip()[:160])
    return made


@pytest.mark.parametrize("program", ["decode", "prefill-2", "prefill-4"])
def test_jamba_program_fits_and_moves_no_state(jamba_serving, step_program,
                                               program):
    """The jamba family's step programs (decode, and the chunk program
    at both of the engine's heights in the cell, whose decode window is
    4 steps: 2 rows, one admission's, and 4), compiled whole at the
    cell's size:
    a run of mamba layers is ONE loop (three of them, of 7, 13 and 6
    layers), so the two attention calls and three of each scan kernel are
    in them under the names a trace finds them by; no layer of the state
    (257 slots x 16 x 5,120 float32, 84 MB) and nothing of the tail is
    copied, sliced out or put back, in the entry or in a loop's body;
    no weight plane is cut out of its stack into a buffer of its own (a
    matmul's fusion slices its plane where it lies); the donated pool
    (pages, state, tails) is updated in place; weights + pool + what the
    program needs besides are 9.5-10.5 GB of the chip's 16. In the decode
    program every value a slot a row over the mixer's 5,120 channels (the
    step kernel's xs, dt and y, the gate, what is fused to them) lies a
    slot a SUBLANE, 8 to a tile: with the kernel's blocks one slot tall
    the compiler kept 96 such values a row a tile, `[256, 1, 5120]` in
    `T(1,128)` and `T(2,128)`, and the fusions beside the kernel ran on
    an eighth of a register (PR 54)."""
    cfg, _params, pool = jamba_serving
    assert pool["ssm_state"].shape == (26, J_SLOTS + 1, 16, 5120)
    assert pool["ssm_conv"].shape == (26, 3, J_SLOTS + 1, 5120)
    assert pool["k"].shape == (2, J_PAGES + 1, J_PS, J_K)
    compiled = step_program("jamba", program)
    runs = 3
    kernels = ({"paged_decode_attn": 2, "ssm_decode_step": runs,
                "ssm_conv_step": runs} if program == "decode" else
               {"paged_prefill_attn": 2, "ssm_chunk_scan": runs})
    text = compiled.as_text()
    assert len(re.findall(r" while\(", text)) == runs
    for name, n in kernels.items():
        assert len(re.findall(rf"%\w*{name}[\w.]* = [^\n]*custom-call\(",
                              text)) == n, name
    if program == "decode":
        sparse = re.findall(
            rf"\w+\[{J_SLOTS},(?:1,)?5120\]\{{[^}}]*T\([124],128\)[^}}]*\}}",
            text)
        assert not sparse, f"{len(sparse)} values a row a tile: {sparse[:4]}"
        assert f"[{J_SLOTS},5120]" in text
    moved = (_pool_moves(text, "f32", (J_SLOTS + 1) * 16 * 5120)
             + _pool_moves(text, "bf16", 26 * 3 * (J_SLOTS + 1) * 5120))
    assert not moved, "state-sized moves:\n" + "\n".join(moved)
    planes = {",".join(map(str, a.shape[1:])) for name, a in _params.items()
              if len(a.shape) == 3 and name[:2] in ("m_", "a_", "w_")}
    assert "2560,8192" in planes and "2560,10240" in planes
    made = _planes_made(text, planes)
    assert not made, "weight planes written out:\n" + "\n".join(made)
    # (The compiler transposes W_q of the two attention layers for the
    # decode step's q projection, one of them through HBM: 13 MB of the
    # 11 GB a step moves, and no stack's doing: it did so for a leaf a
    # layer too.)
    written = _weight_planes_written_to_hbm(text, planes)
    assert set(written) <= {"a_wq"} and sum(written.values()) < 14e6
    mem = compiled.memory_analysis()
    pool_bytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                     for a in pool.values())
    assert mem.alias_size_in_bytes >= pool_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"jamba {program}: arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB, temp "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB, total {total / 1e9:.3f} GB")
    assert 9.5e9 < total < 10.5e9


# --- the kimi_k2 family: a latent cache, ONE plane -----------------------
# Kimi-K2.6 as `benchmarks/configs/kimi-k2.6.json` serves it: one chip of
# a 32-chip group, layers 0-4 (a dense layer, four expert layers of 12
# held experts of 384 and a shared one), 20,480 vocabulary rows; 256
# slots x 72 pages of 64 tokens; a cached row of 576 values in 640 lanes
# under 64 query heads, its first 512 lanes the value.
K2_SLOTS, K2_PAGES, K2_WIDTH, K2_H, K2_ROW, K2_LATENT = (256, 18432, 72, 64,
                                                         640, 512)


@pytest.fixture(scope="module")
def kimi_k2_serving(chip):
    """(cfg, params, pool) of the kimi-k2.6 cell as shapes on one
    described chip (the tree the engine serves: `lay_out` over the
    stacks), with the backend questions steered to the chip's
    answers."""
    from ray_tpu.models import kimi_k2

    cfg = kimi_k2.KimiK2Config(n_layers=5, n_experts=12, vocab_size=20480)
    params = _served(chip, cfg, _stacks(chip, kimi_k2, cfg))
    pool = jax.tree.map(
        lambda x: chip(x.shape, x.dtype),
        jax.eval_shape(lambda: kimi_k2.init_paged_kv(
            cfg, K2_PAGES, PS, K2_SLOTS)))
    with _chips_answers("paged_attention", "moe", "grouped_matmul"):
        yield cfg, params, pool


def test_latent_kernels_compile_at_the_cells_shapes(chip):
    """Both paged kernels in their latent form at the kimi-k2.6 cell's
    shapes: 64 query heads over ONE plane of 640-lane rows, the value its
    first 512 lanes, at the cell's table widths and both chunk heights;
    under names of their own. The decode call's body of its own
    (`_latent_decode_kernel`: 8 pages a block, FOUR block buffers, the
    two queries and the two score scratches of its two-stage schedule)
    takes the VMEM its rule reckons, and with a group of 32 slots'
    queries and outputs stays inside the 16 MiB a kernel gets. A plane
    declared at the row's 576 values is refused before Mosaic is asked
    (which would refuse a 576-lane DMA of what the chip stores in 640)."""
    import importlib

    attn = importlib.import_module("ray_tpu.ops.paged_attention")
    pool = chip((5, K2_PAGES + 1, PS, K2_ROW), jnp.bfloat16)
    decode = lambda q, kv, l, t, n: paged_attention(
        q, kv, None, l, t, n, latent=K2_LATENT, interpret=False)
    for width in (64, K2_WIDTH):    # the window's width, and `max_len`'s
        args = (chip((K2_SLOTS, K2_H, K2_ROW), jnp.bfloat16), pool,
                _layer(chip), chip((K2_SLOTS, width), jnp.int32),
                chip((K2_SLOTS,), jnp.int32))
        _compile(decode, *args, kernels=("paged_decode_attn_latent",))
        (eqn,) = [e for e in jax.make_jaxpr(decode)(*args).eqns
                  if e.primitive.name == "pallas_call"]
        spec = eqn.params["grid_mapping"]
        assert spec.grid == (8,)
        scratch = [v.aval for v in eqn.params["jaxpr"].invars[
            -spec.num_scratch_operands:]]
        vmem = sum(int(np.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
                   for a in scratch if "sem" not in str(a.dtype).lower())
        n = attn.decode_block_pages(width, PS, K2_ROW, 2, K2_H, K2_LATENT,
                                    latent=True)
        assert n == 8 and attn._LATENT_BUFFERS == 4
        block = n * PS
        assert vmem == (4 * block * K2_ROW * 2          # page buffers
                        + 2 * K2_H * K2_ROW * 2         # two queries
                        + 2 * K2_H * block * 4          # two score tiles
                        + 2 * K2_H * 128 * 4 + K2_H * K2_LATENT * 4)
        reckoned = attn._decode_vmem_bytes(n, PS, K2_ROW, 2, K2_H,
                                           K2_LATENT, latent=True)
        tiles = 2 * K2_H * block * 4        # masked scores, probabilities
        assert vmem + tiles <= reckoned <= attn._DECODE_VMEM_BUDGET
        queries = 2 * 32 * K2_H * (K2_ROW + K2_LATENT) * 2
        assert reckoned + queries <= attn._DECODE_GROUP_BUDGET < 16 * 2**20
    for rows in ONE_WIDTH_HEIGHTS:
        _compile(lambda q, kv, l, t, o, n: paged_prefill_attention(
            q, kv, None, l, t, o, n, latent=K2_LATENT, interpret=False),
            chip((rows, C, K2_H, K2_ROW), jnp.bfloat16), pool, _layer(chip),
            chip((rows, K2_WIDTH), jnp.int32), chip((rows,), jnp.int32),
            chip((rows,), jnp.int32),
            kernels=("paged_prefill_attn_latent",))
    with pytest.raises(ValueError, match="lies in 640"):
        jax.eval_shape(lambda q, kv, t, n: paged_attention(
            q, kv, None, 0, t, n, latent=K2_LATENT, interpret=False),
            chip((K2_SLOTS, K2_H, 576), jnp.bfloat16),
            chip((5, K2_PAGES + 1, PS, 576), jnp.bfloat16),
            chip((K2_SLOTS, K2_WIDTH), jnp.int32),
            chip((K2_SLOTS,), jnp.int32))


@pytest.mark.parametrize("program", ONE_WIDTH_PROGRAMS)
def test_kimi_k2_program_fits_and_moves_no_plane(kimi_k2_serving,
                                                 step_program, program):
    """The kimi_k2 family's step programs (decode, and the chunk program
    at both of the engine's heights), compiled whole at the cell's size:
    five latent attention calls and the experts' grouped matmul are in
    them under the names a trace finds them by; no layer of experts (12 x
    7,168 x 2,048 bf16, 352 MB a matrix) and no layer of the latent plane
    (18,433 pages x 64 x 640, 1.51 GB) is copied, sliced out or put back;
    no weight plane is cut out of its stack into a buffer of its own (the
    tree is stacks: a copy a layer of each plane, 2.2 GB, does not fit
    beside this pool); the donated pool is updated in place; and the
    ARGUMENTS' bytes are what the cell's arithmetic says: 6.99 GB of
    weights + 7.55 GB of pool (a row of 576 values in 640 lanes; 6.80 GB
    at 1,152 B a token), under the chip's 16 GB with what the program
    needs besides."""
    cfg, params, pool = kimi_k2_serving
    assert set(pool) == {"kv", "moe_counters"}
    assert pool["kv"].shape == (5, K2_PAGES + 1, PS, K2_ROW)
    assert params["w_uk"].shape == (5, K2_H, 128, K2_LATENT)
    assert params["w_uv"].shape == (5, K2_H, K2_LATENT, 128)
    assert "wkv_b" not in params
    compiled = step_program("kimi_k2", program)
    kernel = _attn_kernel(program) + "_latent"
    text = compiled.as_text()
    assert len(re.findall(rf"%\w*{kernel}[\w.]* = [^\n]*custom-call\(",
                          text)) == 5
    # three grouped matmuls an expert layer (four of the five layers)
    assert _grouped_matmuls(text) == 3 * 4
    assert f"bf16[{4 * 12},{cfg.d_model},{cfg.d_ff}]" in text
    moved = (_pool_moves(text, "bf16", 12 * cfg.d_model * cfg.d_ff)
             + _pool_moves(text, "bf16", (K2_PAGES + 1) * PS * K2_ROW))
    assert not moved, "layer-sized moves:\n" + "\n".join(moved)
    planes = {",".join(map(str, a.shape[1:])) for name, a in params.items()
              if len(a.shape) == 3 and a.shape[1] * a.shape[2] >= _PLANE}
    assert {"7168,1536", "8192,7168", "7168,18432", "7168,2048"} <= planes
    made = _planes_made(text, planes)
    assert not made, "weight planes written out:\n" + "\n".join(made)
    mem = compiled.memory_analysis()
    nbytes = lambda tree: sum(int(np.prod(a.shape)) * a.dtype.itemsize
                              for a in jax.tree.leaves(tree))
    pool_bytes, weight_bytes = nbytes(pool), nbytes(params)
    assert mem.alias_size_in_bytes >= pool_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"kimi_k2 {program}: arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB (weights "
          f"{weight_bytes / 1e9:.3f} + pool {pool_bytes / 1e9:.3f}), temp "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB, total {total / 1e9:.3f} GB")
    assert 6.98e9 < weight_bytes < 7.00e9 and 7.54e9 < pool_bytes < 7.56e9
    assert abs(mem.argument_size_in_bytes - weight_bytes - pool_bytes) < 1e7
    assert 14.5e9 < total < 15.2e9


# --- the olmo_hybrid family: a 96 x 192 delta state, 3,840-lane pages ----
# Olmo-Hybrid-7B as `benchmarks/configs/olmo-hybrid-7b.json` serves it:
# stage 0 of a four-stage pipeline, layers 0-7 (six Gated DeltaNet layers
# of 30 heads of 96 x 192, two multi-head attention layers of 30 x 128,
# MLPs of 11,008), the whole vocabulary of 100,352; 96 slots x 44 pages of
# 64 tokens.
O_SLOTS, O_PAGES, O_WIDTH, O_H, O_K = 96, 4224, 44, 30, 128


@pytest.fixture(scope="module")
def olmo_hybrid_serving(chip):
    """(cfg, params, pool) of the olmo-hybrid cell as shapes on one
    described chip (the tree is served as `param_specs` shapes it: the
    family has no `lay_out`), with the backend questions steered to
    the chip's answers."""
    from ray_tpu.models import olmo_hybrid

    cfg = olmo_hybrid.OlmoHybridConfig(n_layers=8)
    params = _served(chip, cfg, _stacks(chip, olmo_hybrid, cfg))
    pool = jax.tree.map(
        lambda x: chip(x.shape, x.dtype),
        jax.eval_shape(lambda: olmo_hybrid.init_paged_kv(
            cfg, O_PAGES, PS, O_SLOTS)))
    with _chips_answers("paged_attention", "gated_delta"):
        yield cfg, params, pool


def test_decode_kernels_compile_at_olmo_hybrid_rows(chip):
    """Multi-head rows of 30 x 128 = 3,840 lanes, 480 KB a page and
    plane: the decode kernel attends ONE page a block under a
    block-diagonal query of [30, 3840], and the chip's compiler takes
    it; the gated delta step's kernel takes 15 pairs of heads of
    [96, 384] in blocks of 5."""
    from ray_tpu.ops.gated_delta import gdn_decode_step
    from ray_tpu.ops.paged_attention import decode_block_pages

    assert decode_block_pages(O_WIDTH, PS, O_H * O_K, 2, O_H) == 1
    pool = chip((2, O_PAGES + 1, PS, O_H * O_K), jnp.bfloat16)
    _compile(lambda q, k, v, l, t, n: paged_attention(
        q, k, v, l, t, n, interpret=False),
        chip((O_SLOTS, O_H, O_K), jnp.bfloat16), pool, pool, _layer(chip),
        chip((O_SLOTS, O_WIDTH), jnp.int32), chip((O_SLOTS,), jnp.int32),
        kernels=("paged_decode_attn",))
    f32 = lambda *s: chip(s, jnp.float32)
    _compile(lambda s, l, q, k, v, g, b, a: gdn_decode_step(
        s, l, q, k, v, g, b, a, interpret=False),
        f32(6, O_SLOTS + 1, 15, 96, 384), _layer(chip),
        f32(O_SLOTS, 30, 96), f32(O_SLOTS, 30, 96), f32(O_SLOTS, 30, 192),
        f32(O_SLOTS, 30), f32(O_SLOTS, 30), chip((O_SLOTS,), jnp.bool_),
        kernels=("gdn_decode_step",))


@pytest.mark.parametrize("program", ONE_WIDTH_PROGRAMS)
def test_olmo_hybrid_program_fits_and_moves_no_state(olmo_hybrid_serving,
                                                     step_program, program):
    """The olmo_hybrid family's step programs (decode, and the chunk
    program at both of the engine's heights), compiled whole at the
    cell's size: the layers are two nested loops, so the attention call
    over 3,840-lane rows and (in decode) the gated delta step are in
    them ONCE, under the names a trace finds them by; no layer of the
    recurrent state (97 slots x 15 pairs
    of [96, 384] float32, 215 MB: the PACKED leaf, which the chip's
    tiles hold dense) is copied, sliced out or put back; no weight plane
    is cut out of its stack into a buffer of its own (the tree is
    stacks: a copy a layer of each plane, 3.3 GB, does not fit beside
    this pool); the donated pool is updated in place; the ARGUMENTS'
    bytes are what the cell's arithmetic says: 4.87 GB of weights +
    9.64 GB of pool, under the chip's 16 GB with what the program needs
    besides."""
    cfg, params, pool = olmo_hybrid_serving
    assert pool["gdn_state"].shape == (6, O_SLOTS + 1, 15, 96, 384)
    assert pool["gdn_conv"].shape == (6, O_SLOTS + 1, 3, 11520)
    assert pool["k"].shape == (2, O_PAGES + 1, PS, O_H * O_K)
    compiled = step_program("olmo_hybrid", program)
    # The layers are ONE loop over the periods around a loop over a
    # period's linear layers: a program holds each kernel once.
    text = compiled.as_text()
    # (two nested loops of layers; a chunk program's scan walks its rows
    # in loops of its own)
    loops = len(re.findall(r" while\(", text))
    assert loops == 2 if program == "decode" else loops >= 2
    kernels = [_attn_kernel(program)]
    if program == "decode":
        kernels.append("gdn_decode_step")
    for name in kernels:
        assert len(re.findall(rf"%\w*{name}[\w.]* = [^\n]*custom-call\(",
                              text)) == 1, name
    moved = (_pool_moves(text, "f32", (O_SLOTS + 1) * 15 * 96 * 384)
             + _pool_moves(text, "bf16", (O_PAGES + 1) * PS * O_H * O_K))
    assert not moved, "state- or plane-sized moves:\n" + "\n".join(moved)
    planes = {",".join(map(str, a.shape[1:])) for name, a in params.items()
              if len(a.shape) == 3 and a.shape[1] * a.shape[2] >= _PLANE}
    assert {"3840,17280", "5760,3840", "3840,11520", "3840,11008",
            "11008,3840"} <= planes
    made = _planes_made(text, planes)
    assert not made, "weight planes written out:\n" + "\n".join(made)
    assert _weight_planes_written_to_hbm(text, planes) == {}
    mem = compiled.memory_analysis()
    nbytes = lambda tree: sum(int(np.prod(a.shape)) * a.dtype.itemsize
                              for a in jax.tree.leaves(tree))
    pool_bytes, weight_bytes = nbytes(pool), nbytes(params)
    assert mem.alias_size_in_bytes >= pool_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"olmo_hybrid {program}: arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB (weights "
          f"{weight_bytes / 1e9:.3f} + pool {pool_bytes / 1e9:.3f}), temp "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB, total {total / 1e9:.3f} GB")
    assert 4.86e9 < weight_bytes < 4.88e9 and 9.63e9 < pool_bytes < 9.66e9
    assert abs(mem.argument_size_in_bytes - weight_bytes - pool_bytes) < 1e7
    assert 14.5e9 < total < 15.3e9


# --- the nemotron_h family: a 64 x 128 Mamba-2 state, latent experts -----
# Nemotron-3-Super-120B-A12B as
# `benchmarks/configs/nemotron-3-super-120b-a12b.json` serves it: one chip
# of an expert-parallel four at layers 0-10 (`MEMEMEM*EME`: five Mamba-2
# layers of 128 heads of 64 x 128, five expert layers holding 128 of 512
# two-matrix experts in a latent of 1,024 under top-22, one attention
# layer of 32 heads over 2 KV heads), a quarter of the vocabulary; 192
# slots x 36 pages of 64 tokens.
N_SLOTS, N_PAGES, N_WIDTH = 192, 6912, 36


@pytest.fixture(scope="module")
def nemotron_h_serving(chip):
    """(cfg, params, pool) of the nemotron-3-super cell as shapes on one
    described chip (the tree is served as `param_specs` shapes it: the
    family has no `lay_out`), with the backend questions steered to
    the chip's answers."""
    from ray_tpu.models import nemotron_h

    cfg = nemotron_h.NemotronHConfig(vocab_size=32768, n_experts=128,
                                     max_seq=2304)
    params = _served(chip, cfg, _stacks(chip, nemotron_h, cfg))
    pool = jax.tree.map(
        lambda x: chip(x.shape, x.dtype),
        jax.eval_shape(lambda: nemotron_h.init_paged_kv(
            cfg, N_PAGES, PS, N_SLOTS)))
    with _chips_answers("paged_attention", "gated_delta", "selective_scan",
                        "moe", "grouped_matmul"):
        yield cfg, params, pool


def test_decode_kernels_compile_at_nemotron_h_rows(chip):
    """The Mamba-2 step's kernel takes 64 pairs of heads of [128, 128]
    float32 in blocks of 16 (two groups' B and C a block), 192 slots of
    a stack of five layers, aliased in and out; the convolution's step
    takes 16 slots' three planes of 10,240 channels and a float32 plane
    of new inputs (what it returns stays float32: models/nemotron_h.py
    `_ssm_inputs`)."""
    from ray_tpu.ops.selective_scan import ssm_conv_step
    from ray_tpu.ops.ssd import ssd_decode_step

    f32 = lambda *s: chip(s, jnp.float32)
    _compile(lambda s, l, x, dt, A, B, C, a: ssd_decode_step(
        s, l, x, dt, A, B, C, a, interpret=False),
        f32(5, N_SLOTS + 1, 64, 128, 128), _layer(chip),
        f32(N_SLOTS, 128, 64), f32(N_SLOTS, 128), f32(128),
        f32(N_SLOTS, 8, 128), f32(N_SLOTS, 8, 128),
        chip((N_SLOTS,), jnp.bool_),
        kernels=("ssd_decode_step",))
    _compile(lambda t, l, x, w, b, a: ssm_conv_step(
        t, l, x, w, b, a, interpret=False),
        chip((5, 3, N_SLOTS + 1, 10240), jnp.bfloat16), _layer(chip),
        f32(N_SLOTS, 10240), f32(4, 10240), f32(10240),
        chip((N_SLOTS,), jnp.bool_), kernels=("ssm_conv_step",))


@pytest.mark.parametrize("rows", [2432, 5760, 11392])
@pytest.mark.parametrize("K,N", [(1024, 2688), (2688, 1024)])
def test_grouped_matmul_compiles_at_nemotron_h_planes(chip, K, N, rows):
    """The repo's grouped matmul at the cell's shapes: a block of the
    decode step's held rows (2,432) and of the two chunk programs'
    (5,760 and 11,392), the WHOLE stack of 5 x 128 planes of
    [1,024, 2,688] or [2,688, 1,024] bf16 as its operand, float32 out. A
    weight block is one whole plane, 5.5 MB, two of them in flight: the
    chip's compiler takes it within the kernel's own VMEM limit, and the
    program holds the stack once (no copy of it, nor of a layer of it)."""
    from ray_tpu.ops import grouped_matmul as gm

    assert gm._n_tile(K, N, 2) == N
    _grouped_matmul_compiles(chip, K, N, rows, planes=640, held=128)


def _grouped_matmul_compiles(chip, K, N, rows, planes, held):
    """The repo's grouped matmul compiled for the chip at `rows` against
    a stack of `planes` [K, N] bf16: its blocks fit the kernel's own VMEM
    limit, no layer of `held` planes is moved, temporaries under 1 MB."""
    from ray_tpu.ops import grouped_matmul as gm

    tn = gm._n_tile(K, N, 2)
    assert N % tn == 0 and K * tn * 2 <= gm._PLANE_BYTES
    assert 2 * (K * tn * 2 + 128 * K * 2 + 128 * tn * 4) < gm._VMEM_LIMIT
    compiled = _compile(
        lambda a, b, s: gm.moe_grouped_matmul(a, b, s, interpret=False),
        chip((rows, K), jnp.bfloat16), chip((planes, K, N), jnp.bfloat16),
        chip((planes,), jnp.int32), kernels=(gm.KERNEL_NAME,))
    assert not _pool_moves(compiled.as_text(), "bf16", held * K * N)
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


# configuration → (K, N of an expert's up plane, planes of the cell's
# stack, held experts, the router's width, choices a row, decode slots)
_SIBLING_PLANES = {
    "zaya": (2048, 2048, L * 16, 16, None, 1, Z_SLOTS),
    "laguna": (3072, 1024, 4 * 128, 128, 256, 10, G_SLOTS),
    "qwen3_next": (2048, 512, 8 * 128, 128, 512, 10, Q_SLOTS),
    "mimo_v2": (4096, 2048, 6 * 16, 16, 256, 8, M_SLOTS),
    "kimi_k2": (7168, 2048, 4 * 12, 12, 384, 8, K2_SLOTS)}


@pytest.mark.parametrize("program", ["decode", "prefill-8"])
@pytest.mark.parametrize("plane", ["up", "down"])
@pytest.mark.parametrize("family", sorted(_SIBLING_PLANES))
def test_grouped_matmul_compiles_at_sibling_planes(chip, family, plane,
                                                   program):
    """The repo's grouped matmul at the five sibling cells' shapes: a
    block of the decode step's held rows and of the taller chunk
    program's (`moe.block_rows`: 128 and 1,152 rows at zaya1-8b, 384 and
    1,152 at kimi-k2.6, ...), the WHOLE stack of the cell's planes as
    its operand (2 MB a plane at qwen3-next, 29.4 MB at kimi-k2.6),
    float32 out. A weight block is a plane or the equal part of its
    columns that `_PLANE_BYTES` holds (kimi-k2.6's cut in 4,
    mimo-v2-flash's in 2), two of them in flight beside two row tiles of
    `lhs` and of the float32 result: the chip's compiler takes it within
    the kernel's own VMEM limit at every one, and the program holds the
    stack once."""
    from ray_tpu.ops import moe

    K, N, planes, held, routed, k, slots = _SIBLING_PLANES[family]
    if plane == "down":
        K, N = N, K
    tokens = slots if program == "decode" else 8 * C
    rows = moe.block_rows(tokens * k, held, routed)
    _grouped_matmul_compiles(chip, K, N, rows, planes, held)


@pytest.mark.parametrize("program", ONE_WIDTH_PROGRAMS)
def test_nemotron_h_program_fits_and_moves_no_state(nemotron_h_serving,
                                                    step_program, program):
    """The nemotron_h family's step programs (decode, and the chunk
    program at both of the engine's heights), compiled whole at the
    cell's size: the attention call, the Mamba-2 step and the
    convolution's step (in decode, once a Mamba-2 layer the program
    holds) and the experts' grouped matmuls (TWO an expert layer's turn,
    over the whole stack of 5 x 128 experts at the latent's 1,024 lanes:
    the repo's kernel, the planes being 2,688 = 21 x 128 wide, and no
    `ragged-dot` of the compiler's) are in them
    under the names a trace finds them by; no layer of the state (193
    slots x 64 pairs of [128, 128] float32, 0.81 GB: a copy of the
    4.05 GB leaf does not fit) is copied, sliced out or put back, and no
    layer of experts; no weight plane is cut out of its stack into a
    buffer of its own; the donated pool is updated in place; the
    ARGUMENTS' bytes are what ISSUE 62's arithmetic says: 9.30 GB of
    weights + 4.56 GB of pool, under the chip's 16 GB with what the
    program needs besides."""
    cfg, params, pool = nemotron_h_serving
    assert pool["ssm_state"].shape == (5, N_SLOTS + 1, 64, 128, 128)
    assert pool["ssm_conv"].shape == (5, 3, N_SLOTS + 1, 10240)
    assert pool["k"].shape == (1, N_PAGES + 1, PS, 256)
    compiled = step_program("nemotron_h", program)
    text = compiled.as_text()
    calls = lambda name: len(re.findall(
        rf"%\w*{name}[\w.]* = [^\n]*custom-call\(", text))
    assert calls(_attn_kernel(program)) == 1
    # "ME" x 3 is ONE loop body: a program holds three Mamba-2 and three
    # expert layers (the loop's, and layers 6 and 9 / 8 and 10 alone).
    assert len(re.findall(r" while\(", text)) >= 1
    if program == "decode":
        assert calls("ssd_decode_step") == calls("ssm_conv_step") == 3
    assert _grouped_matmuls(text, a_layer=2) == 2 * 3
    assert f"bf16[{5 * 128},1024,2688]" in text     # the stack, whole
    moved = (_pool_moves(text, "f32", (N_SLOTS + 1) * 64 * 128 * 128)
             + _pool_moves(text, "bf16", 128 * 1024 * 2688)
             + _pool_moves(text, "bf16", (N_PAGES + 1) * PS * 256))
    assert not moved, "state-, expert- or plane-sized moves:\n" + "\n".join(
        moved)
    planes = {",".join(map(str, a.shape[1:])) for name, a in params.items()
              if len(a.shape) == 3 and a.shape[1] * a.shape[2] >= _PLANE}
    assert {"4096,18560", "8192,4096", "4096,1024", "1024,4096",
            "4096,5376", "5376,4096", "4096,4096"} <= planes
    # (the 8-row chunk program's embedding rows, 1,024 tokens x 4,096,
    # have W_lat_out's dims and are no plane of it)
    made = [m for m in _planes_made(text, planes) if "%params__wte__" not in m]
    assert not made, "weight planes written out:\n" + "\n".join(made)
    written = _weight_planes_written_to_hbm(text, planes)
    assert set(written) <= {"wte"}, written
    mem = compiled.memory_analysis()
    nbytes = lambda tree: sum(int(np.prod(a.shape)) * a.dtype.itemsize
                              for a in jax.tree.leaves(tree))
    pool_bytes, weight_bytes = nbytes(pool), nbytes(params)
    assert mem.alias_size_in_bytes >= pool_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"nemotron_h {program}: arguments "
          f"{mem.argument_size_in_bytes / 1e9:.3f} GB (weights "
          f"{weight_bytes / 1e9:.3f} + pool {pool_bytes / 1e9:.3f}), temp "
          f"{mem.temp_size_in_bytes / 1e9:.3f} GB, total {total / 1e9:.3f} GB")
    assert 9.29e9 < weight_bytes < 9.31e9 and 4.55e9 < pool_bytes < 4.58e9
    assert abs(mem.argument_size_in_bytes - weight_bytes - pool_bytes) < 1e7
    assert 13.8e9 < total < 15.3e9


# --- the sampling step: the draw under a conditional (PR 55) ------------
_DRAW = re.compile(r"op_name=\"[^\"]*(?:_gumbel|_uniform)")


def _entry(text):
    """The lines of a compiled program's entry computation."""
    entry = text[text.index("\nENTRY "):]
    return entry[:entry.index("\n}")].splitlines()


def _wide_prefetches(lines, rows, least_cols=4096):
    """`copy-start`s of a float32 [rows, >= least_cols] value: the logits
    moved as a conditional's operand, ahead of its predicate."""
    return [line.strip()[:160] for line in lines if " copy-start(" in line
            and any(int(cols) >= least_cols for cols in re.findall(
                rf"f32\[{rows},(\d+)\]", line.partition(" copy-start(")[0]))]


def test_draw_rules_find_what_they_are_there_to_refuse(chip):
    """The two rules of the test below, each shown to see something. The
    four straight lines `_sample_next` was up to PR 54 (scale, arg-max,
    categorical, where), compiled for the chip, draw in the entry
    computation. And `_sample_next` as it is, compiled ALONE, gets its
    logits as a parameter in HBM: the compiler prefetches all of them
    into fast memory as the conditional's operand, before the predicate
    is known (in a step program the head's fusion writes them there)."""
    from ray_tpu.models.paged_kv import _sample_next

    def straight(logits, temps, key):
        key, sub = jax.random.split(key)
        scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
        sampled = jax.random.categorical(sub, scaled, axis=-1)
        return jnp.where(temps <= 0.0, jnp.argmax(logits, axis=-1),
                         sampled).astype(jnp.int32), key

    args = (chip((64, 32768), jnp.float32), chip((64,), jnp.float32),
            chip((2,), jnp.uint32))
    text = jax.jit(straight).lower(*args).compile().as_text()
    assert any(_DRAW.search(line) for line in _entry(text))
    assert " conditional(" not in text
    entry = _entry(jax.jit(_sample_next).lower(*args).compile().as_text())
    assert not any(_DRAW.search(line) for line in entry)
    assert len(_wide_prefetches(entry, 64)) == 1


@pytest.mark.parametrize("family", ["gpt", "jamba", "kimi_k2", "laguna",
                                    "mimo_v2", "qwen3_next", "zaya"])
def test_decode_program_draws_only_inside_its_conditional(step_program,
                                                          family):
    """Every family's window step, compiled whole at its cell's size: the
    sampling step's `lax.cond` is still a `conditional` of the entry
    computation; nothing of the categorical draw (the threefry words, the
    Gumbel noise, the reduction they are fused into: 0.40-0.42 ms of
    every step at 16.8 M logits, PERF.md section 6, PR 55) runs in the
    entry, where a greedy batch would pay for it; the draw is in the
    program all the same, inside a branch; and the logits reach the
    conditional where the head's fusion wrote them, not through a
    prefetch of [slots, vocabulary] float32 made ahead of the predicate
    (which a conditional over a PARAMETER of that size got)."""
    text = step_program(family, "decode").as_text()
    entry = _entry(text)
    assert sum(" conditional(" in line for line in entry) == 1
    drawn = [line.strip()[:160] for line in entry if _DRAW.search(line)]
    assert not drawn, "the draw, in the entry:\n" + "\n".join(drawn)
    assert _DRAW.search(text)
    (slots,) = set(re.findall(r"\(s32\[(\d+)\]\{[^}]*\}\) conditional\(",
                              "\n".join(entry)))
    moved = _wide_prefetches(entry, slots)
    assert not moved, "logits prefetched:\n" + "\n".join(moved)


# --- the expert layer's block: the held rows alone (PR 59) ---------------
_GROUPED = re.compile(r"%moe_grouped_matmul[\w.\-]* = f32\[(\d+),\d+\][^\n]*"
                      r"operand_layout_constraints=\{([^\n]*?)\}\}")
_MOE_OPS = re.compile(r'op_name="([^"]*?)moe\.(?:route|experts)/')

# family → (rows of its decode step, choices a row, rows of a block: the
# held rows of twice an even router's share and a zero row an expert, the
# loops its expert layer's operations sit in: zaya's is its layer scan,
# the other three's the block loop)
_EXPERT_BLOCKS = {"kimi_k2": (K2_SLOTS, 8, 384, 1),
                  "mimo_v2": (M_SLOTS, 8, 384, 1),
                  "qwen3_next": (Q_SLOTS, 10, 896, 1),
                  "laguna": (G_SLOTS, 10, 896, 0),
                  "zaya": (Z_SLOTS, 1, 128, 1)}


@pytest.mark.parametrize("family", sorted(_EXPERT_BLOCKS))
def test_expert_layer_carries_only_a_block_of_held_rows(request, step_program,
                                                        family):
    """Every expert family's decode program at its cell's size: the three
    grouped matmuls take a block's rows and no more (384 of 2,176 at
    kimi_k2, 384 of 1,152 at mimo_v2, 896 of 1,408 at qwen3_next), over
    the whole stack of experts, inside ONE loop a layer and no branch; no
    float32 array of every choice a row is left in the program. Where
    the block IS every choice (zaya holds all 16, laguna half of 256
    under top-10) the rows are the ones they were, and the expert layer
    brings no loop and no branch of its own. The grouped matmuls are the
    repo's kernel in all five, the walk's small ops beside it in the
    same loop and under no branch."""
    from ray_tpu.ops import moe

    slots, k, rows, loops = _EXPERT_BLOCKS[family]
    text = step_program(family, "decode").as_text()
    cfg = request.getfixturevalue(family + "_serving")[0]
    full = moe._pad_rows(slots * k + cfg.n_experts)
    assert moe.block_rows(slots * k, cfg.n_experts,
                          getattr(cfg, "n_experts_routed", None)) == rows
    assert (rows == full) == (family in ("laguna", "zaya"))
    calls = _GROUPED.findall(text)
    assert len(calls) == _grouped_matmuls(text) >= 3
    for result_rows, operands in calls:
        assert int(result_rows) == rows and f"bf16[{rows}," in operands
        stack = re.search(r"bf16\[(\d+),\d+,\d+\]", operands)
        assert stack and int(stack.group(1)) > cfg.n_experts, operands
    around = set(_MOE_OPS.findall(text))
    assert around and max(p.count("while/body") for p in around) == loops
    assert not any("cond" in p or "branch" in p for p in around), around
    wide = re.findall(rf"f32\[(?:{full}|{slots * k}|{slots},{k}),"
                      rf"{cfg.d_model}\]", text)
    assert bool(wide) == (rows == full), wide[:3]
