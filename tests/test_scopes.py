"""The device programs name their parts (ray_tpu/ops/scopes.py).

Every family's decode and chunk program at its `tiny` config, and the
train step, are lowered with debug info and walked op by op: each operation
that does a layer's work (a matmul, a grouped matmul, a kernel call, a
write into the pool, a reduction over the vocabulary) lies in exactly
one scope of the vocabulary, and no scope of the vocabulary opens inside
another. A scope is location metadata only, which the text shows:
without debug info the module mentions none of them.
"""

import functools
import re

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import Mesh

from ray_tpu.models import (gpt, jamba, kimi_k2, laguna, nemotron_h, paged_kv,
                            qwen3_next, serving, zaya)
from ray_tpu.ops import scopes
from ray_tpu.train import spmd

PAGE, N_PAGES, N_SLOTS, CHUNK, ROWS, WIDTH = 16, 24, 4, 16, 2, 4
TINY = {"gpt": gpt.GPTConfig.tiny_untied, "zaya": zaya.ZayaConfig.tiny,
        "laguna": laguna.LagunaConfig.tiny,
        "qwen3_next": qwen3_next.Qwen3NextConfig.tiny,
        "jamba": jamba.JambaConfig.tiny,
        "kimi_k2": kimi_k2.KimiK2Config.tiny,
        "nemotron_h": nemotron_h.NemotronHConfig.tiny}
MODULES = {"gpt": paged_kv, "zaya": zaya, "laguna": laguna,
           "qwen3_next": qwen3_next, "jamba": jamba, "kimi_k2": kimi_k2,
           "nemotron_h": nemotron_h}


def _shapes(fn, *args, **kw):
    return jax.eval_shape(functools.partial(fn, *args, **kw))


def lower_serving(family: str, program: str, attn_impl: str = "kernel"):
    """The family's decode-window step or chunk program, lowered at its
    tiny config from shapes alone."""
    cfg = TINY[family]()
    fam = serving.family_of(cfg)
    params = _shapes(fam.model.init_params, cfg, jax.random.PRNGKey(0))
    if fam.lay_out is not None:         # the tree the engine serves
        params = jax.eval_shape(functools.partial(fam.lay_out, cfg), params)
    ring = {"dispatch_tokens": ROWS * CHUNK} if fam.slot_ring else {}
    pool = _shapes(fam.init_pool, cfg, N_PAGES, PAGE, N_SLOTS, None, **ring)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    mod = MODULES[family]
    if program == "decode":
        return mod._decode_sample_paged.lower(
            cfg, params, i32(N_SLOTS), pool, i32(N_SLOTS),
            i32(N_SLOTS, WIDTH),
            jax.ShapeDtypeStruct((N_SLOTS,), jnp.float32),
            _shapes(jax.random.PRNGKey, 0), attn_impl=attn_impl)
    slots = {"slots": i32(ROWS)} if fam.slot_state or fam.slot_ring else {}
    return mod.prefill_chunk_paged.lower(
        cfg, params, i32(ROWS, CHUNK), pool, i32(ROWS, WIDTH), i32(ROWS),
        i32(ROWS), attn_impl=attn_impl, **slots)


def lower_train():
    """`make_train_step` over the gpt loss as the training cell runs it
    (remat, adafactor), at the tiny config on one device."""
    cfg = gpt.GPTConfig.tiny_untied(remat=True)
    mesh = Mesh(jax.devices()[:1], ("dp",))
    opt = optax.adafactor(1e-4)
    params = _shapes(gpt.init_params, cfg, jax.random.PRNGKey(0))
    p_shard = spmd.param_shardings(gpt.logical_axes(cfg), mesh)
    o_shard = spmd.opt_state_shardings(opt, params, p_shard)
    step = spmd.make_train_step(
        lambda p, t, y: gpt.loss_fn(p, t, y, cfg, mesh), opt, mesh, p_shard,
        o_shard, batch_spec=jax.sharding.PartitionSpec("dp"))
    batch = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    return step.lower(params, _shapes(opt.init, params), (batch, batch))


PROGRAMS = [(f, p) for f in TINY for p in ("decode", "chunk")] + [
    ("gpt", "train")]


@functools.cache
def _lower(family, program):
    """Lowered once for both tests below."""
    return lower_train() if program == "train" else lower_serving(
        family, program)


# --- reading the module

# What does a layer's work: matmuls, kernel calls, writes into a carried
# buffer, and reductions (the argmax over the vocabulary is one).
_WORK = {"stablehlo.dot_general", "chlo.ragged_dot", "stablehlo.custom_call",
         "stablehlo.scatter", "stablehlo.dynamic_update_slice",
         "stablehlo.reduce"}
_NAMED = re.compile(r'^loc\("([^"]*)"\(')
# Not the program's: a `return` repeats its function's location, and a
# scan slices its stacked operands and stacks its results (the saved
# activations, the per-layer gradients) with operations of its own,
# directly under `while/body`, where no scope of ours can reach.
_LOOP_OWN = re.compile(r"(^|/)while/body/dynamic_(update_)?slice$")


def _walk(op):
    for region in op.regions:
        for block in region.blocks:
            for inner in block.operations:
                yield inner
                yield from _walk(inner.operation)


def _vocabulary_scopes(path: str) -> tuple:
    found = []
    for part in path.split("/"):
        while True:
            m = re.fullmatch(r"[\w.]+\((.*)\)", part)
            if not m:
                break
            part = m.group(1)
        if part in scopes.ALL:
            found.append(part)
    return tuple(found)


def _scoped_ops(lowered):
    """[(op name, the vocabulary scopes around it)] for every operation
    of the module, once for each way its function is reached. JAX writes
    an operation's name stack relative to its function (a scan's body, a
    jitted helper like `_var`, shared between its callers), so the
    scopes of a call site are carried down to the callee's operations:
    what the compiler does when it inlines the call."""
    module = lowered.compiler_ir(dialect="stablehlo")
    funcs = {}
    for func in module.body.operations:
        ops = []
        for op in _walk(func.operation):
            m = _NAMED.match(str(op.location))
            callee = (str(op.attributes["callee"]).lstrip("@")
                      if op.operation.name == "func.call" else None)
            name, path = op.operation.name, m.group(1) if m else ""
            if name.endswith(".return") or _LOOP_OWN.search(path):
                continue
            ops.append((name, _vocabulary_scopes(path), callee))
        funcs[str(func.attributes["sym_name"]).strip('"')] = ops
    reached, todo = {"main": {()}}, [("main", ())]
    while todo:
        name, ctx = todo.pop()
        for _op, found, callee in funcs[name]:
            if callee and ctx + found not in reached.setdefault(callee, set()):
                reached[callee].add(ctx + found)
                todo.append((callee, ctx + found))
    return [(op, ctx + found) for name, ops in funcs.items()
            for ctx in reached.get(name, ()) for op, found, _callee in ops]


@pytest.mark.parametrize("family,program", PROGRAMS)
def test_every_part_of_a_program_lies_in_one_scope(family, program):
    ops = _scoped_ops(_lower(family, program))
    assert sum(op in _WORK for op, _found in ops) >= 8
    for op, found in ops:
        assert len(found) <= 1, f"{op}: a scope inside another, {found}"
        if op in _WORK:
            assert found, f"a {op} lies in no scope of the vocabulary"
    seen = {name for _op, found in ops for name in found}
    want = {scopes.EMBED, scopes.ATTN_IN, scopes.ATTN_KERNEL, scopes.ATTN_OUT,
            scopes.HEAD}
    if program == "train":
        want |= {scopes.MLP, scopes.LOSS, scopes.OPTIMIZER}
    else:
        want |= {scopes.ATTN_KV_WRITE}
        want |= {scopes.SAMPLE} if program == "decode" else set()
        want |= {scopes.MLP} if family != "zaya" else set()
        if family not in ("gpt", "jamba"):      # the two without experts
            want |= {scopes.MOE_ROUTE, scopes.MOE_EXPERTS}
            want |= {scopes.COUNTERS} if program == "decode" else set()
        want |= {scopes.SLOT_STATE} if family == "zaya" else set()
        if family == "qwen3_next":
            # The chain of a dispatch's rows is the chunk program's.
            want |= {scopes.GDN_IN, scopes.GDN_SCAN, scopes.GDN_OUT}
            want |= {scopes.SLOT_STATE} if program == "chunk" else set()
        if family == "kimi_k2":
            want |= {scopes.ATTN_ABSORB}
        if family in ("jamba", "nemotron_h"):
            want |= {scopes.SSM_IN, scopes.SSM_SCAN, scopes.SSM_OUT}
            want |= {scopes.SLOT_STATE} if program == "chunk" else set()
        if family == "nemotron_h":
            want |= {scopes.MOE_LATENT}
    assert want <= seen, f"scopes never opened: {sorted(want - seen)}"


@pytest.mark.parametrize("family,program", PROGRAMS)
def test_a_scope_adds_nothing_to_the_module(family, program):
    """Without debug info the module's text names no scope: what the
    compile cache keys on and what the compiler turns into operations is
    what it was before the programs named their parts."""
    text = _lower(family, program).as_text()
    assert "loc(" not in text
    for name in scopes.ALL:
        assert f'"{name}' not in text and f"/{name}/" not in text


def test_the_vocabulary_is_small_and_flat():
    # 21 since PR 56 (`attn.absorb`: latent attention's two matmuls
    # around the call, which are neither its inputs nor its output); 22
    # since PR 62 (`moe.latent`: the projection into the experts' latent
    # and out of it, which is neither the router nor a grouped matmul).
    assert len(scopes.ALL) == len(set(scopes.ALL)) <= 22
    for name in scopes.ALL:
        assert re.fullmatch(r"[a-z_]+(\.[a-z_]+)?", name), name
