"""Ragged paged-attention decode kernel (ops/paged_attention.py).

Exact-match of the Pallas kernel path against the gather reference across
page sizes, ragged slot lengths, and null-page tails — at the op level, at
the jitted decode-step level (models/paged_kv.py), and end-to-end through
the continuous-batching engine (greedy token streams the plain forward's
own, tests/plain_reference.py). On CPU the kernel runs under interpret=True: the fallback
is ASSERTED, never silently skipped — a broken pallas install fails here.
"""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import plain_reference
from ray_tpu.models import gpt
from ray_tpu.ops.paged_attention import (
    _interpret_default,
    decode_block_pages,
    paged_attention,
    paged_prefill_attention,
    prefill_block_pages,
    reference_paged_attention,
    reference_paged_prefill_attention,
)

# The module itself (`ray_tpu.ops` re-exports a function under its name).
pa = importlib.import_module("ray_tpu.ops.paged_attention")

CFG = gpt.GPTConfig.tiny(attn_impl="xla", dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return gpt.init_params(CFG, jax.random.key(42))


@pytest.fixture(scope="module")
def lively_params(params):
    """Weights whose greedy continuation does not settle on one token."""
    return plain_reference.lively(params)


def test_interpret_fallback_is_asserted_off_tpu():
    """CPU-only CI must exercise the kernel code path via interpret mode —
    if pallas failed to import, the module import above would already have
    failed loudly (no importorskip anywhere in this file)."""
    if jax.default_backend() != "tpu":
        assert _interpret_default() is True
    else:
        assert _interpret_default() is False


N_LAYERS = 3      # every op-level pool below holds several layers


def _pool_and_tables(rng, *, B, H, K, ps, n_pg, dtype):
    """A whole pool [L, P, ps, H*K] (the layout the programs store) with
    every slot's pages allocated plus ragged lengths: length 1 (fresh
    slot), mid-page, exact page boundary, full table, and an all-null
    table (idle slot)."""
    n_pages = B * n_pg + 1
    k_pool = jnp.asarray(
        rng.normal(size=(N_LAYERS, n_pages, ps, H * K)), dtype)
    v_pool = jnp.asarray(
        rng.normal(size=(N_LAYERS, n_pages, ps, H * K)), dtype)
    tables = np.zeros((B, n_pg), np.int32)
    lengths = np.zeros(B, np.int32)
    specs = [1, ps // 2 + 1, ps, n_pg * ps, 1]
    next_page = 1
    for b in range(B):
        length = specs[b % len(specs)]
        if b == B - 1:
            # Idle slot: table stays all-null, attends only position 0 of
            # the null page.
            lengths[b] = 1
            continue
        need = (length + ps - 1) // ps
        for j in range(need):
            tables[b, j] = next_page
            next_page += 1
        lengths[b] = length
    return k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lengths)


# (query heads H, KV heads G, head size K): multi-head, and grouped-query
# at the served head size of 128 (G of H: each KV head serves H/G heads).
HEADS = [(4, 4, 16), (8, 2, 128), (4, 1, 128)]


@pytest.mark.parametrize("n_pg", [3, 4, 6])
@pytest.mark.parametrize("heads", HEADS)
@pytest.mark.parametrize("ps", [16, 64])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_matches_gather_reference(ps, dtype, heads, n_pg):
    """Kernel against oracle at EVERY layer of one pool: the layer index
    is part of the block index, and layers hold different values. Under
    grouped-query attention the pool holds G heads ([L, P, ps, G*K]);
    table widths 3, 4 and 6."""
    rng = np.random.default_rng(0)
    (H, G, K), B = heads, 5
    q = jnp.asarray(rng.normal(size=(B, H, K)), dtype)
    k_pool, v_pool, tables, lengths = _pool_and_tables(
        rng, B=B, H=G, K=K, ps=ps, n_pg=n_pg, dtype=dtype)
    assert k_pool.shape[-1] == G * K
    atol = 2e-6 if dtype == jnp.float32 else 3e-2
    outs = []
    for layer in range(N_LAYERS):
        o = paged_attention(q, k_pool, v_pool, jnp.int32(layer), tables,
                            lengths)
        ref = reference_paged_attention(q, k_pool, v_pool, layer, tables,
                                        lengths)
        assert o.dtype == q.dtype
        np.testing.assert_allclose(
            np.asarray(o, np.float32), np.asarray(ref, np.float32),
            atol=atol)
        outs.append(np.asarray(o, np.float32))
    assert not np.allclose(outs[0], outs[1], atol=1e-2)


@pytest.mark.parametrize("head_dim", [64, 256])
def test_kernel_one_path_for_head_dim_64_and_256(head_dim):
    """OPT's head_dim 64 (under the 128 lanes) and GPT-J's 256 go down
    the same path: the same pool layout [L, P, ps, H*K], the same block
    (one page of H*K lanes) and the same kernel body, with nothing in the
    lowered program keyed on the head size but the shapes."""
    rng = np.random.default_rng(5)
    B, H, ps, n_pg = 3, 2, 16, 2
    q = jnp.asarray(rng.normal(size=(B, H, head_dim)), jnp.float32)
    k_pool, v_pool, tables, lengths = _pool_and_tables(
        rng, B=B, H=H, K=head_dim, ps=ps, n_pg=n_pg, dtype=jnp.float32)
    assert k_pool.shape == (N_LAYERS, B * n_pg + 1, ps, H * head_dim)
    layer = jnp.int32(N_LAYERS - 1)
    o = paged_attention(q, k_pool, v_pool, layer, tables, lengths)
    ref = reference_paged_attention(q, k_pool, v_pool, layer, tables,
                                    lengths)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=5e-6)
    text = jax.jit(paged_attention).lower(
        q, k_pool, v_pool, layer, tables, lengths).as_text()
    assert f"x{ps}x{H * head_dim}xf32" in text     # the page block


def _quantized(rng, shape):
    """int8 planes [L, P, ps, H*K] and per-page scale planes [L, P]."""
    planes = [jnp.asarray(rng.integers(-127, 128, shape), jnp.int8)
              for _ in range(2)]
    scales = [jnp.asarray(rng.uniform(0.005, 0.02, shape[:2]), jnp.float32)
              for _ in range(2)]
    return planes, scales


def test_int8_kernel_matches_gather_reference_at_every_layer():
    """int8 pages dequantise in the kernel with THEIR layer's scale row
    (ragged lengths: fresh slot, mid-page end, null tail, idle slot)."""
    rng = np.random.default_rng(2)
    B, H, K, ps, n_pg = 5, 4, 16, 16, 3
    q = jnp.asarray(rng.normal(size=(B, H, K)), jnp.float32)
    _k, _v, tables, lengths = _pool_and_tables(
        rng, B=B, H=H, K=K, ps=ps, n_pg=n_pg, dtype=jnp.float32)
    (k8, v8), (ks, vs) = _quantized(rng, _k.shape)
    for layer in range(N_LAYERS):
        o = paged_attention(q, k8, v8, jnp.int32(layer), tables, lengths,
                            k_scale=ks, v_scale=vs)
        ref = reference_paged_attention(q, k8, v8, layer, tables, lengths,
                                        k_scale=ks, v_scale=vs)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   atol=5e-6)


def _small_pages_rows(ps, n_pg, C):
    """The rows this test always had, on pages of 8 (a whole table is one
    kv block): a chunk ending mid-page, one starting at 0, one whose
    table has a null tail, and an inert row (no valid token)."""
    assert (ps, n_pg) == (8, 4)
    return ([[1, 2, 3, 4], [5, 0, 0, 0], [6, 7, 0, 0], [0, 0, 0, 0]],
            [21, 0, 7, 0], [C, C, 4, 0])


def _block_boundary_rows(ps, n_pg, C):
    """Rows that put the end of the valid keys everywhere a kv block's
    boundary can fall, for a table of `n_pg` columns attended n pages a
    step: the chunk ends in the last block's first page (the null tail
    then starts INSIDE a live block), in its middle page, in the table's
    last page (full table); a chunk that straddles the first block's
    end (a page's end when the table is one block); a chunk at offset 0;
    an inert row."""
    n = prefill_block_pages(n_pg, ps, 128, 4, C, 128, 4, 2)
    last_block = (n_pg - 1) // n * n
    straddle = (n if n_pg > n else 1) * ps
    ends = [last_block * ps + C + 1,                        # first page
            min(last_block + n // 2, n_pg - 1) * ps + ps // 2,  # middle
            n_pg * ps,                                      # last page
            straddle + C // 2,
            C]
    tables, page = [], 1
    for end in ends:
        live = -(-end // ps)
        tables.append(list(range(page, page + live)) + [0] * (n_pg - live))
        page += live
    return (tables + [[0] * n_pg], [e - C for e in ends] + [0],
            [C] * len(ends) + [0])


# (page size, table width, rows): pages of 64 put 4 pages in a kv block
# (256 keys), so the table is narrower than a block (2), exactly one (4),
# two (8), and one and a half, padded with null columns (6); pages of 8
# make the whole table one block.
_PREFILL_GEOMETRY = {
    "ps8": (8, 4, _small_pages_rows),
    "below": (64, 2, _block_boundary_rows),
    "equal": (64, 4, _block_boundary_rows),
    "multiple": (64, 8, _block_boundary_rows),
    "ragged": (64, 6, _block_boundary_rows),
}


@pytest.mark.parametrize("kv,head_dim,heads,geometry", [
    *[(kv, head_dim, heads, "ps8")
      for head_dim in (16, 64, 128, 256)
      for kv in ("float32", "bfloat16", "int8")
      for heads in ((2, 2), (8, 2))],
    *[(kv, 64, heads, geometry)
      for geometry in ("below", "equal", "multiple", "ragged")
      for kv in ("float32", "bfloat16", "int8")
      for heads in ((2, 2), (8, 2))],
    # The gated attention's shape: 16 query heads over 2 KV heads of 256
    # (two lane tiles a head).
    *[(kv, 256, (16, 2), geometry)
      for geometry in ("ps8", "equal", "ragged")
      for kv in ("float32", "bfloat16")],
])
def test_prefill_kernel_matches_gather_reference(kv, head_dim, heads,
                                                 geometry):
    """The chunk kernel against its oracle on one shared pool, at a layer
    other than 0, over `_PREFILL_GEOMETRY`'s rows. `heads` = (H, G): G KV
    heads in the pool under H query heads. An int8 pool has a scale of
    its own for every page, so for each page of one block."""
    rng = np.random.default_rng(4)
    ps, n_pg, rows = _PREFILL_GEOMETRY[geometry]
    (H, G), C = heads, 6
    tables, offsets, n_valid = (jnp.asarray(x, jnp.int32)
                                for x in rows(ps, n_pg, C))
    B = tables.shape[0]
    dtype = jnp.bfloat16 if kv == "bfloat16" else jnp.float32
    q = jnp.asarray(rng.normal(size=(B, C, H, head_dim)), dtype)
    k_pool, v_pool, _t, _n = _pool_and_tables(
        rng, B=B, H=G, K=head_dim, ps=ps, n_pg=n_pg, dtype=dtype)
    assert int(tables.max()) < k_pool.shape[1]
    scales = {}
    if kv == "int8":
        (k_pool, v_pool), (ks, vs) = _quantized(rng, k_pool.shape)
        scales = {"k_scale": ks, "v_scale": vs}
    layer = jnp.int32(N_LAYERS - 1)
    o = paged_prefill_attention(q, k_pool, v_pool, layer, tables, offsets,
                                offsets + n_valid, **scales)
    ref = reference_paged_prefill_attention(
        q, k_pool, v_pool, layer, tables, offsets, offsets + n_valid,
        **scales)
    assert o.shape == q.shape and o.dtype == q.dtype
    valid = np.arange(C)[None, :] < np.asarray(n_valid)[:, None]
    np.testing.assert_allclose(
        np.asarray(o, np.float32)[valid], np.asarray(ref, np.float32)[valid],
        atol=3e-2 if kv == "bfloat16" else 1e-5)


# The two cells' prefill shapes (benchmarks/configs): page 64, chunk 128;
# opt-1.3b 32 heads of 64 over a pool 2,048 lanes wide, zaya1-8b 8 query
# heads of 128 over 2 KV heads (256 lanes); bf16 pools.
_CELL_SHAPES = {
    "opt-1.3b": dict(page_size=64, kv_lanes=2048, kv_itemsize=2, chunk=128,
                     q_lanes=2048, q_itemsize=2, n_heads=32),
    "zaya1-8b": dict(page_size=64, kv_lanes=256, kv_itemsize=2, chunk=128,
                     q_lanes=1024, q_itemsize=2, n_heads=8),
    "qwen3-next-80b-a3b": dict(page_size=64, kv_lanes=512, kv_itemsize=2,
                               chunk=128, q_lanes=4096, q_itemsize=2,
                               n_heads=16)}


@pytest.mark.parametrize("model", sorted(_CELL_SHAPES))
@pytest.mark.parametrize("n_pg", [1, 2, 3, 4, 8, 16, 31, 32])
def test_prefill_block_rule(model, n_pg):
    """The kv block of the prefill kernel: a power of two, never wider
    than the table, at most `_PREFILL_BLOCK_KEYS` keys, within the VMEM
    budget it states (blocks double-buffered, beside q, out and the
    state), and a function of the shapes alone."""
    shape = _CELL_SHAPES[model]
    n = prefill_block_pages(n_pg, **shape)
    assert n >= 1 and n & (n - 1) == 0 and n <= n_pg
    assert n * shape["page_size"] <= pa._PREFILL_BLOCK_KEYS
    C, HK = shape["chunk"], shape["q_lanes"]
    blocks = 2 * 2 * n * shape["page_size"] * shape["kv_lanes"] * 2
    rest = 2 * 2 * C * HK * 2 + C * HK * 4 + 2 * shape["n_heads"] * C * 512
    assert blocks + rest <= pa._PREFILL_VMEM_BUDGET
    assert n == prefill_block_pages(n_pg, **dict(shape))    # no hidden input
    # Wide enough to matter wherever the table allows it: a page a step
    # was the kernel this rule replaced.
    assert n == min(4, 1 << (n_pg.bit_length() - 1))
    # A pool too wide for the budget gets a smaller block, not a refusal.
    assert prefill_block_pages(32, 64, 16384, 2, 128, 16384, 2, 256) == 1



def _decode_boundary_lengths(ps, n_pg, n):
    """Where a decode slot's keys can end against kv blocks of `n` pages
    in a table of `n_pg` columns: on, one under and one over every block
    boundary (one over leaves a live block with n - 1 dead columns), the
    full table, a slot of one token, and an idle slot (all-null table,
    length 1)."""
    block = n * ps
    ends = sorted({e for edge in range(block, n_pg * ps, block)
                   for e in (edge - 1, edge, edge + 1)}
                  | {1, n_pg * ps})
    return ends + [1], len(ends)        # the last row is the idle slot


# (page size, table width, block cap in keys or None for the rule's own):
# a page a step (the parent's grid), a table narrower than the cap, one
# and two whole blocks, and a table the block does not divide (the ring's
# 13 columns), padded with null columns.
_DECODE_GEOMETRY = {
    "page": (16, 4, 16),
    "below": (16, 2, 64),
    "equal": (16, 4, 64),
    "multiple": (16, 8, 64),
    "ragged": (16, 13, 64),
    "rule": (16, 13, None),
}


@pytest.mark.parametrize("kv,heads,geometry", [
    *[(kv, heads, geometry)
      for geometry in _DECODE_GEOMETRY
      for kv in ("float32", "bfloat16", "int8")
      for heads in ((4, 4, 16), (8, 2, 128))],
    *[("float32", heads, geometry)
      for geometry in ("multiple", "ragged")
      for heads in ((48, 8, 128), (72, 8, 128))],
    *[(kv, (16, 2, 256), geometry)
      for geometry in ("page", "multiple", "ragged", "rule")
      for kv in ("float32", "bfloat16")],
])
def test_decode_kernel_at_block_boundaries(kv, heads, geometry,
                                           monkeypatch):
    """The decode kernel against its oracle, at a layer other than 0,
    over `_decode_boundary_lengths`: a block's dead columns and the pad
    columns are position-masked, a dead block is skipped, and an int8
    pool's pages keep a scale each inside one block. `heads` = (H, G,
    K): the served head counts 8 / 2, 48 / 8, 72 / 8 and 16 / 2 at head
    size 256, and G = H."""
    ps, n_pg, cap = _DECODE_GEOMETRY[geometry]
    if cap is not None:
        monkeypatch.setattr(pa, "_DECODE_BLOCK_KEYS", cap)
    (H, G, K) = heads
    dtype = jnp.bfloat16 if kv == "bfloat16" else jnp.float32
    n = decode_block_pages(n_pg, ps, G * K, 1 if kv == "int8" else
                           jnp.dtype(dtype).itemsize, H)
    assert n == (min(cap // ps, 1 << (n_pg.bit_length() - 1)) if cap
                 else 8)
    lengths, n_live = _decode_boundary_lengths(ps, n_pg, n)
    B = len(lengths)
    rng = np.random.default_rng(11)
    tables, page = np.zeros((B, n_pg), np.int32), 1
    for b, end in enumerate(lengths[:n_live]):
        live = -(-end // ps)
        tables[b, :live] = np.arange(page, page + live)
        page += live
    shape = (N_LAYERS, page, ps, G * K)
    scales = {}
    if kv == "int8":
        (k_pool, v_pool), (ks, vs) = _quantized(rng, shape)
        scales = {"k_scale": ks, "v_scale": vs}
    else:
        k_pool, v_pool = (jnp.asarray(rng.normal(size=shape), dtype)
                          for _ in range(2))
    q = jnp.asarray(rng.normal(size=(B, H, K)), dtype)
    args = (jnp.int32(N_LAYERS - 1), jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32))
    o = paged_attention(q, k_pool, v_pool, *args, **scales)
    ref = reference_paged_attention(q, k_pool, v_pool, *args, **scales)
    assert o.shape == q.shape and o.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(ref, np.float32),
        atol=3e-2 if kv == "bfloat16" else 1e-5)


# The cells' decode shapes (benchmarks/configs): page 64, bf16 pools;
# laguna-s-2.1 has two kinds (48 heads over a table of up to 64 columns,
# 72 over a ring of 13).
_DECODE_SHAPES = {
    "opt-1.3b": dict(page_size=64, kv_lanes=2048, kv_itemsize=2, n_heads=32),
    "zaya1-8b": dict(page_size=64, kv_lanes=256, kv_itemsize=2, n_heads=8),
    "laguna-s-2.1": dict(page_size=64, kv_lanes=1024, kv_itemsize=2,
                         n_heads=48),
    "laguna-s-2.1.window": dict(page_size=64, kv_lanes=1024, kv_itemsize=2,
                                n_heads=72),
    "qwen3-next-80b-a3b": dict(page_size=64, kv_lanes=512, kv_itemsize=2,
                               n_heads=16),
    "olmo-hybrid-7b": dict(page_size=64, kv_lanes=3840, kv_itemsize=2,
                           n_heads=30)}
_DECODE_BLOCK_AT_FULL_WIDTH = {"opt-1.3b": 2, "zaya1-8b": 16,
                               "laguna-s-2.1": 4, "laguna-s-2.1.window": 4,
                               "qwen3-next-80b-a3b": 8, "olmo-hybrid-7b": 1}


@pytest.mark.parametrize("model", sorted(_DECODE_SHAPES))
@pytest.mark.parametrize("n_pg", [1, 2, 3, 4, 8, 13, 16, 32, 64])
def test_decode_block_rule(model, n_pg):
    """The kv block of the decode kernel: a power of two, never wider
    than the table, at most `_DECODE_BLOCK_BYTES` of K and
    `_DECODE_BLOCK_KEYS` keys, within the VMEM budget it states, a
    function of the shapes alone, and at the cells' shapes what the
    chip read best (PERF.md, PR 37)."""
    shape = _DECODE_SHAPES[model]
    n = decode_block_pages(n_pg, **shape)
    assert n >= 1 and n & (n - 1) == 0 and n <= n_pg
    page = shape["page_size"] * shape["kv_lanes"] * shape["kv_itemsize"]
    assert n == 1 or n * page <= pa._DECODE_BLOCK_BYTES
    assert n * shape["page_size"] <= pa._DECODE_BLOCK_KEYS
    H, lanes = shape["n_heads"], shape["kv_lanes"]
    blocks = 3 * 2 * n * page               # K, V; three buffers (PR 41)
    rest = (2 * H * n * shape["page_size"] * 4      # scores, probabilities
            + H * lanes * 12 + 2 * H * 128 * 4)     # block-diagonal q, acc
    assert blocks + rest <= pa._DECODE_VMEM_BUDGET
    assert n == decode_block_pages(n_pg, **dict(shape))     # no hidden input
    assert n == min(_DECODE_BLOCK_AT_FULL_WIDTH[model],
                    1 << (n_pg.bit_length() - 1))
    # An int8 pool's block holds the same bytes, so twice the pages,
    # while its f32 copies fit; a pool too wide for the budget gets a
    # page a step, not a refusal.
    assert decode_block_pages(32, 64, 2048, 1, 32) == 4
    assert decode_block_pages(32, 64, 65536, 2, 256) == 1


def _scattered_ring(rng, *, col_page, G, K, ps, dtype=jnp.float32):
    """A ring pool whose column c of slot b holds logical page
    ``col_page[b][c]`` (-1: none) of that slot's dense timeline, at page
    id 1 + b * R + c. -> (k_pool, v_pool, tables, col_page)."""
    col_page = np.asarray(col_page, np.int32)
    B, R = col_page.shape
    T = (col_page.max() + 1) * ps
    dense = [rng.normal(size=(B, T, G * K)).astype(np.float32)
             for _ in range(2)]
    pools = [rng.normal(size=(N_LAYERS, 1 + B * R, ps, G * K)).astype(
        np.float32) for _ in range(2)]
    tables = 1 + np.arange(B * R, dtype=np.int32).reshape(B, R)
    for b, c in zip(*np.nonzero(col_page >= 0)):
        for pool, line in zip(pools, dense):
            pool[:, tables[b, c]] = line[b, col_page[b, c] * ps:
                                         (col_page[b, c] + 1) * ps]
    return (jnp.asarray(pools[0], dtype), jnp.asarray(pools[1], dtype),
            jnp.asarray(tables), jnp.asarray(col_page))


def _live_pages(tables, lengths, ps, *, col_page=None, window=None):
    """Page ids some slot attends: under its length and, in a ring,
    inside the window of its query."""
    tables, lengths = np.asarray(tables), np.asarray(lengths)[:, None]
    first = (np.arange(tables.shape[1])[None] * ps if col_page is None
             else np.where(np.asarray(col_page) < 0, 2**30,
                           np.asarray(col_page) * ps))
    live = first < lengths
    if window is not None:
        live &= first + ps > lengths - window
    return np.unique(tables[live])


@pytest.mark.parametrize("kind", ["full", "ring", "int8"])
def test_a_page_no_slot_holds_live_is_never_read(kind, monkeypatch):
    """The decode kernel fetches live pages only: with EVERY other page
    of the pool NaN (the null page, the pages under a slot's dead
    columns, a ring's columns outside the window; an int8 pool's dead
    pages carry a NaN scale) the output is finite and the oracle's over
    the clean pool. Dead table entries point at poisoned pages, not at
    the null page alone."""
    H, G, K, ps = 8, 2, 128, 16
    monkeypatch.setattr(pa, "_DECODE_BLOCK_KEYS", 64)
    rng = np.random.default_rng(21)
    scales, kw = {}, {}
    if kind == "ring":
        col_page = [[6, 1, 2, 3, 4, 5], [0, -1, -1, -1, -1, -1],
                    [12, 13, 8, 9, 10, 11], [6, 7, 8, 3, 4, 5]]
        lengths = [100, 3, 214, 140]
        k_pool, v_pool, tables, col_page = _scattered_ring(
            rng, col_page=col_page, G=G, K=K, ps=ps)
        kw = dict(window=40, col_page=col_page)
    else:
        n_pg, lengths = 8, [1, 17, 64, 65, 100, 128, 0]
        B = len(lengths)
        tables = rng.permutation(np.arange(1, 1 + B * n_pg)).astype(
            np.int32).reshape(B, n_pg)      # dead columns hold real ids
        shape = (N_LAYERS, 1 + B * n_pg, ps, G * K)
        if kind == "int8":
            (k_pool, v_pool), (ks, vs) = _quantized(rng, shape)
            scales = dict(k_scale=ks, v_scale=vs)
        else:
            k_pool, v_pool = (jnp.asarray(rng.normal(size=shape), jnp.float32)
                              for _ in range(2))
    n = jnp.asarray(lengths, jnp.int32)
    q = jnp.asarray(rng.normal(size=(len(lengths), H, K)), jnp.float32)
    layer = jnp.int32(1)
    want = reference_paged_attention(q, k_pool, v_pool, layer, tables, n,
                                     **scales, **kw)
    dead = np.setdiff1d(np.arange(k_pool.shape[1]),
                        _live_pages(tables, lengths, ps,
                                    col_page=kw.get("col_page"),
                                    window=kw.get("window")))
    assert len(dead) > len(lengths)
    if kind == "int8":
        scales = {name: s.at[:, dead].set(jnp.nan)
                  for name, s in scales.items()}
        k_pool, v_pool = (p.at[:, dead].set(127) for p in (k_pool, v_pool))
    else:
        k_pool, v_pool = (p.at[:, dead].set(jnp.nan)
                          for p in (k_pool, v_pool))
    got = np.asarray(paged_attention(q, k_pool, v_pool, layer, tables, n,
                                     **scales, **kw))
    assert np.isfinite(got).all()
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(got[live], np.asarray(want)[live], atol=1e-5)
    assert not got[~live].any()


@pytest.mark.parametrize("idle", ["first", "last", "all"])
def test_idle_slots_are_never_visited(idle):
    """Length 0: no DMA, no block, no index map; the slot's output is 0
    and its neighbours' are the oracle's, wherever the idle slots sit
    (the walk starts on one, ends on one, or finds no block at all)."""
    H, G, K, ps, n_pg = 4, 4, 16, 16, 8
    lengths = {"first": [0, 0, 70, 128, 1], "last": [33, 128, 5, 0, 0],
               "all": [0, 0, 0, 0, 0]}[idle]
    rng = np.random.default_rng(22)
    k_pool, v_pool, tables, _ = _pool_and_tables(
        rng, B=5, H=G, K=K, ps=ps, n_pg=n_pg, dtype=jnp.float32)
    tables = jnp.asarray(1 + np.arange(5 * n_pg).reshape(5, n_pg), jnp.int32)
    q = jnp.asarray(rng.normal(size=(5, H, K)), jnp.float32)
    n = jnp.asarray(lengths, jnp.int32)
    got = np.asarray(paged_attention(q, k_pool, v_pool, jnp.int32(2), tables,
                                     n))
    want = np.asarray(reference_paged_attention(q, k_pool, v_pool, 2, tables,
                                                n))
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(got[live], want[live], atol=1e-5)
    assert not got[~live].any()


def test_a_batch_too_large_for_one_step_walks_in_slot_groups(monkeypatch):
    """The queries and outputs of the whole batch sit in VMEM beside the
    block's buffers; where they would not fit (`_DECODE_GROUP_BUDGET`,
    forced small here) the grid gets a step a group of slots, each
    fetching its own first blocks: groups of 2 over 6 slots, idle slots
    and a one-token slot among them, read what one step over all reads."""
    H, G, K, ps, n_pg = 8, 2, 128, 16, 8
    lengths = [128, 0, 0, 77, 1, 16]
    rng = np.random.default_rng(25)
    k_pool, v_pool, _, _ = _pool_and_tables(
        rng, B=6, H=G, K=K, ps=ps, n_pg=n_pg, dtype=jnp.float32)
    tables = jnp.asarray(1 + np.arange(6 * n_pg).reshape(6, n_pg), jnp.int32)
    q = jnp.asarray(rng.normal(size=(6, H, K)), jnp.float32)
    args = (jnp.int32(1), tables, jnp.asarray(lengths, jnp.int32))
    whole = paged_attention(q, k_pool, v_pool, *args)
    block = pa._decode_vmem_bytes(
        pa.decode_block_pages(n_pg, ps, G * K, 4, H), ps, G * K, 4, H)
    slot = H * K * 4
    assert pa._decode_slot_group(6, slot, block) == 6
    monkeypatch.setattr(pa, "_DECODE_GROUP_BUDGET", block + 4 * 2 * slot)
    assert pa._decode_slot_group(6, slot, block) == 2
    assert pa._decode_slot_group(7, slot, block) == 1       # no divisor
    grouped = jax.make_jaxpr(lambda *a: paged_attention(*a))(
        q, k_pool, v_pool, *args)
    (call,) = [e for e in grouped.eqns if e.primitive.name == "pallas_call"]
    assert call.params["grid_mapping"].grid == (3,)
    got = paged_attention(q, k_pool, v_pool, *args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(whole))
    want = reference_paged_attention(q, k_pool, v_pool, *args)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=1e-5)


@pytest.mark.parametrize("kv", ["float32", "int8"])
def test_a_short_slot_after_a_long_one_sees_no_stale_row(kv, monkeypatch):
    """A block's buffer keeps the rows of the block before it wherever
    its own slot has a dead column: after a slot that filled every row
    with large values come a slot of exactly one live block, one whose
    last live block has dead columns inside, and one of one token; their
    outputs are the oracle's (the stale rows are position-masked, and an
    int8 block's dequant to 0)."""
    H, G, K, ps, n_pg = 8, 2, 128, 16, 8
    monkeypatch.setattr(pa, "_DECODE_BLOCK_KEYS", 64)        # 4 pages a block
    lengths = [128, 64, 128, 81, 128, 1]
    B = len(lengths)
    rng = np.random.default_rng(23)
    tables = jnp.asarray(1 + np.arange(B * n_pg).reshape(B, n_pg), jnp.int32)
    shape = (N_LAYERS, 1 + B * n_pg, ps, G * K)
    long_pages = np.asarray(tables)[[0, 2, 4]].ravel()
    scales = {}
    if kv == "int8":
        (k_pool, v_pool), (ks, vs) = _quantized(rng, shape)
        scales = dict(k_scale=ks.at[:, long_pages].set(50.0),
                      v_scale=vs.at[:, long_pages].set(50.0))
    else:
        k_pool, v_pool = (jnp.asarray(rng.normal(size=shape), jnp.float32)
                          for _ in range(2))
        v_pool = v_pool.at[:, long_pages].multiply(1e4)
    q = jnp.asarray(rng.normal(size=(B, H, K)), jnp.float32)
    args = (jnp.int32(1), tables, jnp.asarray(lengths, jnp.int32))
    got = np.asarray(paged_attention(q, k_pool, v_pool, *args, **scales))
    want = np.asarray(reference_paged_attention(q, k_pool, v_pool, *args,
                                                **scales))
    short = [1, 3, 5]
    np.testing.assert_allclose(got[short], want[short], atol=1e-5)
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-2)   # the long ones


@pytest.mark.parametrize("ring", ["scattered", "one_page_back"])
def test_a_ring_that_is_not_its_slots_own_view_is_refused(ring):
    """The window decode call walks a slot's ring in closed form (logical
    page p in column p % R, the pages up to the slot's last one): a
    `col_page` that says anything else is refused where it can be read,
    and inside a trace, where it cannot, the call goes by the lengths
    alone and reads the true ring's answer. (The prefill kernel and the
    oracle still go by `col_page`.)"""
    H, G, K, ps, window = 6, 2, 128, 16, 40
    lengths = [100, 100, 159, 20]
    rng = np.random.default_rng(24)
    k_pool, v_pool, tables, col_page, _, _ = _ring(
        rng, lengths=lengths, G=G, K=K, ps=ps, R=6, dtype=jnp.float32)
    q = jnp.asarray(rng.normal(size=(4, H, K)), jnp.float32)
    args = (jnp.int32(2), tables, jnp.asarray(lengths, jnp.int32))
    want = reference_paged_attention(q, k_pool, v_pool, *args, window=window,
                                     col_page=col_page)
    np.testing.assert_allclose(
        np.asarray(paged_attention(q, k_pool, v_pool, *args, window=window,
                                   col_page=col_page)),
        np.asarray(want), atol=1e-5)
    other = {"scattered": jnp.asarray([[4, 0, 6, -1, 3, 5],
                                       [5, 6, -1, 0, 3, 4],
                                       [-1, 9, 1, 8, 7, -1],
                                       [0, -1, -1, -1, -1, 1]], jnp.int32),
             "one_page_back": jnp.maximum(col_page - 1, 0)}[ring]
    with pytest.raises(ValueError, match="ring's own view"):
        paged_attention(q, k_pool, v_pool, *args, window=window,
                        col_page=other)
    traced = jax.jit(lambda cp: paged_attention(
        q, k_pool, v_pool, *args, window=window, col_page=cp))(other)
    np.testing.assert_allclose(np.asarray(traced), np.asarray(want),
                               atol=1e-5)


def test_page_ops_on_the_flat_pool():
    """COW copy, donation gather and adoption scatter are generic over
    the layer and page axes of the flat pool [L, P+1, ps, H*K]: a page
    moves as ps rows of H*K lanes in every layer, scales beside it."""
    from ray_tpu.models.paged_kv import (copy_pages, gather_pages,
                                         init_paged_kv, scatter_pages)

    rng = np.random.default_rng(6)
    pool = init_paged_kv(CFG, 6, 4, "int8")
    lanes = CFG.n_heads * CFG.head_dim
    assert pool["k"].shape == (CFG.n_layers, 7, 4, lanes)
    assert pool["k_scale"].shape == (CFG.n_layers, 7)
    pool = {name: jnp.asarray(rng.integers(1, 100, a.shape), a.dtype)
            for name, a in pool.items()}
    before = jax.tree.map(np.asarray, pool)
    pool = copy_pages(pool, jnp.asarray([2, 0], jnp.int32),
                      jnp.asarray([5, 0], jnp.int32))
    for name, a in pool.items():
        assert np.array_equal(np.asarray(a[:, 5]), before[name][:, 2]), name
        assert np.array_equal(np.asarray(a[:, 1:5]), before[name][:, 1:5])
    ids = jnp.asarray([5, 3], jnp.int32)
    payload = gather_pages(pool, ids)
    assert payload["v"].shape == (CFG.n_layers, 2, 4, lanes)
    assert payload["v_scale"].shape == (CFG.n_layers, 2)
    fresh = scatter_pages(init_paged_kv(CFG, 6, 4, "int8"),
                          jnp.asarray([1, 6], jnp.int32), payload)
    for name in pool:
        assert np.array_equal(np.asarray(fresh[name][:, 1]),
                              before[name][:, 2]), name
        assert np.array_equal(np.asarray(fresh[name][:, 6]),
                              before[name][:, 3]), name
        assert not np.asarray(fresh[name][:, 2:6]).any()


def test_kernel_single_token_slot():
    """length=1 everywhere (the first decode step after a 1-token prompt):
    softmax over one position must be exact."""
    rng = np.random.default_rng(1)
    B, H, K, ps = 2, 4, 8, 16
    q = jnp.asarray(rng.normal(size=(B, H, K)), jnp.float32)
    k_pool = jnp.asarray(rng.normal(size=(2, 3, ps, H * K)), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=(2, 3, ps, H * K)), jnp.float32)
    tables = jnp.asarray([[1], [2]], jnp.int32)
    lengths = jnp.asarray([1, 1], jnp.int32)
    o = paged_attention(q, k_pool, v_pool, jnp.int32(1), tables, lengths)
    # One valid position ⇒ output IS that position's V row (of layer 1).
    np.testing.assert_allclose(
        np.asarray(o[0]), np.asarray(v_pool[1, 1, 0]).reshape(H, K),
        atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(o[1]), np.asarray(v_pool[1, 2, 0]).reshape(H, K),
        atol=1e-6)


class TestDecodeStepEquivalence:
    """kernel vs gather through the jitted decode functions: logits within
    fp32-softmax tolerance, greedy tokens identical."""

    def _setup(self, params, *, page_size, prompt_lens):
        from ray_tpu.models.paged_kv import init_paged_kv, prefill_chunk_paged

        B = len(prompt_lens)
        n_pg = 4
        rng = np.random.default_rng(7)
        n_pages = B * n_pg
        pool = init_paged_kv(CFG, n_pages, page_size)
        chunk = 16
        padded = np.zeros((B, chunk), np.int32)
        lengths = np.asarray(prompt_lens, np.int32)
        for i, n in enumerate(prompt_lens):
            padded[i, :n] = rng.integers(1, CFG.vocab_size, n)
        tables = np.zeros((B, n_pg), np.int32)
        nxt = 1
        for b in range(B):
            need = (prompt_lens[b] + page_size) // page_size + 1
            for j in range(min(need, n_pg)):
                tables[b, j] = nxt
                nxt += 1
        # Each prompt is one chunk row, from offset 0.
        last, pool = prefill_chunk_paged(
            CFG, params, jnp.asarray(padded), pool, jnp.asarray(tables),
            jnp.zeros(B, jnp.int32), jnp.asarray(lengths))
        toks = np.argmax(np.asarray(last), axis=-1).astype(np.int32)
        return pool, jnp.asarray(tables), jnp.asarray(toks), jnp.asarray(
            lengths)

    @pytest.mark.parametrize("page_size", [16, 64])
    def test_decode_step_logits_match(self, params, page_size):
        from ray_tpu.models.paged_kv import decode_step_paged

        pool, tables, toks, positions = self._setup(
            params, page_size=page_size, prompt_lens=[3, 9, 15])
        # Run both impls from identical pool state (copy: the jit donates).
        pool2 = jax.tree.map(jnp.copy, pool)
        lg_g, pool_g = decode_step_paged(
            CFG, params, toks, pool, positions, tables, attn_impl="gather")
        lg_k, pool_k = decode_step_paged(
            CFG, params, toks, pool2, positions, tables, attn_impl="kernel")
        np.testing.assert_allclose(
            np.asarray(lg_k), np.asarray(lg_g), rtol=2e-4, atol=2e-4)
        assert np.argmax(np.asarray(lg_k), -1).tolist() == \
            np.argmax(np.asarray(lg_g), -1).tolist()
        # Pool writes agree within softmax reassociation (layer l's K/V
        # depend on layer l-1's attention output, so exact equality is
        # only layer-0-deep; close everywhere).
        np.testing.assert_allclose(
            np.asarray(pool_k["k"]), np.asarray(pool_g["k"]),
            rtol=1e-4, atol=1e-5)

    def test_decode_multi_tokens_match(self, params):
        from ray_tpu.models.paged_kv import decode_multi_paged

        pool, tables, toks, positions = self._setup(
            params, page_size=16, prompt_lens=[3, 9, 15])
        pool2 = jax.tree.map(jnp.copy, pool)
        temps = jnp.zeros(3, jnp.float32)          # greedy
        key = jax.random.key(0)
        out_g, _ = decode_multi_paged(
            CFG, params, toks, pool, positions, tables, 8, temps, key,
            attn_impl="gather")
        out_k, _ = decode_multi_paged(
            CFG, params, toks, pool2, positions, tables, 8, temps, key,
            attn_impl="kernel")
        assert np.asarray(out_k).tolist() == np.asarray(out_g).tolist()


class TestEngineKernelPath:
    """LLMEngine(attn_impl="kernel"): every token the plain forward's
    greedy one (tests/plain_reference.py), including under pool pressure
    (preempt-by-recompute)."""

    def _run(self, params, prompts, *, max_tokens=6, **kw):
        from ray_tpu.serve.llm import LLMEngine

        eng = LLMEngine(CFG, params, n_slots=4, max_len=64, **kw)
        reqs = [eng.submit(p, max_tokens=max_tokens) for p in prompts]
        for _ in range(500):
            if all(r.done.is_set() for r in reqs):
                break
            eng.step()
        assert all(r.done.is_set() for r in reqs)
        assert all(r.error is None for r in reqs)
        return [r.out_ids for r in reqs], eng

    def test_kernel_engine_matches_plain_forward(self, lively_params):
        prompts = [[5, 9, 2], [17, 3], [1, 2, 3, 4, 5, 6, 7], [11]]
        kernel, eng = self._run(lively_params, prompts, page_size=16,
                                attn_impl="kernel")
        plain_reference.assert_gpt_greedy(CFG, lively_params, prompts, kernel,
                                          n=6)
        m = eng.metrics()
        assert m["llm_attn_impl"] == "kernel"
        assert m["kv_pages_free"] == m["kv_pages_total"]

    def test_kernel_engine_under_preemption(self, lively_params):
        """Pool sized to force mid-generation eviction: the kernel path
        recomputes victims to the plain forward's tokens."""
        prompts = [[5, 9, 2], [17, 3], [2, 4, 6], [8, 1, 0]]
        kernel, eng = self._run(lively_params, prompts, page_size=4,
                                n_pages=7, max_tokens=10,
                                prefill_chunk=4, prefill_token_budget=8,
                                attn_impl="kernel")
        plain_reference.assert_gpt_greedy(CFG, lively_params, prompts, kernel,
                                          n=10)
        assert eng.metrics()["preemptions"] > 0

    def test_gather_knob_restores_reference_path(self, params):
        """llm_attn_impl=gather emits the kernel engine's tokens (each
        held to the plain forward above and in test_llm_serve.py)."""
        prompts = [[5, 9, 2], [17, 3]]
        g, eng = self._run(params, prompts, page_size=16,
                           attn_impl="gather")
        k, _ = self._run(params, prompts, page_size=16,
                         attn_impl="kernel")
        assert eng.metrics()["llm_attn_impl"] == "gather"
        assert g == k

    def test_decode_step_observability(self, params):
        """The engine loop emits per-window tracing spans + the step
        latency histogram + p50/p95 step-time metrics (the knobs the
        bench commits and /metrics exposes)."""
        from ray_tpu import profiling
        from ray_tpu.serve.llm import _DECODE_STEP_HIST

        _, eng = self._run(params, [[5, 9, 2], [7, 7]], page_size=16,
                           attn_impl="kernel", max_tokens=8)
        m = eng.metrics()
        assert m["decode_step_ms_p50"] > 0
        assert m["decode_step_ms_p95"] >= m["decode_step_ms_p50"]
        spans = [e for e in profiling.peek_events()
                 if e.get("name") == "llm.decode_window"]
        assert spans, "engine decode windows emitted no tracing spans"
        assert all("trace_id" in s.get("args", {}) for s in spans)
        counts, _sums = _DECODE_STEP_HIST.snapshot_hist()
        assert any("paged-kernel" in k for k in counts), (
            "step-latency histogram has no paged-kernel series")


# ----------------------------------------------- window layers: a ring a slot

def _ring(rng, *, lengths, G, K, ps, R, dtype):
    """Each slot's K/V timeline [T, G*K] and the ring pool that holds its
    newest pages: logical page j of slot b at row b * R + j % R (the rows
    a slot's tokens have not reached hold another request's garbage).
    -> (k_pool, v_pool, tables, col_page, k_dense, v_dense)."""
    B, T = len(lengths), -(-max(lengths) // ps) * ps
    dense = [rng.normal(size=(B, T, G * K)).astype(np.float32)
             for _ in range(2)]
    pools = [rng.normal(size=(N_LAYERS, B * R, ps, G * K)).astype(np.float32)
             for _ in range(2)]
    col_page = np.full((B, R), -1, np.int32)
    for b, n in enumerate(lengths):
        for j in range(-(-n // ps)):
            for pool, line in zip(pools, dense):
                pool[:, b * R + j % R] = line[b, j * ps:(j + 1) * ps]
            col_page[b, j % R] = j
    tables = np.arange(B * R, dtype=np.int32).reshape(B, R)
    as_dtype = lambda a: jnp.asarray(a, dtype)
    return (as_dtype(pools[0]), as_dtype(pools[1]), jnp.asarray(tables),
            jnp.asarray(col_page), as_dtype(dense[0]), as_dtype(dense[1]))


def _dense_window_attention(q, k, v, qpos, lengths, window, G):
    """q [B, C, H, K] at absolute positions qpos [B, C] against whole
    timelines k, v [B, T, G*K]: plain masked softmax, float64."""
    B, C, H, K = q.shape
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    k = np.repeat(k.reshape(B, -1, G, K), H // G, axis=2)
    v = np.repeat(v.reshape(B, -1, G, K), H // G, axis=2)
    s = np.einsum("bchk,bthk->bhct", q, k) / np.sqrt(K)
    t = np.arange(k.shape[1])[None, None, :]
    seen = ((t <= qpos[:, :, None]) & (t > qpos[:, :, None] - window)
            & (t < np.asarray(lengths)[:, None, None]))
    s = np.where(seen[:, None], s, -1e30)      # (a pad row sees no key)
    p = np.exp(s - s.max(axis=-1, keepdims=True))
    p /= p.sum(axis=-1, keepdims=True)
    return np.einsum("bhct,bthk->bchk", p, v)


# (H, G, K); a window of 2.5 pages in a ring of 6, contexts from inside
# the first window to several turns of the ring.
WINDOW_HEADS = [(4, 4, 16), (6, 2, 128), (9, 1, 128)]
RING = dict(ps=16, R=6, window=40)


@pytest.mark.parametrize("block_keys", [16, 32, None])
@pytest.mark.parametrize("heads", WINDOW_HEADS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_window_decode_kernel_matches_oracle_and_dense(heads, dtype,
                                                       block_keys,
                                                       monkeypatch):
    """Contexts from one token (five of the ring's six columns hold no
    page: `col_page` -1) to several turns of the ring, at a page a grid
    step, at two (the block divides the ring) and at the rule's own four
    (the ring padded to eight columns with null ones)."""
    H, G, K = heads
    ps, R, window = RING["ps"], RING["R"], RING["window"]
    if block_keys is not None:
        monkeypatch.setattr(pa, "_DECODE_BLOCK_KEYS", block_keys)
    assert decode_block_pages(R, ps, G * K, 4, H) == (block_keys or 64) // ps
    lengths = [1, 17, 40, 41, 96, 97, 150, 271]
    rng = np.random.default_rng(7)
    k_pool, v_pool, tables, col_page, k_dense, v_dense = _ring(
        rng, lengths=lengths, G=G, K=K, ps=ps, R=R, dtype=dtype)
    q = jnp.asarray(rng.normal(size=(len(lengths), H, K)), dtype)
    n = jnp.asarray(lengths, jnp.int32)
    layer = jnp.int32(1)
    # a pool whose OTHER layers differ, so the layer index is live
    kw = dict(window=window, col_page=col_page)
    got = paged_attention(q, k_pool.at[0].add(1.0), v_pool, layer, tables,
                          n, **kw)
    ref = reference_paged_attention(q, k_pool, v_pool, layer, tables, n, **kw)
    want = _dense_window_attention(
        q[:, None], k_dense, v_dense, np.asarray(lengths)[:, None] - 1,
        lengths, window, G)[:, 0]
    atol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=atol)
    np.testing.assert_allclose(np.asarray(ref, np.float32), want, atol=atol)


@pytest.mark.parametrize("split", [False, True])
@pytest.mark.parametrize("heads", WINDOW_HEADS)
def test_window_prefill_kernel_matches_oracle_and_dense(heads, split,
                                                        monkeypatch):
    """Chunk rows at offsets inside the first window, across a page's
    edge and after the ring has turned, a ragged last row and an inert
    one; with the grid split by KV head (`prefill_kv_split`, forced here
    by a small VMEM budget) and without."""
    H, G, K = heads
    ps, R, window, C = RING["ps"], RING["R"], RING["window"], 24
    if split:
        monkeypatch.setattr(pa, "_PREFILL_VMEM_BUDGET", 4096)
    assert pa.prefill_kv_split(G * K, C, H * K, 4, H) == (
        G if split and G > 1 and K % 128 == 0 else 1)
    offsets = np.asarray([0, 10, 37, 100, 230, 64, 0], np.int32)
    n_valid = np.asarray([24, 24, 24, 24, 24, 7, 0], np.int32)
    lengths = offsets + n_valid
    rng = np.random.default_rng(8)
    k_pool, v_pool, tables, col_page, k_dense, v_dense = _ring(
        rng, lengths=list(np.maximum(lengths, 1)), G=G, K=K, ps=ps, R=R,
        dtype=jnp.float32)
    col_page = jnp.where(jnp.asarray(lengths)[:, None] > 0, col_page, -1)
    q = jnp.asarray(rng.normal(size=(len(offsets), C, H, K)), jnp.float32)
    args = (jnp.int32(2), tables, jnp.asarray(offsets), jnp.asarray(lengths))
    kw = dict(window=window, col_page=col_page)
    got = paged_prefill_attention(q, k_pool, v_pool, *args, **kw)
    ref = reference_paged_prefill_attention(q, k_pool, v_pool, *args, **kw)
    qpos = offsets[:, None] + np.arange(C)[None, :]
    want = _dense_window_attention(q, k_dense, v_dense, qpos, lengths,
                                   window, G)
    valid = np.arange(C)[None, :] < n_valid[:, None]
    np.testing.assert_allclose(np.asarray(got)[valid], np.asarray(ref)[valid],
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref)[valid], want[valid], atol=1e-5)


@pytest.mark.parametrize("fault", ["window_off_by_one", "another_slots_ring"])
def test_a_window_fault_moves_the_output(fault):
    """What the tolerances above are for: one key more in the window, or
    every slot reading the ring of the slot before it."""
    H, G, K = 6, 2, 128
    ps, R, window = RING["ps"], RING["R"], RING["window"]
    lengths = [96, 150, 271]
    rng = np.random.default_rng(9)
    k_pool, v_pool, tables, col_page, k_dense, v_dense = _ring(
        rng, lengths=lengths, G=G, K=K, ps=ps, R=R, dtype=jnp.float32)
    q = jnp.asarray(rng.normal(size=(3, H, K)), jnp.float32)
    n = jnp.asarray(lengths, jnp.int32)
    want = _dense_window_attention(
        q[:, None], k_dense, v_dense, np.asarray(lengths)[:, None] - 1,
        lengths, window, G)[:, 0]
    if fault == "window_off_by_one":
        kw = dict(window=window + 1, col_page=col_page)
    else:       # every slot reads the ring of the slot before it
        kw = dict(window=window, col_page=col_page)
        tables = jnp.roll(tables, 1, axis=0)
    for attend in (paged_attention, reference_paged_attention):
        got = attend(q, k_pool, v_pool, jnp.int32(0), tables, n, **kw)
        assert np.abs(np.asarray(got) - want).max() > 1e-2


def test_window_and_col_page_go_together():
    rng = np.random.default_rng(1)
    k_pool, v_pool, tables, n = _pool_and_tables(
        rng, B=2, H=2, K=16, ps=8, n_pg=3, dtype=jnp.float32)
    q = jnp.zeros((2, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="go together"):
        paged_attention(q, k_pool, v_pool, jnp.int32(0), tables, n, window=8)
    with pytest.raises(ValueError, match="shaped like tables"):
        paged_attention(q, k_pool, v_pool, jnp.int32(0), tables, n, window=8,
                        col_page=tables[:, :2])


@pytest.mark.parametrize("model", ["opt-1.3b", "zaya1-8b"])
def test_without_a_window_the_kernels_trace_what_they_did(model):
    """`window=None` hands the two families that have no window layer the
    calls they had: the names a trace knows, three and four scalar
    operands ahead of the blocks (no `col_page`), no KV-head axis in
    the prefill grid. Since PR 37 a decode kv block is
    `decode_block_pages` table columns, as a prefill block is
    `prefill_block_pages`: over a table of 8 columns opt-1.3b's 256 KB
    pages go two a block, zaya1-8b's 32 KB pages all eight."""
    H, G, K = (32, 32, 64) if model == "opt-1.3b" else (8, 2, 128)
    pool = jax.ShapeDtypeStruct((2, 9, 64, G * K), jnp.bfloat16)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    decode = jax.make_jaxpr(lambda q, k, v, t, n: paged_attention(
        q, k, v, jnp.int32(1), t, n, interpret=True))(
        jax.ShapeDtypeStruct((4, H, K), jnp.bfloat16), pool, pool, i32(4, 8),
        i32(4))
    chunk = jax.make_jaxpr(lambda q, k, v, t, o, n: paged_prefill_attention(
        q, k, v, jnp.int32(1), t, o, n, interpret=True))(
        jax.ShapeDtypeStruct((2, 128, H, K), jnp.bfloat16), pool, pool,
        i32(2, 8), i32(2), i32(2))
    n = decode_block_pages(8, 64, G * K, 2, H)
    assert n == (2 if model == "opt-1.3b" else 8)
    # Operands: q, the output and, decode (PR 41), the two pools whole
    # (left in HBM, a live page a DMA of the kernel's own; one grid step
    # walks all four slots); prefill, a block of K pages and one of V.
    for jaxpr, name, scalars, grid, operands in (
            (decode, "paged_decode_attn", 3, (1,), 4),
            (chunk, "paged_prefill_attn", 4, (2, 2), 2 * 4 + 2)):
        (call,) = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
        spec = call.params["grid_mapping"]
        assert call.params["name"] == name
        assert spec.num_index_operands == scalars and spec.grid == grid
        assert len(spec.block_mappings) == operands
    shape = _CELL_SHAPES[model]
    from ray_tpu.ops.paged_attention import prefill_kv_split
    assert prefill_kv_split(shape["kv_lanes"], shape["chunk"],
                            shape["q_lanes"], shape["q_itemsize"],
                            shape["n_heads"]) == 1


def test_many_heads_split_the_prefill_grid_by_kv_head():
    """48 and 72 query heads of 128 over 8 KV heads: the query block,
    accumulator and state pass the VMEM budget by themselves, so a grid
    step takes one KV head; the block is then four pages again."""
    from ray_tpu.ops.paged_attention import prefill_kv_split

    for heads in (48, 72):
        assert prefill_kv_split(1024, 128, heads * 128, 2, heads) == 8
        assert prefill_block_pages(64, 64, 1024, 2, 128, heads * 128, 2,
                                   heads) == 4
    assert prefill_block_pages(13, 64, 1024, 2, 128, 72 * 128, 2, 72) == 4


# ------------- K and V heads of unequal size, and a sink in the softmax

def _dense_attention(q, k, v, qpos, lengths, G, *, window=None, sink=None):
    """q [B, C, H, K] at absolute positions qpos [B, C] against whole
    timelines k [B, T, G*K] and v [B, T, G*Kv]: plain masked softmax in
    float64, a `sink` [H] joining each head's denominator with no value
    row. -> [B, C, H, Kv]."""
    B, C, H, K = q.shape
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    k = np.repeat(k.reshape(B, k.shape[1], G, K), H // G, axis=2)
    v = np.repeat(v.reshape(B, v.shape[1], G, -1), H // G, axis=2)
    s = np.einsum("bchk,bthk->bhct", q, k) / np.sqrt(K)
    t = np.arange(k.shape[1])[None, None, :]
    seen = (t <= qpos[:, :, None]) & (t < np.asarray(lengths)[:, None, None])
    if window is not None:
        seen &= t > qpos[:, :, None] - window
    s = np.where(seen[:, None], s, -1e30)
    top = s.max(axis=-1, keepdims=True)
    if sink is not None:
        logit = np.asarray(sink, np.float64)[None, :, None, None]
        top = np.maximum(top, logit)
    p = np.exp(s - top)
    norm = p.sum(axis=-1, keepdims=True)
    if sink is not None:
        norm = norm + np.exp(logit - top)
    return np.einsum("bhct,bthk->bchk", p / norm, v)


def _paged_kv(rng, *, lengths, G, K, Kv, ps, n_pg=None, R=None):
    """Timelines k [B, T, G*K], v [B, T, G*Kv] and pools that hold them:
    by scattered page tables of `n_pg` columns, or (R given) in a ring of
    R pages a slot whose unreached rows hold garbage.
    -> (k_pool, v_pool, tables, col_page or None, k_dense, v_dense)."""
    B, T = len(lengths), -(-max(lengths) // ps) * ps
    widths = (G * K, G * Kv)
    dense = [rng.normal(size=(B, T, w)).astype(np.float32) for w in widths]
    rows = B * R if R else B * n_pg + 1
    pools = [rng.normal(size=(N_LAYERS, rows, ps, w)).astype(np.float32)
             for w in widths]
    col_page = np.full((B, R), -1, np.int32) if R else None
    tables = (np.arange(B * R, dtype=np.int32).reshape(B, R) if R else
              np.zeros((B, n_pg), np.int32))
    free = iter(rng.permutation(np.arange(1, rows)))
    for b, n in enumerate(lengths):
        for j in range(-(-n // ps)):
            if R:
                row, col_page[b, j % R] = b * R + j % R, j
            else:
                row = tables[b, j] = next(free)
            for pool, line in zip(pools, dense):
                pool[:, row] = line[b, j * ps:(j + 1) * ps]
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return (f32(pools[0]), f32(pools[1]), jnp.asarray(tables),
            None if col_page is None else jnp.asarray(col_page),
            dense[0], dense[1])


# (H, G, K, Kv): grouped and multi-head at small sizes, the served 192 /
# 128 (a K head is 1.5 lane tiles), V wider than K.
UNEQUAL_HEADS = [(4, 2, 24, 16), (4, 4, 24, 16), (8, 4, 192, 128),
                 (6, 2, 16, 32)]


@pytest.mark.parametrize("ring", [False, True])
@pytest.mark.parametrize("sink", [False, True])
@pytest.mark.parametrize("heads", UNEQUAL_HEADS)
def test_decode_kernel_at_unequal_head_sizes_and_a_sink(heads, sink, ring):
    """The scores contract K, the output is Kv wide, a sink a head seeds
    (m, l); over scattered pages and over a ring with a window; kernel
    against oracle against a dense float64 softmax."""
    H, G, K, Kv = heads
    ps = 16
    lengths = [1, 17, 40, 41, 96, 150]
    rng = np.random.default_rng(11)
    k_pool, v_pool, tables, col_page, k_dense, v_dense = _paged_kv(
        rng, lengths=lengths, G=G, K=K, Kv=Kv, ps=ps,
        **({"R": 6} if ring else {"n_pg": 10}))
    q = jnp.asarray(rng.normal(size=(len(lengths), H, K)), jnp.float32)
    n = jnp.asarray(lengths, jnp.int32)
    kw = {"sink": jnp.asarray(rng.normal(size=H), jnp.float32)} if sink \
        else {}
    window = 40 if ring else None
    if ring:
        kw.update(window=window, col_page=col_page)
    got = paged_attention(q, k_pool.at[0].add(1.0), v_pool, jnp.int32(1),
                          tables, n, **kw)
    ref = reference_paged_attention(q, k_pool, v_pool, jnp.int32(1), tables,
                                    n, **kw)
    want = _dense_attention(q[:, None], k_dense, v_dense,
                            np.asarray(lengths)[:, None] - 1, lengths, G,
                            window=window, sink=kw.get("sink"))[:, 0]
    assert got.shape == ref.shape == (len(lengths), H, Kv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref), want, atol=1e-5)


# The window decode walk's cases: (page size, ring width, window, slot
# lengths, (rows a block, blocks a slot) the rule must give: a window and
# a tile of rows, 8 of float32 and 16 of bf16).
WINDOW_WALK = {
    # keys 6..13 of page 0, 22..29 of page 1, 99..106 of page 6
    "a_window_inside_one_page": (16, 6, 8, [14, 30, 107], (16, 1)),
    # a window of two pages from a page's first key (32, 64, 112: the
    # block's last tile gets no copy) and from inside one (33, 45)
    "a_window_from_a_page_boundary": (16, 6, 32, [32, 64, 112, 33, 45],
                                      (40, 1)),
    # the window's rows pass the ring's end (96 rows): two runs of rows
    "a_window_over_the_rings_end": (16, 6, 40, [100, 104, 200, 97, 193],
                                    (48, 1)),
    # positions before the slot's first token do not exist (`col_page`
    # -1): the block starts at 0 and its tail gets no copy
    "a_slot_shorter_than_its_window": (16, 6, 40, [1, 5, 17, 39], (48, 1)),
    "idle_slots_between_live_ones": (16, 6, 40, [50, 0, 0, 97, 0, 130, 0],
                                     (48, 1)),
    # 528 rows of 2 KB under a byte cap of 640 KiB: two blocks of 272;
    # slots whose second block is dead (100, 64) or short (513)
    "two_blocks_a_slot": (64, 12, 512, [700, 513, 100, 1280, 64], (272, 2)),
}


@pytest.mark.parametrize("v_narrow", [False, True])
@pytest.mark.parametrize("sink", [False, True])
@pytest.mark.parametrize("case", sorted(WINDOW_WALK))
def test_the_window_decode_walk(case, sink, v_narrow, monkeypatch):
    """A slot's window fetched as consecutive rows of its ring, found in
    closed form, and attended as one block (or the fewest the caps
    allow), against the oracle and a dense float64 softmax; with and
    without a sink, V as wide as K and narrower."""
    ps, R, window, lengths, blocks = WINDOW_WALK[case]
    wide = case == "two_blocks_a_slot"       # a bf16 pool of 1,024 lanes
    if wide:
        monkeypatch.setattr(pa, "_WINDOW_BLOCK_BYTES", 640 * 2**10)
    H, G, K, Kv = ((16, 8, 128, 64) if v_narrow else (8, 8, 128, 128)) \
        if wide else ((8, 4, 192, 128) if v_narrow else (4, 2, 128, 128))
    dtype = jnp.bfloat16 if wide else jnp.float32
    assert pa.window_block_rows(window, G * K, dtype.dtype.itemsize, H,
                                G * Kv) == blocks
    rng = np.random.default_rng(61)
    live = [max(m, 1) for m in lengths]
    k_pool, v_pool, tables, col_page, k_dense, v_dense = _paged_kv(
        rng, lengths=live, G=G, K=K, Kv=Kv, ps=ps, R=R)
    col_page = jnp.where(jnp.asarray(lengths)[:, None] > 0, col_page, -1)
    k_pool, v_pool = k_pool.astype(dtype), v_pool.astype(dtype)
    q = jnp.asarray(rng.normal(size=(len(lengths), H, K)), dtype)
    kw = dict(window=window, col_page=col_page)
    if sink:
        kw["sink"] = jnp.asarray(rng.normal(size=H), jnp.float32)
    args = (jnp.int32(1), tables, jnp.asarray(lengths, jnp.int32))
    got = np.asarray(paged_attention(q, k_pool.at[0].add(1.0), v_pool, *args,
                                     **kw), np.float32)
    ref = np.asarray(reference_paged_attention(q, k_pool, v_pool, *args,
                                               **kw), np.float32)
    cast = lambda a: np.asarray(jnp.asarray(a, dtype), np.float32)
    want = _dense_attention(
        np.asarray(q, np.float32)[:, None], cast(k_dense), cast(v_dense),
        np.asarray(live)[:, None] - 1, live, G, window=window,
        sink=kw.get("sink"))[:, 0]
    held = np.asarray(lengths) > 0
    atol = 3e-2 if wide else 1e-5
    np.testing.assert_allclose(got[held], ref[held], atol=atol)
    np.testing.assert_allclose(ref[held], want[held], atol=atol)
    assert not got[~held].any()


@pytest.mark.parametrize("split", [False, True])
@pytest.mark.parametrize("ring", [False, True])
@pytest.mark.parametrize("sink", [False, True])
@pytest.mark.parametrize("heads", UNEQUAL_HEADS)
def test_prefill_kernel_at_unequal_head_sizes_and_a_sink(heads, sink, ring,
                                                         split, monkeypatch):
    """Chunk rows at offsets inside the first window, across a page's
    edge and after a ring has turned, a ragged last row and an inert one;
    with the grid split by KV heads (`prefill_kv_split`, forced by a
    small VMEM budget: pairs of heads at 192 / 128, nothing at widths
    that fill no lane tile) and without."""
    H, G, K, Kv = heads
    ps, C = 16, 24
    if split:
        monkeypatch.setattr(pa, "_PREFILL_VMEM_BUDGET", 4096)
    assert pa.prefill_kv_split(G * K, C, H * K, 4, H, G * Kv) == (
        G // 2 if split and K == 192 else 1)
    offsets = np.asarray([0, 10, 37, 100, 230, 64, 0], np.int32)
    n_valid = np.asarray([24, 24, 24, 24, 24, 7, 0], np.int32)
    lengths = offsets + n_valid
    rng = np.random.default_rng(12)
    k_pool, v_pool, tables, col_page, k_dense, v_dense = _paged_kv(
        rng, lengths=list(np.maximum(lengths, 1)), G=G, K=K, Kv=Kv, ps=ps,
        **({"R": 6} if ring else {"n_pg": 16}))
    kw = {"sink": jnp.asarray(rng.normal(size=H), jnp.float32)} if sink \
        else {}
    window = 40 if ring else None
    if ring:
        kw.update(window=window, col_page=jnp.where(
            jnp.asarray(lengths)[:, None] > 0, col_page, -1))
    q = jnp.asarray(rng.normal(size=(len(offsets), C, H, K)), jnp.float32)
    args = (jnp.int32(2), tables, jnp.asarray(offsets), jnp.asarray(lengths))
    got = paged_prefill_attention(q, k_pool, v_pool, *args, **kw)
    ref = reference_paged_prefill_attention(q, k_pool, v_pool, *args, **kw)
    qpos = offsets[:, None] + np.arange(C)[None, :]
    want = _dense_attention(q, k_dense, v_dense, qpos, lengths, G,
                            window=window, sink=kw.get("sink"))
    assert got.shape == ref.shape == (len(offsets), C, H, Kv)
    valid = np.arange(C)[None, :] < n_valid[:, None]
    np.testing.assert_allclose(np.asarray(got)[valid], np.asarray(ref)[valid],
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(ref)[valid], want[valid], atol=1e-5)


@pytest.mark.parametrize("heads", [(4, 4, 16, 16), (6, 2, 128, 128)])
def test_a_sink_at_equal_head_sizes(heads):
    """The sink alone, multi-head (a slot's query one dense row) and
    grouped: both kernels against a dense float64 softmax; a sink of
    -inf-like size is no sink, and a large one drains the output."""
    H, G, K, _ = heads
    lengths = [5, 33, 64]
    rng = np.random.default_rng(13)
    k_pool, v_pool, tables, _c, k_dense, v_dense = _paged_kv(
        rng, lengths=lengths, G=G, K=K, Kv=K, ps=16, n_pg=4)
    q = jnp.asarray(rng.normal(size=(3, H, K)), jnp.float32)
    n = jnp.asarray(lengths, jnp.int32)
    sink = jnp.asarray(rng.normal(size=H), jnp.float32)
    call = lambda s: np.asarray(paged_attention(
        q, k_pool, v_pool, jnp.int32(0), tables, n, sink=s))
    want = lambda s: _dense_attention(
        q[:, None], k_dense, v_dense, np.asarray(lengths)[:, None] - 1,
        lengths, G, sink=s)[:, 0]
    np.testing.assert_allclose(call(sink), want(sink), atol=1e-5)
    np.testing.assert_allclose(call(jnp.full(H, -80.0)), want(None),
                               atol=1e-5)
    assert np.abs(call(jnp.full(H, 40.0))).max() < 1e-6
    chunk = np.asarray(paged_prefill_attention(
        q[:, None], k_pool, v_pool, jnp.int32(0), tables, n - 1, n,
        sink=sink))
    np.testing.assert_allclose(chunk[:, 0], want(sink), atol=1e-5)


@pytest.mark.parametrize("fault", ["sink_dropped", "sink_of_another_head",
                                   "sink_scaled_like_a_score"])
def test_a_sink_fault_moves_the_output(fault):
    """What the tolerances above are for."""
    H, G, K, Kv = 8, 4, 192, 128
    lengths = [20, 70, 129]
    rng = np.random.default_rng(14)
    k_pool, v_pool, tables, _c, k_dense, v_dense = _paged_kv(
        rng, lengths=lengths, G=G, K=K, Kv=Kv, ps=16, n_pg=9)
    q = jnp.asarray(rng.normal(size=(3, H, K)), jnp.float32)
    n = jnp.asarray(lengths, jnp.int32)
    sink = jnp.asarray(rng.normal(size=H) + 2.0, jnp.float32)
    want = _dense_attention(q[:, None], k_dense, v_dense,
                            np.asarray(lengths)[:, None] - 1, lengths, G,
                            sink=sink)[:, 0]
    served = {"sink_dropped": None, "sink_of_another_head": jnp.roll(sink, 1),
              "sink_scaled_like_a_score": sink / np.sqrt(K)}[fault]
    for attend in (paged_attention, reference_paged_attention):
        got = attend(q, k_pool, v_pool, jnp.int32(0), tables, n, sink=served)
        assert np.abs(np.asarray(got) - want).max() > 1e-2


def test_a_sink_is_one_logit_a_query_head_and_planes_share_their_pages():
    rng = np.random.default_rng(1)
    k_pool, v_pool, tables, n = _pool_and_tables(
        rng, B=2, H=2, K=16, ps=8, n_pg=3, dtype=jnp.float32)
    q = jnp.zeros((2, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="one logit a query head"):
        paged_attention(q, k_pool, v_pool, jnp.int32(0), tables, n,
                        sink=jnp.zeros(3))
    with pytest.raises(ValueError, match="pool/query shape mismatch"):
        paged_attention(q, k_pool, v_pool[:, :-1], jnp.int32(0), tables, n)
    with pytest.raises(ValueError, match="pool/query shape mismatch"):
        paged_attention(q, k_pool, v_pool[..., :-1], jnp.int32(0), tables, n)


def test_the_vmem_rules_reckon_k_and_v_widths_apart():
    """At the served shapes of the one family whose widths differ (64
    query heads of 192 over 4 and 8 KV heads, V heads of 128, pages of
    64): the decode block is 512 KiB of K at most, the prefill grid
    takes KV heads in pairs (384 and 256 lanes: whole tiles), and a
    narrower V plane never costs more than an equal one."""
    for G, n_pg, n in ((4, 96, 4), (8, 19, 2)):
        k, v = G * 192, G * 128
        assert decode_block_pages(n_pg, 64, k, 2, 64, v) == n
        assert pa._decode_vmem_bytes(n, 64, k, 2, 64, v) < \
            pa._decode_vmem_bytes(n, 64, k, 2, 64) <= pa._DECODE_VMEM_BUDGET
        assert pa.prefill_kv_split(k, 128, 64 * 192, 2, 64, v) == G // 2
        assert prefill_block_pages(n_pg, 64, k, 2, 128, 64 * 192, 2, 64,
                                   v) == 4
    # equal widths: the rule every family had (None means "as K")
    assert pa._decode_vmem_bytes(4, 64, 1024, 2, 48, 1024) == \
        pa._decode_vmem_bytes(4, 64, 1024, 2, 48)
    assert pa.prefill_kv_split(1024, 128, 48 * 128, 2, 48, 1024) == 8


# sha256 (first 16 hex digits) of `str(jax.make_jaxpr(call))`, addresses
# blanked, of each family's two calls as the commit before the unequal
# widths and the sink traced them (02951b4, computed there by this very
# code): with `sink=None` and equal widths every family gets EXACTLY the
# program it had. (H, K, G, table width, the call's extras.)
# `laguna.decode.window` as PR 61 traced it, which meant to change it and
# nothing else: the window kind of the decode call has a walk and a body
# of its own (`_window_walk`, `_window_decode_kernel`: a slot's window
# fetched as a run of its ring's rows and attended as one block); the
# other eleven stayed, `laguna.prefill.window` among them.
_PARENT_PROGRAMS = {
    "gpt.decode": ("6e08013ab7550714", 32, 64, 32, 8, {}),
    "gpt.decode.int8": ("15dad0340c6f9e91", 32, 64, 32, 8, {"int8": True}),
    "gpt.prefill": ("1336a0f5802a205f", 32, 64, 32, 8, {}),
    "gpt.prefill.int8": ("faa614a89791d268", 32, 64, 32, 8, {"int8": True}),
    "zaya.decode": ("e915f0f72052bbe3", 16, 128, 2, 8, {}),
    "zaya.prefill": ("1b4e11ceb1865797", 16, 128, 2, 8, {}),
    "laguna.decode.full": ("9c1cd8a68d51de7d", 48, 128, 8, 8, {}),
    "laguna.decode.window": ("6fb992ce3b6f2ab3", 72, 128, 8, 13,
                             {"window": 512}),
    "laguna.prefill.full": ("865ad16ed04cfb5b", 48, 128, 8, 8, {}),
    "laguna.prefill.window": ("1a61312a96bf9ca6", 72, 128, 8, 13,
                              {"window": 512}),
    "qwen3_next.decode": ("f97fe1c605cecbf8", 16, 256, 2, 8, {}),
    "qwen3_next.prefill": ("cc3e3da62ea1dde2", 16, 256, 2, 8, {}),
}


@pytest.mark.parametrize("program", sorted(_PARENT_PROGRAMS))
def test_the_other_families_trace_exactly_the_programs_they_had(program):
    import hashlib
    import re

    digest, H, K, G, n_pg, extra = _PARENT_PROGRAMS[program]
    int8, window = extra.get("int8", False), extra.get("window")
    i32, bf16 = jnp.int32, jnp.bfloat16
    pool = jnp.zeros((2, 9, 64, G * K), jnp.int8 if int8 else bf16)
    decode = ".decode" in program
    B = 4 if decode else 2
    kw, static = {}, {}
    if int8:
        kw = dict(k_scale=jnp.ones((2, 9)), v_scale=jnp.ones((2, 9)))
    if window:
        kw, static = dict(col_page=jnp.zeros((B, n_pg), i32)), dict(
            window=window)
    tables, rows = jnp.zeros((B, n_pg), i32), jnp.zeros((B,), i32)
    if decode:
        jaxpr = jax.make_jaxpr(lambda q, k, v, l, t, n, kw: paged_attention(
            q, k, v, l, t, n, interpret=True, **kw, **static))(
            jnp.zeros((B, H, K), bf16), pool, pool, jnp.int32(1), tables,
            rows, kw)
    else:
        jaxpr = jax.make_jaxpr(
            lambda q, k, v, l, t, o, n, kw: paged_prefill_attention(
                q, k, v, l, t, o, n, interpret=True, **kw, **static))(
            jnp.zeros((B, 128, H, K), bf16), pool, pool, jnp.int32(1),
            tables, rows, rows, kw)
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


# --- one KV head under twenty query heads -------------------------------
# AI21-Jamba2-3B's two attention layers: 20 query heads of 128 over ONE
# KV head, so the pool's minor axis is a single head's 128 lanes and the
# group is 20, which no other cell has (6, 8, 9) and which is no multiple
# of the 8 sublanes a query block is tiled by. No new kernel: the decode
# kernel's block-diagonal query is [20, 128] with every row in the one
# head's lanes, the prefill kernel's head loop reads the same lane slice
# twenty times. Pages of 64 (16 KB) and of 128 (32 KB, the cell's).
MQA_H, MQA_K = 20, 128


@pytest.mark.parametrize("n_pg", [3, 4])
@pytest.mark.parametrize("ps", [64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_kernel_at_twenty_query_heads_over_one_kv_head(ps, dtype,
                                                              n_pg):
    rng = np.random.default_rng(5)
    B = 5
    q = jnp.asarray(rng.normal(size=(B, MQA_H, MQA_K)), dtype)
    k_pool, v_pool, tables, lengths = _pool_and_tables(
        rng, B=B, H=1, K=MQA_K, ps=ps, n_pg=n_pg, dtype=dtype)
    assert k_pool.shape[-1] == MQA_K
    layer = jnp.int32(N_LAYERS - 1)
    o = paged_attention(q, k_pool, v_pool, layer, tables, lengths)
    ref = reference_paged_attention(q, k_pool, v_pool, layer, tables,
                                    lengths)
    assert o.shape == q.shape and o.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(ref, np.float32),
        atol=2e-6 if dtype == jnp.float32 else 3e-2)
    # The heads differ (each has its own query) though they share K, V.
    assert not np.allclose(np.asarray(o, np.float32)[1, 0],
                           np.asarray(o, np.float32)[1, 1], atol=1e-2)


@pytest.mark.parametrize("ps,n_pg", [(64, 4), (64, 6), (128, 2), (128, 3)])
@pytest.mark.parametrize("kv", ["float32", "bfloat16"])
def test_prefill_kernel_at_twenty_query_heads_over_one_kv_head(kv, ps, n_pg):
    """`_block_boundary_rows` at the cell's head shape: table widths
    under, at and over a kv block, at both page sizes."""
    rng = np.random.default_rng(6)
    C = 6
    tables, offsets, n_valid = (
        jnp.asarray(x, jnp.int32)
        for x in _block_boundary_rows(ps, n_pg, C))
    B = tables.shape[0]
    dtype = jnp.bfloat16 if kv == "bfloat16" else jnp.float32
    q = jnp.asarray(rng.normal(size=(B, C, MQA_H, MQA_K)), dtype)
    k_pool, v_pool, _t, _n = _pool_and_tables(
        rng, B=B, H=1, K=MQA_K, ps=ps, n_pg=n_pg, dtype=dtype)
    assert int(tables.max()) < k_pool.shape[1]
    layer = jnp.int32(1)
    o = paged_prefill_attention(q, k_pool, v_pool, layer, tables, offsets,
                                offsets + n_valid)
    ref = reference_paged_prefill_attention(
        q, k_pool, v_pool, layer, tables, offsets, offsets + n_valid)
    assert o.shape == q.shape and o.dtype == q.dtype
    valid = np.arange(C)[None, :] < np.asarray(n_valid)[:, None]
    np.testing.assert_allclose(
        np.asarray(o, np.float32)[valid], np.asarray(ref, np.float32)[valid],
        atol=3e-2 if kv == "bfloat16" else 1e-5)


def test_a_pool_of_one_kv_head_is_a_group_of_all_the_query_heads():
    """`_check_pool` reads G off the pool's lanes: 128 lanes under heads
    of 128 is ONE KV head serving all 20; a pool of three such heads is
    refused, since 3 does not divide 20, in words that hold at G = 1."""
    from ray_tpu.ops.paged_attention import _check_pool

    pool = lambda g: jnp.zeros((2, 5, 64, g * MQA_K), jnp.bfloat16)
    assert _check_pool(MQA_H, MQA_K, pool(1), pool(1)) == (64, 1, MQA_K)
    assert _check_pool(MQA_H, MQA_K, pool(4), pool(4)) == (64, 4, MQA_K)
    with pytest.raises(ValueError, match="G dividing the query heads"):
        _check_pool(MQA_H, MQA_K, pool(3), pool(3))


# --- the latent form: ONE plane, a row a token ---------------------------
# Kimi-K2.6's cache (models/kimi_k2.py): a token's row is the key of every
# query head (all its lanes) and, in its first `latent` lanes, their
# value; no V pool, no head axis. The decode kernel takes the grouped form
# at G = 1 with ONE set of buffers (a page is fetched once and stands in
# both matmuls); the prefill kernel takes the heads of a chunk as more
# query rows. Rows of whole lane tiles only: 576 values lie in 640 lanes.
LATENT = [(4, 256, 128, 8), (6, 384, 256, 16), (64, 640, 512, 64)]


def _latent_pool(rng, lengths, K, ps, n_pg, dtype=jnp.float32):
    """ONE plane holding each slot's rows by scattered page tables, the
    rows past a slot's length garbage. -> (pool, tables, dense [B, T, K])."""
    pool, _v, tables, _c, dense, _dv = _paged_kv(
        rng, lengths=lengths, G=1, K=K, Kv=K, ps=ps, n_pg=n_pg)
    return pool.astype(dtype), tables, dense


def _latent_dense(q, rows, qpos, lengths, latent, scale):
    """Plain attention of q [B, C, H, K] at positions qpos [B, C] over
    rows [B, T, K] (float64): every head scores a row's K values and
    sums its first `latent`."""
    q, rows = np.asarray(q, np.float64), np.asarray(rows, np.float64)
    s = np.einsum("bchk,btk->bhct", q, rows) * scale
    t = np.arange(rows.shape[1])
    seen = ((t[None, None, :] <= qpos[:, :, None])
            & (t[None, None, :] < np.asarray(lengths)[:, None, None]))
    s = np.where(seen[:, None], s, -np.inf)
    with np.errstate(invalid="ignore"):     # a row that sees no key: nan
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        p /= p.sum(axis=-1, keepdims=True)
    return np.einsum("bhct,btv->bchv", p, rows[..., :latent])


# What the latent decode call's two-stage walk can get wrong (an
# iteration scores the NEXT live block under the softmax chain of THIS
# one): name -> (pages a block or None for the rule's, table width,
# lengths from a page's keys `ps` and a block's `blk`, slots a grid step
# or None for all). The last three shapes of `_LATENT_WALKS` run at the
# cell's own sizes too.
_LATENT_WALKS = {
    # an idle slot, one token, a last page partly full, a table the
    # rule's block (4 pages) does not divide
    "ragged": (None, 6, lambda ps, blk: [0, 1, ps + 3, 5 * ps,
                                         4 * ps + ps // 2], None),
    # no live block at all: the prologue scores what a buffer holds
    "every_slot_idle": (2, 6, lambda ps, blk: [0, 0, 0, 0], None),
    "idle_first_between_last": (2, 6, lambda ps, blk: [
        0, 0, 3 * ps + 1, 0, ps, 0, 0], None),
    # the prologue's block has no next one
    "one_live_block": (2, 6, lambda ps, blk: [0, 0, 5, 0], None),
    # every iteration crosses into the next slot's query
    "a_block_a_slot": (2, 6, lambda ps, blk: [3, blk, 1, ps, blk - 1],
                       None),
    # the next live block is past `end`: a group's last block
    "slot_groups": (2, 6, lambda ps, blk: [3 * blk, 0, 0, blk + 7, 1, ps],
                    2),
    "block_edges": (2, 6, lambda ps, blk: [1, blk, blk + 1, 6 * ps,
                                           2 * blk, 2 * blk + 1], None),
    # the served table width at 16 pages a block: the last block runs
    # past the table
    "72_columns_at_16_pages": (16, 72, lambda ps, blk: [
        1, blk, 64 * ps + 1, 72 * ps, 0, 65 * ps], None),
}


@pytest.mark.parametrize("H,K,latent,ps,walk", [
    shape + (walk,) for walk in _LATENT_WALKS for shape in LATENT[:2]
] + [LATENT[2] + (walk,)
     for walk in ("ragged", "slot_groups", "block_edges")])
def test_decode_kernel_in_the_latent_form(H, K, latent, ps, walk,
                                          monkeypatch):
    """Kernel = oracle = plain attention, and an idle slot's output is 0,
    over the walks above. Every page no live key lies on is NaN (dead
    table entries point at such pages), and in interpret mode the page
    buffers, the score scratch and the state START as NaN, as an earlier
    call may leave them on the chip: none may reach the output."""
    from jax._src.pallas.primitives import uninitialized_value

    assert np.isnan(uninitialized_value((1,), jnp.float32)).all()
    n, n_pg, lengths, group = _LATENT_WALKS[walk]
    if n is not None:
        monkeypatch.setattr(pa, "_LATENT_BLOCK_KEYS", n * ps)
    n = decode_block_pages(n_pg, ps, K, 4, H, latent, latent=True)
    assert n == (4 if walk == "ragged" else _LATENT_WALKS[walk][0])
    lengths = lengths(ps, n * ps)
    B = len(lengths)
    rng = np.random.default_rng(21)
    pool, tables, dense = _latent_pool(rng, lengths, K, ps, n_pg)
    live = _live_pages(tables, lengths, ps)
    dead = np.setdiff1d(np.arange(pool.shape[1]), live)
    pool = pool.at[:, dead].set(jnp.nan)
    tables = jnp.where(tables == 0, jnp.int32(dead[-1]), tables)
    lens = jnp.asarray(lengths, jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, H, K)), jnp.float32)
    args = (q, pool, None, jnp.int32(1), tables, lens)
    if group is not None:
        slot = H * (K + latent) // 2 * 4
        block = pa._decode_vmem_bytes(n, ps, K, 4, H, latent, latent=True)
        monkeypatch.setattr(pa, "_DECODE_GROUP_BUDGET",
                            block + 4 * group * slot)
        (call,) = [e for e in jax.make_jaxpr(lambda *a: paged_attention(
            *a, latent=latent))(*args).eqns
            if e.primitive.name == "pallas_call"]
        assert call.params["grid_mapping"].grid == (B // group,)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(paged_attention(*args, latent=latent, sm_scale=0.11))
        clean = pool.at[:, dead].set(0.0)
        oracle = np.asarray(reference_paged_attention(
            q, clean, *args[2:], latent=latent, sm_scale=0.11))
    assert got.shape == (B, H, latent)
    busy = np.asarray(lengths) > 0
    assert np.isfinite(got).all() and not got[~busy].any()
    if busy.any():
        want = _latent_dense(q[:, None], dense,
                             np.asarray(lengths)[:, None] - 1, lengths,
                             latent, 0.11)[:, 0]
        np.testing.assert_allclose(got[busy], want[busy], atol=2e-5)
        np.testing.assert_allclose(oracle[busy], want[busy], atol=2e-5)


def test_the_latent_block_rule_is_the_engines_too():
    """`decode_block_pages` stays the ONE rule: at the kimi-k2.6 cell's
    shapes the kernel's block and the block the engine's
    `decode_block_fill` counter rounds a slot's pages up to are the same
    8 pages (512 keys; the 512 KiB of one plane that bound it at 4 is
    the other kinds' rule), at every width the engine hands a decode
    call, and within the VMEM budget the rule states."""
    import types

    from ray_tpu.serve.llm import LLMEngine

    assert pa._LATENT_BLOCK_KEYS == 512 and pa._LATENT_BUFFERS == 4
    for width, n in ((1, 1), (4, 4), (8, 8), (16, 8), (64, 8), (72, 8)):
        assert decode_block_pages(width, 64, 640, 2, 64, 512,
                                  latent=True) == n
    reckoned = pa._decode_vmem_bytes(8, 64, 640, 2, 64, 512, latent=True)
    assert (4 * 8 * 64 * 640 * 2          # four buffers of a block
            + 2 * 64 * 512 * 4            # the two score scratches
            ) < reckoned <= pa._DECODE_VMEM_BUDGET
    engine = types.SimpleNamespace(
        _decode_block_at={}, page_size=64, tp=1, n_slots=4,
        cfg=types.SimpleNamespace(n_heads=64, kv_lora_rank=512),
        cache={"kv": jax.ShapeDtypeStruct((5, 9, 64, 640), jnp.bfloat16)},
        positions=np.asarray([0, 511, 512, 3516]),
        pool=types.SimpleNamespace(pages_for=lambda pos: pos // 64 + 1),
        stats=dict.fromkeys(("decode_pages_live", "decode_pages_fetched",
                             "decode_columns"), 0))
    engine._kv_planes = lambda: LLMEngine._kv_planes(engine)
    LLMEngine._count_decode_pages(engine, [0, 1, 2, 3], 72)
    assert engine._decode_block_at == {72: 8}
    assert engine.stats == {"decode_pages_live": 1 + 8 + 9 + 55,
                            "decode_pages_fetched": 8 + 8 + 16 + 56,
                            "decode_columns": 4 * 72}


@pytest.mark.parametrize("H,K,latent,ps", LATENT)
def test_prefill_kernel_in_the_latent_form(H, K, latent, ps):
    """Chunk rows at ragged offsets, an inert row, a tail chunk, a table
    the kv block does not divide: kernel = oracle = plain attention on
    every valid row."""
    rng = np.random.default_rng(22)
    C = 16
    offsets = np.asarray([0, 0, ps, 4 * ps - 5, 2 * ps + 1], np.int32)
    n_valid = np.asarray([0, 1, C, C, 7], np.int32)
    lengths = offsets + n_valid
    pool, tables, dense = _latent_pool(rng, list(lengths), K, ps, 6)
    q = jnp.asarray(rng.normal(size=(5, C, H, K)), jnp.float32)
    args = (q, pool, None, jnp.int32(2), tables, jnp.asarray(offsets),
            jnp.asarray(lengths))
    with jax.default_matmul_precision("highest"):
        got = paged_prefill_attention(*args, latent=latent, sm_scale=0.11)
        oracle = reference_paged_prefill_attention(*args, latent=latent,
                                                   sm_scale=0.11)
    assert got.shape == (5, C, H, latent)
    qpos = offsets[:, None] + np.arange(C)[None]
    want = _latent_dense(q, dense, qpos, lengths, latent, 0.11)
    valid = np.arange(C)[None] < n_valid[:, None]
    assert valid.sum() == 40
    np.testing.assert_allclose(np.asarray(got)[valid], want[valid], atol=2e-5)
    np.testing.assert_allclose(np.asarray(oracle)[valid], want[valid],
                               atol=2e-5)


def test_the_latent_form_reads_one_plane_once():
    """The decode call holds ONE pool operand and ONE set of buffers (no
    second DMA a page), the prefill call a page ref a column; both carry
    names of their own in a trace; at the cell's shapes the decode block
    is 8 pages, the prefill block 4, and a grid step of the prefill call
    takes 8 heads of a 128-token chunk."""
    bf16, i32 = jnp.bfloat16, jnp.int32
    pool = jnp.zeros((2, 9, 64, 640), bf16)
    tables, rows = jnp.zeros((4, 8), i32), jnp.zeros((4,), i32)
    decode = jax.make_jaxpr(lambda q, kv, t, n: paged_attention(
        q, kv, None, jnp.int32(1), t, n, latent=512, interpret=True))(
        jnp.zeros((2, 64, 640), bf16), pool, tables[:2], rows[:2])
    (call,) = [e for e in decode.eqns if e.primitive.name == "pallas_call"]
    assert call.params["name"] == "paged_decode_attn_latent"
    shapes = [v.aval.shape for v in call.params["jaxpr"].invars]
    assert shapes.count(pool.shape) == 1
    assert [s for s in shapes if len(s) == 3 and s[0] == pa._LATENT_BUFFERS
            ] == [(pa._LATENT_BUFFERS, 8 * 64, 640)]
    prefill = jax.make_jaxpr(lambda q, kv, t, o, n: paged_prefill_attention(
        q, kv, None, jnp.int32(1), t, o, n, latent=512, interpret=True))(
        jnp.zeros((4, 128, 64, 640), bf16), pool, tables, rows, rows)
    (call,) = [e for e in prefill.eqns if e.primitive.name == "pallas_call"]
    assert call.params["name"] == "paged_prefill_attn_latent"
    assert [v.aval.shape for v in call.invars].count(pool.shape) == 4
    assert pa.latent_prefill_shape(72, 64, 640, 2, 128, 64, 512) == (4, 8)
    assert decode_block_pages(72, 64, 640, 2, 64, 512, True) == 8
    assert prefill_block_pages(72, 64, 640, 2, 128, 64 * 640, 2, 64, 512,
                               True) == 4
    # no V buffers: four of the one plane take less fast memory than
    # three of each of two planes of those widths
    assert pa._decode_vmem_bytes(8, 64, 640, 2, 64, 512, True) < \
        pa._decode_vmem_bytes(8, 64, 640, 2, 64, 512)


@pytest.mark.parametrize("fault", ["a_v_pool", "a_row_of_576_lanes",
                                   "a_value_of_half_a_tile", "a_window",
                                   "a_sink", "an_int8_plane"])
def test_the_latent_form_refuses_what_it_does_not_read(fault):
    pool = jnp.zeros((2, 5, 16, 256), jnp.float32)
    q = jnp.zeros((2, 4, 256), jnp.float32)
    tables, n = jnp.zeros((2, 3), jnp.int32), jnp.ones((2,), jnp.int32)
    kw, v, latent = {}, None, 128
    if fault == "a_v_pool":
        v = pool
    elif fault == "a_row_of_576_lanes":
        pool, q = jnp.zeros((2, 5, 16, 576)), jnp.zeros((2, 4, 576))
    elif fault == "a_value_of_half_a_tile":
        latent = 64
    elif fault == "a_window":
        kw = dict(window=32, col_page=jnp.zeros((2, 3), jnp.int32))
    elif fault == "a_sink":
        kw = dict(sink=jnp.zeros(4))
    else:
        kw = dict(k_scale=jnp.ones((2, 5)), v_scale=jnp.ones((2, 5)))
    for call in (paged_attention, reference_paged_attention):
        with pytest.raises(ValueError, match="latent form: want ONE bf16"):
            call(q, pool, v, jnp.int32(0), tables, n, latent=latent, **kw)
    for call in (paged_prefill_attention, reference_paged_prefill_attention):
        with pytest.raises(ValueError, match="latent form: want ONE bf16"):
            call(q[:, None], pool, v, jnp.int32(0), tables, n - 1, n,
                 latent=latent, **kw)


# --- wide multi-head rows (olmo-hybrid-7b: 30 x 128 = 3,840 lanes) --------

@pytest.mark.parametrize("kv,n_pg,block", [
    ("bfloat16", 8, 1),     # the cell's shapes: 480 KB a page, one a block
    ("float32", 5, 1),      # f32 pages of 960 KB, a table of odd width
    ("int8", 8, 2),         # an int8 pool: 240 KB a page, two a block
])
def test_decode_kernel_at_wide_multi_head_rows(kv, n_pg, block):
    """Multi-head rows of 30 x 128 = 3,840 lanes at 64 tokens a page (480
    KB a plane in bf16: ONE page a block by the bytes' rule, the widest
    block-diagonal query any family hands the kernel): the kernel is its
    oracle over lengths on, under and over every block boundary, a slot
    of one token and an idle slot, at a layer other than 0; an int8
    pool's pages keep a scale each."""
    H = G = 30
    K, ps = 128, 64
    dtype = jnp.bfloat16 if kv == "bfloat16" else jnp.float32
    item = 1 if kv == "int8" else jnp.dtype(dtype).itemsize
    n = decode_block_pages(n_pg, ps, G * K, item, H)
    assert n == block
    lengths, n_live = _decode_boundary_lengths(ps, n_pg, n)
    B = len(lengths)
    rng = np.random.default_rng(31)
    tables, page = np.zeros((B, n_pg), np.int32), 1
    for b, end in enumerate(lengths[:n_live]):
        live = -(-end // ps)
        tables[b, :live] = np.arange(page, page + live)
        page += live
    shape = (N_LAYERS, page, ps, G * K)
    scales = {}
    if kv == "int8":
        (k_pool, v_pool), (ks, vs) = _quantized(rng, shape)
        scales = {"k_scale": ks, "v_scale": vs}
    else:
        k_pool, v_pool = (jnp.asarray(rng.normal(size=shape), dtype)
                          for _ in range(2))
    q = jnp.asarray(rng.normal(size=(B, H, K)), dtype)
    args = (jnp.int32(N_LAYERS - 1), jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32))
    o = paged_attention(q, k_pool, v_pool, *args, **scales)
    ref = reference_paged_attention(q, k_pool, v_pool, *args, **scales)
    assert o.shape == q.shape and o.dtype == q.dtype
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(ref, np.float32),
        atol=3e-2 if kv == "bfloat16" else 1e-5)


def test_the_wide_row_block_is_the_engines_too():
    """The engine's `decode_block_fill` counter rounds a slot's pages up
    to the block the kernel walks: ONE page at olmo-hybrid-7b's shapes,
    so every fetched page is a live one."""
    import types

    from ray_tpu.serve.llm import LLMEngine

    plane = jax.ShapeDtypeStruct((2, 9, 64, 3840), jnp.bfloat16)
    engine = types.SimpleNamespace(
        _decode_block_at={}, page_size=64, tp=1, n_slots=4,
        cfg=types.SimpleNamespace(n_heads=30, head_dim=128),
        cache={"k": plane, "v": plane},
        positions=np.asarray([0, 127, 128, 2815]),
        pool=types.SimpleNamespace(pages_for=lambda pos: pos // 64 + 1),
        stats=dict.fromkeys(("decode_pages_live", "decode_pages_fetched",
                             "decode_columns"), 0))
    engine._kv_planes = lambda: LLMEngine._kv_planes(engine)
    LLMEngine._count_decode_pages(engine, [0, 1, 2, 3], 44)
    assert engine._decode_block_at == {44: 1}
    assert engine.stats == {"decode_pages_live": 1 + 2 + 3 + 44,
                            "decode_pages_fetched": 1 + 2 + 3 + 44,
                            "decode_columns": 4 * 44}
