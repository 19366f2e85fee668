"""Block-paged KV cache for the serving engine: the ONE cache it has.

A cache of ``[L, B, T_max]`` a slot makes HBM capacity, not compute, cap
the slot count (OPT-1.3B at 16 slots × 2048 OOM'd a 16 GB chip). Paged
KV decouples slot count from max_len: a shared pool of fixed-size pages
``[L, P+1, page_size, H*K]`` plus a per-slot page table
``[B, max_pages]`` of page ids. Slots consume pages as they grow,
so pool capacity is sized to the *expected total live tokens*, not
``B × T_max`` worst case (PAPERS.md "Ragged Paged Attention"; the
reference's serving delegates KV management to torch models —
`/root/reference/python/ray/serve/batching.py:1` is the capability
being out-scaled here). The gpt block's pieces every program here is
built from (`_rotary_pos`, `_qkv`, `_mlp`, `_head`) and the host-side
`sample_token` of the one-step tick are at the top of this module.

XLA-first layout decisions:
- The pool is lane-dense and addressed in place by (layer, page). Its
  minor axis is ``H*K`` — every head of one token, heads major — which is
  a multiple of the TPU's 128 lanes at every served model (2,048 at
  OPT-1.3B, 1,024 a shard for GPT-J at tp=4) where head_dim alone (64)
  is not: with ``[..., H, K]`` the chip's own layout made the PAGE axis
  minor, and every layer of every step cut its plane out of the pool,
  re-laid it out for the kernel, re-laid it back and wrote it back
  whole (90 % of a decode step, PERF.md PR 25). Now the chip's layout IS
  row-major with no padding, a page is ``page_size`` dense rows, and no
  op of a paged program moves more pool bytes than the pages it
  touches: `scan_pool_layers` carries the whole pool, writes land at
  ``(l, page, offset)`` and the kernels' block index is ``(l, page)``.
  One layout, no switch: nothing here looks at head_dim.
- Page 0 is a reserved null page. Table entries that aren't allocated
  point at 0; writes land there harmlessly and reads of it are always
  position-masked, so every shape stays static with no host branching.
- Reads have two implementations, selected by the static ``attn_impl``
  argument (engine knob ``llm_attn_impl``):
  * ``"gather"`` (reference): gather the slot's pages of layer ``l``
    back into a contiguous ``[B, T, H, K]`` timeline (transient, inside
    the layer scan) and attend it with a plain masked softmax — the
    tests' oracle, held to the full-sequence forward (tested).
  * ``"kernel"``: the Pallas ragged paged-attention kernel
    (ops/paged_attention.py) reads K/V pages in place from the pool
    with online-softmax state in VMEM — no timeline is materialized in
    HBM. Exact-match with ``"gather"`` within fp32-softmax
    reassociation (tested); the throughput path on real chips.
- Writes scatter rows of ``H*K`` at ``(l, table[b, pos // ps],
  pos % ps)`` of the carried, donated pool. Distinct live
  slots never share a *writable* page: exclusively-owned pages are the
  common case, and the prefix cache (serve/prefix_cache.py) may bind
  the same already-written page into several slots' tables READ-ONLY —
  every binder's writes start past the shared run, and a prefix tail
  that would be written mid-page is duplicated first via
  ``copy_pages`` (copy-on-write). So scatter indices still never
  collide on real pages.

Page allocation/free is host-side engine policy (ray_tpu.serve.llm):
admission back-pressure, window-bounded lazy allocation, and
preempt-by-recompute when the pool runs dry.
"""

from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import scopes
from ray_tpu.models.blocks import attend_fn
from ray_tpu.models.gpt import (GPTConfig, _layer_norm, stack_block_params,
                                weight_view)


def _rotary_pos(x: jax.Array, rotary_dim: int, pos: jax.Array) -> jax.Array:
    """Rotary with explicit per-row positions. x: [B, S, H, K]; pos: [B, S]."""
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    inv_freq = 1.0 / (10000 ** (jnp.arange(0, rotary_dim, 2) / rotary_dim))
    ang = pos[..., None] * inv_freq  # [B, S, R/2]
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)  # [B, S, 1, R/2]
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    x1, x2 = rot[..., 0::2], rot[..., 1::2]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    rot = jnp.stack([out1, out2], axis=-1).reshape(rot.shape)
    return jnp.concatenate([rot, rest], axis=-1)


def _qkv(h, layer, cfg):
    q = jnp.einsum("bsd,dhk->bshk", h, weight_view(layer, "wq", cfg.dtype))
    k = jnp.einsum("bsd,dhk->bshk", h, weight_view(layer, "wk", cfg.dtype))
    v = jnp.einsum("bsd,dhk->bshk", h, weight_view(layer, "wv", cfg.dtype))
    return q, k, v


def _mlp(x, layer, cfg, tp_axis=None):
    """Feed-forward block. Under tensor parallelism (`tp_axis` set, the
    body running inside a shard_map) w_up/b_up/w_down are sharded on the
    hidden width: the up-projection and gelu are shard-local and the
    down-projection yields a partial sum reduced across shards BEFORE
    the replicated b_down joins the residual (each shard adding b_down
    pre-psum would count it tp times)."""
    h = _layer_norm(x, layer["ln2_scale"], layer["ln2_bias"])
    up = jax.nn.gelu(
        jnp.einsum("bsd,df->bsf", h, weight_view(layer, "w_up", cfg.dtype))
        + layer["b_up"].astype(cfg.dtype))
    down = jnp.einsum("bsf,fd->bsd", up,
                      weight_view(layer, "w_down", cfg.dtype))
    if tp_axis is not None:
        down = jax.lax.psum(down, tp_axis)
    return x + (down + layer["b_down"].astype(cfg.dtype))


def _head(params, cfg, x):
    x = _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"])
    head = params["lm_head"] if not cfg.tie_embeddings else params["wte"].T
    return jnp.einsum("bsd,dv->bsv", x, head.astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def sample_token(logits, *, temperature: float = 0.0, top_k: int = 0,
                 key=None):
    """Greedy (temperature=0) or temperature/top-k sampling. logits: [V] or
    [B, V] fp32 numpy/jax."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    scaled = logits / temperature
    if top_k > 0:
        kth = jnp.sort(scaled, axis=-1)[..., -top_k][..., None]
        scaled = jnp.where(scaled < kth, -1e30, scaled)
    assert key is not None, "sampling needs a PRNG key"
    return jax.random.categorical(key, scaled, axis=-1)


def init_paged_kv(cfg: GPTConfig, n_pages: int, page_size: int,
                  kv_dtype: str | None = None):
    """Shared page pool. Row 0 is the null page (never allocated).

    ``kv_dtype`` None/"bf16" (default): K/V planes in cfg.dtype — the
    original pool. "int8": int8 page planes plus one per-page scale
    PLANE per side (``k_scale``/``v_scale`` [L, P+1], bf16) that rides
    the same page-id axis as the data — so COW (`copy_pages`),
    donation (`gather_pages`), adoption (`scatter_pages`), and
    failover move scales with their pages through the existing
    dict-generic page ops, with zero scheduler/refcount changes. Scales
    are set at a page's FIRST write (any write at in-page offset 0
    resets — offset 0 means the writer owns a fresh or recycled page)
    and frozen until the page restarts; later tokens clip at the
    frozen scale, so no already-written token is ever re-scaled."""
    shape = (cfg.n_layers, n_pages + 1, page_size,
             cfg.n_heads * cfg.head_dim)
    if kv_dtype in (None, "bf16"):
        return {"k": jnp.zeros(shape, cfg.dtype),
                "v": jnp.zeros(shape, cfg.dtype)}
    if kv_dtype != "int8":
        raise ValueError(f"kv_dtype must be bf16|int8, got {kv_dtype!r}")
    scale_shape = (cfg.n_layers, n_pages + 1)
    return {"k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(scale_shape, jnp.bfloat16),
            "v_scale": jnp.zeros(scale_shape, jnp.bfloat16)}


def _rows(x):
    """[..., H, K] → [M, H*K]: K/V of M tokens as rows of the pool's
    minor axis (heads major, so a tp shard's rows are its own heads)."""
    return x.reshape(-1, x.shape[-2] * x.shape[-1])


def _quant_write(plane, scale, l, write_pages, write_offs, values,
                 tp_axis=None):
    """Quantized scatter of per-token K/V rows into layer ``l`` of an
    int8 page plane [L, P+1, ps, H*K], in place, maintaining that
    layer's row of the per-page scale plane [L, P+1].

    values: [M, H*K] float rows landing at (l, write_pages[m],
    write_offs[m]). Scale policy — frozen-at-first-write: a page's
    scale is (re)set from this dispatch's scatter-max of |values| over
    rows landing in it iff some row lands at offset 0 (a fresh/recycled
    page — no earlier live content to invalidate) or the page has never
    been scaled; otherwise the existing scale is kept and rows quantize
    against it (clipped to ±127 — bounded saturation, never corruption
    of already-written tokens). Null-page (id 0) writes perturb only
    the null scale, which no masked read ever consumes. Under tensor
    parallelism the contribution is pmax'd across head shards so the
    replicated scale plane stays shard-identical."""
    n_rows = scale.shape[1]
    v32 = values.astype(jnp.float32)
    vmax = jnp.max(jnp.abs(v32), axis=1)                           # [M]
    starts = jnp.zeros((n_rows,), jnp.int32).at[write_pages].max(
        (write_offs == 0).astype(jnp.int32))
    contrib = jnp.zeros((n_rows,), jnp.float32).at[write_pages].max(vmax)
    if tp_axis is not None:
        contrib = jax.lax.pmax(contrib, tp_axis)
    old = scale[l].astype(jnp.float32)
    new_scale = jnp.where((starts > 0) | (old <= 0.0),
                          jnp.maximum(contrib, 1e-8) / 127.0, old)
    q = jnp.clip(jnp.round(v32 / new_scale[write_pages][:, None]),
                 -127, 127).astype(jnp.int8)
    return (plane.at[l, write_pages, write_offs].set(q),
            scale.at[l].set(new_scale.astype(scale.dtype)))


@jax.named_scope(scopes.ATTN_KV_WRITE)
def _write_rows(pool, l, write_pages, write_offs, k_rows, v_rows,
                tp_axis=None):
    """K/V rows [M, H*K] → ``(l, write_pages[m], write_offs[m])`` of the
    carried pool, quantizing against the layer's scale row for an int8
    pool. Only the M rows move: the pool is updated in place."""
    if "k_scale" in pool:
        k, k_sc = _quant_write(pool["k"], pool["k_scale"], l, write_pages,
                               write_offs, k_rows, tp_axis)
        v, v_sc = _quant_write(pool["v"], pool["v_scale"], l, write_pages,
                               write_offs, v_rows, tp_axis)
        return {"k": k, "v": v, "k_scale": k_sc, "v_scale": v_sc}
    dtype = pool["k"].dtype
    return {"k": pool["k"].at[l, write_pages, write_offs].set(
                k_rows.astype(dtype)),
            "v": pool["v"].at[l, write_pages, write_offs].set(
                v_rows.astype(dtype))}


def scan_pool_layers(body, x, stacked, pool):
    """Scan ``body`` over the layer stack with the page pool in the scan
    CARRY, not as stacked xs/ys operands, and never sliced.

    ``body(x, layer, l, pool) -> (x, pool)`` receives the layer index
    and the WHOLE pool and returns the whole pool: it writes the rows
    (or pages) it touches at ``(l, page, offset)`` and its attention
    reads pages at ``(l, page)``, so no op of a paged program moves
    more pool bytes than the pages it touches. The carry matters for
    memory fit on the chip: a pool scanned as xs → ys is TWO buffers in
    the compiled while loop (the stacked input and the stacked output),
    which doubled the pool's HBM footprint — a 16-slot × 2048-token bf16
    pool at OPT-1.3B (6.4 GB) plus its copy did not fit a 16 GB chip
    beside the weights. A carried pool updated in place is one buffer,
    aliased with the donated argument
    (tests/test_chip_compile.py holds the compiled programs to it)."""

    def step(carry, inputs):
        x, pool = carry
        l, layer = inputs
        return body(x, layer, l, pool), None

    n_layers = pool["k"].shape[0]
    (x, pool), _ = jax.lax.scan(
        step, (x, pool), (jnp.arange(n_layers), stacked))
    return x, pool


@functools.partial(jax.jit, donate_argnums=(0,))
def copy_pages(pool, src, dst):
    """Copy-on-write for the prefix cache: duplicate pages ``src[i]`` →
    ``dst[i]`` across every layer for both K and V in ONE fused dispatch.

    The engine batches a tick's COW copies into a single call (src/dst
    padded to a power-of-two length so the copy lowers one program per
    width bucket, not one per count). Padding pairs are ``(0, 0)``:
    writes to the null page are harmless by layout convention, and
    copying the null page onto itself is a no-op whatever the duplicate
    write order. Real ``dst`` ids are freshly-allocated (never aliased),
    so scatter order between real pairs cannot matter either.
    """
    return {k: v.at[:, dst].set(v[:, src]) for k, v in pool.items()}


@jax.jit
def gather_pages(pool, pages):
    """Read pages ``pages[i]`` out of the pool across every layer for
    both K and V in ONE fused dispatch → ``{"k": [L, n, ps, H*Kd],
    "v": ...}``. The donation path of the KV page-set store
    (serve/kv_objects.py): the caller pads ``pages`` to a power-of-two
    length with null-page (0) ids — reading the null page is harmless
    by layout convention — so the gather lowers one program per width
    bucket, not one per page count."""
    return {k: v[:, pages] for k, v in pool.items()}


@functools.partial(jax.jit, donate_argnums=(0,))
def scatter_pages(pool, pages, payload):
    """Write page payloads ``payload[name][:, i]`` into pool rows
    ``pages[i]`` across every layer in ONE fused dispatch — the
    adoption path of the KV page-set store. ``payload`` carries one
    entry per pool plane (K/V data, plus the per-page scale planes of a
    quantized pool — `gather_pages` emits exactly this dict), so
    adopted pages land with the scales they were quantized under.
    Padding convention mirrors copy_pages: the caller pads ``pages``
    with null-page (0) ids and zero payloads; writes to the null page
    are harmless, and real target ids are freshly allocated (never
    aliased), so scatter order cannot matter."""
    return {k: pool[k].at[:, pages].set(payload[k]) for k in pool}


@jax.named_scope(scopes.ATTN_IN)
def _attn_in(cfg: GPTConfig, layer, x, pos):
    """Pre-norm, q/k/v projections and rotary of one layer: the part of
    every paged body before its K/V write."""
    h = _layer_norm(x, layer["ln1_scale"], layer["ln1_bias"])
    q, k, v = _qkv(h, layer, cfg)
    return (_rotary_pos(q, cfg.rotary_dim, pos),
            _rotary_pos(k, cfg.rotary_dim, pos), v)


def _chunk_paged_forward(cfg: GPTConfig, params, tokens, pool, tables,
                         offsets, n_valid, attn_impl: str,
                         tp_axis: str | None = None):
    """Shared chunk-row transformer body: write one [N, C] chunk batch
    into the page pool at per-row arbitrary offsets and attend causally
    over each slot's whole written prefix. Both chunked PREFILL
    (`prefill_chunk_paged`) and speculative VERIFY
    (`verify_chunk_paged`) lower through this one body — the verify
    pass is structurally a chunked-prefill row, so sharing the body is
    what makes the exactness argument (and the compile count) carry
    over. With `tp_axis` set (the body running inside a shard_map over
    a head-sharded params/pool slice) everything is shard-local except
    the attention-out and MLP-down partial sums, psum'd per layer.
    → (hidden states [N, C, D], updated pool)."""
    attend = attend_fn(attn_impl, chunk=True)
    N, C = tokens.shape
    ps = pool["k"].shape[2]
    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens]        # [N, C, D]
    rel = jnp.arange(C)
    pos = offsets[:, None] + rel[None, :]                  # [N, C]
    stacked = stack_block_params(params, cfg.dtype)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    # Write targets: pad/inert positions (rel >= n_valid) scatter to the
    # null page — harmless, read-masked. The page index is clamped
    # because a padded tail's absolute position can run past the table on
    # a near-max-len prompt — and, with width-bucketed tables, past the
    # sliced width on any row whose offset sits near the bucket edge.
    # Only those write-masked pad positions ever hit the clamp: valid
    # positions fall inside the sliced width by bucket construction.
    with jax.named_scope(scopes.ATTN_KV_WRITE):
        page_idx = jnp.minimum(pos // ps, tables.shape[1] - 1)
        row_pages = jnp.take_along_axis(tables, page_idx, axis=1)  # [N, C]
        write_pages = jnp.where(rel[None, :] < n_valid[:, None],
                                row_pages, 0).reshape(-1)          # [N*C]
        write_offs = (pos % ps).reshape(-1)                        # [N*C]
    kv_lens = offsets + n_valid                                 # [N]

    def body(x, layer, l, pool):
        q, k, v = _attn_in(cfg, layer, x, pos)
        # Write before attending (same order as the decode path): each
        # row then reads its own chunk's K/V back through its table, so
        # intra-chunk causality is just the tpos <= qpos mask.
        # Head count from the array, not the config: under tensor
        # parallelism this body sees the per-shard head slice.
        pool = _write_rows(pool, l, write_pages, write_offs,
                           _rows(k), _rows(v), tp_axis)
        with jax.named_scope(scopes.ATTN_KERNEL):
            attn = attend(q, pool["k"], pool["v"], l, tables, offsets,
                          kv_lens, sm_scale=scale,
                          k_scale=pool.get("k_scale"),
                          v_scale=pool.get("v_scale"))
        with jax.named_scope(scopes.ATTN_OUT):
            attn_out = jnp.einsum("bchk,hkd->bcd", attn,
                                  weight_view(layer, "wo", cfg.dtype))
            if tp_axis is not None:
                attn_out = jax.lax.psum(attn_out, tp_axis)
            x = x + attn_out
        with jax.named_scope(scopes.MLP):
            x = _mlp(x, layer, cfg, tp_axis=tp_axis)
        return x, pool

    x, pool = scan_pool_layers(body, x, stacked, pool)
    return x, pool


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("attn_impl",), donate_argnums=(3,))
def verify_chunk_paged(cfg: GPTConfig, params, tokens, pool, tables,
                       offsets, n_valid, *, attn_impl: str = "gather"):
    """Speculative-verify dispatch: score a [N, C] batch of rows
    ``[pending, draft_1, ..., draft_{k}]`` (C = k+1) written at each
    slot's decode cursor, returning the target's logits at EVERY chunk
    position — row i's logits are the target distribution for the token
    AFTER position offsets+i, which is exactly what rejection sampling
    needs to accept/reject draft_{i+1}.

    Same body as `prefill_chunk_paged` (`_chunk_paged_forward`): the
    verify pass IS a chunked-prefill row — KV for the proposed tokens is
    scattered at arbitrary offsets and causally masked within the chunk,
    so the PR 4 chunk program (and its gather oracle) is the verify
    program, and it buckets by table width for free: the engine feeds
    the decode-side width-sliced table view (`_decode_table_view`), so
    one program lowers per pow-2 width — the log₂(max_pages)+1 half of
    the chunk-program budget. Only the head differs: every position pays
    the LM head (the k+1-wide full-logits head is the whole point — one
    weight pass scores all proposals). The engine rolls rejected
    positions back by rewinding cursors host-side; the garbage KV they
    leave behind sits past every kv-length mask and is overwritten by
    the next write at that position.

    → (logits [N, C, V] fp32, updated pool).
    """
    x, pool = _chunk_paged_forward(cfg, params, tokens, pool, tables,
                                   offsets, n_valid, attn_impl)
    with jax.named_scope(scopes.HEAD):
        return _head(params, cfg, x), pool                 # [N, C, V]


def _decode_once_paged(cfg: GPTConfig, params, tokens, pool, positions,
                       tables, attn_impl: str = "gather", write_mask=None,
                       tp_axis: str | None = None):
    """All B slots advance one token against the page pool.

    tokens: [B]; positions: [B]; tables: [B, max_pages]; attn_impl
    (static): "gather" reconstitutes each slot's contiguous timeline
    [B, T, H, K] (T = max_pages × page_size) per layer and attends it
    with a plain masked softmax; "kernel" runs the Pallas ragged
    paged-attention kernel against the pool in place, at (layer, page).
    `write_mask` ([B] bool, optional) routes masked rows' K/V writes to the null
    page — the speculative draft loop uses it so proposal steps past a
    slot's per-tick budget never touch real pages. `tp_axis` (optional):
    the tensor-parallel mesh axis when this body runs inside a
    shard_map over head-sharded params and pool — both attention impls
    read their per-shard pages unchanged (pages are indexed by id; only
    the H*K axis is sliced, into whole heads) and the attention-out /
    MLP-down partial
    sums psum across shards.
    → (logits [B, V] fp32, updated pool).
    """
    attend = attend_fn(attn_impl, chunk=False)
    ps = pool["k"].shape[2]
    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens][:, None, :]  # [B, 1, D]
    pos = positions[:, None]
    # Pre-cast the stacked block params once: the per-layer weight_view
    # casts inside the scan body become no-ops instead of re-lowering a
    # convert per layer per step (int8 planes stay compressed — their
    # dequant fuses into the consuming einsum).
    stacked = stack_block_params(params, cfg.dtype)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    # Write target + kv length are loop-invariant across layers — computed
    # once here, never inside the scan body. The page index is clamped
    # (like the chunk path) because a masked draft step's position can
    # run past the table on a near-max-len slot.
    with jax.named_scope(scopes.ATTN_KV_WRITE):
        write_page = jnp.take_along_axis(
            tables,
            jnp.minimum(positions // ps, tables.shape[1] - 1)[:, None],
            axis=1)[:, 0]                                    # [B]
        if write_mask is not None:
            write_page = jnp.where(write_mask, write_page, 0)
        write_off = positions % ps                           # [B]
    kv_lengths = positions + 1                               # [B]

    def body(x, layer, l, pool):
        q, k, v = _attn_in(cfg, layer, x, pos)
        pool = _write_rows(pool, l, write_page, write_off,
                           _rows(k), _rows(v), tp_axis)
        with jax.named_scope(scopes.ATTN_KERNEL):
            attn = attend(q[:, 0], pool["k"], pool["v"], l, tables,
                          kv_lengths, sm_scale=scale,
                          k_scale=pool.get("k_scale"),
                          v_scale=pool.get("v_scale"))
        with jax.named_scope(scopes.ATTN_OUT):
            attn_out = jnp.einsum("bhk,hkd->bd", attn,
                                  weight_view(layer, "wo", cfg.dtype))
            if tp_axis is not None:
                attn_out = jax.lax.psum(attn_out, tp_axis)
            x = x + attn_out[:, None, :]
        with jax.named_scope(scopes.MLP):
            x = _mlp(x, layer, cfg, tp_axis=tp_axis)
        return x, pool

    x, pool = scan_pool_layers(body, x, stacked, pool)
    with jax.named_scope(scopes.HEAD):
        logits = _head(params, cfg, x)[:, 0]
    return logits, pool


def _scale_by_temps(logits, temps):
    """Logits [B, V] over each slot's temperature (a greedy slot's row is
    scaled by 1e6 and read by nobody)."""
    return logits / jnp.maximum(temps, 1e-6)[:, None]


@jax.named_scope(scopes.SAMPLE)
def _sample_next(logits, temps, key):
    """Shared on-device sampling step for every fused loop (decode
    window + speculative draft, tp and non-tp twins alike): greedy
    argmax at temp <= 0, else temperature-scaled categorical.

    The draw (threefry bits over [B, V], Gumbel noise, a second arg-max)
    runs under a `lax.cond` on what the step observes in its input: a
    batch in which no slot has a temperature above 0 computes the
    arg-max and nothing else over [B, V] (the draw was 0.4 ms of every
    step at 16.8 M logits, PERF.md section 6, PR 55). The key is split
    ahead of the conditional either way, so a seeded slot's stream and
    the returned key do not depend on whether its neighbours, or an
    earlier step's, were greedy.
    → (next tokens int32, advanced key)."""
    key, sub = jax.random.split(key)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draw():
        sampled = jax.random.categorical(
            sub, _scale_by_temps(logits, temps), axis=-1)
        return jnp.where(temps <= 0.0, greedy, sampled).astype(jnp.int32)

    return jax.lax.cond(jnp.any(temps > 0.0), draw, lambda: greedy), key


@jax.named_scope(scopes.HEAD)
def _last_valid_logits(cfg: GPTConfig, params, x, n_valid):
    """Chunk-head epilogue shared by `prefill_chunk_paged` and its tp
    twin: LM head over the chunk hiddens, then each row's logits at its
    last VALID position (inert rows clamp to 0 — garbage the engine
    ignores). → [N, V] fp32."""
    logits = _head(params, cfg, x)                         # [N, C, V]
    return jnp.take_along_axis(
        logits,
        jnp.maximum(n_valid - 1, 0)[:, None, None].astype(jnp.int32),
        axis=1)[:, 0]                                      # [N, V]


def _spec_propose_scan(cfg: GPTConfig, params, tokens, pool, positions,
                       tables, n_prop, temps, key, k: int, attn_impl: str,
                       need_probs: bool, tp_axis: str | None = None):
    """Shared draft-propose scan body (`spec_draft_propose` runs it
    directly; the tp twin inside a shard_map) — the k+1 masked decode
    steps with on-device sampling. → (proposals [k, B], probs [k, B, V]
    or None, updated pool)."""

    def step(carry, i):
        toks, pos, pool, key = carry
        logits, pool = _decode_once_paged(
            cfg, params, toks, pool, pos, tables, attn_impl,
            write_mask=i <= n_prop, tp_axis=tp_axis)
        nxt, key = _sample_next(logits, temps, key)
        with jax.named_scope(scopes.SAMPLE):
            ys = ((nxt, jax.nn.softmax(_scale_by_temps(logits, temps),
                                       axis=-1)) if need_probs else nxt)
        return (nxt, pos + 1, pool, key), ys

    carry0 = (tokens, positions, pool, key)
    # The k+1th step exists only for its K/V write; its sampled token /
    # probs row is the (k+1)th proposal nobody verifies.
    if need_probs:
        (_, _, pool, _), (toks_out, probs_out) = jax.lax.scan(
            step, carry0, jnp.arange(k + 1))
        return toks_out[:k], probs_out[:k], pool
    (_, _, pool, _), toks_out = jax.lax.scan(
        step, carry0, jnp.arange(k + 1))
    return toks_out[:k], None, pool


def _no_phase(_name: str):
    return contextlib.nullcontext()


@jax.jit
def join_window(carried_mask, carried, tokens, positions):
    """The inputs of a window's first NEW step where a step of the last
    window is still in flight (`_decode_window`): a slot that step covers
    (`carried_mask`) feeds the token it sampled, `carried`, still on the
    device, at the position after the one it wrote; every other slot (one
    whose prompt graduated this tick) feeds the host's `tokens` at the
    host's `positions`. [B]-sized, one program an engine.
    → (tokens [B] int32, positions [B] int32)."""
    return (jnp.where(carried_mask, carried, tokens),
            positions + carried_mask.astype(positions.dtype))


@jax.jit
def snapshot(leaves):
    """A copy of small device values (a family's running counters) that
    outlives the donation of the pool they are leaves of."""
    return jax.tree.map(jnp.copy, leaves)


def _decode_window(step, tokens, pool, positions, n_steps: int, key,
                   phase=_no_phase, also=None, *, carried=None, ahead=None):
    """`n_steps` back-to-back dispatches of one jitted step program
    (`step(tokens, pool, positions, key)` → the same four, advanced).
    Tokens, cursors and the donated pool stay on the device between
    dispatches; all of them are queued before anything is read. The
    window's tokens are then fetched and stacked on the HOST — where the
    engine wants them anyway — so the window compiles nothing of its
    own: a device-side stack would be one more small program per
    (n_steps, B). → (tokens_out [rows, B] int32 numpy, updated pool,
    what `also` named or None).

    One step in flight across the boundary: with `ahead` (a callable)
    ONE more run of the step is queued after the `n_steps`, fed by the
    last one's device-resident tokens, cursors, pool and key, and its
    tokens and key are handed to `ahead(tokens, key)` UNFETCHED. The
    fetch asks for the `n_steps` arrays only, so it returns when step
    `n_steps` is done and the device runs the extra step while the host
    emits, admits and plans. The caller passes those tokens back as the
    next call's `carried` (its `tokens` input joined from them,
    `join_window`; its `key` the one handed over): they come back as the
    first row of that call's `tokens_out`, so rows = `n_steps` + (1 with
    `carried`). Everything goes down ONE in-order queue with the pool
    donated from program to program, which is why the extra step may run
    on slots the host releases meanwhile: whatever the host dispatches
    next runs after it.

    `phase(name)` is the caller's recorder, a context manager factory
    (`LLMEngine._phase`): this loop is the one part of an engine tick the
    engine cannot see into, so each dispatch (the extra one too) reports
    as `decode.dispatch` and the fetch as `decode.pull`.

    `also(pool)` (optional) names device values of the pool as it stands
    after step `n_steps` to fetch in the SAME pull (a family's on-device
    counters; ahead of an extra step, which takes the pool they are
    leaves of, a `snapshot` of them)."""
    out = [] if carried is None else [carried]
    for _ in range(n_steps):
        with phase("decode.dispatch"):
            tokens, positions, pool, key = step(tokens, pool, positions, key)
        out.append(tokens)
    extra = None if also is None else also(pool)
    if ahead is not None:
        with phase("decode.dispatch"):
            if extra is not None:
                extra = snapshot(extra)
            tokens, _positions, pool, key = step(tokens, pool, positions, key)
        ahead(tokens, key)
    with phase("decode.pull"):
        out, extra = jax.device_get((out, extra))
    return np.stack(out), pool, extra


def paged_programs(chunk_forward, decode_once, chunk_logits,
                   counter_names=None):
    """The four serving programs of a model family, from what the family
    writes: a chunk forward, a decode step, and a chunk head.

    `chunk_forward(cfg, params, tokens, pool, tables, offsets, n_valid,
    attn_impl=, **rows)` → (hidden states [N, C, D], updated pool);
    `decode_once(cfg, params, tokens, pool, positions, tables, attn_impl)`
    → (logits [B, V] fp32, updated pool): all B slots advance one token;
    `chunk_logits(cfg, params, x, n_valid)` → [N, V] fp32: each chunk
    row's logits at its last valid token;
    `counter_names` (optional): what the running uint32 totals that the
    family's `decode_once` keeps in ``pool["moe_counters"]`` count.
    → (prefill_chunk_paged, decode_step_paged, _decode_sample_paged,
    decode_multi_paged), which a family module binds under those names.

    The three jitted ones are nested `def`s because a trace finds a device
    program by its function's name (`jit_prefill_chunk_paged`,
    `jit__decode_sample_paged`, `jit_decode_step_paged`:
    benchmarks/harness/trace_reduce.py), and a functools.partial or a
    lambda would give `jax.jit` none. The pool is donated to each."""

    @functools.partial(jax.jit, static_argnums=(0,),
                       static_argnames=("return_logits", "attn_impl"),
                       donate_argnums=(3,))
    def prefill_chunk_paged(cfg, params, tokens, pool, tables, offsets,
                            n_valid, *, return_logits: bool = True,
                            attn_impl: str = "gather", **rows):
        """Write N chunk rows into their prompts' KV pages, each at its own
        arbitrary token offset (Sarathi/Orca-style chunked prefill; rows
        may be consecutive chunks of one prompt or chunks of different
        ones).

        The compile-count story for prefill: N and C are engine constants
        (N = one of the engine's `chunk_heights`: bucketed by width, the
        full chunks one step's token budget holds, at most n_slots;
        C = the chunk size) and `offsets`/`n_valid` are traced vectors,
        so the table WIDTH is the only shape degree of freedom — one
        program lowers per (table width, ``return_logits``) pair. The
        engine slices tables to the pow-2 width each bucket of rows
        actually attends over (`_pow2_width` of pages covering written
        prefix + chunk), so the grid is the width ladder {1, 2, 4, …,
        max_pages}: at most 2·log₂(max_pages)+2 programs
        (``return_logits`` False for interior-only batches, True when any
        row carries a final chunk, which alone pays the LM head).
        Full-width tables remain valid (the width-bucketing-off control
        arm dispatches exactly the PR 4 two-program grid); attention
        compute/bytes scale with the sliced width, which is the whole
        point for interior chunks of long-max-len prompts.

        tokens: [N, C] (tail chunks padded); tables: [N, width] page ids,
        width ≤ max_pages (pages covering positions ``offsets[i] ..
        offsets[i]+n_valid[i]-1`` must be allocated and fall inside the
        sliced width — the engine's bucket rule guarantees this);
        offsets: [N] — absolute position of tokens[i, 0]; n_valid: [N] —
        valid tokens in row i's chunk (0 = inert row: all writes land on
        the null page and its logits row is garbage the engine ignores);
        `rows`: what the family's chunk forward is told of each row
        beside its table: ``slots=`` [N] int32, the slot a row belongs to
        (an inert row's is ignored), where the pool keeps a state or a
        ring of pages by the slot; nothing for the gpt.

        Queries attend causally over everything their slot has written
        so far: each layer scatters the batch's K/V into its pages FIRST
        (pad / inert rows land on the null page), then reads back through
        the page tables — ``gather`` reconstitutes the contiguous
        timelines (exact-semantics default), ``kernel`` runs the ragged
        prefill Pallas kernel (ops/paged_attention.py) against the pool
        in place. Distinct live slots never share a page, so rows are
        independent.

        → (last-valid-token logits [N, V] fp32 if return_logits else
        None, updated pool)."""
        x, pool = chunk_forward(cfg, params, tokens, pool, tables, offsets,
                                n_valid, attn_impl=attn_impl, **rows)
        if not return_logits:
            return None, pool
        return chunk_logits(cfg, params, x, n_valid), pool

    @functools.partial(jax.jit, static_argnums=(0,),
                       static_argnames=("attn_impl",), donate_argnums=(3,))
    def decode_step_paged(cfg, params, tokens, pool, positions, tables, *,
                          attn_impl: str = "gather"):
        """One token for every slot against the paged pool.
        → (logits [B, V] fp32, updated pool)."""
        return decode_once(cfg, params, tokens, pool, positions, tables,
                           attn_impl)

    @functools.partial(jax.jit, static_argnums=(0,),
                       static_argnames=("attn_impl",), donate_argnums=(3,))
    def _decode_sample_paged(cfg, params, tokens, pool, positions, tables,
                             temps, key, *, attn_impl: str = "gather"):
        """One decode-window step: `decode_once` + on-device sampling.
        → (next tokens [B] int32, positions + 1, updated pool, advanced
        key)."""
        logits, pool = decode_once(cfg, params, tokens, pool, positions,
                                   tables, attn_impl)
        nxt, key = _sample_next(logits, temps, key)
        return nxt, positions + 1, pool, key

    def decode_multi_paged(cfg, params, tokens, pool, positions, tables,
                           n_steps: int, temps, key, *,
                           attn_impl: str = "gather", phase=_no_phase,
                           counters=None, carried=None, ahead=None):
        """`n_steps` paged-decode steps with on-device sampling (the
        engine pre-allocates pages covering every position the window
        writes before dispatch, so tables are static across the
        window), plus the step `ahead` asks
        for and after the row `carried` brings (`_decode_window`).
        `counters(dict)` (optional, a family with `counter_names`) is
        handed the pool's running counters as they stand after the
        window's `n_steps`, fetched WITH the window's tokens (what the
        step `ahead` asks for counts arrives with the next window's).
        → (tokens_out [rows, B] int32, updated pool).

        The window is a `_decode_window` of ONE step program, not one
        program scanning over steps. It became that in PR 21, when the
        pool was ``[..., H, K]``: a scan over steps around the scan over
        layers made the TPU compiler re-lay the whole pool out for the
        outer loop (head_dim 64 padded to 128 lanes there — a 2x copy of
        a 6.4 GB pool), which did not fit a 16 GB chip at OPT-1.3B. The
        lane-dense pool has no padded layout to fall into, so that reason
        is gone; the window stays dispatches of one step because a
        dispatch costs 0.45 ms against a 9 ms step (PERF.md section 5),
        and because a step is the unit the engine keeps in flight while
        it reads a window's tokens, which a fused window could not split
        off. The only program a window compiles is that step
        (`_decode_sample_paged`), one per table width, whatever n_steps
        is: under the engine's compile_watch label `decode_multi_paged`,
        `jax_compiles_total{fn}` counts table widths. What it costs is a
        host dispatch a step where the scan paid one a window."""

        def step(toks, kv, pos, rng):
            return _decode_sample_paged(cfg, params, toks, kv, pos, tables,
                                        temps, rng, attn_impl=attn_impl)

        also = (lambda pool: pool["moe_counters"]) if counter_names else None
        toks_out, pool, totals = _decode_window(
            step, tokens, pool, positions, n_steps, key, phase, also,
            carried=carried, ahead=ahead)
        if counters is not None:
            counters(dict(zip(counter_names, (int(t) for t in totals))))
        return toks_out, pool

    return (prefill_chunk_paged, decode_step_paged, _decode_sample_paged,
            decode_multi_paged)


# The gpt's own four. Its chunk head runs over every position of a row
# and then takes the last valid one (`_last_valid_logits`), it is told
# nothing of a row beside its table, and its decode step counts nothing.
(prefill_chunk_paged, decode_step_paged, _decode_sample_paged,
 decode_multi_paged) = paged_programs(
    _chunk_paged_forward, _decode_once_paged, _last_valid_logits)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("k", "attn_impl", "need_probs"),
                   donate_argnums=(3,))
def spec_draft_propose(cfg: GPTConfig, params, tokens, pool, positions,
                       tables, n_prop, temps, key, *, k: int,
                       attn_impl: str = "gather", need_probs: bool = True):
    """Fused speculative draft loop: k+1 draft decode steps with
    on-device sampling against the DRAFT's page pool, sharing the
    target's page tables (the draft owns no pages — its pool rows at
    the same page ids mirror the target's token layout, so target-side
    allocation, COW, prefix sharing, and rollback govern both).

    Step 0 feeds each slot's pending token at its decode cursor
    (`positions`); step i samples proposal d_i from the previous step's
    logits and feeds it at cursor+i, writing the draft's K/V as it
    goes. The scan runs ONE extra step (k+1 total) purely for its
    write: it lands d_k's draft K/V at cursor+k, so after an
    all-accepted tick the draft cursor still equals the target cursor
    and the next tick needs no catch-up pass — the invariant that keeps
    this whole loop a single fixed-shape dispatch per tick (one
    program per (k, attn_impl, need_probs), no host round trips
    inside).

    tokens: [B] pending token per slot; positions: [B] decode cursor;
    n_prop: [B] per-slot proposal budget (step i's write is routed to
    the null page when i > n_prop[b]; -1 = fully inert row); temps: [B]
    sampling temperature (0 = greedy argmax, as `_sample_next`).

    → (proposals [k, B] int32, draft probs [k, B, V] fp32 — the
    temperature-scaled softmax row each proposal was sampled from,
    exactly the q(x) rejection sampling divides by, or None when
    ``need_probs`` is False — and the updated draft pool).

    ``need_probs=False`` (an all-greedy tick, where acceptance is
    argmax-chain matching and nothing reads q) drops the softmax +
    [k, B, V] scan-stack from the program entirely — a second variant
    per (k, attn_impl), the same two-variant bargain
    prefill_chunk_paged strikes with ``return_logits``.
    """
    return _spec_propose_scan(cfg, params, tokens, pool, positions,
                              tables, n_prop, temps, key, k, attn_impl,
                              need_probs)


# --------------------------------------------------------------------------
# Tensor-parallel twins (llm_tp > 1): the SAME bodies as above, run
# per-shard over a 1-axis ("tp",) mesh via utils/jax_compat.shard_map.
# Params shard per models/gpt.py::partition_rules and the page pool
# shards along its minor H*K axis, heads major
# (KV_POOL_PARTITION_RULES below) — each
# shard owns every page id for n_heads/tp heads, so page tables,
# cursors, and the host-side allocator are shard-invariant and both
# attention impls (including the Pallas kernels, which derive H from
# the arrays) run unchanged on their slice. Only the per-layer
# attention-out / MLP-down psums cross shards; logits, argmax, and
# sampling are computed replicated. The engine binds `mesh` once at
# init (functools.partial), so call sites are identical to the non-tp
# dispatch table.
# --------------------------------------------------------------------------

# Pool pytree {"k": [L, P+1, ps, H*K], "v": ...} → the minor axis
# (axis 3, heads major within it) shards over tp: a shard's
# [..., (H/tp)*K] lanes are its own whole, contiguous heads. Lives here
# (not partition.py) because the pool layout is this module's contract;
# the axis name comes from partition.TP_AXIS.
def _kv_pool_partition_rules():
    from jax.sharding import PartitionSpec

    from ray_tpu.models.partition import TP_AXIS

    # Scale planes [L, P+1] are REPLICATED: one per-page scalar covers
    # every head, and _quant_write pmax's the scale contribution across
    # head shards, so each shard's copy stays identical by construction.
    return ((r"^(k|v)$",
             PartitionSpec(None, None, None, TP_AXIS)),
            (r"^(k|v)_scale$", PartitionSpec()))


KV_POOL_PARTITION_RULES = _kv_pool_partition_rules()


def _tp_specs(params, pool):
    """(param specs, pool specs, replicated spec) for one shard_map."""
    from jax.sharding import PartitionSpec

    from ray_tpu.models.gpt import partition_rules
    from ray_tpu.models.partition import match_partition_rules

    return (match_partition_rules(partition_rules(), params),
            match_partition_rules(KV_POOL_PARTITION_RULES, pool),
            PartitionSpec())


def _smap(body, mesh, in_specs, out_specs):
    """shard_map through the jax_compat shim. check_vma off: the bodies
    hold Pallas calls and scans whose replication 0.4.x cannot infer;
    replication of the PS() outputs is by construction (every shard
    computes them from replicated operands)."""
    from ray_tpu.utils.jax_compat import shard_map

    return shard_map(body, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("mesh", "return_logits", "attn_impl"),
                   donate_argnums=(3,))
def prefill_chunk_paged_tp(cfg: GPTConfig, params, tokens, pool, tables,
                           offsets, n_valid, *, mesh,
                           return_logits: bool = True,
                           attn_impl: str = "gather"):
    """`prefill_chunk_paged` over a tp mesh: the chunk body runs
    per-head-shard; the LM head (replicated weights, replicated hidden
    states after the body's psums) runs outside the shard_map so the
    logits row selection is identical to the single-shard program.
    Tables ride through replicated (pages are indexed by id; only the
    head dim is sliced) — width-bucketed table views cost one program
    per pow-2 width here exactly as in the single-shard twin."""
    if attn_impl not in ("gather", "kernel"):
        raise ValueError(
            f"attn_impl must be gather|kernel, got {attn_impl!r}")
    pspecs, kvspecs, rep = _tp_specs(params, pool)

    def body(params, tokens, pool, tables, offsets, n_valid):
        return _chunk_paged_forward(cfg, params, tokens, pool, tables,
                                    offsets, n_valid, attn_impl,
                                    tp_axis="tp")

    x, pool = _smap(body, mesh,
                    (pspecs, rep, kvspecs, rep, rep, rep),
                    (rep, kvspecs))(
        params, tokens, pool, tables, offsets, n_valid)
    if not return_logits:
        return None, pool
    return _last_valid_logits(cfg, params, x, n_valid), pool


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("mesh", "attn_impl"),
                   donate_argnums=(3,))
def verify_chunk_paged_tp(cfg: GPTConfig, params, tokens, pool, tables,
                          offsets, n_valid, *, mesh,
                          attn_impl: str = "gather"):
    """`verify_chunk_paged` over a tp mesh (same body/head split as
    `prefill_chunk_paged_tp`; every position pays the replicated head;
    tables may be width-sliced exactly as in the single-shard twin)."""
    if attn_impl not in ("gather", "kernel"):
        raise ValueError(
            f"attn_impl must be gather|kernel, got {attn_impl!r}")
    pspecs, kvspecs, rep = _tp_specs(params, pool)

    def body(params, tokens, pool, tables, offsets, n_valid):
        return _chunk_paged_forward(cfg, params, tokens, pool, tables,
                                    offsets, n_valid, attn_impl,
                                    tp_axis="tp")

    x, pool = _smap(body, mesh,
                    (pspecs, rep, kvspecs, rep, rep, rep),
                    (rep, kvspecs))(
        params, tokens, pool, tables, offsets, n_valid)
    with jax.named_scope(scopes.HEAD):
        return _head(params, cfg, x), pool                 # [N, C, V]


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("mesh", "attn_impl"),
                   donate_argnums=(3,))
def decode_step_paged_tp(cfg: GPTConfig, params, tokens, pool, positions,
                         tables, *, mesh, attn_impl: str = "gather"):
    """`decode_step_paged` over a tp mesh. The head runs inside the
    shard_map on replicated hidden states (deterministic → identical on
    every shard), so the returned logits are replicated."""
    if attn_impl not in ("gather", "kernel"):
        raise ValueError(
            f"attn_impl must be gather|kernel, got {attn_impl!r}")
    pspecs, kvspecs, rep = _tp_specs(params, pool)

    def body(params, tokens, pool, positions, tables):
        return _decode_once_paged(cfg, params, tokens, pool, positions,
                                  tables, attn_impl, tp_axis="tp")

    return _smap(body, mesh,
                 (pspecs, rep, kvspecs, rep, rep),
                 (rep, kvspecs))(
        params, tokens, pool, positions, tables)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("mesh", "attn_impl"),
                   donate_argnums=(3,))
def _decode_sample_paged_tp(cfg: GPTConfig, params, tokens, pool, positions,
                            tables, temps, key, *, mesh,
                            attn_impl: str = "gather"):
    """`_decode_sample_paged` over a tp mesh: the decode pass AND the
    sampling run inside one shard_map. Sampling consumes replicated
    logits with a replicated key, so every shard draws the same token;
    the only cross-shard values are the per-layer psums."""
    if attn_impl not in ("gather", "kernel"):
        raise ValueError(
            f"attn_impl must be gather|kernel, got {attn_impl!r}")
    pspecs, kvspecs, rep = _tp_specs(params, pool)

    def body(params, tokens, pool, positions, tables, temps, key):
        logits, pool = _decode_once_paged(
            cfg, params, tokens, pool, positions, tables, attn_impl,
            tp_axis="tp")
        nxt, key = _sample_next(logits, temps, key)
        return nxt, positions + 1, pool, key

    return _smap(body, mesh,
                 (pspecs, rep, kvspecs, rep, rep, rep, rep),
                 (rep, rep, kvspecs, rep))(
        params, tokens, pool, positions, tables, temps, key)


def decode_multi_paged_tp(cfg: GPTConfig, params, tokens, pool, positions,
                          tables, n_steps: int, temps, key, *, mesh,
                          attn_impl: str = "gather", phase=_no_phase,
                          carried=None, ahead=None):
    """`decode_multi_paged` over a tp mesh: the same `_decode_window`
    of the sharded step program."""

    def step(toks, kv, pos, rng):
        return _decode_sample_paged_tp(
            cfg, params, toks, kv, pos, tables, temps, rng,
            mesh=mesh, attn_impl=attn_impl)

    return _decode_window(step, tokens, pool, positions, n_steps, key, phase,
                          carried=carried, ahead=ahead)[:2]


@functools.partial(jax.jit, static_argnames=("mesh",), donate_argnums=(0,))
def copy_pages_tp(pool, src, dst, *, mesh):
    """`copy_pages` over a tp mesh: page ids are shard-invariant and the
    copy never touches the head axis, so each shard duplicates its own
    head slice of the pages — COW semantics identical to single-shard."""
    _, kvspecs, rep = _tp_specs({}, pool)

    def body(pool, src, dst):
        return {k: v.at[:, dst].set(v[:, src]) for k, v in pool.items()}

    return _smap(body, mesh, (kvspecs, rep, rep), kvspecs)(pool, src, dst)


@functools.partial(jax.jit, static_argnames=("mesh",))
def gather_pages_tp(pool, pages, *, mesh):
    """`gather_pages` over a tp mesh: each shard reads its own head
    slice of the requested pages; the output rides the pool's sharded
    specs, so a host-side ``np.asarray`` on the result reassembles the
    FULL-head page planes — the donation path stays tp-invariant at the
    payload level and the per-shard split happens on host (see
    partition.split_head_planes)."""
    _, kvspecs, rep = _tp_specs({}, pool)

    def body(pool, pages):
        return {k: v[:, pages] for k, v in pool.items()}

    return _smap(body, mesh, (kvspecs, rep), kvspecs)(pool, pages)


@functools.partial(jax.jit, static_argnames=("mesh",), donate_argnums=(0,))
def scatter_pages_tp(pool, pages, payload, *, mesh):
    """`scatter_pages` over a tp mesh: the full-head payload shards
    along the same head-axis specs as the pool, so each shard writes
    exactly its head slice — an adopter at ANY tp degree re-slices a
    donated full-head payload per its own mesh at bind time (the
    resharding-adoption contract). Padding convention matches the
    single-shard twin (null-page ids + zero payloads)."""
    _, kvspecs, rep = _tp_specs({}, pool)

    def body(pool, pages, payload):
        return {k: pool[k].at[:, pages].set(payload[k]) for k in pool}

    return _smap(body, mesh, (kvspecs, rep, kvspecs), kvspecs)(
        pool, pages, payload)


@functools.partial(jax.jit, static_argnums=(0,),
                   static_argnames=("k", "attn_impl", "need_probs", "mesh"),
                   donate_argnums=(3,))
def spec_draft_propose_tp(cfg: GPTConfig, params, tokens, pool, positions,
                          tables, n_prop, temps, key, *, k: int, mesh,
                          attn_impl: str = "gather",
                          need_probs: bool = True):
    """`spec_draft_propose` over a tp mesh: the fused k+1-step draft
    loop (decode body + on-device sampling + budget write-masking —
    `_spec_propose_scan`, the non-tp program's own body with tp_axis
    threaded) runs inside one shard_map against the head-sharded DRAFT
    pool, sharing the replicated target page tables. Proposals and
    probs come back replicated; the draft pool stays sharded."""
    pspecs, kvspecs, rep = _tp_specs(params, pool)

    def body(params, tokens, pool, positions, tables, n_prop, temps, key):
        toks_out, probs_out, pool = _spec_propose_scan(
            cfg, params, tokens, pool, positions, tables, n_prop, temps,
            key, k, attn_impl, need_probs, tp_axis="tp")
        if need_probs:
            return toks_out, probs_out, pool
        return toks_out, pool       # probs_out is None: not a leaf for
                                    # shard_map's out_specs to carry

    if need_probs:
        return _smap(body, mesh,
                     (pspecs, rep, kvspecs, rep, rep, rep, rep, rep),
                     (rep, rep, kvspecs))(
            params, tokens, pool, positions, tables, n_prop, temps, key)
    toks_out, pool = _smap(
        body, mesh,
        (pspecs, rep, kvspecs, rep, rep, rep, rep, rep),
        (rep, kvspecs))(
        params, tokens, pool, positions, tables, n_prop, temps, key)
    return toks_out, None, pool


__all__ = [
    "init_paged_kv", "copy_pages", "gather_pages", "scatter_pages",
    "prefill_chunk_paged", "verify_chunk_paged", "spec_draft_propose",
    "decode_step_paged", "decode_multi_paged", "join_window", "snapshot",
    "paged_programs", "scan_pool_layers",
    "KV_POOL_PARTITION_RULES", "prefill_chunk_paged_tp",
    "verify_chunk_paged_tp", "decode_step_paged_tp",
    "decode_multi_paged_tp", "copy_pages_tp", "spec_draft_propose_tp",
    "gather_pages_tp", "scatter_pages_tp",
]
