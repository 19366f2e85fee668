"""Ragged paged-attention decode kernel (Pallas TPU).

The serve engine's paged KV read was gather semantics: every decode step
reconstituted each slot's contiguous ``[B, T, H, K]`` timeline from the
page pool per layer (models/paged_kv.py), costing three KV passes over HBM
(pool gather-read + timeline write + attention re-read) and lowering to
XLA gathers instead of page-granular DMA — the engine-side decode gap
measured in VERDICT.md weak #2 (311 tok/s vs an ~4 ms/step weight-traffic
roofline at OPT-1.3B bf16 B=16). This kernel is the decode twin of the
training flash kernel (ops/attention.py): it reads K/V pages **in place**
from the pool and fuses QK → online softmax → V, so no timeline is ever
materialized in HBM.

Design notes:
- Grid is (batch-slot, kv-page) with ``PrefetchScalarGridSpec``
  (num_scalar_prefetch=2): the page table ``[B, n_pg]`` and per-slot kv
  lengths ``[B]`` land in SMEM before the body runs, so the K/V BlockSpec
  index maps can select block ``(tables[b, j], ...)`` — the page id IS the
  block index into the pool. Each grid step DMAs exactly one page.
- Online-softmax state (m, l, acc) lives in VMEM scratch across the kv
  dimension ("arbitrary" grid semantics), exactly like the flash kernel.
- Null / past-length pages: unallocated table tail entries are 0 (the
  reserved null page, models/paged_kv.py), so their index maps repeat
  block 0 and Pallas's revisit elision fetches it at most once;
  ``pl.when(j*ps < len)`` skips their compute entirely. In-page
  raggedness (a slot ending mid-page) is position-masked like the flash
  kernel's kv_len mask.
- Softmax statistics stay fp32; the QKᵀ/PV contractions run in the input
  dtype with fp32 accumulate (MXU fast path — upcasting operands would
  drop the MXU into its ~4x slower fp32 mode).
- On non-TPU backends the kernels run under ``interpret=True`` (same
  pattern as ops/attention.py): that checks their semantics, not that
  Mosaic can compile them — compile acceptance is
  tests/test_chip_compile.py, numbers on hardware chip_smoke.py. A
  broken pallas install fails loudly in CI instead of silently skipping.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _decode_kernel(
    *refs,
    sm_scale, page_size, n_pg, quantized=False,
):
    # Ref order: scalar-prefetch (SMEM) first — page tables, kv lengths,
    # and (quantized pools only) the layer's per-page K/V scale vectors —
    # then VMEM blocks (q, k, v), the output, and the (m, l, acc)
    # scratch. `quantized` is a Python-level trace switch: the bf16
    # program is untouched and the int8 program dequants each page right
    # after its DMA, inside the kernel — the fp32 plane never exists in
    # HBM.
    if quantized:
        (tables_ref, lengths_ref, ks_ref, vs_ref,
         q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref) = refs
    else:
        (tables_ref, lengths_ref, q_ref, k_ref, v_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = lengths_ref[b]

    def _compute():
        q = q_ref[0]                         # [H, K]
        k = k_ref[0]                         # [ps, H, K]
        v = v_ref[0]
        if quantized:
            page = tables_ref[b, j]
            k = k.astype(jnp.float32) * ks_ref[page]
            v = v.astype(jnp.float32) * vs_ref[page]
        # s[h, t] = q[h] · k[t, h] — a per-head batched matvec; decode
        # attention is HBM-bound (~2 flops/byte), so MXU shape efficiency
        # is irrelevant next to reading the page once. Written as the
        # prefill kernel's contraction with a one-row query block:
        # Mosaic's matmul needs a non-contracting lhs dimension, and
        # refused the bare "hk,thk->ht".
        s = jnp.einsum("chk,thk->cht", q[None], k,
                       preferred_element_type=jnp.float32)[0] * sm_scale
        # In-page raggedness: positions at or past the slot's kv length
        # are masked (covers the null page when it IS the write target of
        # an idle slot, and a live slot's partial last page).
        tpos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(tpos < kv_len, s, NEG_INF)

        m_prev = m_ref[...]                  # [H, LANES] (uniform rows)
        row_max = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, row_max)
        p = jnp.exp(s - m_new[:, :1])        # [H, ps] fp32
        corr = jnp.exp(m_prev[:, :1] - m_new[:, :1])
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jnp.einsum("cht,thk->chk", p.astype(v.dtype)[None], v,
                        preferred_element_type=jnp.float32)[0]
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    # Skip pages entirely past the slot's kv length — the whole null tail
    # of the table does no compute (its repeated block-0 index map also
    # elides the DMA after the first fetch).
    pl.when(j * page_size < kv_len)(_compute)

    @pl.when(j == n_pg - 1)
    def _finish():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def paged_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    tables: jax.Array,
    lengths: jax.Array,
    *,
    sm_scale: float | None = None,
    interpret: bool | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Single-token decode attention straight against the KV page pool.

    Args:
      q: [B, H, K] — each slot's current-token query (post-rotary).
      k_pool, v_pool: [P, page_size, H, K] — ONE layer's page pool (row 0
        is the reserved null page). May be int8 (quantized serving), in
        which case ``k_scale``/``v_scale`` must carry the layer's
        per-page scale vectors [P] — they ride the scalar-prefetch path
        next to the page table, and each page is dequanted in VMEM right
        after its DMA (the fp32 plane never exists in HBM).
      tables: [B, n_pg] int32 page ids per slot (unallocated tail = 0).
      lengths: [B] int32 valid kv positions per slot (= position + 1; the
        current token's K/V must already be written to its page).
    Returns [B, H, K] in q.dtype. Numerics match the gather reference
    within blockwise-fp32-softmax reassociation (see
    ``reference_paged_attention``).
    """
    B, H, K = q.shape
    P, ps, Hp, Kp = k_pool.shape
    if (Hp, Kp) != (H, K) or v_pool.shape != k_pool.shape:
        raise ValueError(
            f"pool/query shape mismatch: q {q.shape}, k_pool {k_pool.shape},"
            f" v_pool {v_pool.shape}")
    n_pg = tables.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(K)
    if interpret is None:
        interpret = _interpret_default()
    tables = tables.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    quantized = k_scale is not None

    kernel = functools.partial(
        _decode_kernel, sm_scale=sm_scale, page_size=ps, n_pg=n_pg,
        quantized=quantized)
    if quantized:
        prefetch = (tables, lengths, k_scale.astype(jnp.float32),
                    v_scale.astype(jnp.float32))
        im_q = lambda b, j, tbl, lens, ks, vs: (b, 0, 0)
        im_kv = lambda b, j, tbl, lens, ks, vs: (tbl[b, j], 0, 0, 0)
    else:
        prefetch = (tables, lengths)
        im_q = lambda b, j, tbl, lens: (b, 0, 0)
        im_kv = lambda b, j, tbl, lens: (tbl[b, j], 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, n_pg),
        in_specs=[
            pl.BlockSpec((1, H, K), im_q),
            pl.BlockSpec((1, ps, H, K), im_kv),
            pl.BlockSpec((1, ps, H, K), im_kv),
        ],
        out_specs=pl.BlockSpec((1, H, K), im_q),
        scratch_shapes=[
            pltpu.VMEM((H, _LANES), jnp.float32),
            pltpu.VMEM((H, _LANES), jnp.float32),
            pltpu.VMEM((H, K), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, K), q.dtype),
        interpret=interpret,
        name="paged_decode_attn",
    )(*prefetch, q, k_pool, v_pool)


def _prefill_kernel(
    *refs,
    sm_scale, page_size, n_pg, quantized=False,
):
    """Ragged chunked-prefill attention: one query BLOCK (a prompt chunk at
    an arbitrary token offset) against the slot's page pool. The decode
    kernel's twin with a C-sized query dimension: same scalar-prefetch page
    table (the page id IS the DMA block index), same online-softmax (m, l,
    acc) VMEM state across the kv-page grid axis — plus the causal mask
    INSIDE the chunk (tpos <= query's absolute position), which is what
    lets the chunk's own K/V be written to the pool before the kernel runs
    and then read back like any earlier page. Ref order mirrors
    `_decode_kernel`: scalar-prefetch (tables, offsets, lengths, and for
    int8 pools the per-page K/V scale vectors) first, then VMEM blocks;
    `quantized` dequants each page in VMEM right after its DMA."""
    if quantized:
        (tables_ref, offsets_ref, lengths_ref, ks_ref, vs_ref,
         q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref) = refs
    else:
        (tables_ref, offsets_ref, lengths_ref, q_ref, k_ref, v_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = lengths_ref[b]
    q_off = offsets_ref[b]

    def _compute():
        q = q_ref[0]                         # [C, H, K]
        k = k_ref[0]                         # [ps, H, K]
        v = v_ref[0]
        if quantized:
            page = tables_ref[b, j]
            k = k.astype(jnp.float32) * ks_ref[page]
            v = v.astype(jnp.float32) * vs_ref[page]
        s = jnp.einsum("chk,thk->cht", q, k,
                       preferred_element_type=jnp.float32) * sm_scale
        # Causal within the whole sequence: query row c sits at absolute
        # position q_off + c and may attend tpos <= that. The kv_len bound
        # additionally masks pad rows (c >= this chunk's valid tokens,
        # whose absolute position runs past kv_len) to the valid prefix so
        # their softmax stays finite; their output is discarded host-side.
        tpos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2)
        qpos = q_off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where((tpos <= qpos) & (tpos < kv_len), s, NEG_INF)

        m_prev = m_ref[...]                  # [C, H, LANES] (uniform lanes)
        row_max = jnp.max(s, axis=2, keepdims=True)          # [C, H, 1]
        m_new = jnp.maximum(m_prev, row_max)
        p = jnp.exp(s - m_new[:, :, :1])     # [C, H, ps] fp32
        corr = jnp.exp(m_prev[:, :, :1] - m_new[:, :, :1])
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=2, keepdims=True)
        pv = jnp.einsum("cht,thk->chk", p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    # Pages entirely past the chunk's last valid position do no compute
    # (null-table tail included; its repeated block-0 index map also
    # elides the DMA after the first fetch).
    pl.when(j * page_size < kv_len)(_compute)

    @pl.when(j == n_pg - 1)
    def _finish():
        l = l_ref[:, :, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def paged_prefill_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    tables: jax.Array,
    offsets: jax.Array,
    lengths: jax.Array,
    *,
    sm_scale: float | None = None,
    interpret: bool | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
) -> jax.Array:
    """Chunked-prefill attention straight against the KV page pool.

    Args:
      q: [B, C, H, K] — each slot's chunk of C queries (post-rotary),
        starting at absolute position ``offsets[b]``.
      k_pool, v_pool: [P, page_size, H, K] — ONE layer's page pool (row 0
        is the reserved null page). May be int8 (quantized serving) with
        ``k_scale``/``v_scale`` [P] per-page scale vectors, handled
        exactly as in `paged_attention`.
      tables: [B, n_pg] int32 page ids per slot (unallocated tail = 0).
        n_pg may be a WIDTH-SLICED view of the engine's full page table
        (the pow-2 bucket covering each row's written prefix + chunk):
        the grid is (B, n_pg), so compute and pool-page bytes scale with
        the sliced width — interior chunks of a long-max-len prompt pay
        for the prefix they attend over, not for max_pages.
      offsets: [B] int32 absolute position of q[:, 0].
      lengths: [B] int32 valid kv positions per slot (= offset + valid
        chunk tokens; must satisfy lengths[b] <= n_pg * page_size).
    Returns [B, C, H, K] in q.dtype; rows past a slot's valid chunk tokens
    are defined but meaningless (the engine discards them)."""
    B, C, H, K = q.shape
    P, ps, Hp, Kp = k_pool.shape
    if (Hp, Kp) != (H, K) or v_pool.shape != k_pool.shape:
        raise ValueError(
            f"pool/query shape mismatch: q {q.shape}, k_pool {k_pool.shape},"
            f" v_pool {v_pool.shape}")
    n_pg = tables.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(K)
    if interpret is None:
        interpret = _interpret_default()
    tables = tables.astype(jnp.int32)
    offsets = offsets.astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)
    quantized = k_scale is not None

    kernel = functools.partial(
        _prefill_kernel, sm_scale=sm_scale, page_size=ps, n_pg=n_pg,
        quantized=quantized)
    if quantized:
        prefetch = (tables, offsets, lengths, k_scale.astype(jnp.float32),
                    v_scale.astype(jnp.float32))
        im_q = lambda b, j, tbl, offs, lens, ks, vs: (b, 0, 0, 0)
        im_kv = lambda b, j, tbl, offs, lens, ks, vs: (tbl[b, j], 0, 0, 0)
    else:
        prefetch = (tables, offsets, lengths)
        im_q = lambda b, j, tbl, offs, lens: (b, 0, 0, 0)
        im_kv = lambda b, j, tbl, offs, lens: (tbl[b, j], 0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, n_pg),
        in_specs=[
            pl.BlockSpec((1, C, H, K), im_q),
            pl.BlockSpec((1, ps, H, K), im_kv),
            pl.BlockSpec((1, ps, H, K), im_kv),
        ],
        out_specs=pl.BlockSpec((1, C, H, K), im_q),
        scratch_shapes=[
            pltpu.VMEM((C, H, _LANES), jnp.float32),
            pltpu.VMEM((C, H, _LANES), jnp.float32),
            pltpu.VMEM((C, H, K), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, C, H, K), q.dtype),
        interpret=interpret,
        name="paged_prefill_attn",
    )(*prefetch, q, k_pool, v_pool)


# Speculative-verify reuse: the verify pass of draft-model speculative
# decoding (serve/llm.py) is structurally a ragged chunked-prefill row —
# k+1 tokens (pending + k draft proposals) written at the slot's decode
# cursor, causally masked WITHIN the chunk, attending every earlier page
# through the same scalar-prefetched table. No new kernel exists or is
# needed: the prefill kernel above (and its gather oracle below) IS the
# verify kernel, with C = k+1, reached through the shared chunk body
# (models/paged_kv._chunk_paged_forward); rejected proposals are rolled
# back host-side by rewinding cursors (models/paged_kv.py
# verify_chunk_paged documents why the garbage K/V they leave is inert).

def reference_paged_attention(q, k_pool, v_pool, tables, lengths, *,
                              sm_scale=None, k_scale=None, v_scale=None):
    """Gather-semantics oracle: reconstitute each slot's contiguous
    timeline and run plain-XLA attention — byte-for-byte the math of
    models/paged_kv.py's gather read path (test oracle + fallback).

    int8 pools pass per-page ``k_scale``/``v_scale`` [P]; the dequant
    (page.astype(f32) * scale) mirrors the fused kernel exactly."""
    B, H, K = q.shape
    ps = k_pool.shape[1]
    T = tables.shape[1] * ps
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(K)
    k_view = k_pool[tables]                      # [B, n_pg, ps, H, K]
    v_view = v_pool[tables]
    if k_scale is not None:
        k_view = (k_view.astype(jnp.float32)
                  * k_scale[tables][:, :, None, None, None].astype(jnp.float32))
        v_view = (v_view.astype(jnp.float32)
                  * v_scale[tables][:, :, None, None, None].astype(jnp.float32))
    k_view = k_view.reshape(B, T, H, K)
    v_view = v_view.reshape(B, T, H, K)
    s = jnp.einsum("bhk,bthk->bht", q, k_view,
                   preferred_element_type=jnp.float32) * sm_scale
    mask = jnp.arange(T)[None, :] < lengths[:, None]        # [B, T]
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    # q.dtype out unconditionally: the dequanted v_view is f32, and the
    # einsum's promotion must not leak into callers' scan carries.
    return jnp.einsum("bht,bthk->bhk", probs, v_view).astype(q.dtype)


def reference_paged_prefill_attention(q, k_pool, v_pool, tables, offsets,
                                      lengths, *, sm_scale=None,
                                      k_scale=None, v_scale=None):
    """Gather-semantics oracle for chunked prefill: reconstitute each
    slot's contiguous timeline from the pool and run plain-XLA causal
    attention for a C-query chunk at absolute offset — byte-for-byte the
    math of models/paged_kv.py's chunked-prefill gather path (the
    exact-semantics default off-TPU; also the kernel's test oracle).

    q: [B, C, H, K]; offsets/lengths: [B] (lengths = offset + valid chunk
    tokens). `tables` may be a width-sliced view (see
    `paged_prefill_attention`): the reconstituted timeline T =
    tables.shape[1] · page_size shrinks with the bucket width, so the
    oracle's gather/einsum bytes scale the same way the kernel's grid
    does. → [B, C, H, K] in q.dtype."""
    B, C, H, K = q.shape
    ps = k_pool.shape[1]
    T = tables.shape[1] * ps
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(K)
    k_view = k_pool[tables]                      # [B, n_pg, ps, H, K]
    v_view = v_pool[tables]
    if k_scale is not None:
        k_view = (k_view.astype(jnp.float32)
                  * k_scale[tables][:, :, None, None, None].astype(jnp.float32))
        v_view = (v_view.astype(jnp.float32)
                  * v_scale[tables][:, :, None, None, None].astype(jnp.float32))
    k_view = k_view.reshape(B, T, H, K)
    v_view = v_view.reshape(B, T, H, K)
    s = jnp.einsum("bchk,bthk->bhct", q, k_view,
                   preferred_element_type=jnp.float32) * sm_scale
    tpos = jnp.arange(T)                                    # [T]
    qpos = offsets[:, None] + jnp.arange(C)[None, :]        # [B, C]
    mask = ((tpos[None, None, :] <= qpos[:, :, None])
            & (tpos[None, None, :] < lengths[:, None, None]))  # [B, C, T]
    s = jnp.where(mask[:, None], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    # q.dtype out unconditionally (see reference_paged_attention).
    return jnp.einsum("bhct,bthk->bchk", probs, v_view).astype(q.dtype)


__all__ = [
    "paged_attention", "paged_prefill_attention",
    "reference_paged_attention", "reference_paged_prefill_attention",
]
