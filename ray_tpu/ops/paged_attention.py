"""Ragged paged-attention kernels (Pallas TPU), decode and chunked prefill.

The serve engine's paged KV read was gather semantics: every decode step
reconstituted each slot's contiguous ``[B, T, H, K]`` timeline from the
page pool per layer (models/paged_kv.py), costing three KV passes over HBM
(pool gather-read + timeline write + attention re-read) and lowering to
XLA gathers instead of page-granular DMA. These kernels read K/V pages
**in place** from the pool and fuse QK → online softmax → V, so no
timeline is ever materialized in HBM.

Design notes:
- The pool is the WHOLE plane ``[L, P, page_size, H*K]`` (every layer,
  heads flattened into the minor axis: models/paged_kv.py), never one
  layer's slice of it. The layer index ``[1]``, the page table
  ``[B, n_pg]`` and per-slot kv lengths ``[B]`` are scalar-prefetched
  (``PrefetchScalarGridSpec``) and land in SMEM before the body runs:
  layer and page id ARE the address of a page in the pool. A page
  arrives by its own DMA, as ``page_size`` dense rows of ``H*K`` lanes
  (2,048 at OPT-1.3B): the minor axis is a multiple of 128 lanes for
  every served model, so the chip's own layout of the pool is row-major
  and nothing re-lays it out. Both kernels attend a BLOCK of
  consecutive table columns at a time: `prefill_block_pages` columns in
  the prefill kernel (4 pages = 256 keys at the served shapes),
  `decode_block_pages` in the decode kernel (up to 512 KiB of K a
  block: 16 pages of 32 KB at zaya1-8b, 4 of 128 KB at laguna-s-2.1, 2
  of 256 KB at opt-1.3b, ONE of 480 KB = 64 keys at olmo-hybrid-7b's
  30 x 128 multi-head rows; a latent pool's by its keys: 8 pages of 80
  KB at kimi-k2.6). One page a block is no collapse where the page is
  that wide: its DMA (1.2 us) covers the softmax chain, and the call
  reads 87.6 % of the HBM peak there (PERF.md, PR 60).
- How a page gets to VMEM differs. Prefill: the grid is (row, kv block),
  the pool is handed over once a column of the block, and the K/V
  BlockSpec index map selects block ``(layer, tables[b, j], 0, 0)``:
  Pallas's pipeline fetches it (a table the block does not divide is
  padded with null columns). Decode (PR 41): the pools stay in HBM
  (``memory_space=pl.ANY``), one grid step walks the live blocks of
  every slot in one flat loop, and the kernel starts a
  ``make_async_copy`` a LIVE page, from ``pool[layer, tables[b, c]]``
  straight to that page's rows of a block-sized VMEM buffer, two blocks
  ahead of the one it attends (`_DECODE_BUFFERS`), across slot
  boundaries. A grid step a (slot, block) with a K and a V operand a
  column cost ~0.05 us an operand, live or dead: 4.9 of the call's
  5.6 ms at zaya1-8b, for 2.1 ms of bytes (PERF.md, PR 37). In the flat
  loop a dead column of a live block gets no DMA and is position-masked,
  and a dead block and an idle slot are never visited: no operand, no
  step, no index map.
- The per-head split happens in VMEM, after the read. Decode: the
  slot's query row ``[1, H*K]`` is spread into a block-diagonal
  ``[H, H*K]`` (row h keeps head h's K lanes, zeros elsewhere), so
  ``QKᵀ`` for all heads is ONE matmul against the block's pages stacked
  into one ``[n*ps, H*K]`` operand (the zeros add exactly 0.0 to the
  fp32 accumulation), ``PV`` is one matmul into a ``[H, H*K]``
  accumulator, and the head-h block of row h is what the output keeps.
  The softmax chain (score tile → masked row maximum → ``exp`` → row
  sum → accumulator rescale) is serially dependent and costs the same
  over 64 keys as over 1,024, so it is paid once a block, not once a
  page (PERF.md, PR 37: at a page a step the chain was 45 % of the
  kernel at zaya1-8b's shapes and the grid steps' own bookkeeping the
  rest). Prefill: a static loop over heads, each on its K-lane slice of
  the query chunk and the accumulator and on ONE stack of the
  block's pages' K-lane slices, so a head pays one QKᵀ, one softmax
  update and one PV a block of keys, not a page: at a page a step the
  head's fixed work (a lane reduction for the row maximum, the state's
  read and write, the accumulator's rescale) was ~25x what its 64 keys
  cost the MXU (PERF.md, PR 34).
- Grouped-query attention: the pool's minor axis is ``G*K`` for G KV
  heads, each serving H/G query heads (G = H above). Decode takes the
  query as ``[H, K]`` and puts row h into the lanes of KV head
  h // (H/G) of the same block-diagonal ``[H, G*K]``; prefill's head
  loop reads that KV head's lane slice of the page. G = H runs the code
  it always ran.
- Window layers (`window`, `col_page`): a layer that attends only the
  last `window` keys keeps its K/V in a RING a slot (models/laguna.py),
  so table column c no longer holds logical page c. `col_page[b, c]` is
  the logical page column c holds NOW (-1: none), a key's position is
  ``col_page[b, c] * page_size + i``, and it is attended when
  ``pos - window < key <= pos``. With `window=None` (every caller before
  the window kind) column c is page c and the kernels trace what they
  always did; the window calls carry names of their own in a trace
  (`paged_decode_attn_window`, `paged_prefill_attn_window`). Prefill
  goes by `col_page` (a block whose every column lies wholly outside the
  window is skipped under `pl.when`; columns arrive in ring order, which
  the online softmax does not mind). DECODE has a walk of its own
  (`_window_walk`, `_window_decode_kernel`; PERF.md, PR 61), because the
  page walk was the wrong shape for a ring: 128 live keys in a ring of
  19 columns lay in 3 columns and 2 blocks of 2 (a 192 KB page, the
  512 KiB cap), so a slot paid two score tiles and two softmax chains,
  and the call's compute alone (0.224 ms at mimo-v2-flash's shapes, 128
  slots) outlasted its DMAs alone (0.176 ms: three whole pages a slot at
  714 GB/s). A ring is R consecutive rows of the pool and logical page p
  lies in column p % R (`_check_ring`: the contract models/laguna.py
  `ring_pool` and `_ring_view` keep), so a slot's window is a RUN OF ROWS
  of its ring known from its length alone: the kernel takes the pools as
  planes of rows and fetches, a slot, the rows from the sublane tile its
  first attended key lies in to the tile its last one does (144 rows for
  128 keys, 528 for 512: ONE copy a plane, two runs where the window
  passes the ring's end), as ONE block (`window_block_rows`) with one
  wait, one score tile and one softmax with no state carried. No column
  is looked up, nothing is sought, and `col_page` is not an operand.
- Many query heads (prefill): the query block, accumulator and (m, l)
  state of H heads x C rows pass the kernel's VMEM at 48 or 72 heads of
  128. `prefill_kv_split` then gives the grid a KV-head axis: a grid
  step holds ONE KV head's K lanes of the block's pages and its H/G
  query heads, the same kernel body at n_heads = H/G, n_kv_heads = 1.
- The latent form (`latent=`; models/kimi_k2.py): ONE plane whose row is
  every head's key and, in its first lanes, their value; no head axis,
  no V pool, so a page is fetched once and stands in both matmuls, and
  the 64 heads are the rows of ONE query operand (no block-diagonal
  surplus: 115 operations a byte read, where the other kinds do 16-64).
  At 64 query rows a 128x128 MXU tile pass is half empty whichever
  operand is latched, so a block's two matmuls take the MXU as long as
  its pages take the DMAs, and the decode walk's serial body (score ->
  mask, row maximum, `exp`, fold, cast -> PV -> accumulator, nothing of
  block j+1 begun before block j's is stored) left the call at half its
  limit. Measured on the chip at the cell's shapes (256 slots, 362,000
  cached tokens; PERF.md, PR 57), the parent's body at 4 / 8 / 16 pages
  a block: the whole call 1.082 / 0.887 / 0.815 ms; every DMA left in
  and the compute emptied 0.683 / 0.680 / 0.678 (733 GB/s: the floor of
  any schedule); the DMAs taken out and the compute left 0.878 / 0.675 /
  0.593. So the latent decode call has a body of its own,
  `_latent_decode_kernel` (the prefill side has `_latent_prefill_kernel`):
  the same walk (`_block_walk`), a block sized by its keys
  (`_LATENT_BLOCK_KEYS`, 8 pages of 64), and TWO blocks in flight
  through the compute: an iteration scores the NEXT live block and
  runs the softmax chain and PV of THIS one in one basic block, the
  next block's scores carried in VMEM scratch, a fourth page buffer
  keeping two blocks ahead of the one waited for, `sm_scale` folded
  into the query once a slot. Compute alone 0.545 ms, the whole call
  0.778 at 8 pages (0.843 at 4, 0.793 at 16). What chooses the body is
  the pool's form the caller hands over (`latent is not None`).
- Online-softmax state (m, l, acc) lives in VMEM scratch across the kv
  blocks of a row or slot, exactly like the flash kernel.
  Both kernels keep m lane-uniform and use it at full width, and keep l
  as a partial sum a lane that is summed over lanes once, after the
  last block: cutting a [C, 1] column out of the state and spreading
  it again, and a second lane reduction a block, were most of a head's
  time once the block was wide.
- Null / past-length pages. Prefill: unallocated table tail entries are
  0 (the reserved null page, models/paged_kv.py), so their index maps
  repeat one block and Pallas's revisit elision fetches it at most
  once; ``pl.when`` skips the compute of a block whose first key is
  past the length. Decode: a dead column's table entry is never read as
  a page. In both, in-page raggedness (a slot ending mid-page) and the
  dead columns of a live block are position-masked like the flash
  kernel's kv_len mask. The decode buffers' dead rows keep what an
  earlier block left there: their probabilities are 0, and 0 x NaN is
  NaN in PV, so V's buffers are zeroed once a call and only pool pages
  land in them after (an int8 block's dead rows dequant by a scale of 0).
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
import types

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128
# Contract the minor axis of both operands: a @ b.T without the transpose.
_NT = (((1,), (1,)), ((), ()))


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _check_pool(q_heads, q_head_dim, k_pool, v_pool):
    """-> (page_size, G, Kv) of whole-pool planes ``[L, P, ps, G*K]`` and
    ``[L, P, ps, G*Kv]``: G KV heads whose keys have the query's head
    size, each serving H/G query heads (G = H is multi-head attention),
    and whose values are Kv wide (= K for every family but one)."""
    GK = k_pool.shape[3] if k_pool.ndim == 4 else 0
    G = GK // q_head_dim
    if (G < 1 or G * q_head_dim != GK or q_heads % G
            or v_pool.shape[:3] != k_pool.shape[:3]
            or v_pool.shape[3] % G):
        raise ValueError(
            f"pool/query shape mismatch: q heads x head_dim "
            f"{q_heads}x{q_head_dim}, k_pool {k_pool.shape}, "
            f"v_pool {v_pool.shape} (want [L, P, page_size, G*K] and "
            f"[L, P, page_size, G*Kv] with G dividing the query heads)")
    return k_pool.shape[2], G, v_pool.shape[3] // G


def _check_latent(q_head_dim, kv_pool, v_pool, latent, *others):
    """-> (page_size, 1, `latent`) of ONE plane ``[L, P, ps, K]`` whose
    row is a token's key (all K lanes, the query's head size) and its
    value (the first `latent` lanes): a latent cache has no head axis and
    no V pool, and takes neither an int8 pool, a window nor a sink
    (`others`: those operands, all None)."""
    if (v_pool is not None or kv_pool.ndim != 4
            or kv_pool.shape[3] != q_head_dim or q_head_dim % _LANES
            or not 0 < latent <= q_head_dim or latent % _LANES
            or any(o is not None for o in others)):
        raise ValueError(
            f"latent form: want ONE bf16 plane [L, P, page_size, K] with K "
            f"the query's head size {q_head_dim}, K and the value whole "
            f"lane tiles of {_LANES} (a row of 576 values lies in 640 "
            f"lanes on the chip whatever the array says, and a DMA takes "
            f"whole tiles), no V pool (got latent={latent}, pool "
            f"{kv_pool.shape}), and no scales, window or sink")
    return kv_pool.shape[2], 1, latent


def _prefetch(layer, scalars, k_scale, v_scale):
    """Scalar-prefetch operands, SMEM order: layer [1], `scalars` (page
    tables and per-slot vectors), then for an int8 pool THIS layer's
    per-page scale rows [P] (cut out of the [L, P] planes here: 2 KB)."""
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    ops = (layer,) + tuple(s.astype(jnp.int32) for s in scalars)
    if k_scale is not None:
        ops += tuple(jax.lax.dynamic_index_in_dim(
            s, layer[0], 0, keepdims=False).astype(jnp.float32)
            for s in (k_scale, v_scale))
    return ops


def _check_window(window, col_page, tables, quantized):
    if (window is None) != (col_page is None):
        raise ValueError("`window` and `col_page` go together: a window "
                         "layer's ring says which page each column holds")
    if window is not None and (quantized
                               or col_page.shape != tables.shape):
        raise ValueError(
            f"a window call takes a bf16 pool and col_page shaped like "
            f"tables {tables.shape}, got {col_page.shape}"
            + (" and an int8 pool" if quantized else ""))


def _check_ring(tables, col_page, lengths, page_size):
    """The window decode call's contract, refused where it can be read
    (outside a trace; inside one it is the caller's, and
    models/laguna.py `ring_pool` and `_ring_view` keep it): a slot's ring
    is R CONSECUTIVE rows of the pool, ``tables[b, c] == tables[b, 0] +
    c``, and logical page p of the slot lies in column p % R, so that
    `col_page` is the view the slot's own length gives: with
    ``last_page = (lengths - 1) // page_size``, column c holds page
    ``last_page - (last_page - c) % R``, -1 where that is negative. The
    kernel fetches a slot's window as rows of the pool by that closed
    form and reads neither operand's columns."""
    if any(isinstance(a, jax.core.Tracer) for a in (tables, col_page,
                                                    lengths)):
        return
    R = tables.shape[1]
    cols = np.arange(R)[None]
    tables = np.asarray(tables)
    if not np.array_equal(tables, tables[:, :1] + cols):
        raise ValueError(
            "a window decode call fetches a slot's window as consecutive "
            "rows of the pool: a ring's table must be R consecutive page "
            "ids a slot (models/laguna.py `ring_pool`)")
    last = ((np.asarray(lengths) - 1) // page_size)[:, None]
    view = last - (last - cols) % R
    if not np.array_equal(np.where(view < 0, -1, view), np.asarray(col_page)):
        raise ValueError(
            "a window decode call walks a slot's ring in closed form: "
            "`col_page` must be the ring's own view of `lengths` (column c "
            "holds page last_page - (last_page - c) % R, -1 where negative; "
            "models/laguna.py `_ring_view`)")


def _call_form(name, layer, scalars, k_scale, v_scale, window, col_page):
    """(a prefill call's name in a trace, its scalar-prefetch operands,
    the kernel's extra keywords): a window layer's ring adds `col_page`
    to the scalars and `_window` to the name; without one the call is
    the one every family had."""
    if window is None:
        return name, _prefetch(layer, scalars, k_scale, v_scale), {}
    return (name + "_window",
            _prefetch(layer, scalars + (col_page,), None, None),
            {"window": int(window)})


def _head_mask(n_heads, head_dim, n_kv_heads=None):
    """[H, G*K] bool: lane c belongs to the KV head of query head r
    (c // K == r // (H/G); with G = H, lane c belongs to head r)."""
    n_kv_heads = n_kv_heads or n_heads
    shape = (n_heads, n_kv_heads * head_dim)
    kv_head = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    if n_kv_heads != n_heads:       # G = H compiles what it always did
        kv_head = kv_head // (n_heads // n_kv_heads)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return (lane >= kv_head * head_dim) & (lane < (kv_head + 1) * head_dim)


def _pool_call(kernel, name, q, k_pool, v_pool, prefetch, n_pg, scratch,
               interpret, block_pages=1, kv_split=1, sink=None):
    """The one pallas_call shape both kernels share: grid (slot, kv block),
    the slot's query rows ``q[b]`` ([rows, H*K]) and its output
    ([rows, H*Kv]) as one block per slot, K and V a page at
    ``(layer, tables[b, ·])`` of the whole pool (whose minor axis is the
    KV heads', narrower than the query's under grouped-query attention;
    V's may differ from K's). A kv block is `block_pages`
    consecutive table columns: the pool is handed over once a column of
    the block (K's pages, then V's), so each page is still its own DMA
    and the kernel sees `block_pages` page refs a pool; `n_pg` must be a
    multiple of it. `prefetch` is `_prefetch`'s tuple (layer first, the
    page table second). With `kv_split` = S > 1 the grid is (slot, KV
    head group, kv block): a step sees its G/S KV heads' lanes of each
    page and those heads' H/S query heads' lanes of q and of the output.
    `sink` [H, LANES] float32 (optional) follows q: a step sees its own
    heads' rows."""
    B, rows, HK = q.shape
    ps, GK, GV = k_pool.shape[2], k_pool.shape[3], v_pool.shape[3]
    HV = HK // GK * GV
    n = block_pages
    if kv_split > 1:
        S = kv_split
        grid, HK, HV, GK, GV = (B, S, n_pg // n), HK // S, HV // S, \
            GK // S, GV // S
        im_q = lambda b, g, j, *_: (b, 0, g)
        im_sink = lambda b, g, j, *_: (g, 0)
        im_kv = lambda i: (lambda b, g, j, layer, tbl, *_: (
            layer[0], tbl[b, j * n + i], 0, g))
    else:
        grid = (B, n_pg // n)
        im_q = lambda b, j, *_: (b, 0, 0)
        im_sink = lambda b, j, *_: (0, 0)

        im_kv = lambda i: (lambda b, j, layer, tbl, *_: (
            layer[0], tbl[b, j * n + i], 0, 0))

    pages = lambda lanes: [pl.BlockSpec((None, None, ps, lanes), im_kv(i))
                           for i in range(n)]
    sinks = ([] if sink is None else
             [pl.BlockSpec((sink.shape[0] // kv_split, _LANES), im_sink)])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=grid,
        in_specs=([pl.BlockSpec((None, rows, HK), im_q)] + sinks
                  + pages(GK) + pages(GV)),
        out_specs=pl.BlockSpec((None, rows, HV), im_q),
        scratch_shapes=scratch,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rows, HV * kv_split), q.dtype),
        interpret=interpret,
        name=name,
    )(*prefetch, q, *(() if sink is None else (sink,)),
      *([k_pool] * n), *([v_pool] * n))


def _sink_form(extra, sink, n_heads, head_dim, v_head_dim):
    """What a V head size of its own and a learned softmax sink add to a
    call: the kernel's keywords (into `extra`; none where V is as wide
    as K and there is no sink) and, → the sink [H] as the [H, LANES]
    float32 operand the kernels seed their (m, l) state from, or None."""
    if v_head_dim != head_dim:
        extra["v_head_dim"] = v_head_dim
    if sink is None:
        return None
    if sink.shape != (n_heads,):
        raise ValueError(f"`sink` is one logit a query head: want "
                         f"({n_heads},), got {sink.shape}")
    extra["sink"] = True
    return jnp.broadcast_to(sink.astype(jnp.float32)[:, None],
                            (n_heads, _LANES))


def _seed_state(m_ref, l_ref, sink):
    """(m, l) before the first kv block: (-inf, 0), or with a sink
    ([rows, LANES], lane-uniform) m = the sink's logit and l = 1: the
    sink is in the denominator and has no value row. l is a partial sum
    a lane, so the 1 sits in lane 0."""
    if sink is None:
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        return
    m_ref[...] = jnp.broadcast_to(sink, m_ref.shape)
    lane = jax.lax.broadcasted_iota(jnp.int32, l_ref.shape, l_ref.ndim - 1)
    l_ref[...] = jnp.where(lane == 0, 1.0, 0.0)


# A ring column that holds no page yet (`col_page` -1) starts here: past
# every length, so its keys are masked and the column is skipped.
_NO_PAGE = 2**30


def _first_key(col, page_size):
    """Position of the first key of the logical page(s) `col`."""
    return jnp.where(col < 0, _NO_PAGE, col * page_size)


def _block_walk(tables_ref, lengths_ref, pools, sem, layer, *, n, ps, end):
    """What the full and the latent decode kernels walk a slot group's
    LIVE kv blocks by, in (slot, block) order up to slot `end`:
    `columns(b, j)`, `after(b, j)`, `seek(b, j)`,
    `copies(act, b, cols, buf, go)` and `fetch(item, buf)`. A block is `n`
    consecutive table columns of `ps` keys, column c logical page c;
    `pools`: ((pool in HBM, its [buffers, n*ps, lanes] VMEM buffers),
    ...), one DMA semaphore a buffer index in `sem`. (A ring has a walk
    of its own, `_window_walk`.)"""
    n_pg = tables_ref.shape[1]
    n_blk = -(-n_pg // n)
    block = n * ps
    ragged = n_blk * n != n_pg      # the last block runs past the table

    def columns(b, j):
        """[(live, table column, first key)] of block j of slot b: what
        the copies, their waits and the position mask all go by."""
        kv_len = lengths_ref[b]
        out = []
        for i in range(n):
            c = j * n + i
            held = jnp.minimum(c, n_pg - 1) if ragged else c
            first = c * ps
            out.append((first < kv_len, held, first))
        return out

    def after(b, j):
        """(slot, block) after (b, j) in the order the walk goes."""
        last = j == n_blk - 1
        return jnp.where(last, b + 1, b), jnp.where(last, 0, j + 1)

    def seek(b, j):
        """The first live block at or after (b, j) in (slot, block)
        order; a slot at or past `end` when the group has none left."""
        def live(b, j):
            return j * block < lengths_ref[b]

        def dead(at):
            return (at[0] < end) & ~live(jnp.minimum(at[0], end - 1), at[1])

        # The blocks after a dead one are dead: on to the next slot.
        return jax.lax.while_loop(
            dead, lambda at: (at[0] + 1, jnp.int32(0)), (b, j))

    def copies(act, b, cols, buf, go=True):
        """`act` ("start" or "wait") on the K and the V copy of every
        live column of a block of slot b (`cols`: its `columns`), from
        the pool to the column's rows of buffer `buf`; none if not `go`."""
        for i, (live, held, _) in enumerate(cols):
            @pl.when(live & go)
            def _():
                page = tables_ref[b, held]
                for pool, dst in pools:
                    getattr(pltpu.make_async_copy(
                        pool.at[layer, page],
                        dst.at[buf, pl.ds(i * ps, ps)], sem.at[buf]), act)()

    def fetch(item, buf):
        """Start block `item` on its way into `buf`; past the group's
        end there is nothing to start."""
        b = jnp.minimum(item[0], end - 1)
        copies("start", b, columns(b, item[1]), buf, item[0] < end)

    return types.SimpleNamespace(columns=columns, after=after, seek=seek,
                                 copies=copies, fetch=fetch)


def _slot_softmax(q_ref, sink_ref, o_ref, qbd_ref, m_ref, l_ref, acc_ref, *,
                  sm_scale, n_heads, n_kv_heads, v_head_dim, base):
    """One slot's attention over its kv blocks, as the full and the window
    decode kernels both do it, whatever walk brings the blocks:
    `init(b)`, then a block `query()`, `scores(qbd, k)`, the kernel's own
    position mask, `fold(s, v)`, and `finish(b)`. A block pays ONE score
    tile, one masked row maximum, one `exp`, one partial row sum and one
    accumulator update whatever it holds (PERF.md, PR 37).

    Multi-head (G = H): a slot's query is one dense row [1, H*K].
    Grouped (G < H): it is [H, K], a head a row, and the pool's minor
    axis holds G heads; row h of the block-diagonal query then sits in
    the lanes of KV head h // (H/G), so the two matmuls are the same.
    K and V of unequal head size take the grouped form whatever G."""
    grouped = n_kv_heads != n_heads or v_head_dim is not None
    head_dim = q_ref.shape[-1] if grouped else q_ref.shape[-1] // n_heads
    v_dim = v_head_dim or head_dim
    mask = lambda: _head_mask(n_heads, head_dim, n_kv_heads)
    v_mask = mask if v_dim == head_dim else (
        lambda: _head_mask(n_heads, v_dim, n_kv_heads))
    GK = acc_ref.shape[1]

    def block_diagonal(b):
        # Row h = the query with every lane outside head h's KV head
        # zeroed (the select runs in fp32: the mask is built from 32-bit
        # iotas).
        q = q_ref[b - base].astype(jnp.float32)
        q = (jnp.concatenate([q] * n_kv_heads, axis=1) if grouped
             else jnp.broadcast_to(q, qbd_ref.shape))
        return jnp.where(mask(), q, 0.0).astype(qbd_ref.dtype)

    def init(b):
        _seed_state(m_ref, l_ref, None if sink_ref is None else sink_ref[...])
        acc_ref[...] = jnp.zeros_like(acc_ref)
        qbd_ref[...] = block_diagonal(b)

    def query(upcast=False):
        qbd = qbd_ref[...]                   # [H, G*K]
        return qbd.astype(jnp.float32) if upcast else qbd

    def scores(qbd, k):
        # s[h, t] = q[h] · k[t, head h's lanes]: the block-diagonal query
        # makes it one [H, G*K] x [block, G*K]ᵀ matmul. Decode attention
        # is HBM-bound (~2 flops/byte), so the H-fold surplus of
        # multiplies by zero is free next to reading the pages once.
        return jax.lax.dot_general(
            qbd, k, _NT, preferred_element_type=jnp.float32) * sm_scale

    def fold(s, v):
        # The state as the prefill kernel keeps it: m lane-uniform
        # [H, LANES] and used at full width, l a partial sum a lane.
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _spread(m_new, s.shape[1]))  # [H, block] fp32
        corr = jnp.exp(m_prev - m_new)               # [H, LANES]
        l_ref[...] = l_ref[...] * corr + _fold(p)
        # Row h of [H, G*K]: head h's probabilities against EVERY KV
        # head's V lanes; only its own block is kept at the end.
        pv = jnp.dot(p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * _spread(corr, GK) + pv
        m_ref[...] = m_new

    def own_heads(own_lanes, rows):
        """[H, Kv] (grouped) or [1, H*Kv] out of rows [H, G*Kv]: each
        row's own KV head's lanes (`own_lanes`: `v_mask()`)."""
        own = jnp.where(own_lanes, rows, 0.0)
        if grouped:
            # One non-zero K-lane block per row: their sum is [H, K].
            return sum(own[:, g * v_dim:(g + 1) * v_dim]
                       for g in range(n_kv_heads))
        # One non-zero row per lane: the sum over rows is the gather
        # of each head's own block, as one dense [1, H*K] row.
        return jnp.sum(own, axis=0, keepdims=True)

    def finish(b):
        l = jnp.sum(l_ref[...], axis=1, keepdims=True)
        o_ref[b - base] = own_heads(v_mask(), acc_ref[...] / l).astype(
            o_ref.dtype)

    def whole(b, k, v, keep):
        """A slot whose keys are ONE block (`k`, `v`; `keep(s)` its
        position mask): the same softmax with nothing carried between
        blocks, so no state is seeded, rescaled, stored or read back."""
        s = keep(scores(block_diagonal(b), k))
        m = jnp.max(s, axis=1, keepdims=True)
        if sink_ref is not None:
            m = jnp.maximum(m, sink_ref[:, :1])
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=1, keepdims=True)
        if sink_ref is not None:
            l = l + jnp.exp(sink_ref[:, :1] - m)
        pv = jnp.dot(p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
        # (a grouped row is divided after its eight lane blocks are one)
        out = (own_heads(v_mask(), pv) / l if grouped
               else own_heads(v_mask(), pv / l))
        o_ref[b - base] = out.astype(o_ref.dtype)

    return types.SimpleNamespace(init=init, query=query, scores=scores,
                                 fold=fold, finish=finish, whole=whole)


def _finite_rows(v_buf, o_ref):
    """What a decode kernel's body starts with. A dead column's rows of a
    buffer keep what they held. Their probabilities are 0, and 0 x NaN is
    NaN in PV: V's rows are made finite once a call, and only pages of
    the pool land there after. (K's rows may hold anything: their scores
    are masked.) An idle slot is never visited: its output is 0."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        v_buf[...] = jnp.zeros_like(v_buf)
    o_ref[...] = jnp.zeros_like(o_ref)


def _decode_kernel(
    *refs,
    sm_scale, page_size, block_pages, n_heads, n_kv_heads, quantized=False,
    v_head_dim=None, sink=False,
):
    """Every slot of a group against its LIVE kv blocks, one grid step a
    group (the whole batch at every served shape), the pages fetched by
    the kernel's own DMAs. Ref order: scalar-prefetch (SMEM) first
    (layer, page tables, kv lengths, and for an int8 pool the layer's
    per-page K/V scale vectors), the group's queries (VMEM), the K and V
    pools WHOLE and left in HBM, the output, and the scratch:
    `_DECODE_BUFFERS` K and as many V buffers of a block
    ([buffers, block_pages*ps, G*K]) with a DMA semaphore a buffer pair,
    the block-diagonal query and the (m, l, acc) softmax state.

    One flat loop walks the live blocks of the group in (slot, block)
    order. A block is `block_pages` consecutive table columns; a live
    column's page goes from ``pool[layer, tables[b, c]]`` straight to
    its rows of the buffer (the DMA's destination does the stacking), a
    dead column of a live block gets no DMA and is position-masked, and
    a dead block and an idle slot are never visited. While a block is
    attended the next live blocks, of this slot or of the next live
    ones, are already in flight into the other buffers, so no slot waits
    for its own first page (a wait a slot cost more than the whole grid
    did: PERF.md, PR 41). What a block pays: `_slot_softmax`.
    `quantized` is a Python-level trace switch: the int8 program dequants
    each page of the block by its own scale after the wait, inside the
    kernel, and the fp32 plane never exists in HBM. `v_head_dim`: the
    V plane's head size where it is not K's (the accumulator and the
    output are that wide); `sink`: a [H, LANES] operand follows the
    queries, the logit each head's softmax starts from. (A latent pool
    and a window layer's ring have bodies of their own,
    `_latent_decode_kernel` and `_window_decode_kernel`.)"""
    n, ps = block_pages, page_size
    refs = iter(refs)
    take = lambda count: [next(refs) for _ in range(count)]
    layer_ref, tables_ref, lengths_ref = take(3)
    ks_ref, vs_ref = take(2) if quantized else (None, None)
    (q_ref,) = take(1)
    (sink_ref,) = take(1) if sink else (None,)
    (k_hbm, v_hbm, o_ref), (k_buf, v_buf) = take(3), take(2)
    pools = ((k_hbm, k_buf), (v_hbm, v_buf))
    sem, qbd_ref, m_ref, l_ref, acc_ref = refs
    block = n * ps
    group = q_ref.shape[0]
    n_buf = k_buf.shape[0]
    base = pl.program_id(0) * group
    end = base + group
    layer = layer_ref[0]
    _finite_rows(v_buf, o_ref)

    walk = _block_walk(tables_ref, lengths_ref, pools, sem, layer,
                       n=n, ps=ps, end=end)
    columns, after, seek = walk.columns, walk.after, walk.seek
    copies, fetch = walk.copies, walk.fetch
    slot = _slot_softmax(q_ref, sink_ref, o_ref, qbd_ref, m_ref, l_ref,
                         acc_ref, sm_scale=sm_scale, n_heads=n_heads,
                         n_kv_heads=n_kv_heads, v_head_dim=v_head_dim,
                         base=base)

    def attend(b, j, cols, buf):
        kv_len = lengths_ref[b]
        qbd = slot.query(upcast=quantized)

        def stacked(dst, scale_ref):
            if not quantized:
                return dst[buf]              # [block, G*K], as the DMAs left it
            # A dead column's rows are stale int8 under a scale nobody
            # wrote: they dequant to 0.
            parts = [dst[buf, i * ps:(i + 1) * ps].astype(jnp.float32)
                     * jnp.where(live, scale_ref[tables_ref[b, held]], 0.0)
                     for i, (live, held, _) in enumerate(cols)]
            return parts[0] if n == 1 else jnp.concatenate(parts, axis=0)

        k = stacked(k_buf, ks_ref)
        v = stacked(v_buf, vs_ref)
        s = slot.scores(qbd, k)
        # Raggedness: positions at or past the slot's kv length are
        # masked (a live block's dead columns, a partial last page).
        lane = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        slot.fold(jnp.where(j * block + lane < kv_len, s, NEG_INF), v)

    def visit(at):
        # `ahead`: this block and the ones in flight behind it. The next
        # live block after them, this slot's or a later live slot's, goes
        # out into the buffer the block before this one left, before
        # this one is waited for.
        ahead, buf, prev = at
        b, j = ahead[0]
        ahead += (seek(*after(*ahead[-1])),)
        fetch(ahead[-1], jnp.where(buf == 0, n_buf - 1, buf - 1))
        pl.when(b != prev)(lambda: slot.init(b))
        cols = columns(b, j)
        copies("wait", b, cols, buf)
        attend(b, j, cols, buf)
        pl.when(ahead[1][0] != b)(lambda: slot.finish(b))
        return ahead[1:], jnp.where(buf == n_buf - 1, 0, buf + 1), b

    ahead = (seek(base, jnp.int32(0)),)
    for _ in range(n_buf - 2):
        ahead += (seek(*after(*ahead[-1])),)
    for buf, item in enumerate(ahead):
        fetch(item, buf)
    jax.lax.while_loop(lambda at: at[0][0][0] < end, visit,
                       (ahead, jnp.int32(0), jnp.int32(-1)))


# The decode kernel's kv block. A live block costs one softmax chain and
# one pass of its keys through the MXU whatever it holds, and its pages'
# DMAs hide behind the blocks before it, so the block is as large as its
# bytes stay small beside the chain: 512 KiB of K (and of V) read best
# at all three served page sizes (16 pages of 32 KB, 4 of 128 KB, 2 of
# 256 KB: PERF.md, PR 37; with the kernel's own DMAs half and twice that
# read within 2 % of it, PR 41), and past it a live block's dead columns
# cost more compute than the chains saved. That was measured for the
# page walk (multi-head and grouped, bf16 and int8; their block-diagonal
# matmuls leave the MXU time to spare), not for a latent pool, whose
# block goes by `_LATENT_BLOCK_KEYS`, and it does not cover a window
# layer's ring, whose block is a run of rows with no dead column in it:
# there ONE block a slot read best (`_WINDOW_BLOCK_BYTES`, over the
# 1,056 KiB of K that laguna-s-2.1's 528 rows take: 0.192 ms a call
# against 0.211 as two blocks of 272; PERF.md, PR 61). Three buffers a
# pool: two
# blocks in flight behind the one attended, because a slot's last block
# is often a page or two and its DMA ends long before the block's fixed
# compute does (the third buffer is worth 3 % at 32 KB pages, 15 % in
# the ring; a fourth nothing: PERF.md, PR 41). The budget is what the
# kernel may take of the 16 MiB of VMEM it gets by default: the block's
# buffers and tiles (`_decode_vmem_bytes`) and beside them a slot
# group's queries and outputs.
_DECODE_BLOCK_BYTES = 512 * 2**10
_DECODE_BLOCK_KEYS = 1024
_WINDOW_BLOCK_BYTES = 1280 * 2**10
_DECODE_BUFFERS = 3
_DECODE_VMEM_BUDGET = 12 * 2**20
_DECODE_GROUP_BUDGET = 14 * 2**20
# The latent form's block (`_latent_decode_kernel`) goes by its keys: it
# has no V buffers, so 512 KiB of one plane bounded it at 4 pages of 80 KB
# where 8 read best (the whole call at 4 / 8 / 16 pages a block, 362,000
# cached tokens in 256 slots: 0.84 / 0.78 / 0.79 ms; a live block's dead
# columns cost MXU passes here, and past 512 keys they cost more than the
# larger block amortises: PERF.md, PR 57). FOUR buffers: the block
# attended still stands in its value matmul while the next one is scored,
# and two more are in flight behind that one (three read 16 % slower, five
# 0.7 % faster).
_LATENT_BLOCK_KEYS = 512
_LATENT_BUFFERS = 4


def _decode_vmem_bytes(n, page_size, kv_lanes, kv_itemsize, n_heads,
                       v_lanes=None, latent=False) -> int:
    """VMEM the decode kernel takes at `n` pages a block, beside its
    queries and outputs: `_DECODE_BUFFERS` K and as many V buffers of a
    block (`kv_lanes` = G*K and `v_lanes` = G*Kv wide; None: as K), an
    int8 block's f32 copies, the score and probability tiles, and
    whatever the block: the block-diagonal query (K's width) with the
    f32 accumulator and its update (V's) and the (m, l) state. `latent`:
    `_LATENT_BUFFERS` buffers of the one plane (the value is lanes of
    its rows), the tiles, this block's scores and the next one's in
    scratch, and the two queries where the block-diagonal one was."""
    v_lanes = kv_lanes if v_lanes is None else v_lanes
    both = kv_lanes + (0 if latent else v_lanes)
    page = page_size * both * kv_itemsize
    dequant = n * page_size * both * 4 if kv_itemsize == 1 else 0
    tiles = 2 * n_heads * n * page_size * 4
    fixed = (n_heads * (kv_lanes * 4 + v_lanes * (4 + 4))
             + 2 * n_heads * _LANES * 4)
    if latent:
        return _LATENT_BUFFERS * n * page + 2 * tiles + fixed
    return _DECODE_BUFFERS * n * page + dequant + tiles + fixed


def decode_block_pages(n_pg, page_size, kv_lanes, kv_itemsize,
                       n_heads, v_lanes=None, latent=False) -> int:
    """Table columns one block of the decode kernel holds: the largest
    power of two that is at most `n_pg`, keeps the block's K pages
    (`kv_lanes` = G*K wide) at or under `_DECODE_BLOCK_BYTES` and
    `_DECODE_BLOCK_KEYS` keys, and fits `_DECODE_VMEM_BUDGET`
    (`_decode_vmem_bytes`, which counts V's pages at `v_lanes`). A
    `latent` pool's block goes by its keys alone, `_LATENT_BLOCK_KEYS`,
    and the same budget (no V buffers, four of K's and the next block's
    scores). Pure in the shapes: the engine's `decode_block_fill`
    counter and the kernel ask it the same question. (A window layer's
    ring is not cut into pages: its block goes by the window,
    `window_block_rows`, under `_WINDOW_BLOCK_BYTES`, the same
    `_DECODE_BLOCK_KEYS` and the same budget.)"""
    page = page_size * kv_lanes * kv_itemsize
    if latent:
        bounded = lambda m: m * page_size <= _LATENT_BLOCK_KEYS
    else:
        bounded = lambda m: (m * page <= _DECODE_BLOCK_BYTES
                             and m * page_size <= _DECODE_BLOCK_KEYS)
    n = 1
    while (2 * n <= n_pg and bounded(2 * n)
           and _decode_vmem_bytes(2 * n, page_size, kv_lanes, kv_itemsize,
                                  n_heads, v_lanes, latent)
           <= _DECODE_VMEM_BUDGET):
        n *= 2
    return n


def window_block_rows(window, kv_lanes, kv_itemsize, n_heads,
                      v_lanes=None) -> tuple[int, int]:
    """(rows a block, blocks a slot) of the window decode kernel. A slot
    attends `window` keys that lie in consecutive rows of its ring; they
    are fetched from the sublane tile (16 rows of bf16) its first key
    lies in to the tile its last one does, `window` + a tile of rows at
    most, cut into the fewest equal blocks of whole tiles that keep to
    `_WINDOW_BLOCK_BYTES` of K, `_DECODE_BLOCK_KEYS` keys and
    `_DECODE_VMEM_BUDGET`: ONE block of 144 rows (432 KiB of K) at
    mimo-v2-flash's window of 128, ONE of 528 (1,056 KiB) at
    laguna-s-2.1's of 512. The byte cap is not the page walk's 512 KiB:
    that was measured with blocks whose dead columns cost compute
    (PERF.md, PRs 37 and 41), and a window's block has none."""
    tile = _sublanes(kv_itemsize)
    total = -(-(window + tile - 1) // tile)             # in tiles
    fits = lambda m: (
        m * tile * kv_lanes * kv_itemsize <= _WINDOW_BLOCK_BYTES
        and m * tile <= _DECODE_BLOCK_KEYS
        and _decode_vmem_bytes(1, m * tile, kv_lanes, kv_itemsize, n_heads,
                               v_lanes) <= _DECODE_VMEM_BUDGET)
    most = max([m for m in range(1, total + 1) if fits(m)], default=1)
    n_blk = -(-total // most)
    return -(-total // n_blk) * tile, n_blk


def _sublanes(itemsize) -> int:
    """Rows of one tile of the chip's layout: 8 of float32, 16 of bf16."""
    return 32 // itemsize


def _decode_slot_group(n_slots, slot_bytes, block_bytes) -> int:
    """Slots one grid step of the decode kernel walks: the largest
    divisor of `n_slots` whose queries and outputs (`slot_bytes` a slot
    each, double-buffered by the pipeline) fit `_DECODE_GROUP_BUDGET`
    beside the block's `block_bytes`: the whole batch at every served
    shape. A group fetches its own first block, so a smaller group is a
    wait more, not another program."""
    room = _DECODE_GROUP_BUDGET - block_bytes
    return max(g for g in range(1, n_slots + 1)
               if n_slots % g == 0 and (g == 1 or 4 * g * slot_bytes <= room))


def _pad_columns(tables, col_page, n):
    """The table (and a ring's `col_page`) padded with null columns to a
    multiple of `n`: position-masked like any dead column of a block (in
    a ring: columns that hold no page)."""
    pad = -tables.shape[1] % n
    if pad:
        tables = jnp.pad(tables, ((0, 0), (0, pad)))
        if col_page is not None:
            col_page = jnp.pad(col_page, ((0, 0), (0, pad)),
                               constant_values=-1)
    return tables, col_page


def paged_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    layer,
    tables: jax.Array,
    lengths: jax.Array,
    *,
    sm_scale: float | None = None,
    interpret: bool | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    window: int | None = None,
    col_page: jax.Array | None = None,
    sink: jax.Array | None = None,
    latent: int | None = None,
) -> jax.Array:
    """Single-token decode attention straight against the KV page pool.

    Args:
      q: [B, H, K] — each slot's current-token query (post-rotary).
      k_pool, v_pool: [L, P, page_size, G*K] — the WHOLE page pool (row 0
        of every layer is the reserved null page), left in HBM and read
        in place at ``(layer, page)`` by the kernel's own DMAs, a live
        page each; G KV heads, each serving H/G query heads (G = H:
        multi-head). May be int8 (quantized serving), in which case
        ``k_scale``/``v_scale`` must carry the per-page scale planes
        [L, P] — the layer's row rides the scalar-prefetch path next to
        the page table, and each page is dequanted in VMEM right after
        its DMA (the fp32 plane never exists in HBM).
      layer: int32 scalar (traced inside the layer scan) — which layer's
        pages to attend over.
      tables: [B, n_pg] int32 page ids per slot (unallocated tail = 0;
        a dead column's entry is never read as a page).
      lengths: [B] int32 valid kv positions per slot (= position + 1; the
        current token's K/V must already be written to its page; 0: an
        idle slot, whose output is 0).
      window, col_page: a window layer's ring (both or neither): only
        keys within `window` of the query are attended, and table column
        c holds logical page ``col_page[b, c]`` ([B, n_pg] int32, -1:
        none). None: column c is page c and every key under the length
        is attended.
      sink: [H] float32, a learned logit a query head that joins the
        softmax's denominator and has no value row (None: no sink).
      latent: the LATENT form (a compressed cache): ``k_pool`` is ONE
        plane [L, P, page_size, K] with no head axis, ``v_pool`` is None;
        a token's row is the key of every query head (all K lanes) and,
        in its first `latent` lanes, their value. A page is fetched once
        and stands in both matmuls; the call's name ends `_latent`.
    V's heads may be narrower or wider than K's (``v_pool``
    [L, P, page_size, G*Kv]): the scores contract K, the output is Kv
    wide. Returns [B, H, Kv] in q.dtype. Numerics match the gather
    reference within blockwise-fp32-softmax reassociation (see
    ``reference_paged_attention``).
    """
    B, H, K = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(K)
    if interpret is None:
        interpret = _interpret_default()
    if latent is not None:
        _check_latent(K, k_pool, v_pool, latent, k_scale, window, sink)
        return _latent_decode(q, k_pool, layer, tables, lengths, latent,
                              sm_scale, interpret)
    ps, G, Kv = _check_pool(H, K, k_pool, v_pool)
    quantized = k_scale is not None
    _check_window(window, col_page, tables, quantized)
    if window is not None:
        return _window_decode(q, k_pool, v_pool, layer, tables, lengths,
                              window, col_page, sink, sm_scale, interpret)
    kv_item = k_pool.dtype.itemsize
    n = decode_block_pages(tables.shape[1], ps, G * K, kv_item, H, G * Kv)
    prefetch = _prefetch(layer, (tables, lengths), k_scale, v_scale)
    extra = {}
    sink = _sink_form(extra, sink, H, K, Kv)
    kernel = functools.partial(
        _decode_kernel, sm_scale=sm_scale, page_size=ps, block_pages=n,
        n_heads=H, n_kv_heads=G, quantized=quantized, **extra)
    return _slot_group_call(
        kernel, "paged_decode_attn", q, (k_pool, v_pool), prefetch, sink,
        n * ps,
        _decode_vmem_bytes(n, ps, G * K, kv_item, H, G * Kv), interpret)


def _slot_group_call(kernel, name, q, pools, prefetch, sink, block_rows,
                     block_bytes, interpret):
    """The pallas_call of the full and the window decode kernel: q
    [B, H, K] against `pools` (K's and V's, left in HBM whole, minor axes
    G*K and G*Kv) -> [B, H, Kv]; one grid step a group of slots
    (`_decode_slot_group` beside the block's `block_bytes`), `prefetch`
    in SMEM, `sink` [H, LANES] or None after the queries, and the
    scratch both kernels name: `_DECODE_BUFFERS` buffers of `block_rows`
    rows a pool with a DMA semaphore a buffer pair, the block-diagonal
    query and the (m, l, acc) state."""
    B, H, K = q.shape
    GK, GKv = (p.shape[-1] for p in pools)
    Kv = GKv // (GK // K)
    dense = GK == H * K and Kv == K     # a slot's query is one row [1, H*K]
    # A grouped slot's [H, K] and [H, Kv] blocks, as VMEM pads them.
    tiled = lambda lanes: lanes if dense else -(-lanes // _LANES) * _LANES
    group = _decode_slot_group(
        B, H * (tiled(K) + tiled(Kv)) // 2 * q.dtype.itemsize, block_bytes)
    q = q.reshape(B, 1, H * K) if dense else q
    out_shape = q.shape[:2] + (q.shape[2] // K * Kv,)
    slots = lambda shape: pl.BlockSpec((group,) + shape[1:],
                                       lambda g, *_: (g, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    sinks = ([] if sink is None else
             [pl.BlockSpec((H, _LANES), lambda g, *_: (0, 0))])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B // group,),
        in_specs=[slots(q.shape)] + sinks + [pool] * len(pools),
        out_specs=slots(out_shape),
        scratch_shapes=[
            pltpu.VMEM((_DECODE_BUFFERS, block_rows, p.shape[-1]), p.dtype)
            for p in pools] + [
            pltpu.SemaphoreType.DMA((_DECODE_BUFFERS,)),   # one a K, V pair
            pltpu.VMEM((H, GK), q.dtype),            # block-diagonal query
            pltpu.VMEM((H, _LANES), jnp.float32),    # m
            pltpu.VMEM((H, _LANES), jnp.float32),    # l
            pltpu.VMEM((H, GKv), jnp.float32),       # acc
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, q.dtype),
        interpret=interpret,
        name=name,
    )(*prefetch, q, *(() if sink is None else (sink,)), *pools)
    return out.reshape(B, H, Kv)


# ------------------------------------------- a window layer's ring, decode

def _window_walk(row0_ref, lengths_ref, pools, sem, layer, *, rows, n_blk,
                 tile, ring, window, end):
    """What the window decode kernel walks a slot group's rings by.
    Nothing is sought: a slot of `kv_len` keys attends positions ``lo =
    max(kv_len - window, 0) .. kv_len - 1``, position t lies in row
    ``t % ring`` of the slot's ring (`ring` = R pages x page_size rows,
    consecutive in the pool from row ``row0_ref[b]``: `_check_ring`),
    and the slot's `n_blk` blocks of `rows` positions start at the tile
    `lo` lies in. What a block fetches is its positions from there to
    the tile the last key lies in (every row of it this slot's own, but
    the last tile's tail): ONE copy a plane where that is the whole
    block and does not pass the ring's end (`window` + a tile of rows
    covers 15 lengths in 16), else the rows up to the ring's end and
    the rows from its start, each a `run`. Rows of a buffer that get no
    copy are position-masked. An idle slot (`kv_len` 0) is never visited
    and a block past the slot's last key neither. `pools`: ((plane
    [L, rows, lanes] in HBM, its [buffers, block rows, lanes] VMEM
    buffers), ...), one DMA semaphore a buffer index in `sem`.
    `span(b, j)`, `seek(b)`, `after(b, j)`, `copies(act, b, j, buf, go)`,
    `fetch(item, buf)`."""
    sizes = [tile << k for k in reversed(range((rows // tile).bit_length()))]
    tiles = lambda x: x if isinstance(x, int) else pl.multiple_of(x, tile)

    def bounds(b):
        """(kv length, first attended key, first position of block 0)."""
        kv_len = lengths_ref[b]
        lo = jnp.maximum(kv_len - window, 0)
        return kv_len, lo, jax.lax.div(lo, tile) * tile

    def span(b, j):
        """(kv length, first attended key, first position of block j,
        one past the last position it fetches)."""
        kv_len, lo, start = bounds(b)
        first = start + j * rows
        return kv_len, lo, first, jnp.minimum(
            first + rows, jax.lax.div(kv_len + tile - 1, tile) * tile)

    def seek(b):
        """The first slot at or after b that has a key (its block 0); a
        slot at or past `end` when the group has none left."""
        held = lambda b: lengths_ref[jnp.minimum(b, end - 1)]
        return jax.lax.while_loop(lambda b: (b < end) & (held(b) == 0),
                                  lambda b: b + 1, b), jnp.int32(0)

    def after(b, j):
        """(slot, block) after (b, j): this slot's next live block, else
        the next live slot's first."""
        nxt = seek(b + 1)
        if n_blk == 1:
            return nxt
        kv_len, _, start = bounds(jnp.minimum(b, end - 1))
        last = j == jax.lax.div(kv_len - 1 - start, rows)
        return jnp.where(last, nxt[0], b), jnp.where(last, 0, j + 1)

    def move(act, buf, src, dst, size):
        """`act` ("start" or "wait") on the copy of `size` rows from row
        `src` of each plane to row `dst` of its buffer `buf`."""
        for pool, block in pools:
            getattr(pltpu.make_async_copy(
                pool.at[layer, pl.ds(tiles(src), size)],
                block.at[buf, pl.ds(tiles(dst), size)], sem.at[buf]), act)()

    def run(act, buf, src, dst, length):
        """`move` for a run whose `length` is not static, as a DMA's
        size has to be: the binary digits of `length`, a tile times the
        powers of two, largest first."""
        for size in sizes:
            take = (length & size) != 0
            pl.when(take)(functools.partial(move, act, buf, src, dst, size))
            step = jnp.where(take, size, 0)
            src, dst = src + step, dst + step

    def copies(act, b, j, buf, go=True):
        _, _, first, stop = span(b, j)
        src = jax.lax.rem(first, ring)
        length = stop - first
        ahead = jnp.minimum(length, ring - src)     # rows up to the ring's end
        base = row0_ref[b]
        whole = (length == rows) & (ahead == rows)
        pl.when(whole & go)(
            functools.partial(move, act, buf, base + src, 0, rows))

        @pl.when(~whole & go)
        def _():
            run(act, buf, base + src, 0, ahead)
            run(act, buf, base, ahead, length - ahead)

    def fetch(item, buf):
        copies("start", jnp.minimum(item[0], end - 1), item[1], buf,
               item[0] < end)

    return types.SimpleNamespace(span=span, seek=seek, after=after,
                                 copies=copies, fetch=fetch)


def _window_decode_kernel(
    *refs,
    sm_scale, block_rows, n_blocks, ring_rows, n_heads, n_kv_heads, window,
    v_head_dim=None, sink=False,
):
    """The decode kernel of a window layer's ring (bf16 pools handed over
    as planes of rows, [L, P * page_size, lanes]): `_decode_kernel`'s
    arithmetic (`_slot_softmax`) and its three block buffers with two
    blocks in flight behind the one attended, under a walk of its own
    (`_window_walk`). A slot's window is consecutive rows of its ring, so
    a block is `block_rows` consecutive positions, not pages: ONE block
    a slot where the window and a tile of rows fit one
    (`window_block_rows`: every served shape), which then pays one copy
    a plane, one wait, one score tile and one softmax with no state
    carried (`whole`); where they do not, `n_blocks` blocks through the
    (m, l, acc) state as in `_decode_kernel`. Ref order: layer, each
    slot's first ring row and kv length (SMEM), the group's queries, a
    sink's logits, the K and V planes in HBM, the output, and
    `_decode_kernel`'s scratch."""
    refs = iter(refs)
    take = lambda count: [next(refs) for _ in range(count)]
    layer_ref, row0_ref, lengths_ref, q_ref = take(4)
    (sink_ref,) = take(1) if sink else (None,)
    (k_hbm, v_hbm, o_ref), (k_buf, v_buf) = take(3), take(2)
    sem, qbd_ref, m_ref, l_ref, acc_ref = refs
    group = q_ref.shape[0]
    n_buf = k_buf.shape[0]
    base = pl.program_id(0) * group
    end = base + group
    _finite_rows(v_buf, o_ref)

    walk = _window_walk(
        row0_ref, lengths_ref, ((k_hbm, k_buf), (v_hbm, v_buf)), sem,
        layer_ref[0], rows=block_rows, n_blk=n_blocks,
        tile=_sublanes(k_buf.dtype.itemsize), ring=ring_rows, window=window,
        end=end)
    slot = _slot_softmax(q_ref, sink_ref, o_ref, qbd_ref, m_ref, l_ref,
                         acc_ref, sm_scale=sm_scale, n_heads=n_heads,
                         n_kv_heads=n_kv_heads, v_head_dim=v_head_dim,
                         base=base)

    def keep(b, j):
        """The position mask of block j of slot b. Its keys are
        consecutive positions: the window and the length are two scalar
        bounds."""
        kv_len, lo, first, _ = walk.span(b, j)

        def masked(s):
            tpos = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            return jnp.where((tpos >= lo) & (tpos < kv_len), s, NEG_INF)
        return masked

    def visit(at):
        # `ahead`: this block and the ones in flight behind it; the one
        # after them goes out into the buffer the block before this one
        # left, before this one is waited for.
        ahead, buf, prev = at
        b, j = ahead[0]
        ahead += (walk.after(*ahead[-1]),)
        walk.fetch(ahead[-1], jnp.where(buf == 0, n_buf - 1, buf - 1))
        walk.copies("wait", b, j, buf)
        if n_blocks == 1:
            slot.whole(b, k_buf[buf], v_buf[buf], keep(b, j))
        else:
            pl.when(b != prev)(lambda: slot.init(b))
            slot.fold(keep(b, j)(slot.scores(slot.query(), k_buf[buf])),
                      v_buf[buf])
            pl.when(ahead[1][0] != b)(lambda: slot.finish(b))
        return ahead[1:], jnp.where(buf == n_buf - 1, 0, buf + 1), b

    ahead = (walk.seek(base),)
    for _ in range(n_buf - 2):
        ahead += (walk.after(*ahead[-1]),)
    for buf, item in enumerate(ahead):
        walk.fetch(item, buf)
    jax.lax.while_loop(lambda at: at[0][0][0] < end, visit,
                       (ahead, jnp.int32(0), jnp.int32(-1)))


def _window_decode(q, k_pool, v_pool, layer, tables, lengths, window,
                   col_page, sink, sm_scale, interpret):
    """`paged_attention` over a window layer's ring: q [B, H, K] against
    the slots' rings in the pool -> [B, H, Kv]."""
    _, H, K = q.shape
    ps, G, Kv = _check_pool(H, K, k_pool, v_pool)
    item = k_pool.dtype.itemsize
    ring = tables.shape[1] * ps
    if ps % _sublanes(item) or ring < window + ps:
        raise ValueError(
            f"a window decode call fetches whole tiles of {_sublanes(item)} "
            f"rows out of a ring that holds the window and a page: got "
            f"pages of {ps} rows, {tables.shape[1]} a ring, window {window}")
    _check_ring(tables, col_page, lengths, ps)
    rows, n_blk = window_block_rows(window, G * K, item, H, G * Kv)
    extra = {}
    sink = _sink_form(extra, sink, H, K, Kv)
    kernel = functools.partial(
        _window_decode_kernel, sm_scale=sm_scale, block_rows=rows,
        n_blocks=n_blk, ring_rows=ring, n_heads=H, n_kv_heads=G,
        window=int(window), **extra)
    # A ring's pages as the rows they are, [L, P * ps, lanes], and a
    # slot's ring by its first row.
    pools = [p.reshape(p.shape[0], -1, p.shape[3]) for p in (k_pool, v_pool)]
    prefetch = _prefetch(layer, (tables[:, 0] * ps, lengths), None, None)
    return _slot_group_call(
        kernel, "paged_decode_attn_window", q, pools, prefetch, sink, rows,
        _decode_vmem_bytes(1, rows, G * K, item, H, G * Kv), interpret)


# ----------------------------------------------- the latent form, decode

def _latent_decode_kernel(*refs, sm_scale, page_size, block_pages, v_dim):
    """`_decode_kernel` for a latent pool: ONE plane, whose row is every
    head's key (all K lanes) and, in its first `v_dim` lanes, their
    value, so a page is fetched once and stands in both matmuls. The
    same flat walk over the group's live blocks (`_block_walk`), under a
    schedule of its own: TWO blocks in flight through the compute, as
    two are through the DMAs. One iteration scores the NEXT live block
    (its pages waited for; its slot's query, scaled once a slot, in the
    other half of `qs_ref` when that slot is not this one) and runs the
    softmax chain and the value matmul of THIS one on the scores the
    iteration before left in `s_ref`. The two depend on nothing of each
    other and lie in one basic block, so the next block's pass through
    the MXU runs under this block's vector chain and the chain's waits
    for its own matmuls: at 64 query rows against 640-lane rows the
    MXU's time a block equals its DMA's, and a serial body left the call
    at half its limit (design notes). Past the walk's end the "next"
    block is whatever a buffer holds, and nothing reads its scores.
    Refs: layer, tables, lengths (SMEM); the group's queries
    [group, H, K]; the pool in HBM; the output [group, H, v_dim]; scratch:
    `_LATENT_BUFFERS` block buffers with a DMA semaphore each, the two
    queries, this block's scores and the next one's, and (m, l, acc)."""
    n, ps = block_pages, page_size
    (layer_ref, tables_ref, lengths_ref, q_ref, kv_hbm, o_ref,
     kv_buf, sem, qs_ref, s_ref, s_next_ref, m_ref, l_ref, acc_ref) = refs
    block = n * ps
    group = q_ref.shape[0]
    n_buf = kv_buf.shape[0]
    base = pl.program_id(0) * group
    end = base + group
    walk = _block_walk(tables_ref, lengths_ref, ((kv_hbm, kv_buf),), sem,
                       layer_ref[0], n=n, ps=ps, end=end)
    following = lambda item: walk.seek(*walk.after(*item))

    @pl.when(pl.program_id(0) == 0)
    def _finite_rows():
        # A dead column's rows keep what they held, a row is its own
        # value, and 0 x NaN is NaN in PV: made finite once a call.
        kv_buf[...] = jnp.zeros_like(kv_buf)
    o_ref[...] = jnp.zeros_like(o_ref)       # an idle slot is never visited
    # A slot's first block drops the state before it by a factor of 0
    # (`attend`); what the first block of all drops has to be finite.
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def ready(item, which, buf, fresh):
        """Block `item` ready to be scored: its slot's query in half
        `which` of `qs_ref` (scaled here, once a slot: when `fresh`) and
        its pages waited for in `buf`. Past the group's end: nothing."""
        more = item[0] < end
        b = jnp.minimum(item[0], end - 1)

        @pl.when(fresh & more)
        def _query():
            q = q_ref[b - base].astype(jnp.float32) * sm_scale
            qs_ref[which] = q.astype(qs_ref.dtype)
        walk.copies("wait", b, walk.columns(b, item[1]), buf, more)

    def score(which, buf):
        """[H, block] float32: every head's query against the rows of
        the block in `buf`, all K lanes."""
        return jax.lax.dot_general(qs_ref[which], kv_buf[buf], _NT,
                                   preferred_element_type=jnp.float32)

    def attend(b, j, buf):
        """The softmax chain and PV of block j of slot b, whose scores
        lie in `s_ref` and whose rows in `buf`. No branch: a slot's
        first block takes m = -inf for the state before it, so `corr`
        is 0 and (l, acc) start over."""
        lane = jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, 1)
        s = jnp.where(lane < lengths_ref[b] - j * block, s_ref[...], NEG_INF)
        m_prev = jnp.where(j == 0, NEG_INF, m_ref[...])
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _spread(m_new, block))       # [H, block] fp32
        corr = jnp.exp(m_prev - m_new)               # [H, LANES]
        l_ref[...] = l_ref[...] * corr + _fold(p)
        pv = jnp.dot(p.astype(kv_buf.dtype), kv_buf[buf, :, :v_dim],
                     preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * _spread(corr, v_dim) + pv
        m_ref[...] = m_new

    def finish(b):
        l = jnp.sum(l_ref[...], axis=1, keepdims=True)
        o_ref[b - base] = (acc_ref[...] * (1.0 / l)).astype(o_ref.dtype)

    def visit(at):
        # `ahead`: this block, the next one (fetched; scored here) and
        # those in flight behind it. The live block after them goes out
        # into the buffer the block before this one left.
        ahead, buf, which = at
        (b, j), nxt = ahead[0], ahead[1]
        ahead += (following(ahead[-1]),)
        walk.fetch(ahead[-1], jnp.where(buf == 0, n_buf - 1, buf - 1))
        buf_n = jnp.where(buf == n_buf - 1, 0, buf + 1)
        crossing = nxt[0] != b
        which_n = jnp.where(crossing, 1 - which, which)
        ready(nxt, which_n, buf_n, crossing)
        # One basic block from here to the scores' copy (a `pl.when`
        # between the two halves would keep the scheduler from
        # interleaving them; two scratches, not two halves of one under
        # an index: it takes accesses at a dynamic index for dependent).
        s_next_ref[...] = score(which_n, buf_n)
        attend(b, j, buf)
        s_ref[...] = s_next_ref[...]
        pl.when(crossing)(lambda: finish(b))
        return ahead[1:], buf_n, which_n

    ahead = (walk.seek(base, jnp.int32(0)),)
    for _ in range(n_buf - 2):
        ahead += (following(ahead[-1]),)
    for buf, item in enumerate(ahead):
        walk.fetch(item, buf)
    ready(ahead[0], 0, 0, True)
    s_ref[...] = score(0, 0)
    jax.lax.while_loop(lambda at: at[0][0][0] < end, visit,
                       (ahead, jnp.int32(0), jnp.int32(0)))


def _latent_decode(q, kv_pool, layer, tables, lengths, latent, sm_scale,
                   interpret):
    """`paged_attention`'s latent form: q [B, H, K] against ONE plane
    [L, P, ps, K] left in HBM -> [B, H, latent]."""
    B, H, K = q.shape
    ps, item = kv_pool.shape[2], kv_pool.dtype.itemsize
    n = decode_block_pages(tables.shape[1], ps, K, item, H, latent,
                           latent=True)
    group = _decode_slot_group(
        B, H * (K + latent) // 2 * q.dtype.itemsize,
        _decode_vmem_bytes(n, ps, K, item, H, latent, latent=True))
    prefetch = _prefetch(layer, (tables, lengths), None, None)
    slots = lambda lanes: pl.BlockSpec((group, H, lanes),
                                       lambda g, *_: (g, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B // group,),
        in_specs=[slots(K), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=slots(latent),
        scratch_shapes=[
            pltpu.VMEM((_LATENT_BUFFERS, n * ps, K), kv_pool.dtype),
            pltpu.SemaphoreType.DMA((_LATENT_BUFFERS,)),
            pltpu.VMEM((2, H, K), q.dtype),          # this slot's q, the next's
            pltpu.VMEM((H, n * ps), jnp.float32),    # this block's scores
            pltpu.VMEM((H, n * ps), jnp.float32),    # the next block's
            pltpu.VMEM((H, _LANES), jnp.float32),    # m
            pltpu.VMEM((H, _LANES), jnp.float32),    # l
            pltpu.VMEM((H, latent), jnp.float32),    # acc
        ],
    )
    return pl.pallas_call(
        functools.partial(_latent_decode_kernel, sm_scale=sm_scale,
                          page_size=ps, block_pages=n, v_dim=latent),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, latent), q.dtype),
        interpret=interpret,
        name="paged_decode_attn_latent",
    )(*prefetch, q, kv_pool)


# The prefill kernel's kv block. A block under ~256 keys leaves the two
# matmuls of a head at a fraction of an MXU tile and pays the head's fixed
# work (softmax-state update, accumulator rescale) once a 64-key page;
# the flash kernel beside this one (ops/attention.py) never goes under
# 128 keys. The budget is what the blocks may take of the 16 MiB of VMEM
# a kernel gets by default on the chip, beside q, out and the state.
_PREFILL_BLOCK_KEYS = 256
_PREFILL_VMEM_BUDGET = 12 * 2**20


def _prefill_fixed_bytes(chunk, q_lanes, q_itemsize, n_heads,
                         o_lanes=None) -> int:
    """VMEM the prefill kernel takes whatever its kv block: the query
    and output blocks ([chunk, q_lanes] and [chunk, o_lanes]; None: as
    the query's; double-buffered), the f32 accumulator and the (m, l)
    state of `n_heads` heads."""
    o_lanes = q_lanes if o_lanes is None else o_lanes
    return (2 * chunk * (q_lanes + o_lanes) * q_itemsize
            + 4 * chunk * o_lanes + 2 * n_heads * chunk * _LANES * 4)


def prefill_kv_split(kv_lanes, chunk, q_lanes, q_itemsize, n_heads,
                     v_lanes=None) -> int:
    """1, or the S where the prefill kernel's grid gets a KV-head axis
    of S steps: when the query block, accumulator and state of all
    `n_heads` heads pass `_PREFILL_VMEM_BUDGET` by themselves (48 or 72
    heads of 128 over a 128-token chunk: 16-24 MB). A grid step then
    takes G/S KV heads' slice of a page, the fewest whose K lanes and V
    lanes (`v_lanes` = G*Kv; None: as K) are both whole lane tiles: one
    KV head (S = G) at a head size of 128 or 256, two (S = G/2) where K
    heads are 192 wide; 1 where no group but the whole is, and for heads
    narrower than a lane tile."""
    head_dim = q_lanes // n_heads
    G = kv_lanes // head_dim
    v_lanes = kv_lanes if v_lanes is None else v_lanes
    o_lanes = q_lanes // kv_lanes * v_lanes         # H/G x G*Kv
    if (G > 1 and head_dim >= _LANES
            and _prefill_fixed_bytes(chunk, q_lanes, q_itemsize, n_heads,
                                     o_lanes) > _PREFILL_VMEM_BUDGET):
        for S in range(G, 1, -1):
            if (G % S == 0 and kv_lanes // S % _LANES == 0
                    and v_lanes // S % _LANES == 0):
                return S
    return 1


def prefill_block_pages(n_pg, page_size, kv_lanes, kv_itemsize, chunk,
                        q_lanes, q_itemsize, n_heads, v_lanes=None,
                        latent=False) -> int:
    """Table columns one grid step of the prefill kernel attends: the
    largest power of two that is at most `n_pg`, keeps the block at or
    under `_PREFILL_BLOCK_KEYS` keys, and whose K and V blocks
    (`kv_lanes` = G*K and `v_lanes` = G*Kv wide; None: as K;
    double-buffered by the pipeline) fit
    `_PREFILL_VMEM_BUDGET` beside the query and output blocks ([chunk,
    `q_lanes`], double-buffered), the f32 accumulator and the (m, l)
    state of `n_heads` heads. Pure in the shapes: the engine's
    `prefill_block_fill` counter and the kernel ask it the same
    question. Where the grid splits by KV head (`prefill_kv_split`) the
    shapes are one step's. `latent`: the latent form's own rule
    (`latent_prefill_shape`; `v_lanes` the value's lanes of a row)."""
    if latent:
        return latent_prefill_shape(n_pg, page_size, kv_lanes, kv_itemsize,
                                    chunk, n_heads, v_lanes)[0]
    v_lanes = kv_lanes if v_lanes is None else v_lanes
    o_lanes = q_lanes // kv_lanes * v_lanes         # H/G x G*Kv
    S = prefill_kv_split(kv_lanes, chunk, q_lanes, q_itemsize, n_heads,
                         v_lanes)
    kv_lanes, v_lanes, q_lanes, o_lanes, n_heads = (
        kv_lanes // S, v_lanes // S, q_lanes // S, o_lanes // S,
        n_heads // S)
    fixed = _prefill_fixed_bytes(chunk, q_lanes, q_itemsize, n_heads,
                                 o_lanes)
    page = 2 * page_size * (kv_lanes + v_lanes) * kv_itemsize  # two buffers
    n = 1
    while (2 * n <= n_pg and 2 * n * page_size <= _PREFILL_BLOCK_KEYS
           and fixed + 2 * n * page <= _PREFILL_VMEM_BUDGET):
        n *= 2
    return n


def _spread(x, width, start=0):
    """[C, width] of a lane-uniform [C, LANES] array, taken at lane
    `start` where that keeps it aligned with what it meets."""
    if start + width <= _LANES:
        return x[:, start:start + width]
    if width % _LANES == 0:
        return jnp.concatenate([x] * (width // _LANES), axis=1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], width))


def _fold(p):
    """[C, LANES] whose lanes sum to the rows' sums of p [C, width]: the
    LANES-wide pieces added up (the sum over lanes itself waits for the
    last kv block, once a row and head, not once a block)."""
    width = p.shape[1]
    if width % _LANES:
        total = jnp.sum(p, axis=1, keepdims=True)
        lane = jax.lax.broadcasted_iota(jnp.int32, (p.shape[0], _LANES), 1)
        return jnp.where(lane == 0, total, 0.0)
    return sum(p[:, i:i + _LANES] for i in range(0, width, _LANES))


def _prefill_kernel(
    *refs,
    sm_scale, page_size, block_pages, n_heads, n_kv_heads, quantized=False,
    window=None, kv_axis=1, v_head_dim=None, sink=False,
):
    """Ragged chunked-prefill attention: one query BLOCK (a prompt chunk at
    an arbitrary token offset) against the slot's page pool. The decode
    kernel's twin with a C-sized query dimension: same scalar-prefetch
    layer and page table (they ARE the DMA block index), same
    online-softmax (m, l, acc) VMEM state across the kv grid axis —
    plus the causal mask INSIDE the chunk (tpos <= query's absolute
    position), which is what lets the chunk's own K/V be written to the
    pool before the kernel runs and then read back like any earlier page.
    A grid step attends a BLOCK of `block_pages` consecutive table
    columns: their pages arrive as that many [ps, G*K] refs a pool, and
    a KV head's K-lane slices of them are stacked into one
    [block_pages*ps, K] operand, so a head does one QKᵀ, one softmax
    update and one PV a block. Heads are a static loop over K-lane
    slices of the [C, H*K] query block and accumulator (head h reads KV
    head h // (H/G)'s stack). Ref order mirrors `_decode_kernel`:
    scalar-prefetch (layer, tables, offsets, lengths, and for int8 pools
    the per-page K/V scale vectors) first, then VMEM blocks; `quantized`
    dequants each page of the block by its own scale as it is stacked.
    `v_head_dim` and `sink` as in `_decode_kernel`: V's lane slices, the
    accumulator and the output go by V's head size, and a head's (m, l)
    start from its sink's row."""
    n = block_pages
    refs = iter(refs)
    take = lambda count: [next(refs) for _ in range(count)]
    _layer_ref, tables_ref, offsets_ref, lengths_ref = take(4)
    (col_ref,) = take(1) if window is not None else (None,)
    ks_ref, vs_ref = take(2) if quantized else (None, None)
    (q_ref,) = take(1)
    (sink_ref,) = take(1) if sink else (None,)
    k_refs, v_refs = take(n), take(n)
    o_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    j = pl.program_id(kv_axis)
    C, HK = q_ref.shape
    head_dim = HK // n_heads
    v_dim = v_head_dim or head_dim
    group = n_heads // n_kv_heads
    block = n * page_size

    @pl.when(j == 0)
    def _init():
        if sink_ref is None:
            _seed_state(m_ref, l_ref, None)
        else:
            for h in range(n_heads):
                _seed_state(m_ref.at[h], l_ref.at[h], sink_ref[h:h + 1, :])
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = lengths_ref[b]
    q_off = offsets_ref[b]
    if window is not None:      # a ring: where each column's page starts
        firsts = [_first_key(col_ref[b, j * n + i], page_size)
                  for i in range(n)]

    def _compute():
        # Causal within the whole sequence: query row c sits at absolute
        # position q_off + c and may attend tpos <= that. The kv_len bound
        # additionally masks pad rows (c >= this chunk's valid tokens,
        # whose absolute position runs past kv_len) to the valid prefix so
        # their softmax stays finite; their output is discarded host-side.
        # A dead column inside a live block is the null page, whose
        # positions are past kv_len like any other.
        if window is None:
            tpos = j * block + jax.lax.broadcasted_iota(
                jnp.int32, (C, block), 1)
            qpos = q_off + jax.lax.broadcasted_iota(jnp.int32, (C, block), 0)
            visible = (tpos <= qpos) & (tpos < kv_len)
        else:
            # A row whose window has not reached this block yet has no
            # visible key in it: its state takes exp(0) for the block and
            # drops all of it (corr = 0) at its first visible key, which
            # every row has (its own position, or for a pad row the
            # chunk's valid tokens).
            in_page = jax.lax.broadcasted_iota(
                jnp.int32, (C, page_size), 1)
            tpos = jnp.concatenate([f + in_page for f in firsts], axis=1)
            qpos = q_off + jax.lax.broadcasted_iota(jnp.int32, (C, block), 0)
            visible = ((tpos <= qpos) & (tpos < kv_len)
                       & (tpos > qpos - window))
        k_sc = v_sc = [None] * n
        if quantized:
            pages = [tables_ref[b, j * n + i] for i in range(n)]
            k_sc = [ks_ref[p] for p in pages]
            v_sc = [vs_ref[p] for p in pages]

        def stack(page_refs, lanes, scales):
            parts = [r[:, lanes] if sc is None
                     else r[:, lanes].astype(jnp.float32) * sc
                     for r, sc in zip(page_refs, scales)]     # n x [ps, K]
            return parts[0] if n == 1 else jnp.concatenate(parts, axis=0)

        for g in range(n_kv_heads):
            k = stack(k_refs, slice(g * head_dim, (g + 1) * head_dim), k_sc)
            v = stack(v_refs, slice(g * v_dim, (g + 1) * v_dim), v_sc)
            for h in range(g * group, (g + 1) * group):
                q = q_ref[:, h * head_dim:(h + 1) * head_dim]    # [C, K]
                lanes = slice(h * v_dim, (h + 1) * v_dim)
                if quantized:
                    q = q.astype(jnp.float32)
                s = jax.lax.dot_general(
                    q, k, _NT, preferred_element_type=jnp.float32) * sm_scale
                s = jnp.where(visible, s, NEG_INF)

                # The state is lane-uniform [C, LANES] (m) and a partial
                # sum a lane (l), used at full width: a [C, 1] column cut
                # out of it and spread again costs more than the matmuls.
                m_prev = m_ref[h]
                m_new = jnp.maximum(m_prev,
                                    jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - _spread(m_new, block))   # [C, block] fp32
                corr = jnp.exp(m_prev - m_new)           # [C, LANES]
                l_ref[h] = l_ref[h] * corr + _fold(p)
                pv = jnp.dot(p.astype(v.dtype), v,
                             preferred_element_type=jnp.float32)  # [C, Kv]
                acc_ref[:, lanes] = (
                    acc_ref[:, lanes]
                    * _spread(corr, v_dim, lanes.start % _LANES) + pv)
                m_ref[h] = m_new

    # Blocks entirely past the chunk's last valid position do no compute
    # (null-table tail included; its repeated block index also elides
    # the DMA after the first fetch). In a ring a block is live when any
    # of its columns holds keys the chunk's FIRST query can still see.
    if window is None:
        pl.when(j * block < kv_len)(_compute)
    else:
        live = [(f < kv_len) & (f + page_size > q_off - window + 1)
                for f in firsts]
        pl.when(functools.reduce(jnp.logical_or, live))(_compute)

    @pl.when(j == pl.num_programs(kv_axis) - 1)
    def _finish():
        for h in range(n_heads):
            lanes = slice(h * v_dim, (h + 1) * v_dim)
            l = jnp.sum(l_ref[h], axis=1, keepdims=True)
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[:, lanes] = (acc_ref[:, lanes] / l_safe).astype(
                o_ref.dtype)


def paged_prefill_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    layer,
    tables: jax.Array,
    offsets: jax.Array,
    lengths: jax.Array,
    *,
    sm_scale: float | None = None,
    interpret: bool | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    window: int | None = None,
    col_page: jax.Array | None = None,
    sink: jax.Array | None = None,
    latent: int | None = None,
) -> jax.Array:
    """Chunked-prefill attention straight against the KV page pool.

    Args:
      q: [B, C, H, K] — each slot's chunk of C queries (post-rotary),
        starting at absolute position ``offsets[b]``.
      k_pool, v_pool: [L, P, page_size, G*K] — the WHOLE page pool, read
        in place at ``(layer, page)`` (row 0 of every layer is the
        reserved null page); G KV heads under H query heads. May be int8 (quantized serving) with
        ``k_scale``/``v_scale`` [L, P] per-page scale planes, handled
        exactly as in `paged_attention`.
      layer: int32 scalar (traced inside the layer scan).
      tables: [B, n_pg] int32 page ids per slot (unallocated tail = 0).
        n_pg may be a WIDTH-SLICED view of the engine's full page table
        (the pow-2 bucket covering each row's written prefix + chunk).
        The grid is (B, ceil(n_pg / n)) with n = `prefill_block_pages`
        table columns a step (a width n does not divide is padded with
        null columns here), so compute and pool-page bytes scale with
        the sliced width in blocks of n pages — interior chunks of a
        long-max-len prompt pay for the prefix they attend over, not for
        max_pages.
      offsets: [B] int32 absolute position of q[:, 0].
      lengths: [B] int32 valid kv positions per slot (= offset + valid
        chunk tokens; must satisfy lengths[b] <= n_pg * page_size, or in
        a ring that every page a query can see is in some column).
      window, col_page: as in `paged_attention`: query i attends keys j
        with ``i - window < j <= i``.
      sink: [H] float32, as in `paged_attention`.
      latent: as in `paged_attention`: ONE plane, no ``v_pool``; the
        heads of a chunk are more query rows against the same pages
        (`_latent_prefill_kernel`).
    Returns [B, C, H, Kv] in q.dtype (Kv: V's head size, K's unless the
    V plane says otherwise); rows past a slot's valid chunk tokens
    are defined but meaningless (the engine discards them)."""
    B, C, H, K = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(K)
    if interpret is None:
        interpret = _interpret_default()
    if latent is not None:
        _check_latent(K, k_pool, v_pool, latent, k_scale, window, sink)
        return _latent_prefill(q, k_pool, layer, tables, offsets, lengths,
                               latent, sm_scale, interpret)
    ps, G, Kv = _check_pool(H, K, k_pool, v_pool)
    n_pg = tables.shape[1]
    quantized = k_scale is not None
    _check_window(window, col_page, tables, quantized)
    n = prefill_block_pages(n_pg, ps, G * K, k_pool.dtype.itemsize, C,
                            H * K, q.dtype.itemsize, H, G * Kv)
    tables, col_page = _pad_columns(tables, col_page, n)
    name, prefetch, extra = _call_form(
        "paged_prefill_attn", layer, (tables, offsets, lengths), k_scale,
        v_scale, window, col_page)
    split = prefill_kv_split(G * K, C, H * K, q.dtype.itemsize, H, G * Kv)
    if split > 1:       # G/split KV heads and their query heads a grid step
        extra["kv_axis"] = 2
    H_step, G_step = H // split, G // split
    sink = _sink_form(extra, sink, H, K, Kv)

    kernel = functools.partial(
        _prefill_kernel, sm_scale=sm_scale, page_size=ps, block_pages=n,
        n_heads=H_step, n_kv_heads=G_step, quantized=quantized, **extra)
    scratch = [
        pltpu.VMEM((H_step, C, _LANES), jnp.float32),  # m
        pltpu.VMEM((H_step, C, _LANES), jnp.float32),  # l
        pltpu.VMEM((C, H_step * Kv), jnp.float32),     # acc
    ]
    out = _pool_call(kernel, name, q.reshape(B, C, H * K),
                     k_pool, v_pool, prefetch, tables.shape[1], scratch,
                     interpret, block_pages=n, kv_split=split, sink=sink)
    return out.reshape(B, C, H, Kv)


# ---------------------------------------------- the latent form, prefill

def latent_prefill_shape(n_pg, page_size, lanes, itemsize, chunk, n_heads,
                         v_lanes) -> tuple[int, int]:
    """(table columns a kv block, query heads a grid step) of the prefill
    kernel's latent form. A latent row is every head's key, so the heads
    of a chunk are only more query rows against the same pages: a grid
    step takes `heads` of them as ONE [heads*chunk, lanes] operand. The
    block is `prefill_block_pages`' rule without its head terms (the
    largest power of two at most `n_pg` and `_PREFILL_BLOCK_KEYS` keys);
    `heads` the largest divisor of `n_heads` whose query, output, f32
    accumulator, (m, l) state and score tiles fit `_PREFILL_VMEM_BUDGET`
    beside the block's pages (double-buffered). Pure in the shapes."""
    n = 1
    while 2 * n <= n_pg and 2 * n * page_size <= _PREFILL_BLOCK_KEYS:
        n *= 2
    block = n * page_size
    tiled = -(-lanes // _LANES) * _LANES
    pages = 2 * block * tiled * itemsize
    row = (2 * (tiled + v_lanes) * itemsize + 4 * v_lanes
           + 2 * _LANES * 4 + 3 * block * 4)
    return n, max(h for h in range(1, n_heads + 1) if n_heads % h == 0
                  and (h == 1 or pages + h * chunk * row
                       <= _PREFILL_VMEM_BUDGET))


def _latent_prefill_kernel(*refs, sm_scale, page_size, block_pages, chunk,
                           v_dim):
    """`_prefill_kernel` for a latent pool. Grid (chunk row, head group,
    kv block); the step's queries are [heads*chunk, K], head after head,
    so row r sits at the chunk's position ``r % chunk``. No loop over
    heads: ONE score tile against the block's pages stacked, one softmax
    update and one PV against the first `v_dim` lanes of the SAME stack.
    Refs: layer, tables, offsets, lengths (SMEM), q, `block_pages` page
    refs [ps, K], the output [heads*chunk, v_dim], and (m, l, acc)."""
    n = block_pages
    refs = iter(refs)
    take = lambda count: [next(refs) for _ in range(count)]
    _layer_ref, _tables_ref, offsets_ref, lengths_ref = take(4)
    (q_ref,), k_refs = take(1), take(n)
    o_ref, m_ref, l_ref, acc_ref = refs
    b, j = pl.program_id(0), pl.program_id(2)
    rows, block = q_ref.shape[0], n * page_size
    kv_len, q_off = lengths_ref[b], offsets_ref[b]

    @pl.when(j == 0)
    def _init():
        _seed_state(m_ref, l_ref, None)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j * block < kv_len)
    def _compute():
        k = (k_refs[0][...] if n == 1 else
             jnp.concatenate([r[...] for r in k_refs], axis=0))  # [block, K]
        tpos = j * block + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block), 1)
        qpos = q_off + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block), 0) % chunk
        s = jax.lax.dot_general(
            q_ref[...], k, _NT, preferred_element_type=jnp.float32) * sm_scale
        # Causal in the whole sequence, and pad rows held to the valid
        # prefix: `_prefill_kernel` has the why.
        s = jnp.where((tpos <= qpos) & (tpos < kv_len), s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _spread(m_new, block))
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + _fold(p)
        pv = jnp.dot(p.astype(k.dtype), k[:, :v_dim],
                     preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * _spread(corr, v_dim) + pv
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        l = jnp.sum(l_ref[...], axis=1, keepdims=True)
        o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def _latent_prefill(q, kv_pool, layer, tables, offsets, lengths, latent,
                    sm_scale, interpret):
    """`paged_prefill_attention`'s latent form: q [B, C, H, K] against
    ONE plane [L, P, ps, K] → [B, C, H, latent]."""
    B, C, H, K = q.shape
    ps = kv_pool.shape[2]
    n, heads = latent_prefill_shape(
        tables.shape[1], ps, K, kv_pool.dtype.itemsize, C, H, latent)
    tables, _ = _pad_columns(tables, None, n)
    prefetch = _prefetch(layer, (tables, offsets, lengths), None, None)
    rows = heads * C
    im_q = lambda b, g, j, *_: (b, g, 0)
    page = lambda i: pl.BlockSpec(
        (None, None, ps, K),
        lambda b, g, j, layer, tbl, *_: (layer[0], tbl[b, j * n + i], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, H // heads, tables.shape[1] // n),
        in_specs=[pl.BlockSpec((None, rows, K), im_q)]
        + [page(i) for i in range(n)],
        out_specs=pl.BlockSpec((None, rows, latent), im_q),
        scratch_shapes=[
            pltpu.VMEM((rows, _LANES), jnp.float32),    # m
            pltpu.VMEM((rows, _LANES), jnp.float32),    # l
            pltpu.VMEM((rows, latent), jnp.float32),    # acc
        ],
    )
    out = pl.pallas_call(
        functools.partial(_latent_prefill_kernel, sm_scale=sm_scale,
                          page_size=ps, block_pages=n, chunk=C, v_dim=latent),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H * C, latent), q.dtype),
        interpret=interpret,
        name="paged_prefill_attn_latent",
    )(*prefetch, q.transpose(0, 2, 1, 3).reshape(B, H * C, K),
      *([kv_pool] * n))
    return out.reshape(B, H, C, latent).transpose(0, 2, 1, 3)


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

def _gather_timeline(k_pool, v_pool, layer, tables, n_heads, head_dim,
                     k_scale, v_scale, latent=None):
    """Each slot's contiguous K and V timelines [B, T, H, K] and
    [B, T, H, Kv], gathered from the whole pool at ``[layer, tables]``
    (pages only: no layer's plane is cut out) and, for an int8 pool,
    dequanted exactly as the fused kernels do (page.astype(f32) *
    scale). `latent`: ONE plane; the timelines are [B, T, 1, K] and its
    first `latent` lanes, one row for every head."""
    B, n_pg = tables.shape
    ps = k_pool.shape[2]
    if latent is not None:
        view = k_pool[layer, tables].reshape(B, n_pg * ps, 1, head_dim)
        return view, view[..., :latent]
    G = k_pool.shape[3] // head_dim
    views = []
    for pool, scale in ((k_pool, k_scale), (v_pool, v_scale)):
        view = pool[layer, tables]               # [B, n_pg, ps, H*K]
        if scale is not None:
            view = (view.astype(jnp.float32)
                    * scale[layer, tables][:, :, None, None].astype(
                        jnp.float32))
        view = view.reshape(B, n_pg * ps, G, pool.shape[3] // G)
        # Grouped-query: every query head sees its KV head's timeline.
        views.append(view if G == n_heads
                     else jnp.repeat(view, n_heads // G, axis=2))
    return views


def _key_positions(tables, page_size, col_page):
    """[B, T] position of every key of the gathered timelines: column c
    is page c, or in a ring the page `col_page` says it holds (a column
    that holds none lies past every length)."""
    B, n_pg = tables.shape
    if col_page is None:
        return jnp.broadcast_to(jnp.arange(n_pg * page_size)[None],
                                (B, n_pg * page_size))
    first = _first_key(col_page, page_size)
    return (first[:, :, None] + jnp.arange(page_size)[None, None, :]
            ).reshape(B, n_pg * page_size)


def _softmax(s, sink):
    """Softmax over the last axis of s [B, H, ..., T] float32; with a
    `sink` [H] each head's logit joins the denominator and is dropped
    from the probabilities (it has no value row)."""
    if sink is None:
        return jax.nn.softmax(s, axis=-1)
    logit = sink.astype(jnp.float32).reshape((1, -1) + (1,) * (s.ndim - 2))
    joined = jnp.concatenate(
        [s, jnp.broadcast_to(logit, s.shape[:-1] + (1,))], axis=-1)
    return jax.nn.softmax(joined, axis=-1)[..., :-1]


def reference_paged_attention(q, k_pool, v_pool, layer, tables, lengths, *,
                              sm_scale=None, k_scale=None, v_scale=None,
                              window=None, col_page=None, sink=None,
                              latent=None):
    """Gather-semantics oracle: reconstitute each slot's contiguous
    timeline and run plain-XLA attention — byte-for-byte the math of
    models/paged_kv.py's gather read path (test oracle + fallback).
    Same operands as `paged_attention`: the whole pool
    [L, P, page_size, H*K] and the layer index; int8 pools pass the
    per-page ``k_scale``/``v_scale`` planes [L, P]."""
    B, H, K = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(K)
    _check_window(window, col_page, tables, k_scale is not None)
    k_view, v_view = _gather_timeline(k_pool, v_pool, layer, tables, H, K,
                                      k_scale, v_scale, latent)
    if latent is not None:      # one row a token under every head
        _check_latent(K, k_pool, v_pool, latent, k_scale, window, sink)
        qk, pv = "bhk,btgk->bht", "bht,btgk->bhk"
    else:
        qk, pv = "bhk,bthk->bht", "bht,bthk->bhk"
    s = jnp.einsum(qk, q, k_view,
                   preferred_element_type=jnp.float32) * sm_scale
    tpos = _key_positions(tables, k_pool.shape[2], col_page)   # [B, T]
    mask = tpos < lengths[:, None]
    if window is not None:
        mask &= tpos >= lengths[:, None] - window
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    probs = _softmax(s, sink).astype(q.dtype)
    # q.dtype out unconditionally: the dequanted v_view is f32, and the
    # einsum's promotion must not leak into callers' scan carries.
    return jnp.einsum(pv, probs, v_view).astype(q.dtype)


def reference_paged_prefill_attention(q, k_pool, v_pool, layer, tables,
                                      offsets, lengths, *, sm_scale=None,
                                      k_scale=None, v_scale=None,
                                      window=None, col_page=None, sink=None,
                                      latent=None):
    """Gather-semantics oracle for chunked prefill: reconstitute each
    slot's contiguous timeline from the pool and run plain-XLA causal
    attention for a C-query chunk at absolute offset — byte-for-byte the
    math of models/paged_kv.py's chunked-prefill gather path (the
    exact-semantics default off-TPU; also the kernel's test oracle).

    q: [B, C, H, K]; pool and layer as in `paged_prefill_attention`;
    offsets/lengths: [B] (lengths = offset + valid chunk tokens).
    `tables` may be a width-sliced view (see `paged_prefill_attention`):
    the reconstituted timeline T = tables.shape[1] · page_size shrinks
    with the bucket width, so the oracle's gather/einsum bytes scale the
    same way the kernel's grid does. → [B, C, H, K] in q.dtype."""
    B, C, H, K = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(K)
    _check_window(window, col_page, tables, k_scale is not None)
    k_view, v_view = _gather_timeline(k_pool, v_pool, layer, tables, H, K,
                                      k_scale, v_scale, latent)
    if latent is not None:
        _check_latent(K, k_pool, v_pool, latent, k_scale, window, sink)
        qk, pv = "bchk,btgk->bhct", "bhct,btgk->bchk"
    else:
        qk, pv = "bchk,bthk->bhct", "bhct,bthk->bchk"
    s = jnp.einsum(qk, q, k_view,
                   preferred_element_type=jnp.float32) * sm_scale
    tpos = _key_positions(tables, k_pool.shape[2], col_page)[:, None]
    qpos = (offsets[:, None] + jnp.arange(C)[None, :])[:, :, None]
    mask = (tpos <= qpos) & (tpos < lengths[:, None, None])    # [B, C, T]
    if window is not None:
        mask &= tpos > qpos - window
    s = jnp.where(mask[:, None], s, NEG_INF)
    probs = _softmax(s, sink).astype(q.dtype)
    # q.dtype out unconditionally (see reference_paged_attention).
    return jnp.einsum(pv, probs, v_view).astype(q.dtype)


__all__ = [
    "paged_attention", "paged_prefill_attention",
    "reference_paged_attention", "reference_paged_prefill_attention",
    "prefill_block_pages", "prefill_kv_split", "decode_block_pages",
    "latent_prefill_shape",
]
