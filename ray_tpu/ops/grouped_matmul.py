"""A grouped matmul of the repo's own: `moe_grouped_matmul`.

    out[r] = lhs[r] @ rhs[g]      for every row r of group g

`lhs` [M, K] holds its rows in contiguous groups of `sizes` [G] from row
0; `rhs` [G, K, N] is one plane a group; the result is [M, N] float32,
accumulated in float32. It is `jax.lax.ragged_dot` on the TPU, for every
plane it can tile (`tiles`; ops/moe.py `_grouped_dot` asks).

The kernel walks VISITS: a (group, row tile) pair for every tile of 128
rows a non-empty group has a row in, in row order. A visit's weight
block is as much of its group's plane as `_PLANE_BYTES` takes (the whole
[K, N] plane where it fits: ONE contiguous DMA a group), and the
pipeline fetches the next visit's block while this one multiplies, across
the group boundary. Two visits of one group follow each other, so the
block is fetched once for the rows of its group. An EMPTY group has no
visit and streams nothing; a group of one row streams its plane. Tiles
past the last group are not visited: their rows of the result hold
whatever was there.

The visits are scalars the kernel reads before its grid (`visits`), a
handful of small XLA ops over `sizes`; the grid's length is the number
of visits, a value of the program and not a shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_NAME = "moe_grouped_matmul"

_LANES = 128
_ROW_TILE = 128
# One buffer of a weight block: the pipeline holds two. [1,024, 2,688]
# bf16 is 5.5 MB; a wider plane is cut along N into equal parts (kimi-k2.6's
# 29.4 MB in 4, mimo-v2-flash's 16.8 MB in 2). Neither the size of a part
# nor how many are in flight moves a call: PERF.md section 6, PR 65.
_PLANE_BYTES = 8 << 20
_VMEM_LIMIT = 64 << 20


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _n_tile(K: int, N: int, itemsize: int) -> int:
    """Columns of a weight block: the widest part of N, a multiple of 128
    that divides it, whose [K, part] fits `_PLANE_BYTES`."""
    parts = N // _LANES
    for cut in range(1, parts + 1):
        if parts % cut == 0 and K * (N // cut) * itemsize <= _PLANE_BYTES:
            return N // cut
    return _LANES


def tiles(lhs_shape, rhs_shape) -> bool:
    """Whether the kernel takes `lhs` [M, K] against `rhs` [G, K, N]: M a
    multiple of 128 rows, K and N of 128 lanes."""
    (M, K), (_, K_rhs, N) = lhs_shape, rhs_shape
    return K_rhs == K and not (M % _ROW_TILE or K % _LANES or N % _LANES)


def visits(sizes: jax.Array, n_rows: int):
    """The kernel's walk over `sizes` [G] (rows a group, from row 0) in
    tiles of 128 rows → (group [V], tile [V], start [V], end [V], count
    []), int32, V = n_rows // 128 + G: visit v multiplies rows
    `start[v]:end[v]` of the whole, all in tile `tile[v]`, by plane
    `group[v]`. Entries from `count` on repeat the last visit. Sizes
    that pass `n_rows` are cut there: no visit names a tile that is not."""
    G, tm = sizes.shape[0], _ROW_TILE
    tiles = n_rows // tm
    sizes = sizes.astype(jnp.int32)
    ends = jnp.minimum(jnp.cumsum(sizes), n_rows)
    starts = jnp.minimum(ends - sizes, n_rows)
    first = starts // tm
    spans = jnp.where(ends > starts, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(spans)                  # visits through group g
    count = upto[-1]
    v = jnp.minimum(jnp.arange(tiles + G, dtype=jnp.int32),
                    jnp.maximum(count - 1, 0))
    group = jnp.sum(upto[None, :] <= v[:, None], axis=1, dtype=jnp.int32)
    group = jnp.minimum(group, G - 1)         # (no visit at all: any plane)
    tile = first[group] + v - (upto[group] - spans[group])
    start = jnp.maximum(starts[group], tile * tm)
    end = jnp.minimum(ends[group], (tile + 1) * tm)
    return group, tile, start, end, count


def _kernel(group_ref, tile_ref, start_ref, end_ref, lhs_ref, rhs_ref,
            out_ref):
    del group_ref
    v = pl.program_id(1)
    row = (tile_ref[v] * _ROW_TILE
           + jax.lax.broadcasted_iota(jnp.int32, (_ROW_TILE, 1), 0))
    mine = (row >= start_ref[v]) & (row < end_ref[v])
    product = jnp.dot(lhs_ref[...], rhs_ref[...],
                      preferred_element_type=jnp.float32)
    # A tile's first visit finds whatever the buffer held: rows of another
    # group are written by that group's visit, rows of none are nobody's.
    out_ref[...] = jnp.where(mine, product, out_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_grouped_matmul(lhs: jax.Array, rhs: jax.Array, sizes: jax.Array,
                       *, interpret: bool | None = None) -> jax.Array:
    """lhs [M, K] in contiguous groups of `sizes` [G] int32, rhs [G, K, N]
    → [M, N] float32. M, K and N multiples of 128; rows past the last
    group come back holding anything."""
    if interpret is None:
        interpret = _interpret_default()
    (M, K), (G, _, N), tm = lhs.shape, rhs.shape, _ROW_TILE
    if not tiles(lhs.shape, rhs.shape) or sizes.shape != (G,):
        raise ValueError(
            f"moe_grouped_matmul: lhs {lhs.shape}, rhs {rhs.shape}, sizes "
            f"{sizes.shape}: rows must be a multiple of {tm}, K and N of "
            f"{_LANES}, a plane and a size a group")
    tn = _n_tile(K, N, rhs.dtype.itemsize)
    group, tile, start, end, count = visits(sizes, M)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(N // tn, count),
            in_specs=[
                pl.BlockSpec((tm, K), lambda n, v, g, t, s, e: (t[v], 0)),
                pl.BlockSpec((None, K, tn),
                             lambda n, v, g, t, s, e: (g[v], 0, n)),
            ],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda n, v, g, t, s, e: (t[v], n)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=KERNEL_NAME,
    )(group, tile, start, end, lhs, rhs)
