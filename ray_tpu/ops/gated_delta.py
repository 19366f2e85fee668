"""The gated delta rule: a linear-attention layer's recurrence over a
matrix state a head, in its two serving forms.

A head's state S is [dk keys, dv values], float32. Token t, with a log
decay g_t <= 0, a write strength beta_t in (0, 2) (a sigmoid in
qwen3-next, twice a sigmoid where the model allows a negative
eigenvalue: olmo-hybrid's `linear_allow_neg_eigval`; I - beta k k^T
then reflects the state's component along k and every eigenvalue still
lies in [-1, 1]), a unit key k_t, a scaled unit query q_t and a value
v_t:

    S <- exp(g_t) S;  d_t = beta_t (v_t - S^T k_t);
    S <- S + k_t d_t^T;  o_t = S^T q_t

**The stack's layout is the caller's, by `packed_heads`.** The pool
holds ``[L, n_slots+1, H / p, dk, p dv]``: p heads SIDE BY SIDE along
the lanes, p the fewest heads whose values fill whole lane tiles of 128
(1 where dv is a multiple of 128: qwen3-next's [32, 128, 128], which is
the plain [H, dk, dv]; 2 at olmo-hybrid's dv = 192: [15, 96, 384], 3
lane tiles over 12 sublane tiles, dense, where [30, 96, 192] would lie
in 256 lanes a row on the chip: a third more bytes in HBM and in every
DMA). Both forms read p off the stack's and v's last axes (`pack_state`
/ `unpack_state` are the two views); no option names it.

`gdn_decode_step` advances every live slot's state by ONE token, in
place: a Pallas kernel (`gdn_decode_step` in a trace) whose grid walks
(slot, block of packed heads) over the whole stack, aliased in and out,
so a step reads and writes each live slot's state (64 KiB a head at 128
x 128, 72 KiB at 96 x 192) once and nothing is gathered, scattered or
copied. A block is the most packed heads that divide H / p, stay under
`_DECODE_HEADS` and under `_DECODE_BLOCK_BYTES` (16 heads = 1 MiB at
qwen3-next's sizes, 5 pairs = 720 KiB at olmo-hybrid's). A packed
head's lanes share one decay / beta / value row (the heads' rows side by
side) and differ only in which key column multiplies them, chosen by
lane; the sums run down the sublanes, so heads never mix. An idle
slot's grid steps name the null slot's block (the stack's last row) and
do no work: consecutive idle steps move nothing. `reference_gdn_decode_step`
is the same step in plain XLA (the oracle, and the path off the TPU).

`gdn_chunk_scan` runs N rows of C tokens by the chunked delta rule, in
blocks of `block` tokens. Within a block, with G the cumulated g, the
d's solve a unit lower-triangular system (the UT transform):

    (I + A) D = beta V - beta exp(G) K S_0,
    A_ij = beta_i exp(G_i - G_j) (k_i . k_j)  for i > j

so U = (I + A)^-1 beta V and W = (I + A)^-1 beta exp(G) K need no state
and are made for every row and block at once; only
D = U - W S_0,  O = exp(G) Q S_0 + tril(exp(G_i - G_j) q_i . k_j) D  and
S' = exp(G_T) S_0 + (exp(G_T - G) K)^T D  walk a row's blocks, and a
slot's rows, in order. A decay ratio is only ever `exp` of a DIFFERENCE
of cumulated g's that is masked to the causal triangle BEFORE the `exp`:
g reaches -60 a token, one token of exp(-cumsum) passes float32's range,
and an `inf` under a mask is a NaN. The difference G_i - G_j is summed
from its own terms g_{j+1} .. g_i, so it keeps its digits whatever came
before token j. (I + A)^-1 is made from the
diagonal blocks of `_INVERSE_BASE` tokens, each by forward substitution
(its rows in order, every entry a bounded sum, every block of every row
and head at once along the lanes), merged pair by pair
([[X, 0], [-Y A21 X, Y]]) up to the block: the true inverse's entries
stay bounded because the recurrence is a contraction at any beta in
(0, 2), and nothing larger is ever formed. (The nilpotent series
(I - A)(I + A^2)(I + A^4)... this replaces forms A^k first, whose
entries grow like (beta k.k)^k C(T, k) and cancel: at a mean key cosine
of 0.5 it lost 2e-3 of an output at beta <= 1 and everything at
beta <= 2, block 64; tests/test_gated_delta.py holds both at cosines up
to 0.99.)
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_LANES = 128
# Packed heads of one slot a grid step of the decode kernel holds: at
# most 16 and at most 1 MiB in and as much out, double-buffered 4 MiB of
# the 16 MiB of VMEM a kernel gets by default (16 x 64 KiB at 128 x 128
# heads; 5 pairs x 144 KiB at 96 x 192).
_DECODE_HEADS = 16
_DECODE_BLOCK_BYTES = 2**20
# Tokens of a diagonal block that `_unit_lower_inverse` solves row by row.
# The whole scan of 8 rows of 128 tokens on the chip, by base (PERF.md,
# PR 60; 128 x 128 / 96 x 192 heads): 4 1.147 / 1.376 ms, 8 1.064 /
# 1.275, 16 2.423 / 2.653, 32 4.064 / 4.139; the series 1.168 / 1.409.
_INVERSE_BASE = 8


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


# ----------------------------------------------------------- a prompt chunk

def _substituted(a):
    """(I + a)^-1 for strictly lower-triangular a [..., t, t], t small,
    by forward substitution: row i of the inverse is
    e_i - sum_{j<i} a_ij row_j. Elementwise throughout (t^2 / 2 fused
    multiply-adds of a row), no matmul and nothing larger than the
    inverse's own entries. The BATCH lies on the lanes while it runs (a
    row is [t, M], M the product of the leading axes): a row of t = 8
    values alone would fill a sixteenth of a lane tile."""
    t = a.shape[-1]
    a_m = jnp.moveaxis(a.reshape((-1, t, t)), 0, -1)         # [t, t, M]
    eye = jnp.eye(t, dtype=a.dtype)
    rows = []
    for i in range(t):
        row = jnp.broadcast_to(eye[i][:, None], a_m.shape[1:])
        for j in range(i):
            row = row - a_m[i, j] * rows[j]
        rows.append(row)
    return jnp.moveaxis(jnp.stack(rows), -1, 0).reshape(a.shape)


def _unit_lower_inverse(a):
    """(I + a)^-1 for a strictly lower-triangular a [..., T, T] (T a
    power of two): the diagonal blocks of `_INVERSE_BASE` tokens by
    forward substitution, then pairs of neighbours merged,
    [[X, 0], [c, Y]]^-1 = [[X^-1, 0], [-Y^-1 c X^-1, Y^-1]], until one
    block is left."""
    T = a.shape[-1]
    t = min(_INVERSE_BASE, T)
    mm = functools.partial(jnp.matmul, precision=_HIGHEST)
    at = lambda r, c, t: a[..., r * t:(r + 1) * t, c * t:(c + 1) * t]
    inv = _substituted(
        jnp.stack([at(b, b, t) for b in range(T // t)], axis=-3))
    while t < T:
        top, bot = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        corner = -mm(bot, mm(jnp.stack(
            [at(2 * p + 1, 2 * p, t) for p in range(T // (2 * t))],
            axis=-3), top))
        inv = jnp.concatenate(
            [jnp.concatenate([top, jnp.zeros_like(top)], axis=-1),
             jnp.concatenate([corner, bot], axis=-1)], axis=-2)
        t *= 2
    return inv[..., 0, :, :]


def gdn_chunk_scan(q, k, v, g, beta, state, chain, fresh, *, block: int = 64):
    """N rows of C consecutive tokens through the gated delta rule.

    q, k [N, C, H, dk], v [N, C, H, dv], g, beta [N, C, H] (a token that
    must leave the state alone carries g = 0 and beta = 0); `state`
    [N, H, dk, dv] float32: each row's slot's state as the pool holds
    it; `chain` [N] int32: the row ABOVE whose final state this row
    starts from (the same slot's chunk before it), -1 for none; `fresh`
    [N] bool: the row starts a prompt, from zeros. A row reads the first
    of fresh / chain / state that applies. Every matmul multiplies
    float32 operands at the highest precision (the chip's default would
    round them to bfloat16; the scan is a tenth of a chunk program).
    → (o [N, C, H, dv] float32, finals [N, H, dk, dv] float32: the state
    after each row)."""
    N, C, H, dk = q.shape
    dv = v.shape[-1]
    T = min(block, C)
    if C % T or T & (T - 1):
        raise ValueError(f"a row of {C} tokens does not cut into blocks of "
                         f"{T} (a power of two)")
    nb = C // T
    # [N, nb, H, T, ...]: a block's tokens a head, float32 throughout.
    cut = lambda t: jnp.moveaxis(
        t.astype(_F32).reshape((N, nb, T, H) + t.shape[3:]), 3, 2)
    q, k, v, g, beta = (cut(t) for t in (q, k, v, g, beta))
    mm = functools.partial(jnp.einsum, precision=_HIGHEST,
                           preferred_element_type=_F32)
    G = jnp.cumsum(g, axis=-1)                               # [N, nb, H, T]
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    # G_i - G_j for i > j as the sum of ITS OWN terms, g_{j+1} .. g_i (0
    # elsewhere), not as a difference of two cumulated sums: one token
    # whose g is -1e10 (a large A times a large step) leaves every later
    # G near -1e10, and their differences multiples of 512.
    diff = jnp.cumsum(jnp.where(i > j, g[..., :, None], 0.0), axis=-2)
    ratio = lambda seen: jnp.exp(jnp.where(seen, diff, -jnp.inf))
    a = beta[..., None] * mm("...ik,...jk->...ij", k, k) * ratio(i > j)
    inv = _unit_lower_inverse(a)
    from_start = jnp.exp(G)[..., None]                       # exp(G_i - G_0)
    to_end = jnp.exp(diff[..., -1, :])[..., None]            # exp(G_T - G_i)
    u = mm("...ij,...jv->...iv", inv, beta[..., None] * v)
    w = mm("...ij,...jk->...ik", inv, beta[..., None] * from_start * k)
    qk = mm("...ik,...jk->...ij", q, k) * ratio(i >= j)
    q_start, k_end = q * from_start, k * to_end
    decay = jnp.exp(G[..., -1])[..., None, None]             # [N, nb, H, 1, 1]

    def row(finals, n):
        s = jnp.where(chain[n] >= 0, finals[jnp.maximum(chain[n], 0)],
                      state[n])
        s = jnp.where(fresh[n], 0.0, s)
        outs = []
        for b in range(nb):
            d = u[n, b] - mm("hik,hkv->hiv", w[n, b], s)
            outs.append(mm("hik,hkv->hiv", q_start[n, b], s)
                        + mm("hij,hjv->hiv", qk[n, b], d))
            s = decay[n, b] * s + mm("hik,hiv->hkv", k_end[n, b], d)
        return finals.at[n].set(s), jnp.stack(outs)          # [nb, H, T, dv]

    finals, o = jax.lax.scan(row, jnp.zeros((N, H, dk, dv), _F32),
                             jnp.arange(N))
    return jnp.moveaxis(o, 2, 3).reshape(N, C, H, dv), finals


def reference_gdn_scan(q, k, v, g, beta, state):
    """The recurrence itself, token by token (`lax.scan`): q, k [C, H, dk],
    v [C, H, dv], g, beta [C, H], state [H, dk, dv] → (o [C, H, dv],
    final state), float32. What `gdn_chunk_scan` is tested against."""
    def token(s, inputs):
        q_t, k_t, v_t, g_t, beta_t = inputs
        s = s * jnp.exp(g_t)[:, None, None]
        d = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", s, k_t))
        s = s + k_t[:, :, None] * d[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, q_t)

    final, o = jax.lax.scan(
        token, state.astype(_F32),
        tuple(t.astype(_F32) for t in (q, k, v, g, beta)))
    return o, final


# ----------------------------------------------------------- a decode step

def packed_heads(n_heads: int, dv: int) -> int:
    """Heads the pool's state keeps side by side along the lanes: the
    fewest whose values fill whole lane tiles, where that many divide
    the heads; else 1 (the plain [H, dk, dv])."""
    p = _LANES // math.gcd(dv, _LANES)
    return p if n_heads % p == 0 else 1


def pack_state(s, p: int):
    """States [..., H, dk, dv] as the pool holds them,
    [..., H / p, dk, p dv]: heads p h .. p h + p - 1 side by side."""
    if p == 1:
        return s
    *lead, H, dk, dv = s.shape
    return jnp.moveaxis(s.reshape(*lead, H // p, p, dk, dv), -3, -2).reshape(
        *lead, H // p, dk, p * dv)


def unpack_state(s, dv: int):
    """`pack_state`'s inverse: the pool's [..., H / p, dk, p dv] as
    [..., H, dk, dv], p read off the last axis."""
    p = s.shape[-1] // dv
    if p == 1:
        return s
    *lead, Hp, dk, _ = s.shape
    return jnp.moveaxis(s.reshape(*lead, Hp, dk, p, dv), -2, -3).reshape(
        *lead, Hp * p, dk, dv)


def reference_gdn_decode_step(state, layer, q, k, v, g, beta, active, *,
                              repeat: int = 1, delta: bool = True):
    """One token for slots 0 .. B-1 of `state` [L, n_slots+1, H / p, dk,
    p dv] at `layer`, in plain XLA. q, k [B, Hk, dk]: the KEY heads'
    (value head h reads key head h // `repeat`); v [B, H, dv], g, beta
    [B, H] float32; `active` [B] bool: the others' state stays. `delta`
    False: the write is beta v itself, not beta (v - S^T k): a gated
    LINEAR recurrence over the same stack (ops/ssd.py).
    → (o [B, H, dv] float32, the updated stack)."""
    B, dv = q.shape[0], v.shape[-1]
    q, k = (jnp.repeat(t.astype(_F32), repeat, axis=1) for t in (q, k))
    old = unpack_state(state[layer, :B], dv)
    s = old * jnp.exp(g)[..., None, None]
    read = functools.partial(jnp.einsum, "bhkv,bhk->bhv", precision=_HIGHEST)
    d = beta[..., None] * (v - read(s, k) if delta else v)
    s = s + k[..., :, None] * d[..., None, :]
    o = read(s, q)
    s = jnp.where(active[:, None, None, None], s, old)
    return o, state.at[layer, :B].set(pack_state(s, state.shape[-1] // dv))


def _decode_kernel(layer_ref, rows_ref, kq_ref, v_ref, a_ref, beta_ref,
                   s_ref, o_ref, s_out_ref, *, heads, pack, repeat, null_slot,
                   delta=True):
    """One slot's block of `heads` packed heads (`pack` value heads side
    by side each). kq_ref [dk, LANES]: column j the key of the block's
    j-th key head, column LANES / 2 + j its query, so that a key lies
    along the state's sublanes; v, a (= exp(g)), beta [heads, pack dv]
    rows, a and beta one value a head spread over its lanes."""
    del layer_ref
    live = rows_ref[pl.program_id(0)] != null_slot

    @pl.when(live)
    def _():
        dk, width = s_ref.shape[-2:]
        dv = width // pack

        def column(h, first):
            """[dk, width] for packed head h: lanes j dv .. (j + 1) dv - 1
            hold kq_ref's column `first` + the key head of its j-th
            value head."""
            at = lambda j: first + (h * pack + j) // repeat
            col = lambda j: jnp.broadcast_to(kq_ref[:, at(j):at(j) + 1],
                                             (dk, width))
            out = col(pack - 1)
            if pack > 1:
                lane = jax.lax.broadcasted_iota(jnp.int32, (dk, width), 1)
                for j in range(pack - 2, -1, -1):
                    out = jnp.where(lane < (j + 1) * dv, col(j), out)
            return out

        for h in range(heads):
            kcol, qcol = column(h, 0), column(h, _LANES // 2)
            s = s_ref[h] * a_ref[h:h + 1, :]
            if delta:
                d = beta_ref[h:h + 1, :] * (
                    v_ref[h:h + 1, :]
                    - jnp.sum(s * kcol, axis=0, keepdims=True))
            else:
                d = beta_ref[h:h + 1, :] * v_ref[h:h + 1, :]
            s = s + kcol * d
            s_out_ref[h] = s
            o_ref[h:h + 1, :] = jnp.sum(s * qcol, axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _decode_block_heads(n_packed: int, pack: int, repeat: int,
                        head_bytes: int) -> int:
    """Packed heads a grid step holds: the most that divide `n_packed`,
    are whole key heads, and stay under `_DECODE_HEADS`, under
    `_DECODE_BLOCK_BYTES` and under the half of `kq`'s lanes their keys
    lie in; 0 if none does."""
    fits = lambda n: (n_packed % n == 0 and (n * pack) % repeat == 0
                      and n * head_bytes <= _DECODE_BLOCK_BYTES
                      and n * pack // repeat <= _LANES // 2)
    return max((n for n in range(1, min(_DECODE_HEADS, n_packed) + 1)
                if fits(n)), default=0)


def gdn_decode_step(state, layer, q, k, v, g, beta, active, *,
                    repeat: int = 1, interpret=None, delta: bool = True,
                    name: str = "gdn_decode_step"):
    """`reference_gdn_decode_step` as one kernel over the whole stack,
    donated: slot b's heads are read and written once, an idle slot's
    not at all. q, k [B, Hk, dk] float32 are the KEY heads' (value head h
    reads key head h // `repeat`); v [B, H, dv], g, beta [B, H]. `delta`
    and `name`: the write without the delta term, under the name its
    caller's kernel has in a trace (ops/ssd.py).
    → (o [B, H, dv] float32, the updated stack)."""
    if interpret is None:
        interpret = _interpret_default()
    L, rows, Hp, dk, width = state.shape
    B, Hk, _ = q.shape
    H, dv = v.shape[1:]
    pack = width // dv
    heads = _decode_block_heads(Hp, pack, repeat, dk * width * 4)
    if (Hp * pack != H or pack * dv != width or Hk * repeat != H or not heads
            or (not interpret and (width % _LANES or dk % 8))):
        raise ValueError(
            f"gdn_decode_step wants a stack [L, slots, H / p, dk, p dv] "
            f"with p dv a multiple of {_LANES} and dk of 8, {repeat} value "
            f"heads a key head, in blocks of whole key heads; got "
            f"state {state.shape}, H={H}, Hk={Hk}, dv={dv}")
    null_slot = rows - 1
    slot_rows = jnp.where(active, jnp.arange(B, dtype=jnp.int32), null_slot)
    # A head block's keys and queries with dk along the sublanes:
    # [B, Hp / heads, dk, its keys | 0 | its queries | 0].
    n_hb, half = Hp // heads, _LANES // 2
    keys = heads * pack // repeat
    cols = lambda t: jnp.pad(
        t.astype(_F32).reshape(B, n_hb, keys, dk),
        ((0, 0), (0, 0), (0, half - keys), (0, 0)))
    kq = jnp.swapaxes(jnp.concatenate([cols(k), cols(q)], axis=2), 2, 3)
    # A packed head's row is its heads' rows side by side; a block's
    # rows a plane of their own, [B, Hp / heads, heads, p dv] (a block of
    # 5 of 15 rows is neither whole sublane tiles nor the whole axis).
    side = lambda t: t.reshape(B, n_hb, heads, width)
    spread = lambda t: side(jnp.broadcast_to(t.astype(_F32)[..., None],
                                             (B, H, dv)))
    per_head = pl.BlockSpec((None, None, heads, width),
                            lambda b, hb, *_: (b, hb, 0, 0))
    block = pl.BlockSpec(
        (None, None, heads, dk, width),
        lambda b, hb, layer, slot, *_: (layer[0], slot[b], hb, 0, 0))
    kernel = functools.partial(_decode_kernel, heads=heads, pack=pack,
                               repeat=repeat, null_slot=null_slot,
                               delta=delta)
    o, state = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, n_hb),
            in_specs=[pl.BlockSpec((None, None, dk, _LANES),
                                   lambda b, hb, *_: (b, hb, 0, 0)),
                      per_head, per_head, per_head, block],
            out_specs=[per_head, block]),
        out_shape=[jax.ShapeDtypeStruct((B, n_hb, heads, width), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={6: 1},
        interpret=interpret,
        name=name,
    )(jnp.asarray(layer, jnp.int32).reshape(1), slot_rows, kq,
      side(v.astype(_F32)), spread(jnp.exp(g)), spread(beta), state)
    return o.reshape(B, H, dv), state


__all__ = ["gdn_chunk_scan", "gdn_decode_step", "reference_gdn_scan",
           "reference_gdn_decode_step", "packed_heads", "pack_state",
           "unpack_state"]
