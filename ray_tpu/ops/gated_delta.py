"""The gated delta rule: a linear-attention layer's recurrence over a
matrix state a head, in its two serving forms.

A head's state S is [dk keys, dv values], float32. Token t, with a log
decay g_t <= 0, a write strength beta_t in (0, 1), a unit key k_t, a
scaled unit query q_t and a value v_t:

    S <- exp(g_t) S;  d_t = beta_t (v_t - S^T k_t);
    S <- S + k_t d_t^T;  o_t = S^T q_t

`gdn_decode_step` advances every live slot's state by ONE token, in
place: a Pallas kernel (`gdn_decode_step` in a trace) whose grid walks
(slot, block of heads) over the whole ``[L, n_slots+1, H, dk, dv]``
stack, aliased in and out, so a step reads and writes each live slot's
64 KiB a head once and nothing is gathered, scattered or copied. An idle
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
and an `inf` under a mask is a NaN. (I + A)^-1 is the nilpotent series
(I - A)(I + A^2)(I + A^4)...: log2(block) - 1 squarings, all matmuls.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST
_LANES = 128
# Heads of one slot a grid step of the decode kernel holds: a block of
# 16 x 64 KiB = 1 MiB in and as much out, double-buffered 4 MiB of the
# 16 MiB of VMEM a kernel gets by default.
_DECODE_HEADS = 16


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


# ----------------------------------------------------------- a prompt chunk

def _unit_lower_inverse(a):
    """(I + a)^-1 for a strictly lower-triangular a [..., T, T] (T a
    power of two): the finite series sum_k (-a)^k as
    (I - a)(I + a^2)(I + a^4)..."""
    T = a.shape[-1]
    mm = functools.partial(jnp.matmul, precision=_HIGHEST)
    power = -a
    inv = jnp.eye(T, dtype=a.dtype) + power
    for _ in range(max(T.bit_length() - 2, 0)):
        power = mm(power, power)
        inv = inv + mm(inv, power)
    return inv


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
    diff = G[..., :, None] - G[..., None, :]                 # G_i - G_j
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    ratio = lambda seen: jnp.exp(jnp.where(seen, diff, -jnp.inf))
    a = beta[..., None] * mm("...ik,...jk->...ij", k, k) * ratio(i > j)
    inv = _unit_lower_inverse(a)
    from_start = jnp.exp(G)[..., None]                       # exp(G_i - G_0)
    to_end = jnp.exp(G[..., -1:] - G)[..., None]             # exp(G_T - G_i)
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

def reference_gdn_decode_step(state, layer, q, k, v, g, beta, active, *,
                              repeat: int = 1):
    """One token for slots 0 .. B-1 of `state` [L, n_slots+1, H, dk, dv]
    at `layer`, in plain XLA. q, k [B, Hk, dk]: the KEY heads' (value
    head h reads key head h // `repeat`); v [B, H, dv], g, beta [B, H]
    float32; `active` [B] bool: the others' state stays.
    → (o [B, H, dv] float32, the updated stack)."""
    B = q.shape[0]
    q, k = (jnp.repeat(t.astype(_F32), repeat, axis=1) for t in (q, k))
    old = state[layer, :B]
    s = old * jnp.exp(g)[..., None, None]
    read = functools.partial(jnp.einsum, "bhkv,bhk->bhv", precision=_HIGHEST)
    d = beta[..., None] * (v - read(s, k))
    s = s + k[..., :, None] * d[..., None, :]
    o = read(s, q)
    s = jnp.where(active[:, None, None, None], s, old)
    return o, state.at[layer, :B].set(s)


def _decode_kernel(layer_ref, rows_ref, kq_ref, v_ref, a_ref, beta_ref,
                   s_ref, o_ref, s_out_ref, *, heads, repeat, null_slot):
    """One slot's block of `heads` value heads. kq_ref [dk, LANES]:
    column j the key of the block's j-th key head, column LANES / 2 + j
    its query, so that a key lies along the state's sublanes; v, a
    (= exp(g)), beta [heads, dv] rows, a and beta one value a head
    spread over the lanes."""
    del layer_ref
    live = rows_ref[pl.program_id(0)] != null_slot

    @pl.when(live)
    def _():
        dk, dv = s_ref.shape[-2:]
        for h in range(heads):
            kh = h // repeat                # value head h's key head
            kcol = jnp.broadcast_to(kq_ref[:, kh:kh + 1], (dk, dv))
            at = _LANES // 2 + kh
            qcol = jnp.broadcast_to(kq_ref[:, at:at + 1], (dk, dv))
            s = s_ref[h] * a_ref[h:h + 1, :]
            d = beta_ref[h:h + 1, :] * (
                v_ref[h:h + 1, :] - jnp.sum(s * kcol, axis=0, keepdims=True))
            s = s + kcol * d
            s_out_ref[h] = s
            o_ref[h:h + 1, :] = jnp.sum(s * qcol, axis=0, keepdims=True)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def gdn_decode_step(state, layer, q, k, v, g, beta, active, *,
                    repeat: int = 1, interpret=None):
    """`reference_gdn_decode_step` as one kernel over the whole stack,
    donated: slot b's heads are read and written once, an idle slot's
    not at all. q, k [B, Hk, dk] float32 are the KEY heads' (value head h
    reads key head h // `repeat`); v [B, H, dv], g, beta [B, H].
    → (o [B, H, dv] float32, the updated stack)."""
    if interpret is None:
        interpret = _interpret_default()
    L, rows, H, dk, dv = state.shape
    B, Hk, _ = q.shape
    heads = min(_DECODE_HEADS, H)
    if (H % heads or heads % repeat or Hk * repeat != H
            or (not interpret and (dv % _LANES or dk % 8))):
        raise ValueError(
            f"gdn_decode_step wants value heads in blocks of {heads}, "
            f"{repeat} a key head, and dv a multiple of {_LANES}; got "
            f"H={H}, Hk={Hk}, dk={dk}, dv={dv}")
    null_slot = rows - 1
    slot_rows = jnp.where(active, jnp.arange(B, dtype=jnp.int32), null_slot)
    # A head block's keys and queries with dk along the sublanes:
    # [B, H / heads, dk, its keys | 0 | its queries | 0].
    n_hb, half = H // heads, _LANES // 2
    cols = lambda t: jnp.pad(
        t.astype(_F32).reshape(B, n_hb, heads // repeat, dk),
        ((0, 0), (0, 0), (0, half - heads // repeat), (0, 0)))
    kq = jnp.swapaxes(jnp.concatenate([cols(k), cols(q)], axis=2), 2, 3)
    spread = lambda t: jnp.broadcast_to(t.astype(_F32)[..., None], (B, H, dv))
    per_head = pl.BlockSpec((None, heads, dv), lambda b, hb, *_: (b, hb, 0))
    block = pl.BlockSpec(
        (None, None, heads, dk, dv),
        lambda b, hb, layer, slot, *_: (layer[0], slot[b], hb, 0, 0))
    kernel = functools.partial(_decode_kernel, heads=heads, repeat=repeat,
                               null_slot=null_slot)
    o, state = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, H // heads),
            in_specs=[pl.BlockSpec((None, None, dk, _LANES),
                                   lambda b, hb, *_: (b, hb, 0, 0)),
                      per_head, per_head, per_head, block],
            out_specs=[per_head, block]),
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={6: 1},
        interpret=interpret,
        name="gdn_decode_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), slot_rows, kq,
      v.astype(_F32), spread(jnp.exp(g)), spread(beta), state)
    return o, state


__all__ = ["gdn_chunk_scan", "gdn_decode_step", "reference_gdn_scan",
           "reference_gdn_decode_step"]
