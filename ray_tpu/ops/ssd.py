"""Mamba-2's recurrence (the state-space dual): a MATRIX state a head
under ONE scalar decay a head and token, in its two serving forms.

A head h of the H heads keeps S_h [P values, N states], float32, and
belongs to group h // (H / G) of the G groups, whose write and read
vectors B_t, C_t [N] it shares with the group's other heads. Token t,
with the head's input x_t [P], its step dt_t > 0 and its rate A < 0:

    S <- exp(dt_t A) S + dt_t x_t B_t^T;   y_t = S C_t

(the skip D x_t, the gate and the norm are the caller's). Neither of the
other two recurrence files computes it. ops/selective_scan.py is built
on the opposite case, a decay a (channel, state) PAIR, which has no
matmul form over a block of tokens. ops/gated_delta.py has the scalar
decay and the matmul form, but its chunk scan is the delta rule's (a
unit lower-triangular solve a block, a write that subtracts S^T k):
none of that exists here, where the write is dt x B^T whatever the
state holds. So the chunk scan below is this file's own, and the decode
step IS gated_delta's kernel with the delta term switched off: the same
stack layout, the same grid, the same in-place walk, one `if`.

**The state's layout on the chip.** The pool holds ``[L, n_slots+1,
H / p, N, p P]``: a head's state TRANSPOSED, the N states down the
sublanes and the P values along the lanes, p heads side by side so that
the lanes are whole tiles of 128 (`gated_delta.packed_heads`: p = 2 at
P = 64, so a pair of heads is [128, 128] float32 = 16 sublane tiles of
one lane tile each, dense: 64 KiB, no padding). Why not the [P, N] the
equations are written in: with N = 128 on the lanes a head's x_t (64
values) would have to lie DOWN the sublanes to meet it, a column a head,
and y_t would leave as a column, a sum along the lanes; the projections
on either side of the scan make x and take y as rows [.., H P] along
the lanes. Transposed, x_t and y_t are rows exactly as the matmuls
leave and take them (a pair of heads' 128 values one lane tile), the
decay and dt a head spread over its 64 lanes, y_t a sum down the
sublanes, and only B_t and C_t enter as columns: ONE column each for the
16 heads of a group, which a block of 16 pairs (1 MiB, two groups)
fetches as four columns of one 64 KiB plane. Both forms read p off the
stack's and x's last axes; `pack_state` / `unpack_state` (gated_delta's)
are the two views.

`ssd_decode_step` advances every live slot's state by ONE token, in
place (`ssd_decode_step` in a trace): the grid walks (slot, block of
head pairs) over the whole stack, aliased in and out; an idle slot's
steps name the null slot's block and move nothing. The stack is never
gathered, sliced or copied: at 192 slots a layer's states are 0.8 GB.

`ssd_chunk_scan` runs N rows of C tokens in the matmul form, in blocks
of `block` tokens (the model's `chunk_size`). With G the cumulated log
decay of a block, the block's own tokens give

    Y_intra = ((C B^T) * L * dt) X,   L_ij = exp(G_i - G_j), i >= j

(C B^T once a GROUP, L and dt a head), needing no state and made for
every row, block and head at once; only Y_inter = exp(G) C S_0 and
S' = exp(G_T) S_0 + (exp(G_T - G) dt B)^T X walk a row's blocks, and a
slot's rows within one dispatch, in order. A decay ratio is only ever
`exp` of a DIFFERENCE of cumulated logs, summed from its own terms and
masked to the causal triangle BEFORE the `exp` (as gated_delta's: one
token's dt A can reach -1e4, and exp(-G) of it is not a float32).

`reference_*` are the same in plain XLA, token by token (`lax.scan`):
the tests' oracle, the path off the TPU and the full-sequence forward's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops import gated_delta
from ray_tpu.ops.gated_delta import (packed_heads, pack_state,
                                     unpack_state)
# The state a chunk row starts from: zeros, an earlier row's end, or the
# slot's (one rule for both state-space scans).
from ray_tpu.ops.selective_scan import _start

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


# ----------------------------------------------------------- a prompt chunk

def reference_ssd_scan(x, dt, A, B, C, state):
    """The recurrence itself, token by token: x [T, H, P], dt [T, H]
    (a token that must leave the state alone carries dt = 0), A [H]
    (< 0), B, C [T, G, N], state [H, N, P] → (y [T, H, P] float32 without
    the skip, final state). What `ssd_chunk_scan` is tested against."""
    rep = x.shape[1] // B.shape[1]
    A = A.astype(_F32)

    def token(s, inputs):
        x_t, dt_t, b_t, c_t = inputs
        b_t, c_t = (jnp.repeat(t, rep, axis=0) for t in (b_t, c_t))
        s = (s * jnp.exp(dt_t * A)[:, None, None]
             + b_t[:, :, None] * (dt_t[:, None] * x_t)[:, None, :])
        return s, jnp.einsum("hnp,hn->hp", s, c_t, precision=_HIGHEST)

    final, y = jax.lax.scan(
        token, state.astype(_F32),
        tuple(t.astype(_F32) for t in (x, dt, B, C)))
    return y, final


def reference_ssd_chunk_scan(x, dt, A, B, C, state, chain, fresh):
    """`ssd_chunk_scan` in plain XLA: the rows in order, each token by
    token."""
    def row(finals, n):
        y, final = reference_ssd_scan(
            x[n], dt[n], A, B[n], C[n], _start(n, finals, state, chain, fresh))
        return finals.at[n].set(final), y

    finals, y = jax.lax.scan(row, jnp.zeros(state.shape, _F32),
                             jnp.arange(x.shape[0]))
    return y, finals


def ssd_chunk_scan(x, dt, A, B, C, state, chain, fresh, *, block: int = 128):
    """N rows of C consecutive tokens through Mamba-2's recurrence, in
    its matmul form over blocks of `block` tokens.

    x [N, C, H, P], dt [N, C, H] (a token that must leave the state alone
    carries dt = 0), A [H] (< 0), B, C [N, C, G, N_s]; `state`
    [N, H, N_s, P] float32: each row's slot's state, unpacked; `chain`
    [N] int32: the row ABOVE whose final state this row starts from (the
    same slot's chunk before it), -1 for none; `fresh` [N] bool: the row
    starts a prompt, from zeros. A row reads the first of fresh / chain /
    state that applies. Every matmul multiplies float32 operands at the
    highest precision: the state is float32 and what is written to it
    must be.
    → (y [N, C, H, P] float32 without the skip, finals [N, H, N_s, P]
    float32: the state after each row)."""
    N, C_, H, P = x.shape
    G, Ns = B.shape[2:]
    T = min(block, C_)
    if C_ % T or H % G:
        raise ValueError(f"a row of {C_} tokens does not cut into blocks of "
                         f"{T}, or {H} heads into {G} groups")
    nb, R = C_ // T, H // G
    f32 = lambda t: t.astype(_F32)
    mm = functools.partial(jnp.einsum, precision=_HIGHEST,
                           preferred_element_type=_F32)
    # A block's tokens a (group, head of the group): [N, nb, G, R, T, ..].
    heads = lambda t: jnp.moveaxis(
        f32(t).reshape((N, nb, T, G, R) + t.shape[3:]), 2, 4)
    groups = lambda t: jnp.moveaxis(f32(t).reshape(N, nb, T, G, Ns), 2, 3)
    xs, dts = heads(x), heads(dt)                # [.., T, P], [.., T]
    Bs, Cs = groups(B), groups(C)                # [N, nb, G, T, Ns]
    g = dts * f32(A).reshape(G, R, 1)
    i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    # G_i - G_j for i > j as the sum of ITS OWN terms g_{j+1} .. g_i.
    diff = jnp.cumsum(jnp.where(i > j, g[..., :, None], 0.0), axis=-2)
    decay = jnp.exp(jnp.where(i >= j, diff, -jnp.inf))   # L [.., T, T]
    cb = mm("...in,...jn->...ij", Cs, Bs)                # [N, nb, G, T, T]
    y_intra = mm("...ij,...jp->...ip",
                 cb[:, :, :, None] * decay * dts[..., None, :], xs)
    from_start = jnp.exp(jnp.cumsum(g, axis=-1))         # exp(G_i - G_0)
    to_end = jnp.exp(diff[..., -1, :])                   # exp(G_T - G_j)
    written = (to_end * dts)[..., None] * xs             # [.., T, P]
    state = state.reshape(N, G, R, Ns, P)

    def row(finals, n):
        s, outs = _start(n, finals, state, chain, fresh), []
        for b in range(nb):
            outs.append(from_start[n, b][..., None]
                        * mm("gin,grnp->grip", Cs[n, b], s))
            s = (from_start[n, b][..., -1, None, None] * s
                 + mm("gin,grip->grnp", Bs[n, b], written[n, b]))
        return finals.at[n].set(s), jnp.stack(outs)      # [nb, G, R, T, P]

    finals, y_inter = jax.lax.scan(row, jnp.zeros(state.shape, _F32),
                                   jnp.arange(N))
    y = jnp.moveaxis(y_intra + y_inter, 4, 2)            # [N, nb, T, G, R, P]
    return y.reshape(N, C_, H, P), finals.reshape(N, H, Ns, P)


# ----------------------------------------------------------- a decode step

def _as_delta(x, dt, A, B, C):
    """The step's operands as gated_delta's names them: the key B, the
    query C, the value x, the log decay dt A, the write strength dt."""
    f32 = lambda t: t.astype(_F32)
    return (f32(C), f32(B), f32(x), f32(dt) * f32(A), f32(dt),
            x.shape[1] // B.shape[1])


def reference_ssd_decode_step(state, layer, x, dt, A, B, C, active):
    """One token for slots 0 .. n-1 of `state` [L, n_slots+1, H / p, N_s,
    p P] at `layer`, in plain XLA. x [n, H, P], dt [n, H], A [H], B, C
    [n, G, N_s]; `active` [n] bool: the others' state stays.
    → (y [n, H, P] float32 without the skip, the updated stack)."""
    q, k, v, g, beta, repeat = _as_delta(x, dt, A, B, C)
    return gated_delta.reference_gdn_decode_step(
        state, layer, q, k, v, g, beta, active, repeat=repeat, delta=False)


def ssd_decode_step(state, layer, x, dt, A, B, C, active, *, interpret=None):
    """`reference_ssd_decode_step` as one kernel over the whole stack,
    donated (gated_delta's, without the delta term): slot b's heads are
    read and written once, an idle slot's not at all."""
    if interpret is None:
        interpret = gated_delta._interpret_default()
    q, k, v, g, beta, repeat = _as_delta(x, dt, A, B, C)
    return gated_delta.gdn_decode_step(
        state, layer, q, k, v, g, beta, active, repeat=repeat,
        interpret=interpret, delta=False, name="ssd_decode_step")


__all__ = ["ssd_chunk_scan", "ssd_decode_step", "reference_ssd_scan",
           "reference_ssd_chunk_scan", "reference_ssd_decode_step",
           "packed_heads", "pack_state", "unpack_state"]
