"""The selective scan: a state-space layer's recurrence over a DIAGONAL
state a channel, in its two serving forms.

A channel c of the d_inner channels keeps S state values, float32. Token
t, with the channel's input xs_t[c], its step dt_t[c] > 0, the token's
write and read vectors B_t, C_t [S], the layer's decay rates
A[s, c] < 0 and skip D[c]:

    h[s, c] <- exp(dt_t[c] A[s, c]) h[s, c] + dt_t[c] xs_t[c] B_t[s]
    y_t[c]  =  sum_s h[s, c] C_t[s] + D[c] xs_t[c]

The decay is one number a (channel, state) pair and a token, so the
recurrence has no matmul form over a block of tokens (ops/gated_delta.py's
has: one scalar a head and a matrix state): it is `exp`, multiplies and
adds on the vector units, and what decides its cost is where the state
lives while a row's tokens pass. The state is laid out ``[.., S, d_inner]``:
the channels on the lanes (S = 16 on the minor axis would fill an eighth
of a vector register), the S values of a channel down the sublanes, so
B_t and C_t enter as columns spread over the lanes and y_t is a sum over
sublanes. A token that must leave the state alone carries dt = 0.

`ssm_decode_step` advances every live slot's state by ONE token, in
place: a Pallas kernel (`ssm_decode_step` in a trace) over the whole
``[L, n_slots+1, S, d_inner]`` stack, aliased in and out, whose grid walks
the slots in blocks of `_STEP_SLOTS` (row b of the batch is slot b): a
grid step reads a block's states ``[8, S, d_inner]`` (2.6 MB at the
served sizes), advances them and writes them back, so nothing is
gathered, scattered or copied. Everything a slot brings is a plane with
the slots down the sublanes (xs, dt and y ``[n, d_inner]``, eight slots a
tile) or along the lanes (B and C ``[S, n]``, a value of the state a
sublane), which are the layouts the compiler gives those values unasked:
with one slot a grid step they were ``[n, 1, d_inner]``, a row a tile,
and the fusions beside the kernel ran on an eighth of a register. An idle
slot inside a live block keeps every bit; a block with no live slot names
the block the step before it named, so nothing of it is fetched or
written and its body is skipped. The kernel runs at what HBM gives a
stream read and written at once (~650 GB/s on the v5e, where reads alone
reach 746 and writes alone 650), whatever the block's height.

`ssm_chunk_scan` runs N rows of C tokens (`ssm_chunk_scan` in a trace):
the grid walks (block of channels, row), a row's state sits in fast
memory while its C tokens pass, and the rows' final states of the
channel block stay in a scratch, which is where a row chained to an
earlier row of the same dispatch finds its start. Nothing of size
``[N, C, S, d_inner]`` exists anywhere.

`ssm_conv_step` is the decode step of the causal depthwise convolution
in front of the scan (`ssm_conv_step` in a trace): each slot's last
taps-1 inputs ``[L, taps-1, n_slots+1, d_inner]``, a plane of slots a
tap, aliased in and out; a block of slots' planes is read, the new
input convolved, biased and passed through SiLU, and the planes written
back one tap older. It is a kernel for the tail's sake, not the
arithmetic's: left to the compiler, the update is a scatter by slot (256
rows a tap and layer, one by one) or, as block updates inside a loop over
layers, has the whole tail re-laid out on its way into the loop and out.

`reference_*` are the same in plain XLA, token by token (`lax.scan`): the
oracle of the kernels' tests, the path off the TPU ("gather") and the
full-sequence forward's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
# Channels a grid step of the chunk kernel holds: the state of a block
# is S x 512 float32 = 8 vector registers at S = 16, and with the
# per-token temporaries beside it the loop stays in registers.
_CHUNK_CHANNELS = 512
# Slots a grid step of the convolution's decode step holds: their tail is
# (taps-1) x 32 x 5,120 bf16 = 1 MB in and 1 MB out, twice for the
# pipeline; a multiple of the 16 rows a bf16 tile has. Wider channels
# take fewer slots a block, so that its tail stays under that 1 MB (16
# slots at 10,240 channels: 32 ran the chip's compiler out of VMEM).
_CONV_SLOTS = 32
_CONV_BLOCK_BYTES = 2**20
# Slots a grid step of the scan's decode step holds: a float32 tile's 8
# sublanes, so xs, dt and y move as whole tiles [8, d_inner]; their state
# is 8 x 16 x 5,120 float32 = 2.6 MB in and 2.6 MB out, twice for the
# pipeline, inside the default VMEM limit (16 needs it raised). On the
# v5e 8, 16 and 32 read 0.2719, 0.2724 and 0.2740 ms a layer of 256 slots
# (PERF.md, PR 54): the stream is HBM's, not the grid's.
_STEP_SLOTS = 8


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _token(h, xs, dt, b, c, A, D):
    """One token of the recurrence over any leading axes. h [.., S, Dn];
    xs, dt [.., Dn]; b, c [.., S]; A [S, Dn]; D [Dn] → (h, y [.., Dn])."""
    dt_ = dt[..., None, :]
    h = jnp.exp(dt_ * A) * h + (dt_ * xs[..., None, :]) * b[..., :, None]
    return h, jnp.sum(h * c[..., :, None], axis=-2) + D * xs


# ----------------------------------------------------------- a prompt chunk

def reference_ssm_scan(xs, dt, B, C, A, D, state):
    """The recurrence itself, token by token: xs, dt [T, Dn], B, C [T, S],
    A [S, Dn], D [Dn], state [S, Dn] → (y [T, Dn], final state), float32.
    Leading batch axes after T are carried along (xs [T, .., Dn], state
    [.., S, Dn])."""
    A, D = A.astype(_F32), D.astype(_F32)

    def token(h, inputs):
        return _token(h, *inputs, A, D)

    final, y = jax.lax.scan(
        token, state.astype(_F32),
        tuple(t.astype(_F32) for t in (xs, dt, B, C)))
    return y, final


def _start(n, finals, state, chain, fresh):
    """The state row n starts from: zeros, an earlier row's end, or the
    slot's."""
    s = jnp.where(chain[n] >= 0, finals[jnp.maximum(chain[n], 0)], state[n])
    return jnp.where(fresh[n], 0.0, s)


def reference_ssm_chunk_scan(xs, dt, B, C, A, D, state, chain, fresh):
    """`ssm_chunk_scan` in plain XLA: the rows in order, each token by
    token."""
    N, _C, Dn = xs.shape
    S = B.shape[-1]

    def row(finals, n):
        y, final = reference_ssm_scan(xs[n], dt[n], B[n], C[n], A, D,
                                      _start(n, finals, state, chain, fresh))
        return finals.at[n].set(final), y

    finals, y = jax.lax.scan(row, jnp.zeros((N, S, Dn), _F32), jnp.arange(N))
    return y, finals


def _chunk_kernel(chain_ref, fresh_ref, xs_ref, dt_ref, b_ref, c_ref, a_ref,
                  d_ref, state_ref, y_ref, finals_ref, h_ref, ends_ref):
    """One row's C tokens over one block of channels. b_ref, c_ref
    [S, C]: token t's B and C are column t, spread over the lanes for
    the state's sublanes; h_ref [S, T] scratch: the state; ends_ref
    [N, S, T] scratch: the final state of every row so far, of this
    block of channels."""
    n = pl.program_id(1)
    chained = chain_ref[n] >= 0
    fresh = fresh_ref[n] != 0

    @pl.when(fresh)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    @pl.when(jnp.logical_not(fresh) & chained)
    def _():
        h_ref[...] = ends_ref[jnp.maximum(chain_ref[n], 0)]

    @pl.when(jnp.logical_not(fresh) & jnp.logical_not(chained))
    def _():
        h_ref[...] = state_ref[...]

    S, T = h_ref.shape
    a, d = a_ref[...], d_ref[...]
    h = h_ref[...]
    for t in range(xs_ref.shape[0]):
        xs, dt = xs_ref[t:t + 1, :], dt_ref[t:t + 1, :]
        b = jnp.broadcast_to(b_ref[:, t:t + 1], (S, T))
        c = jnp.broadcast_to(c_ref[:, t:t + 1], (S, T))
        h = jnp.exp(dt * a) * h + (dt * xs) * b
        y_ref[t:t + 1, :] = jnp.sum(h * c, axis=0, keepdims=True) + d * xs
    ends_ref[n] = h
    finals_ref[...] = h


def ssm_chunk_scan(xs, dt, B, C, A, D, state, chain, fresh, *,
                   interpret=None):
    """N rows of C consecutive tokens through the selective scan.

    xs, dt [N, C, Dn] (a token that must leave the state alone carries
    dt = 0), B, C [N, C, S], A [S, Dn], D [Dn]; `state` [N, S, Dn]
    float32: each row's slot's state as the pool holds it; `chain` [N]
    int32: the row ABOVE whose final state this row starts from (the same
    slot's chunk before it), -1 for none; `fresh` [N] bool: the row
    starts a prompt, from zeros. A row reads the first of fresh / chain /
    state that applies. All float32.
    → (y [N, C, Dn] float32, finals [N, S, Dn] float32: the state after
    each row)."""
    if interpret is None:
        interpret = _interpret_default()
    N, C_, Dn = xs.shape
    S = B.shape[-1]
    T = min(_CHUNK_CHANNELS, Dn)
    if Dn % T or (not interpret and (T % 128 or S % 8 or C_ % 8)):
        raise ValueError(
            f"ssm_chunk_scan wants channels in blocks of {T} (a multiple "
            f"of 128), a state of a multiple of 8 values and rows of a "
            f"multiple of 8 tokens; got d_inner={Dn}, S={S}, C={C_}")
    f32 = lambda t: t.astype(_F32)
    tokens = pl.BlockSpec((None, C_, T), lambda j, n, *_: (n, 0, j))
    columns = pl.BlockSpec((None, S, C_), lambda j, n, *_: (n, 0, 0))
    a_state = pl.BlockSpec((None, S, T), lambda j, n, *_: (n, 0, j))
    y, finals = pl.pallas_call(
        _chunk_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(Dn // T, N),
            in_specs=[tokens, tokens, columns, columns,
                      pl.BlockSpec((S, T), lambda j, n, *_: (0, j)),
                      pl.BlockSpec((1, T), lambda j, n, *_: (0, j)),
                      a_state],
            out_specs=[tokens, a_state],
            scratch_shapes=[pltpu.VMEM((S, T), _F32),
                            pltpu.VMEM((N, S, T), _F32)]),
        out_shape=[jax.ShapeDtypeStruct((N, C_, Dn), _F32),
                   jax.ShapeDtypeStruct((N, S, Dn), _F32)],
        interpret=interpret,
        name="ssm_chunk_scan",
    )(chain.astype(jnp.int32), fresh.astype(jnp.int32), f32(xs), f32(dt),
      jnp.swapaxes(f32(B), 1, 2), jnp.swapaxes(f32(C), 1, 2), f32(A),
      f32(D).reshape(1, Dn), f32(state))
    return y, finals


# ----------------------------------------------------------- a decode step

def reference_ssm_decode_step(state, layer, xs, dt, B, C, A, D, active):
    """One token for slots 0 .. n-1 of `state` [L, n_slots+1, S, Dn] at
    `layer`, in plain XLA. xs, dt [n, Dn], B, C [n, S] float32; `active`
    [n] bool: the others' state stays.
    → (y [n, Dn] float32, the updated stack)."""
    n = xs.shape[0]
    old = state[layer, :n]
    h, y = _token(old, *(t.astype(_F32) for t in (xs, dt, B, C)),
                  A.astype(_F32), D.astype(_F32))
    h = jnp.where(active[:, None, None], h, old)
    return y, state.at[layer, :n].set(h)


def _step_kernel(layer_ref, named_ref, live_ref, xs_ref, dt_ref, b_ref,
                 c_ref, a_ref, d_ref, h_ref, y_ref, h_out_ref):
    """A block of R consecutive slots. xs_ref (in the caller's dtype),
    dt_ref, y_ref [R, Dn]: a slot a sublane; b_ref, c_ref [S, n]: EVERY
    slot's B and C, a slot a lane and a value of the state a sublane, as
    the state has them; h_ref [R, S, Dn]; live_ref [n] int32 in scalar
    memory: `active`."""
    del layer_ref, named_ref
    g = pl.program_id(0)
    R = h_ref.shape[0]
    live = [live_ref[g * R + r] != 0 for r in range(R)]
    any_live = functools.reduce(jnp.logical_or, live)

    @pl.when(any_live)
    def _():
        a, d = a_ref[...], d_ref[...]
        xs_rows = xs_ref[...].astype(_F32)
        b_all, c_all = b_ref[...], c_ref[...]
        slot = jax.lax.broadcasted_iota(jnp.int32, b_all.shape, 1)
        for r in range(R):
            # Slot g R + r's column of B and of C, [S, 1].
            mine = slot == g * R + r
            b, c = (jnp.sum(jnp.where(mine, t, 0.0), axis=1, keepdims=True)
                    for t in (b_all, c_all))
            xs, dt = xs_rows[r:r + 1, :], dt_ref[r:r + 1, :]
            old = h_ref[r]
            h = jnp.exp(dt * a) * old + (dt * xs) * b
            h_out_ref[r] = jnp.where(live[r], h, old)
            y_ref[r:r + 1, :] = (jnp.sum(h * c, axis=0, keepdims=True)
                                 + d * xs)

    @pl.when(jnp.logical_not(any_live))
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

        # The first grid step's block is written back whatever follows:
        # with no live slot in it, as it came.
        @pl.when(g == 0)
        def _():
            h_out_ref[...] = h_ref[...]


def ssm_decode_step(state, layer, xs, dt, B, C, A, D, active, *,
                    interpret=None):
    """`reference_ssm_decode_step` as one kernel over the whole stack,
    donated: row b of the batch is slot b, a grid step holds a block of
    `_STEP_SLOTS` consecutive slots (all n where n is no multiple), and a
    live block's states are read and written once, in place.

    An idle slot inside a live block rides along and keeps every bit
    (`where(live, h, old)`: its 328 KB are moved, which a full batch
    never pays). A block with NO live slot moves nothing: its grid step
    names the block the step before it named (the last live block at or
    before it; before the first live block, that one; the last block
    when no slot is live), so nothing is fetched, the body is skipped,
    and the block in fast memory is written back once, when the next
    live block takes its place. A batch at a tenth of its slots streams
    a tenth of its blocks, give or take how the live slots lie (measured,
    PERF.md PR 54: 24 live slots of 256 in three blocks 0.033 ms a layer
    against a full batch's 0.272).
    → (y [n, Dn] float32, zeros for an idle block; the updated stack)."""
    if interpret is None:
        interpret = _interpret_default()
    _L, _rows, S, Dn = state.shape
    n = xs.shape[0]
    if state.dtype != _F32 or (not interpret and (Dn % 128 or S % 8)):
        raise ValueError(
            f"ssm_decode_step wants a float32 state of a multiple of 8 "
            f"values over a multiple of 128 channels; got {state.dtype} "
            f"{state.shape}")
    R = _STEP_SLOTS if n % _STEP_SLOTS == 0 else n
    G = n // R
    f32 = lambda t: t.astype(_F32)
    # named [G]: the last block at or before g that holds a live slot,
    # else the first that does, else the last of all. (Each from `active`
    # itself by one reduction, no wider than it: what the compiler lifts
    # out of a loop over layers.)
    at = jnp.arange(G, dtype=jnp.int32)
    block_of = jnp.arange(n, dtype=jnp.int32) // R
    last_live = jnp.max(
        jnp.where(active[None, :] & (block_of[None, :] <= at[:, None]),
                  block_of[None, :], -1), axis=1)
    named = jnp.where(last_live >= 0, last_live,
                      jnp.min(jnp.where(active, block_of, G - 1)))
    rows = pl.BlockSpec((R, Dn), lambda g, layer, named, live: (named[g], 0))
    whole = lambda *shape: pl.BlockSpec(shape, lambda g, *_: (0, 0))
    block = pl.BlockSpec((None, R, S, Dn),
                         lambda g, layer, named, live:
                         (layer[0], named[g], 0, 0))
    y, state = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(G,),
            in_specs=[rows, rows, whole(S, n), whole(S, n), whole(S, Dn),
                      whole(1, Dn), block],
            out_specs=[pl.BlockSpec((R, Dn), lambda g, *_: (g, 0)), block]),
        out_shape=[jax.ShapeDtypeStruct((n, Dn), _F32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={9: 1},
        interpret=interpret,
        name="ssm_decode_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), named,
      active.astype(jnp.int32), xs, f32(dt), f32(B).T, f32(C).T,
      f32(A), f32(D).reshape(1, Dn), state)
    return y, state


# ------------------------------------------- the convolution's decode step

def reference_ssm_conv_step(tail, layer, xs, taps, bias, active):
    """One token of the causal depthwise convolution for slots 0 .. n-1
    of `tail` [L, taps-1, n_slots+1, Dn] at `layer` (the slots' last
    inputs, oldest first), in plain XLA. xs [n, Dn]: the new inputs;
    taps [taps, Dn], bias [Dn] float32; `active` [n] bool: the others'
    tail stays.
    → (silu(sum_j taps[j] x[t-taps+1+j] + bias) [n, Dn] in xs.dtype, the
    updated tail)."""
    n, Dn = xs.shape
    n_tail = tail.shape[1]
    before = jax.lax.dynamic_slice(tail, (layer, 0, 0, 0),
                                   (1, n_tail, n, Dn))[0]
    ext = jnp.concatenate([before, xs[None].astype(tail.dtype)], axis=0)
    acc = taps[0] * ext[0].astype(_F32)
    for j in range(1, n_tail + 1):
        acc = acc + taps[j] * ext[j].astype(_F32)
    after = jnp.where(active[None, :, None], ext[1:], before)
    return (jax.nn.silu(acc + bias).astype(xs.dtype),
            jax.lax.dynamic_update_slice(tail, after[None],
                                         (layer, 0, 0, 0)))


def _conv_kernel(layer_ref, xs_ref, live_ref, taps_ref, bias_ref, tail_ref,
                 act_ref, tail_out_ref):
    """A block of R slots. xs_ref [R, Dn]; live_ref [R, 1] int32;
    tail_ref [taps-1, R, Dn]."""
    del layer_ref
    n_tail = tail_ref.shape[0]
    planes = [tail_ref[j].astype(_F32) for j in range(n_tail)]
    planes.append(xs_ref[...].astype(tail_ref.dtype).astype(_F32))
    acc = taps_ref[0:1, :] * planes[0]
    for j in range(1, n_tail + 1):
        acc = acc + taps_ref[j:j + 1, :] * planes[j]
    act_ref[...] = jax.nn.silu(acc + bias_ref[...]).astype(act_ref.dtype)
    live = jnp.broadcast_to(live_ref[...], planes[0].shape) != 0
    for j in range(n_tail):
        tail_out_ref[j] = jnp.where(live, planes[j + 1],
                                    planes[j]).astype(tail_out_ref.dtype)


def ssm_conv_step(tail, layer, xs, taps, bias, active, *, interpret=None):
    """`reference_ssm_conv_step` as one kernel over the whole tail,
    donated: a slot's planes are read and written once, in place."""
    if interpret is None:
        interpret = _interpret_default()
    _L, n_tail, _rows, Dn = tail.shape
    n = xs.shape[0]
    R = _CONV_SLOTS if n % _CONV_SLOTS == 0 else n
    while (R % 32 == 0 and n % (R // 2) == 0
           and n_tail * R * Dn * tail.dtype.itemsize > _CONV_BLOCK_BYTES):
        R //= 2
    if not interpret and (Dn % 128 or R % 16):
        raise ValueError(
            f"ssm_conv_step wants slots in blocks of 16 over a multiple of "
            f"128 channels; got {n} slots, d_inner={Dn}")
    rows = lambda *shape: pl.BlockSpec(shape, lambda r, *_: (r, 0))
    whole = lambda *shape: pl.BlockSpec(shape, lambda r, *_: (0, 0))
    block = pl.BlockSpec((None, n_tail, R, Dn),
                         lambda r, layer: (layer[0], 0, r, 0))
    act, tail = pl.pallas_call(
        _conv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n // R,),
            in_specs=[rows(R, Dn), rows(R, 1), whole(n_tail + 1, Dn),
                      whole(1, Dn), block],
            out_specs=[rows(R, Dn), block]),
        out_shape=[jax.ShapeDtypeStruct((n, Dn), xs.dtype),
                   jax.ShapeDtypeStruct(tail.shape, tail.dtype)],
        input_output_aliases={5: 1},
        interpret=interpret,
        name="ssm_conv_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), xs,
      active.astype(jnp.int32).reshape(n, 1), taps.astype(_F32),
      bias.astype(_F32).reshape(1, Dn), tail)
    return act, tail


__all__ = ["ssm_chunk_scan", "ssm_decode_step", "ssm_conv_step",
           "reference_ssm_scan", "reference_ssm_chunk_scan",
           "reference_ssm_decode_step", "reference_ssm_conv_step"]
