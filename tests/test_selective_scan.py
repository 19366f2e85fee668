"""ops/selective_scan.py on the CPU: both kernels (interpret mode) and
their plain-XLA twins against the recurrence written out token by token
in numpy, at the served state size (16 values a channel).

Tolerance 2e-5 of the largest value compared (`_close`): kernel and
oracle compute the same float32 products in another order (the sum over
the state's 16 values; `exp` of the same argument), and at a step of 30
a token the outputs reach the hundreds."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.selective_scan import (
    reference_ssm_chunk_scan, reference_ssm_conv_step,
    reference_ssm_decode_step, reference_ssm_scan, ssm_chunk_scan,
    ssm_conv_step, ssm_decode_step)

ATOL = 2e-5
S = 16

# The step a token: from one that forgets nothing to one that forgets
# everything at once; "spread" gives every channel its own, 1e-3 to 30,
# as the seeded weights do.
STEPS = {
    "slow": lambda s: np.full(s, 1e-3),
    "fast": lambda s: np.full(s, 30.0),
    "spread": lambda s: np.broadcast_to(np.geomspace(1e-3, 30.0, s[-1]),
                                        s).copy(),
    "mixed": lambda s: np.abs(np.random.default_rng(3).normal(size=s)) * 3,
}


def _inputs(rng, N, C, Dn, step_of):
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    return tuple(f32(t) for t in (
        rng.normal(size=(N, C, Dn)), step_of((N, C, Dn)),
        rng.normal(size=(N, C, S)), rng.normal(size=(N, C, S)),
        -np.exp(rng.normal(size=(S, Dn))), rng.normal(size=Dn)))


def _close(got, want):
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=ATOL * max(1.0, np.abs(want).max()))


def _by_hand(xs, dt, B, C, A, D, h):
    """The definition in numpy float64: one row's tokens in order."""
    xs, dt, B, C, A, D, h = (np.asarray(t, np.float64)
                             for t in (xs, dt, B, C, A, D, h))
    ys = []
    for t in range(xs.shape[0]):
        h = (np.exp(dt[t][None, :] * A) * h
             + (dt[t] * xs[t])[None, :] * B[t][:, None])
        ys.append((h * C[t][:, None]).sum(axis=0) + D * xs[t])
    return np.stack(ys), h


@pytest.mark.parametrize("step", sorted(STEPS))
def test_the_token_by_token_twin_is_the_definition(step):
    rng = np.random.default_rng(0)
    xs, dt, B, C, A, D = _inputs(rng, 1, 40, 128, STEPS[step])
    h0 = rng.normal(size=(S, 128))
    y, final = reference_ssm_scan(xs[0], dt[0], B[0], C[0], A, D,
                                  jnp.asarray(h0, jnp.float32))
    y_ref, h_ref = _by_hand(xs[0], dt[0], B[0], C[0], A, D, h0)
    assert np.all(np.isfinite(y))
    _close(y, y_ref)
    _close(final, h_ref)


@pytest.mark.parametrize("step", sorted(STEPS))
@pytest.mark.parametrize("scan", [ssm_chunk_scan, reference_ssm_chunk_scan],
                         ids=["kernel", "xla"])
def test_chunk_scan_is_the_recurrence(scan, step):
    """32 tokens a row, two rows over two blocks of channels, from a
    non-zero state: outputs and final state agree with the definition,
    with no inf and no NaN at any step size."""
    rng = np.random.default_rng(0)
    N, C, Dn = 2, 32, 1024
    xs, dt, B, Cm, A, D = _inputs(rng, N, C, Dn, STEPS[step])
    state = jnp.asarray(rng.normal(size=(N, S, Dn)), jnp.float32)
    y, finals = scan(xs, dt, B, Cm, A, D, state,
                     jnp.full(N, -1, jnp.int32), jnp.zeros(N, bool))
    assert np.all(np.isfinite(y)) and np.all(np.isfinite(finals))
    for n in range(N):
        y_ref, h_ref = _by_hand(xs[n], dt[n], B[n], Cm[n], A, D, state[n])
        _close(y[n], y_ref)
        _close(finals[n], h_ref)


@pytest.mark.parametrize("scan", [ssm_chunk_scan, reference_ssm_chunk_scan],
                         ids=["kernel", "xla"])
def test_chunk_scan_chains_fresh_rows_and_tokens_that_are_none(scan):
    """Row 1 continues row 0 (its final state, not the pool's); row 2
    starts a prompt from zeros whatever the pool holds; row 3 continues
    row 1 ACROSS row 2; row 4 reads the pool's state and its last 12
    tokens are none (dt = 0): they leave the state as token 19 left it."""
    rng = np.random.default_rng(1)
    N, C, Dn = 5, 32, 256
    xs, dt, B, Cm, A, D = _inputs(rng, N, C, Dn, STEPS["mixed"])
    dt = dt.at[4].set(jnp.where((jnp.arange(C) < 20)[:, None], dt[4], 0.0))
    state = jnp.asarray(rng.normal(size=(N, S, Dn)), jnp.float32)
    chain = jnp.asarray([-1, 0, -1, 1, -1], jnp.int32)
    fresh = jnp.asarray([False, False, True, False, False])
    y, finals = scan(xs, dt, B, Cm, A, D, state, chain, fresh)
    ref = lambda n, h, upto=C: _by_hand(xs[n, :upto], dt[n, :upto],
                                        B[n, :upto], Cm[n, :upto], A, D, h)
    y0, h0 = ref(0, state[0])
    y1, h1 = ref(1, h0)
    y2, h2 = ref(2, np.zeros((S, Dn)))
    y3, h3 = ref(3, h1)
    y4, h4 = ref(4, state[4], upto=20)
    for n, (y_ref, h_ref) in enumerate(
            [(y0, h0), (y1, h1), (y2, h2), (y3, h3)]):
        _close(y[n], y_ref)
        _close(finals[n], h_ref)
    _close(y[4, :20], y4)
    _close(finals[4], h4)
    # What the chain is for: row 1 from the pool's state ends elsewhere.
    assert np.abs(ref(1, state[1])[1] - h1).max() > 1e-2


# The live slots of a batch: the step kernel takes the slots in blocks of
# 8, and all of them as one block where their count is no multiple.
_T, _F = True, False
DECODE_BATCHES = {
    "under-one-block": [_T, _F, _T, _T, _F],
    "one-and-a-half-blocks": [_T] * 7 + [_F] + [_T, _F, _T, _T],
    "idle-slot-in-a-live-block": [_T] * 3 + [_F] + [_T] * 12,
    "idle-block-between": [_T, _F] * 4 + [_F] * 8 + [_F] * 7 + [_T],
    "idle-blocks-first": [_F] * 16 + [_F, _T] * 4,
    "idle-blocks-last": [_F, _T, _T] + [_F] * 21,
    "all-idle": [_F] * 16,
    "all-live": [_T] * 16,
}


@pytest.mark.parametrize("batch", sorted(DECODE_BATCHES))
@pytest.mark.parametrize("step", [ssm_decode_step, reference_ssm_decode_step],
                         ids=["kernel", "xla"])
def test_decode_step_updates_the_live_slots_in_place(step, batch):
    """n slots and the null slot, three layers: the live slots of layer 1
    advance by the definition; the idle slots (inside a live block, or a
    whole block of them), the null slot and the other layers keep every
    bit."""
    rng = np.random.default_rng(2)
    L, Dn = 3, 256
    active = np.asarray(DECODE_BATCHES[batch])
    n = len(active)
    xs, dt, B, Cm, A, D = _inputs(rng, 1, n, Dn, STEPS["mixed"])
    stack = jnp.asarray(rng.normal(size=(L, n + 1, S, Dn)), jnp.float32)
    y, out = step(stack, 1, xs[0], dt[0], B[0], Cm[0], A, D,
                  jnp.asarray(active))
    assert y.shape == (n, Dn) and out.shape == stack.shape
    for b in np.flatnonzero(active):
        y_ref, h_ref = _by_hand(xs[0, b:b + 1], dt[0, b:b + 1],
                                B[0, b:b + 1], Cm[0, b:b + 1], A, D,
                                stack[1, b])
        _close(y[b], y_ref[0])
        _close(out[1, b], h_ref)
    assert np.all(np.isfinite(y))
    assert np.array_equal(out[1, :n][~active], stack[1, :n][~active])
    assert np.array_equal(out[1, n], stack[1, n])
    assert np.array_equal(out[0], stack[0]) and np.array_equal(out[2],
                                                               stack[2])


def test_decode_step_and_chunk_scan_walk_the_same_states():
    """A prompt's row through the chunk kernel, then three decode steps
    through the step kernel, end where the definition over all 35 tokens
    ends."""
    rng = np.random.default_rng(4)
    C, Dn = 32, 256
    xs, dt, B, Cm, A, D = _inputs(rng, 1, C + 3, Dn, STEPS["mixed"])
    head = lambda t: t[:, :C]
    _y, finals = ssm_chunk_scan(head(xs), head(dt), head(B), head(Cm), A, D,
                                jnp.zeros((1, S, Dn)),
                                jnp.full(1, -1, jnp.int32), jnp.ones(1, bool))
    stack = jnp.zeros((1, 2, S, Dn), jnp.float32).at[0, 0].set(finals[0])
    ys = []
    for t in range(C, C + 3):
        y, stack = ssm_decode_step(stack, 0, xs[:, t], dt[:, t], B[:, t],
                                   Cm[:, t], A, D, jnp.ones(1, bool))
        ys.append(y[0])
    y_ref, h_ref = _by_hand(xs[0], dt[0], B[0], Cm[0], A, D,
                            np.zeros((S, Dn)))
    _close(np.stack(ys), y_ref[C:])
    _close(stack[0, 0], h_ref)


@pytest.mark.parametrize("n", [5, 64], ids=["one-block", "two-blocks"])
@pytest.mark.parametrize("step", [ssm_conv_step, reference_ssm_conv_step],
                         ids=["kernel", "xla"])
def test_conv_step_shifts_the_live_slots_tails_in_place(step, n):
    """The convolution's decode step over n slots and the null slot,
    three layers, bf16 as served: a live slot of layer 1 reads
    silu(taps . [its three last inputs, the new one] + bias) and keeps
    its last three inputs, the new one among them; the idle slots, the
    null slot and the other layers keep every bit."""
    rng = np.random.default_rng(5)
    L, taps, Dn = 3, 4, 256
    bf = lambda t: jnp.asarray(t, jnp.bfloat16)
    tail = bf(rng.normal(size=(L, taps - 1, n + 1, Dn)))
    xs = bf(rng.normal(size=(n, Dn)))
    w = jnp.asarray(rng.normal(size=(taps, Dn)), jnp.float32)
    b = jnp.asarray(rng.normal(size=Dn), jnp.float32)
    active = jnp.asarray(rng.random(n) < 0.6)
    act, out = step(tail, 1, xs, w, b, active)
    f64 = lambda t: np.asarray(t.astype(jnp.float32), np.float64)
    ext = np.concatenate([f64(tail[1, :, :n]), f64(xs)[None]])
    acc = (f64(w)[:, None, :] * ext).sum(axis=0) + f64(b)
    want = acc / (1 + np.exp(-acc))
    assert act.dtype == jnp.bfloat16
    # (bf16 holds 8 bits: half a unit in the last place of the output)
    np.testing.assert_allclose(f64(act), want, rtol=2 ** -8, atol=1e-6)
    live = np.asarray(active)
    assert np.array_equal(out[1, :, :n][:, live], bf(ext[1:])[:, live])
    assert np.array_equal(out[1, :, :n][:, ~live], tail[1, :, :n][:, ~live])
    assert np.array_equal(out[1, :, n], tail[1, :, n])
    assert np.array_equal(out[0], tail[0]) and np.array_equal(out[2], tail[2])


def test_the_kernels_refuse_what_the_chip_would():
    f32 = lambda *s: jnp.zeros(s, jnp.float32)
    with pytest.raises(ValueError, match="float32 state"):
        ssm_decode_step(jnp.zeros((1, 3, S, 128), jnp.bfloat16), 0,
                        f32(2, 128), f32(2, 128), f32(2, S), f32(2, S),
                        f32(S, 128), f32(128), jnp.ones(2, bool))
    with pytest.raises(ValueError, match="multiple of 128 channels"):
        ssm_decode_step(f32(1, 3, S, 96), 0, f32(2, 96), f32(2, 96),
                        f32(2, S), f32(2, S), f32(S, 96), f32(96),
                        jnp.ones(2, bool), interpret=False)
    with pytest.raises(ValueError, match="blocks of 512"):
        ssm_chunk_scan(f32(1, 8, 640), f32(1, 8, 640), f32(1, 8, S),
                       f32(1, 8, S), f32(S, 640), f32(640), f32(1, S, 640),
                       jnp.full(1, -1, jnp.int32), jnp.zeros(1, bool))
