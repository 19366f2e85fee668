"""ops/ssd.py: Mamba-2's recurrence in its two serving forms against the
recurrence itself, token by token (`reference_ssd_scan`).

Tolerances: everything is float32 at the highest matmul precision, and
the forms differ by reassociation only. A chunk's output sums up to 128
products of O(1) values where the recurrence adds them one by one:
ATOL = 2e-4 on outputs that reach tens (a relative 1e-5), and on states
likewise.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import ssd

ATOL = 2e-4

# (heads, head size, groups, states): the published head (64 x 128, 16
# heads a group, one group of the model's eight) and a tiny one.
SHAPES = {"published_head": (16, 64, 1, 128), "tiny": (8, 16, 2, 16)}


def _inputs(shape, n_rows, n_tokens, seed=0, dt_scale=2.0):
    H, P, G, Ns = SHAPES[shape]
    k = jax.random.split(jax.random.key(seed), 6)
    return dict(
        x=jax.random.normal(k[0], (n_rows, n_tokens, H, P)),
        dt=jax.nn.softplus(dt_scale * jax.random.normal(
            k[1], (n_rows, n_tokens, H))),
        A=-jnp.exp(jax.random.normal(k[2], (H,))),
        B=jax.random.normal(k[3], (n_rows, n_tokens, G, Ns)),
        C=jax.random.normal(k[4], (n_rows, n_tokens, G, Ns)),
        state=jax.random.normal(k[5], (n_rows, H, Ns, P)))


def _chain(n_rows, how):
    """(chain, fresh) of one dispatch: every row its own slot from the
    pool's state; the rows ONE prompt's consecutive chunks from zeros;
    or two prompts interleaved, the second continuing from its state."""
    if how == "apart":
        return -jnp.ones(n_rows, jnp.int32), jnp.zeros(n_rows, bool)
    if how == "one_prompt":
        return (jnp.arange(n_rows, dtype=jnp.int32) - 1,
                jnp.arange(n_rows) == 0)
    chain = jnp.asarray([-1, -1] + list(range(n_rows - 2)), jnp.int32)
    return chain, jnp.arange(n_rows) == 0


@pytest.mark.parametrize("how", ["apart", "one_prompt", "interleaved"])
@pytest.mark.parametrize("shape,tokens,block", [
    ("tiny", 32, 16), ("tiny", 128, 128), ("tiny", 48, 16),
    ("published_head", 128, 128), ("published_head", 32, 16)])
def test_the_chunk_scan_is_the_recurrence(shape, tokens, block, how):
    """Rows of `tokens` tokens in blocks of `block` (16: several blocks a
    row; 128: the model's `chunk_size`, one block a row), chained within
    the dispatch as the engine chains a prompt's chunks: outputs and
    every row's final state against the recurrence run token by token
    over the same rows in order."""
    n_rows = 4
    a = _inputs(shape, n_rows, tokens)
    chain, fresh = _chain(n_rows, how)
    with jax.default_matmul_precision("highest"):
        want_y, want_s = ssd.reference_ssd_chunk_scan(**a, chain=chain,
                                                      fresh=fresh)
        got_y, got_s = jax.jit(ssd.ssd_chunk_scan, static_argnames="block")(
            **a, chain=chain, fresh=fresh, block=block)
    assert float(jnp.abs(want_y).max()) > 1.0
    np.testing.assert_allclose(got_y, want_y, atol=ATOL, rtol=1e-5)
    np.testing.assert_allclose(got_s, want_s, atol=ATOL, rtol=1e-5)
    if how == "one_prompt":
        # ... which is ONE sequence of n_rows x tokens tokens from zeros.
        flat = lambda t: t.reshape((n_rows * tokens,) + t.shape[2:])
        y, final = ssd.reference_ssd_scan(
            flat(a["x"]), flat(a["dt"]), a["A"], flat(a["B"]), flat(a["C"]),
            jnp.zeros_like(a["state"][0]))
        np.testing.assert_allclose(flat(got_y), y, atol=ATOL, rtol=1e-5)
        np.testing.assert_allclose(got_s[-1], final, atol=ATOL, rtol=1e-5)


def test_a_token_that_is_none_leaves_the_state_alone():
    """dt = 0 past a row's valid tokens: the final state is the state
    after the valid ones, whatever x, B and C hold there."""
    a = _inputs("tiny", 2, 32)
    valid = jnp.arange(32)[None, :] < jnp.asarray([11, 32])[:, None]
    a["dt"] = jnp.where(valid[..., None], a["dt"], 0.0)
    chain, fresh = _chain(2, "apart")
    _y, finals = ssd.ssd_chunk_scan(**a, chain=chain, fresh=fresh, block=16)
    _y, short = ssd.reference_ssd_scan(
        a["x"][0, :11], a["dt"][0, :11], a["A"], a["B"][0, :11],
        a["C"][0, :11], a["state"][0])
    np.testing.assert_allclose(finals[0], short, atol=ATOL, rtol=1e-5)


def test_a_decay_beyond_float32_is_not_a_nan():
    """One token whose dt A is -1e4 (exp of it is 0, exp of its negative
    is not a float32): every ratio is `exp` of a masked difference, so
    the outputs stay finite and the recurrence's."""
    a = _inputs("tiny", 1, 32)
    a["dt"] = a["dt"].at[0, 9].set(1e4)
    chain, fresh = _chain(1, "apart")
    with jax.default_matmul_precision("highest"):
        got_y, got_s = ssd.ssd_chunk_scan(**a, chain=chain, fresh=fresh,
                                          block=16)
        want_y, want_s = ssd.reference_ssd_chunk_scan(**a, chain=chain,
                                                      fresh=fresh)
    assert bool(jnp.isfinite(got_y).all() & jnp.isfinite(got_s).all())
    scale = float(jnp.abs(want_y).max())
    np.testing.assert_allclose(got_y / scale, want_y / scale, atol=1e-5)
    np.testing.assert_allclose(got_s / scale, want_s / scale, atol=1e-5)


def test_rows_that_do_not_cut_into_blocks_are_refused():
    a = _inputs("tiny", 1, 24)
    chain, fresh = _chain(1, "apart")
    with pytest.raises(ValueError, match="blocks of 16"):
        ssd.ssd_chunk_scan(**a, chain=chain, fresh=fresh, block=16)


# ----------------------------------------------------------- a decode step

def _stack(shape, n_layers, n_slots, seed=3):
    H, P, _G, Ns = SHAPES[shape]
    p = ssd.packed_heads(H, P)
    return jax.random.normal(jax.random.key(seed),
                             (n_layers, n_slots + 1, H // p, Ns, p * P))


@pytest.mark.parametrize("step", ["kernel", "reference"])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_decode_step_is_the_recurrence_and_idle_slots_keep_every_bit(
        shape, step):
    """Five slots of which two are idle, layer 1 of a stack of three: a
    live slot's output and new state are one token of the recurrence on
    its unpacked state; an idle slot's state, the null slot's and the
    other layers' keep EVERY BIT. The stack's layout: two published
    heads side by side ([.., 8, 128, 128] for 16 heads of 64 x 128)."""
    H, P, G, Ns = SHAPES[shape]
    a = _inputs(shape, 5, 1, seed=5)
    x, dt, B, C = (a[n][:, 0] for n in ("x", "dt", "B", "C"))
    stack = _stack(shape, 3, 5)
    if shape == "published_head":
        assert stack.shape == (3, 6, 8, 128, 128)
    active = jnp.asarray([True, False, True, True, False])
    fn = (ssd.ssd_decode_step if step == "kernel"
          else ssd.reference_ssd_decode_step)
    y, new = jax.jit(fn)(stack, 1, x, dt, a["A"], B, C, active)
    assert y.shape == (5, H, P) and new.shape == stack.shape
    old = ssd.unpack_state(stack[1, :5], P)
    for b in np.flatnonzero(np.asarray(active)):
        want_y, want_s = ssd.reference_ssd_scan(
            x[b][None], dt[b][None], a["A"], B[b][None], C[b][None], old[b])
        np.testing.assert_allclose(y[b], want_y[0], atol=ATOL, rtol=1e-5)
        np.testing.assert_allclose(ssd.unpack_state(new[1, b], P), want_s,
                                   atol=ATOL, rtol=1e-5)
    keeps = lambda got, was: np.testing.assert_array_equal(
        np.asarray(got).view(np.uint32), np.asarray(was).view(np.uint32))
    for b in (1, 4, 5):                     # idle, idle, the null slot
        keeps(new[1, b], stack[1, b])
    keeps(new[0], stack[0])
    keeps(new[2], stack[2])


def test_the_kernel_is_its_reference():
    a = _inputs("tiny", 4, 1, seed=6)
    x, dt, B, C = (a[n][:, 0] for n in ("x", "dt", "B", "C"))
    stack = _stack("tiny", 2, 4)
    active = jnp.asarray([True, True, False, True])
    y_k, s_k = ssd.ssd_decode_step(stack, 0, x, dt, a["A"], B, C, active)
    y_r, s_r = ssd.reference_ssd_decode_step(stack, 0, x, dt, a["A"], B, C,
                                             active)
    live = np.asarray(active)
    np.testing.assert_allclose(np.asarray(y_k)[live], np.asarray(y_r)[live],
                               atol=1e-5)
    np.testing.assert_allclose(s_k, s_r, atol=1e-5)


def test_a_chunk_then_steps_is_one_sequence():
    """A prompt through the chunk scan, its final state packed into the
    stack, then tokens one at a time through the decode step: the
    outputs are the recurrence's over the whole sequence."""
    H, P, _G, _Ns = SHAPES["tiny"]
    a = _inputs("tiny", 1, 40, seed=8)
    chain, fresh = _chain(1, "one_prompt")
    head = {n: (v[:, :32] if v.ndim > 1 and n != "state" else v)
            for n, v in a.items()}
    with jax.default_matmul_precision("highest"):
        y0, finals = ssd.ssd_chunk_scan(**head, chain=chain, fresh=fresh,
                                        block=16)
        stack = jnp.zeros((1, 2, H // ssd.packed_heads(H, P), 16,
                           ssd.packed_heads(H, P) * P))
        stack = stack.at[0, 0].set(
            ssd.pack_state(finals[0], ssd.packed_heads(H, P)))
        ys = []
        for t in range(32, 40):
            y, stack = ssd.ssd_decode_step(
                stack, 0, a["x"][:, t], a["dt"][:, t], a["A"], a["B"][:, t],
                a["C"][:, t], jnp.asarray([True]))
            ys.append(y[0])
        want, _final = ssd.reference_ssd_scan(
            a["x"][0], a["dt"][0], a["A"], a["B"][0], a["C"][0],
            jnp.zeros_like(a["state"][0]))
    got = jnp.concatenate([y0[0], jnp.stack(ys)])
    np.testing.assert_allclose(got, want, atol=ATOL, rtol=1e-5)
