"""ops/gated_delta.py on the CPU: the chunked scan against the
recurrence it stands for, and the decode kernel (interpret mode) against
its plain-XLA oracle, at the served head sizes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.gated_delta import (
    gdn_chunk_scan, gdn_decode_step, reference_gdn_decode_step,
    reference_gdn_scan)


def _inputs(rng, N, C, H, dk, dv, g_of):
    unit = lambda t: t / np.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(N, C, H, dk))) / np.sqrt(dk)
    k = unit(rng.normal(size=(N, C, H, dk)))
    v = rng.normal(size=(N, C, H, dv))
    beta = 1 / (1 + np.exp(-rng.normal(size=(N, C, H))))
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    return tuple(f32(t) for t in (q, k, v, g_of((N, C, H)), beta))


# The decay a token, from nearly none to one that passes float32's range
# in two tokens; the last spreads the heads from 1e-3 to 60 as the seeded
# weights do.
DECAYS = {
    "slow": lambda s: np.full(s, -1e-3),
    "fast": lambda s: np.full(s, -60.0),
    "spread": lambda s: np.broadcast_to(
        -np.geomspace(1e-3, 60.0, s[-1]), s).copy(),
    "mixed": lambda s: -np.abs(np.random.default_rng(3).normal(size=s)) * 20,
}


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("block", [16, 64])
def test_chunk_scan_is_the_recurrence(decay, block):
    """128 tokens a row, two rows, from a non-zero state: outputs and
    final state agree with the token-by-token form to float32 rounding,
    with no inf and no NaN at any decay."""
    rng = np.random.default_rng(0)
    N, C, H, dk, dv = 2, 128, 8, 32, 32
    q, k, v, g, beta = _inputs(rng, N, C, H, dk, dv, DECAYS[decay])
    state = jnp.asarray(rng.normal(size=(N, H, dk, dv)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        o, finals = gdn_chunk_scan(
            q, k, v, g, beta, state, jnp.full(N, -1, jnp.int32),
            jnp.zeros(N, bool), block=block)
        want = [reference_gdn_scan(q[n], k[n], v[n], g[n], beta[n], state[n])
                for n in range(N)]
    assert np.all(np.isfinite(o)) and np.all(np.isfinite(finals))
    for n, (o_ref, s_ref) in enumerate(want):
        np.testing.assert_allclose(o[n], o_ref, atol=2e-5, rtol=0)
        np.testing.assert_allclose(finals[n], s_ref, atol=2e-5, rtol=0)


def test_chunk_scan_chains_fresh_rows_and_tokens_that_are_none():
    """Row 1 continues row 0 (its final state, not the pool's); row 2
    starts a prompt from zeros whatever the pool holds; row 3 reads the
    pool's state and its last 40 tokens are none (g = 0, beta = 0): they
    leave the state as token 87 left it."""
    rng = np.random.default_rng(1)
    N, C, H, dk, dv = 4, 128, 4, 16, 16
    q, k, v, g, beta = _inputs(rng, N, C, H, dk, dv, DECAYS["mixed"])
    live = jnp.arange(C) < 88
    g = g.at[3].set(jnp.where(live[:, None], g[3], 0.0))
    beta = beta.at[3].set(jnp.where(live[:, None], beta[3], 0.0))
    state = jnp.asarray(rng.normal(size=(N, H, dk, dv)), jnp.float32)
    chain = jnp.asarray([-1, 0, -1, -1], jnp.int32)
    fresh = jnp.asarray([False, False, True, False])
    with jax.default_matmul_precision("highest"):
        o, finals = gdn_chunk_scan(q, k, v, g, beta, state, chain, fresh,
                                   block=64)
        ref = lambda n, s, upto=C: reference_gdn_scan(
            q[n, :upto], k[n, :upto], v[n, :upto], g[n, :upto],
            beta[n, :upto], s)
        o0, s0 = ref(0, state[0])
        o1, s1 = ref(1, s0)
        o2, s2 = ref(2, jnp.zeros_like(state[2]))
        o3, s3 = ref(3, state[3], 88)
    for got, want in ((o[0], o0), (o[1], o1), (o[2], o2), (o[3, :88], o3),
                      (finals[0], s0), (finals[1], s1), (finals[2], s2),
                      (finals[3], s3)):
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("idle", ["none", "some", "all"])
def test_decode_kernel_is_its_oracle(idle):
    """At the served sizes (32 value heads over 16 key heads of 128), at
    a layer other than 0 of the stack: live slots' outputs and states
    are the oracle's, an idle slot's state is untouched and its output
    zero, and no other layer moves."""
    rng = np.random.default_rng(2)
    L, B, H, Hk, dk, dv = 2, 5, 32, 16, 128, 128
    f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    state = f32(L, B + 1, H, dk, dv)
    q, k, v = f32(B, Hk, dk) / dk, f32(B, Hk, dk) / np.sqrt(dk), f32(B, H, dv)
    g, beta = -jnp.abs(f32(B, H)), jax.nn.sigmoid(f32(B, H))
    active = jnp.asarray({"none": [1, 1, 1, 1, 1], "some": [0, 1, 0, 0, 1],
                          "all": [0, 0, 0, 0, 0]}[idle], bool)
    o_ref, s_ref = reference_gdn_decode_step(
        state, 1, q, k, v, g, beta, active, repeat=2)
    o, s = gdn_decode_step(state, 1, q, k, v, g, beta, active, repeat=2,
                           interpret=True)
    live = np.asarray(active)
    np.testing.assert_allclose(o[live], o_ref[live], atol=1e-5, rtol=0)
    assert not np.any(np.asarray(o)[~live])
    np.testing.assert_allclose(s[1, :B], s_ref[1, :B], atol=1e-5, rtol=0)
    np.testing.assert_array_equal(s[0], state[0])
    np.testing.assert_array_equal(s[1, :B][~live], state[1, :B][~live])
