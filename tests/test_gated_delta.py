"""ops/gated_delta.py on the CPU: the chunked scan against the
recurrence it stands for, and the decode kernel (interpret mode) against
its plain-XLA oracle, at the served head sizes: qwen3-next's (32 value
heads over 16 key heads of 128 x 128, beta in (0, 1)) and olmo-hybrid's
(30 heads of 96 x 192, a key head a value head, beta in (0, 2), the
pool's state two heads side by side)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.gated_delta import (
    gdn_chunk_scan, gdn_decode_step, pack_state, packed_heads,
    reference_gdn_decode_step, reference_gdn_scan, unpack_state)


def _inputs(rng, N, C, H, dk, dv, g_of, beta_max=1.0, cosine=0.0):
    """`beta_max`: 1 for a sigmoid, 2 for twice one (drawn wide, so the
    reflecting range past 1 is well filled); `cosine`: the keys' mean
    cosine to a direction a head and row shares (0: independent keys)."""
    unit = lambda t: t / np.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(N, C, H, dk))) / np.sqrt(dk)
    k = unit(cosine * unit(rng.normal(size=(N, 1, H, dk)))
             + (1 - cosine) * unit(rng.normal(size=(N, C, H, dk))))
    v = rng.normal(size=(N, C, H, dv))
    beta = beta_max / (1 + np.exp(-beta_max * rng.normal(size=(N, C, H))))
    f32 = lambda t: jnp.asarray(t, jnp.float32)
    return tuple(f32(t) for t in (q, k, v, g_of((N, C, H)), beta))


# (H, dk, dv, the write strength's range): qwen3-next's ratio small, and
# olmo-hybrid's heads as published (dk != dv, neither a lane multiple) at
# 6 and at all 30 heads.
SHAPES = {"8x32x32-beta1": (8, 32, 32, 1.0),
          "6x96x192-beta2": (6, 96, 192, 2.0),
          "30x96x192-beta2": (30, 96, 192, 2.0)}


# The decay a token, from nearly none to one that passes float32's range
# in two tokens; the last spreads the heads from 1e-3 to 60 as the seeded
# weights do.
DECAYS = {
    "slow": lambda s: np.full(s, -1e-3),
    "fast": lambda s: np.full(s, -60.0),
    "spread": lambda s: np.broadcast_to(
        -np.geomspace(1e-3, 60.0, s[-1]), s).copy(),
    "mixed": lambda s: -np.abs(np.random.default_rng(3).normal(size=s)) * 20,
    # A large A times a large step: one token in eight forgets everything
    # at -1e10, the rest decay by 0.1 (cumulated naively, every later
    # difference of G is a multiple of 512).
    "annihilating": lambda s: np.where(
        np.random.default_rng(7).random(s) < 0.125, -1e10, -0.1),
}


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("block", [16, 64])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_chunk_scan_is_the_recurrence(decay, block, shape):
    """128 tokens a row, two rows, from a non-zero state: outputs and
    final state agree with the token-by-token form to float32 rounding,
    with no inf and no NaN at any decay, at beta up to 1 and up to 2."""
    rng = np.random.default_rng(0)
    N, C = 2, 128
    H, dk, dv, beta_max = SHAPES[shape]
    q, k, v, g, beta = _inputs(rng, N, C, H, dk, dv, DECAYS[decay], beta_max)
    assert beta_max / 2 < float(beta.max()) < beta_max
    state = jnp.asarray(rng.normal(size=(N, H, dk, dv)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        o, finals = gdn_chunk_scan(
            q, k, v, g, beta, state, jnp.full(N, -1, jnp.int32),
            jnp.zeros(N, bool), block=block)
        want = [reference_gdn_scan(q[n], k[n], v[n], g[n], beta[n], state[n])
                for n in range(N)]
    assert np.all(np.isfinite(o)) and np.all(np.isfinite(finals))
    # Outputs are O(0.5); a state's entries reach several units after
    # 128 writes of unit values, more where beta passes 1 (a write may
    # add twice what it removes), and carry float32's rounding at that
    # size: 2e-5 at beta <= 1 as before, 1e-4 at beta <= 2 (the worst
    # reading, 30 heads at block 64 under the mixed decay, is 5.7e-5).
    for n, (o_ref, s_ref) in enumerate(want):
        np.testing.assert_allclose(o[n], o_ref, atol=2e-5, rtol=0)
        np.testing.assert_allclose(finals[n], s_ref,
                                   atol=2e-5 if beta_max == 1 else 1e-4, rtol=0)


@pytest.mark.parametrize("cosine", [0.5, 0.9, 0.99])
@pytest.mark.parametrize("block", [16, 64])
@pytest.mark.parametrize("shape", ["8x32x32-beta1", "6x96x192-beta2"])
def test_chunk_scan_holds_where_the_keys_agree(cosine, block, shape):
    """A trained model's keys are not independent draws. With every key
    of a head near ONE direction (mean cosine 0.5 to 0.99) and a slow
    decay, A's entries are near beta and the powers A^k of the nilpotent
    series this file once summed grow like beta^k C(T, k) before they
    cancel: at block 64 that form lost 2e-3 of an output at a cosine of
    0.5 under beta <= 1, and every digit under beta <= 2 (1e5 at 0.5,
    1e18 at 0.99). The blocked substitution forms nothing larger than
    the inverse's own entries, which the recurrence bounds."""
    rng = np.random.default_rng(4)
    H, dk, dv, beta_max = SHAPES[shape]
    q, k, v, g, beta = _inputs(rng, 1, 128, H, dk, dv, DECAYS["slow"],
                               beta_max, cosine)
    state = jnp.asarray(rng.normal(size=(1, H, dk, dv)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        o, finals = gdn_chunk_scan(
            q, k, v, g, beta, state, jnp.full(1, -1, jnp.int32),
            jnp.zeros(1, bool), block=block)
        o_ref, s_ref = reference_gdn_scan(q[0], k[0], v[0], g[0], beta[0],
                                          state[0])
    np.testing.assert_allclose(o[0], o_ref, atol=2e-5, rtol=0)
    np.testing.assert_allclose(finals[0], s_ref, atol=5e-5, rtol=0)


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


# (value heads, key heads, dk, dv): qwen3-next's served sizes (the pool's
# leaf the plain [H, dk, dv]), and olmo-hybrid's at 6 and at all 30
# heads (the leaf two heads side by side, [H / 2, 96, 384]; 3 and 15
# pairs in blocks of 3 and 5).
DECODE_SHAPES = {"32over16x128x128": (32, 16, 128, 128),
                 "6x96x192": (6, 6, 96, 192),
                 "30x96x192": (30, 30, 96, 192)}


@pytest.mark.parametrize("idle", ["none", "some", "all"])
@pytest.mark.parametrize("shape", sorted(DECODE_SHAPES))
def test_decode_kernel_is_its_oracle(idle, shape):
    """At the served sizes, at a layer other than 0 of the stack, beta
    drawn in (0, 2): live slots' outputs and states are the oracle's
    (which is held to the token-by-token recurrence below), an idle
    slot's state is untouched and its output zero, and no other layer
    moves."""
    rng = np.random.default_rng(2)
    H, Hk, dk, dv = DECODE_SHAPES[shape]
    L, B, p = 2, 5, packed_heads(H, dv)
    assert p == (2 if dv == 192 else 1)
    f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    state = f32(L, B + 1, H // p, dk, p * dv)
    q, k, v = f32(B, Hk, dk) / dk, f32(B, Hk, dk) / np.sqrt(dk), f32(B, H, dv)
    g, beta = -jnp.abs(f32(B, H)), 2 * jax.nn.sigmoid(2 * f32(B, H))
    active = jnp.asarray({"none": [1, 1, 1, 1, 1], "some": [0, 1, 0, 0, 1],
                          "all": [0, 0, 0, 0, 0]}[idle], bool)
    o_ref, s_ref = reference_gdn_decode_step(
        state, 1, q, k, v, g, beta, active, repeat=H // Hk)
    o, s = gdn_decode_step(state, 1, q, k, v, g, beta, active,
                           repeat=H // Hk, interpret=True)
    live = np.asarray(active)
    np.testing.assert_allclose(o[live], o_ref[live], atol=1e-5, rtol=0)
    assert not np.any(np.asarray(o)[~live])
    np.testing.assert_allclose(s[1, :B], s_ref[1, :B], atol=1e-5, rtol=0)
    np.testing.assert_array_equal(s[0], state[0])
    np.testing.assert_array_equal(s[1, :B][~live], state[1, :B][~live])


@pytest.mark.parametrize("shape", sorted(DECODE_SHAPES))
def test_decode_oracle_is_one_token_of_the_recurrence(shape):
    """The plain-XLA step over the pool's (packed) leaf against
    `reference_gdn_scan` over one token of the plain [H, dk, dv] states:
    the packing is a view, head h's state is head h's."""
    rng = np.random.default_rng(5)
    H, Hk, dk, dv = DECODE_SHAPES[shape]
    B, p, r = 3, packed_heads(H, dv), H // Hk
    f32 = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    plain = f32(B + 1, H, dk, dv)
    q, k, v = f32(B, Hk, dk) / dk, f32(B, Hk, dk) / np.sqrt(dk), f32(B, H, dv)
    g, beta = -jnp.abs(f32(B, H)), 2 * jax.nn.sigmoid(f32(B, H))
    o, s = reference_gdn_decode_step(
        pack_state(plain, p)[None], 0, q, k, v, g, beta, jnp.ones(B, bool),
        repeat=r)
    for b in range(B):
        o_ref, s_ref = reference_gdn_scan(
            jnp.repeat(q[b], r, axis=0)[None], jnp.repeat(k[b], r, axis=0)[None],
            v[b][None], g[b][None], beta[b][None], plain[b])
        np.testing.assert_allclose(o[b], o_ref[0], atol=1e-5, rtol=0)
        np.testing.assert_allclose(unpack_state(s[0, b], dv), s_ref,
                                   atol=1e-5, rtol=0)


def test_the_pools_leaf_packs_heads_into_whole_lane_tiles():
    """`packed_heads`: the fewest heads whose values fill whole tiles of
    128 lanes, where they divide the heads; `pack_state` puts heads
    p h .. p h + p - 1 side by side and `unpack_state` reads p off the
    last axis; at p = 1 both hand their argument back."""
    assert [packed_heads(*a) for a in (
        (32, 128), (30, 192), (6, 64), (4, 16), (8, 16), (30, 96), (7, 192),
        (30, 256))] == [1, 2, 2, 1, 8, 1, 1, 1]
    rng = np.random.default_rng(6)
    s = jnp.asarray(rng.normal(size=(3, 6, 8, 192)), jnp.float32)
    packed = pack_state(s, 2)
    assert packed.shape == (3, 3, 8, 384)
    np.testing.assert_array_equal(packed[:, 1, :, :192], s[:, 2])
    np.testing.assert_array_equal(packed[:, 1, :, 192:], s[:, 3])
    np.testing.assert_array_equal(unpack_state(packed, 192), s)
    assert pack_state(s, 1) is s and unpack_state(s, 192) is s


def test_decode_kernel_refuses_what_it_cannot_lay_out():
    f32 = lambda *s: jnp.zeros(s, jnp.float32)
    with pytest.raises(ValueError, match="p dv a multiple of 128"):
        gdn_decode_step(f32(1, 3, 30, 96, 192), 0, f32(2, 30, 96),
                        f32(2, 30, 96), f32(2, 30, 192), f32(2, 30),
                        f32(2, 30), jnp.ones(2, bool), interpret=False)
