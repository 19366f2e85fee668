"""The decode step's sampling, against the four straight lines it was.

`paged_kv._sample_next` draws under a `lax.cond` on whether any slot has
a temperature above 0 (PR 55). No cell of the benchmark sends a sampled
request, so the branch that draws is held here: tokens AND the returned
key are the straight-line form's, bit for bit, whatever mix of greedy
and sampling slots a step holds, and a chain of steps advances the key
as the straight-line form does.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.paged_kv import _sample_next

B, V = 8, 4096

_TEMPS = {
    "all_greedy": np.zeros(B, np.float32),
    "all_drawn": np.linspace(0.3, 1.5, B).astype(np.float32),
    # a greedy slot between two that sample
    "mixed": np.array([0.7, 0.0, 1.0, 0.0, 0.0, 1.3, 0.0, 0.2], np.float32),
    "one_drawn": np.array([0.0] * 7 + [0.9], np.float32),
}


def _reference(logits, temps, key):
    """`_sample_next` as every step ran it up to PR 54: the draw made
    whatever the temperatures."""
    key, sub = jax.random.split(key)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    greedy = jnp.argmax(logits, axis=-1)
    sampled = jax.random.categorical(sub, scaled, axis=-1)
    nxt = jnp.where(temps <= 0.0, greedy, sampled).astype(jnp.int32)
    return nxt, key


def _logits(seed, step=0):
    return jax.random.normal(jax.random.PRNGKey(1000 * seed + step), (B, V),
                             jnp.float32) * 3.0


@pytest.mark.parametrize("seed", [0, 7, 2147483001])
@pytest.mark.parametrize("temps", sorted(_TEMPS))
def test_tokens_and_key_are_the_straight_lines(temps, seed):
    """Three chained steps under jit, each fed the key the last returned:
    every step's tokens and key `array_equal` to the reference's."""
    t = jnp.asarray(_TEMPS[temps])
    step, ref = jax.jit(_sample_next), jax.jit(_reference)
    key = ref_key = jax.random.PRNGKey(seed)
    for i in range(3):
        logits = _logits(seed, i)
        nxt, key = step(logits, t, key)
        want, ref_key = ref(logits, t, ref_key)
        assert nxt.dtype == jnp.int32 and nxt.shape == (B,)
        np.testing.assert_array_equal(np.asarray(nxt), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(key), np.asarray(ref_key))
    assert not np.array_equal(np.asarray(key),
                              np.asarray(jax.random.PRNGKey(seed)))


def test_a_sampling_slots_stream_ignores_its_neighbours():
    """A seeded slot draws the same token whether the other slots are
    greedy or sample, and whether the step before drew at all: the key
    advances on greedy steps too."""
    alone, crowd = _TEMPS["one_drawn"], _TEMPS["all_drawn"].copy()
    crowd[-1] = alone[-1]
    step = jax.jit(_sample_next)
    key = jax.random.PRNGKey(3)
    _, after_greedy = step(_logits(5), jnp.asarray(_TEMPS["all_greedy"]), key)
    _, after_drawn = step(_logits(5), jnp.asarray(crowd), key)
    np.testing.assert_array_equal(np.asarray(after_greedy),
                                  np.asarray(after_drawn))
    a, _ = step(_logits(5, 1), jnp.asarray(alone), after_greedy)
    b, _ = step(_logits(5, 1), jnp.asarray(crowd), after_greedy)
    assert int(a[-1]) == int(b[-1])
    greedy = np.asarray(jnp.argmax(_logits(5, 1), axis=-1))
    np.testing.assert_array_equal(np.asarray(a[:-1]), greedy[:-1])


def test_the_draw_is_inside_the_conditional():
    """One `cond` on the temperatures; every random-bits equation and
    every [B, V] equation but the arg-max lives in its branches."""
    jaxpr = jax.make_jaxpr(_sample_next)(
        jnp.zeros((B, V), jnp.float32), jnp.zeros(B, jnp.float32),
        jax.random.PRNGKey(0)).jaxpr
    names = [e.primitive.name for e in jaxpr.eqns]
    assert names.count("cond") == 1
    wide = [e.primitive.name for e in jaxpr.eqns
            if any(getattr(v.aval, "shape", ()) == (B, V) for v in e.invars)
            and e.primitive.name != "cond"]
    assert wide == ["argmax"]
    cond = jaxpr.eqns[names.index("cond")]
    drawn = [str(b) for b in cond.params["branches"]]
    assert sum("random_bits" in b or "threefry2x32" in b for b in drawn) == 1
