"""Every family's chunk, decode-step and window-step program, letter for
letter.

The serving programs of the nine families of models/serving.py are built
by one builder (models/paged_kv.py `paged_programs`) from parts that
several families share (models/blocks.py). A refactor of either must
leave every lowered program as it was: the digests below were computed
BEFORE the builder and the shared module existed, by this very code, and
none is edited by a change that claims to move no number.
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import (gpt, jamba, kimi_k2, laguna, mimo_v2,
                            nemotron_h, olmo_hybrid, paged_kv, qwen3_next,
                            zaya)

PAGE, N_PAGES, N_SLOTS, CHUNK = 16, 24, 3, 16

# sha256 (first 16 hex digits) of `str(jax.make_jaxpr(program))`,
# addresses blanked, of each family's chunk program, decode-step program
# and decode window's step (`sample`: the step with sampling on the
# device) at its tiny size with the kernels on. The first eight as commit
# 02951b4 traced them (the commit before the mimo_v2 family), the mimo_v2
# pair as commit c3726bb did (the commit before the builder). The six
# `sample` programs as PR 55 traced them, which meant to change them and
# nothing else: `paged_kv._sample_next` makes its categorical draw under
# a `lax.cond` on whether any slot's temperature is above 0, where every
# step had drawn [slots, vocabulary] threefry words and thrown them away
# (tokens and key bit for bit the straight lines': test_sample_next.py);
# the six `decode` programs, the same decode step without the sampling,
# stayed where they were. `qwen3_next.chunk` as PR 53 traced it: the rule
# by which a dispatch's rows find their predecessor moved to
# `blocks.dispatch_order` (the same equations, `iota(N)` traced four
# equations later), and the COMPILED chunk program is instruction for
# instruction what ffb23a00a1eeb73d compiled to (17,774 lines of
# optimized HLO, compared less source locations: CHANGES.md, PR 53).
# `jamba.chunk` as PR 53, the family's first, traced it; `jamba.decode`
# as PR 54 did, which meant to change it (and `jamba.sample` with it):
# the mamba sublayer of a decode step on planes without the token axis
# and `ssm_decode_step` over blocks of slots. The `kimi_k2` three as PR 56,
# the family's first, traced them over the tree its `lay_out` makes; that
# PR moved mimo_v2's router and counter row to models/blocks.py
# (`biased_route`, `counter_row_biased`), laguna's `yarn_inv_freq` and
# `init_from_specs` likewise, and gave both paged kernels a latent form:
# the eighteen above did not move. `kimi_k2.decode` and `kimi_k2.sample`
# as PR 57 traced them, which meant to change them and nothing else: the
# latent decode call has a kernel body of its own
# (`ops/paged_attention.py` `_latent_decode_kernel`: a block of 512 keys,
# the next block's scores under this block's softmax chain);
# `kimi_k2.chunk` runs the prefill form and stayed, as did the other
# eighteen. The twelve of `laguna`, `qwen3_next`, `mimo_v2` and `kimi_k2`
# as PR 59 traced them, which meant to change them by ONE thing at these
# sizes: the pool's counter row is one uint32 longer (`rows_over`, the
# held choices the expert layer's first block did not take: five
# equations a sparse layer in a decode step, `max(0, sum(counts) - 124)`;
# a chunk program only carries the longer row through). At the tiny
# configurations (4 of 8 experts held, top-3) the expert layer's block is
# every choice, so the layer itself traces the parent's equations
# (compared equation for equation with the parent's jaxpr); where the
# block is less (12 of 384 held), tests/test_chip_compile.py holds the
# compiled program. `zaya`, which holds every expert and counts neither
# share nor overflow, `gpt` and `jamba` stayed. The three of `qwen3_next`
# as PR 60 traced them, which meant to change them in `ops/gated_delta.py`
# alone, for the family that shares it (`olmo_hybrid`, whose three are
# its first): `qwen3_next.chunk` inverts the unit lower-triangular block
# by substitution over 8-token diagonal blocks (the batch of blocks along
# the lanes) merged pairwise, where it summed the nilpotent series (that form lost every digit at beta up to 2
# and correlated keys: tests/test_gated_delta.py), and sums a decay ratio's
# exponent G_i - G_j from its own terms where it took the difference of
# two cumulated sums (one token at g = -1e10 left every later difference
# a multiple of 512); `qwen3_next.decode`
# and `.sample` hand `gdn_decode_step` the heads' value, decay and beta
# rows as [B, H / 16, 16, dv] where they were [B, H, dv] (a reshape on
# either side of the call: a block of 5 of olmo-hybrid's 15 packed rows
# is neither whole sublane tiles nor the whole axis), the kernel's body
# equation for equation what it was at one head a packed head. The other
# eighteen stayed. `laguna.decode`, `laguna.sample`, `mimo_v2.decode` and
# `mimo_v2.sample` as PR 61 traced them, which meant to change them in
# ONE call: the window layers' decode call
# (`ops/paged_attention.py` `_window_decode_kernel`: a slot's window
# fetched as a run of its ring's rows, one block, one softmax; the pools
# handed over as planes of rows, the ring by its first row); the chunk
# programs run the prefill kernel and stayed, as did the other families'.
# The `nemotron_h` three as PR 62, the family's first, traced them; that
# PR gave `ops/moe.py` `token_choice_experts` a second expert form (two
# stacks: squared ReLU), `ops/gated_delta.py`'s decode step a form without
# the delta term (ops/ssd.py) and moved jamba's causal convolution to
# models/blocks.py: the twenty-four above did not move.
_PINNED = {
    "gpt.chunk": "aff570174e390475", "gpt.decode": "c4ebbb44ff02a83f",
    "zaya.chunk": "e3c3c03e1e115475", "zaya.decode": "21818dedb3b70792",
    "laguna.chunk": "416053d2794175ae", "laguna.decode": "ffdc1d6d32268b9b",
    "qwen3_next.chunk": "1bdf3087055bdd93",
    "qwen3_next.decode": "cef6eae62e3648d3",
    "mimo_v2.chunk": "6c90d3e82314369a",
    "mimo_v2.decode": "4d65032cbde12581",
    "gpt.sample": "057837dac4200223", "zaya.sample": "55f97b147af972ff",
    "laguna.sample": "af1ceae66db8ca3e",
    "qwen3_next.sample": "4dcf0d487ad684ba",
    "mimo_v2.sample": "61fae9884dba1561",
    "jamba.chunk": "16c983f57626c3f6", "jamba.decode": "5262465ac313b68d",
    "jamba.sample": "09798055e3a58725",
    "kimi_k2.chunk": "c7042b4b88e13c46",
    "kimi_k2.decode": "aacbe068562d9c01",
    "kimi_k2.sample": "d123ed2e5fdcf2d5",
    "olmo_hybrid.chunk": "d3337227332042d8",
    "olmo_hybrid.decode": "54ec2aa7a880ab04",
    "olmo_hybrid.sample": "8099e350910eabed",
    "nemotron_h.chunk": "d2a290f93d9ff44b",
    "nemotron_h.decode": "3e6fa39a4f27e0b6",
    "nemotron_h.sample": "91ddb6cb0f06a571",
}

_RING = {"dispatch_tokens": 2 * CHUNK}
# family -> (the module of its programs, its model module, a tiny
# configuration, init_paged_kv's keywords beside the sizes).
_FAMILIES = {
    "gpt": (paged_kv, gpt, gpt.GPTConfig.tiny(), None),
    "zaya": (zaya, zaya, zaya.ZayaConfig.tiny(), {}),
    "laguna": (laguna, laguna, laguna.LagunaConfig.tiny(), _RING),
    "qwen3_next": (qwen3_next, qwen3_next,
                   qwen3_next.Qwen3NextConfig.tiny(), {}),
    "mimo_v2": (mimo_v2, mimo_v2, mimo_v2.MiMoV2Config.tiny(), _RING),
    "jamba": (jamba, jamba, jamba.JambaConfig.tiny(), {}),
    "kimi_k2": (kimi_k2, kimi_k2, kimi_k2.KimiK2Config.tiny(), {}),
    "olmo_hybrid": (olmo_hybrid, olmo_hybrid,
                    olmo_hybrid.OlmoHybridConfig.tiny(), {}),
    "nemotron_h": (nemotron_h, nemotron_h,
                   nemotron_h.NemotronHConfig.tiny(), {}),
}


def _traced(program: str):
    """The jaxpr of `<family>.<chunk|decode|sample>` at tiny size."""
    name, which = program.split(".")
    mod, model, cfg, pool_kw = _FAMILIES[name]
    i32 = lambda *s: jnp.zeros(s, jnp.int32)
    zeros = lambda tree: jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype), tree)
    params = zeros(jax.eval_shape(
        lambda: model.init_params(cfg, jax.random.key(0))))
    if name == "kimi_k2":       # the tree its engine serves
        params = model.lay_out(cfg, params)
    pool = zeros(jax.eval_shape(
        (lambda: paged_kv.init_paged_kv(cfg, N_PAGES, PAGE))
        if pool_kw is None else
        (lambda: mod.init_paged_kv(cfg, N_PAGES, PAGE, N_SLOTS, **pool_kw))))
    if which == "chunk":
        kw = {} if name in ("gpt", "kimi_k2") else {"slots": i32(2)}
        fn = lambda p, kv: mod.prefill_chunk_paged.__wrapped__(
            cfg, p, i32(2, CHUNK), kv, i32(2, 8), i32(2), i32(2),
            attn_impl="kernel", **kw)
    elif which == "decode":
        fn = lambda p, kv: mod.decode_step_paged.__wrapped__(
            cfg, p, i32(N_SLOTS), kv, i32(N_SLOTS), i32(N_SLOTS, 8),
            attn_impl="kernel")
    else:
        fn = lambda p, kv: mod._decode_sample_paged.__wrapped__(
            cfg, p, i32(N_SLOTS), kv, i32(N_SLOTS), i32(N_SLOTS, 8),
            jnp.zeros(N_SLOTS, jnp.float32), jax.random.PRNGKey(0),
            attn_impl="kernel")
    return jax.make_jaxpr(fn)(params, pool)


def _digest(program: str) -> str:
    text = re.sub(r"0x[0-9a-f]+", "0x", str(_traced(program)))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("program", sorted(_PINNED))
def test_every_family_gets_exactly_the_pinned_program(program):
    assert _digest(program) == _PINNED[program]


def _walk(jaxpr, inside_cond=False):
    """(equation, whether a `cond` encloses it) over a jaxpr and every
    jaxpr its equations carry, a kernel's own aside (its `pl.when`s are
    `cond`s of another kind)."""
    for eqn in jaxpr.eqns:
        yield eqn, inside_cond
        if eqn.primitive.name == "pallas_call":
            continue
        within = inside_cond or eqn.primitive.name == "cond"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub, within)


@pytest.mark.parametrize("family", sorted(_FAMILIES))
def test_a_window_step_draws_only_under_its_conditional(family):
    """Every family's window step holds ONE `cond` (the sampling step's,
    on whether any slot's temperature is above 0: PR 55), and nothing
    that makes random bits runs outside it: a greedy batch pays for the
    arg-max alone."""
    eqns = list(_walk(_traced(family + ".sample").jaxpr))
    assert sum(e.primitive.name == "cond" for e, _ in eqns) == 1
    bits = [inside for e, inside in eqns
            if e.primitive.name in ("random_bits", "threefry2x32")]
    assert bits and all(bits)
