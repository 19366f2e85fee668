"""ops/moe.py `token_choice_experts`: the no-drop token-choice expert
layer against a per-token loop, and the parts of chips holding disjoint
expert ranges against the whole; in both expert forms (`FORMS`: three
stacks a gated-SiLU expert, two a squared-ReLU one).
"""

import collections
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import moe
from ray_tpu.ops.moe import token_choice_experts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

N, D, F, E = 24, 16, 12, 8


# An expert's form is how many stacks the layer is handed.
FORMS = {"gated_silu": 3, "relu2": 2}


def _stacks(mk, d, f, form="gated_silu"):
    """An expert form's stacks from `mk(*shape)`: [.., d, f] once (relu2)
    or twice (gated SiLU), then [.., f, d]."""
    return tuple(mk(d, f) for _ in range(FORMS[form] - 1)) + (mk(f, d),)


def _weights(seed=0, n_layers=None, form="gated_silu"):
    rng = np.random.default_rng(seed)
    lead = (E,) if n_layers is None else (n_layers, E)
    mk = lambda *s: jnp.asarray(rng.normal(size=lead + s) * 0.3, jnp.float32)
    return _stacks(mk, D, F, form)


def _expert64(xn, mats):
    """One expert on one row in float64: W_down(silu(W_gate x) * W_up x)
    of three matrices, W_down relu(W_up x)^2 of two."""
    xn = np.asarray(xn, np.float64)
    if len(mats) == 2:
        return np.maximum(xn @ mats[0], 0.0) ** 2 @ mats[1]
    a = xn @ mats[0]
    return (a / (1.0 + np.exp(-a)) * (xn @ mats[1])) @ mats[2]


def _loop(x, ids, gates, *w, valid=None):
    """One token at a time, one choice at a time: the definition."""
    x, ids, gates = (np.asarray(a) for a in (x, ids, gates))
    ids, gates = ids.reshape(len(x), -1), gates.reshape(len(x), -1)
    w = [np.asarray(t, np.float64) for t in w]
    out = np.zeros((len(x), w[-1].shape[-1]))
    for n in range(len(x)):
        if valid is not None and not valid[n]:
            continue
        for e, g in zip(ids[n], gates[n]):
            out[n] += g * _expert64(x[n], [t[e] for t in w])
    return out


ROUTINGS = {
    # forced imbalance: every row to one expert, seven experts with none
    "all_to_one": lambda rng, n: np.full(n, 5),
    # an expert with none (3), the rest uneven
    "one_empty": lambda rng, n: rng.choice([0, 1, 1, 2, 4, 5, 6, 7, 7, 7], n),
    "uniform": lambda rng, n: rng.integers(0, E, n),
    "top2": lambda rng, n: np.stack([rng.permutation(E)[:2]
                                     for _ in range(n)]),
}


# 24 rows and their 8 zero rows fit one 128-row tile; 121 (+ 8, and twice
# that at top-2) spill into the next and are padded to an odd number of
# tiles.
@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("n_rows", [N, 121])
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_matches_a_per_token_loop_and_drops_no_row(routing, n_rows, form):
    rng = np.random.default_rng(1)
    ids = ROUTINGS[routing](rng, n_rows).astype(np.int32)
    x = jnp.asarray(rng.normal(size=(n_rows, D)), jnp.float32)
    gates = jnp.asarray(rng.uniform(0.1, 1.0, ids.shape), jnp.float32)
    w = _weights(form=form)
    with jax.default_matmul_precision("highest"):
        y, counts = jax.jit(token_choice_experts)(
            x, jnp.asarray(ids), gates, *w)
    np.testing.assert_allclose(np.asarray(y), _loop(x, ids, gates, *w),
                               atol=2e-5)
    # No capacity: every assignment is counted where it was sent.
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(ids.reshape(-1), minlength=E))
    assert int(counts.sum()) == ids.size
    if routing == "all_to_one":
        assert int(counts[5]) == n_rows and int((counts > 0).sum()) == 1
    if routing == "one_empty":
        assert int(counts[3]) == 0


def test_rows_that_carry_no_token_reach_no_expert():
    rng = np.random.default_rng(2)
    ids = rng.integers(0, E, N).astype(np.int32)
    x = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    gates = jnp.ones(N, jnp.float32)
    valid = rng.uniform(size=N) < 0.5
    w = _weights()
    with jax.default_matmul_precision("highest"):
        y, counts = token_choice_experts(x, jnp.asarray(ids), gates, *w,
                                         valid=jnp.asarray(valid))
    np.testing.assert_allclose(np.asarray(y),
                               _loop(x, ids, gates, *w, valid=valid),
                               atol=2e-5)
    assert int(counts.sum()) == int(valid.sum())
    assert np.all(np.asarray(y)[~valid] == 0.0)


def test_the_layer_index_picks_its_experts_out_of_the_whole_stack():
    """Weights [L, E, ...] and a traced layer index, as the layer scan
    calls it: the same as that layer's slice."""
    rng = np.random.default_rng(3)
    ids = jnp.asarray(rng.integers(0, E, N), jnp.int32)
    x = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    gates = jnp.asarray(rng.uniform(0.1, 1.0, N), jnp.float32)
    stack = _weights(seed=4, n_layers=3)
    with jax.default_matmul_precision("highest"):
        for layer in range(3):
            got, _ = jax.jit(token_choice_experts)(
                x, ids, gates, *stack, layer=jnp.int32(layer))
            want, _ = token_choice_experts(
                x, ids, gates, *(w[layer] for w in stack))
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-6)


def test_the_parts_of_two_chips_add_up_to_the_references_layer():
    """A share tied to the whole: the layer holding experts 0-7 and the
    layer holding experts 8-15 each return only their part, and the two
    parts add up to the uncut plain reference's expert sublayer
    (zaya_ref._experts: router, top-1 and experts, float32)."""
    from harness.reference import zaya_ref
    from ray_tpu.models import zaya

    cfg = zaya.ZayaConfig.tiny(dtype=jnp.float32, n_experts=16, n_layers=1)
    params = zaya.init_params(cfg, jax.random.key(0))
    layer = {k: v[0] for k, v in params.items()
             if k not in ("wte", "ln_f_scale")}
    layer["w_down"] = layer["w_down"] * 500.0       # an output to compare
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(40, cfg.d_model)), jnp.float32)
    r = jnp.asarray(rng.normal(size=(40, cfg.router_dim)), jnp.float32)
    RC = collections.namedtuple("RC", "n_heads n_kv_heads norm_eps")
    with jax.default_matmul_precision("highest"):
        whole, r_ref = zaya_ref._experts(
            x, r, layer, RC(cfg.n_heads, cfg.n_kv_heads, cfg.norm_eps))
        u = zaya._rms_norm(x, layer["ln2_scale"], cfg.norm_eps)
        expert, gate, r_got = zaya._route(cfg, layer, u, r)
        parts, counts = [], []
        for lo in (0, 8):
            held = (layer[k][lo:lo + 8] for k in ("w_gate", "w_up", "w_down"))
            y, c = token_choice_experts(u, expert, gate, *held,
                                        first_expert=lo)
            parts.append(np.asarray(y))
            counts.append(int(c.sum()))
    assert sum(counts) == 40 and min(counts) > 0    # both chips had rows
    assert np.abs(whole).max() > 0.05
    assert np.abs(parts[0]).max() > 0 and np.abs(parts[1]).max() > 0
    np.testing.assert_allclose(parts[0] + parts[1], np.asarray(whole),
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(r_got), np.asarray(r_ref),
                               atol=2e-6)
    # a row's output comes from ONE chip
    assert not np.any((np.abs(parts[0]).max(axis=1) > 0)
                      & (np.abs(parts[1]).max(axis=1) > 0))


# ------------------------- top-10 of 256 with 128 held: the laguna cell's k

def _top_k_routing(rng, n_rows, n_experts, k):
    ids = np.stack([rng.permutation(n_experts)[:k] for _ in range(n_rows)])
    gates = rng.uniform(0.1, 1.0, ids.shape)
    return ids.astype(np.int32), (2.5 * gates / gates.sum(axis=1,
                                                          keepdims=True))


@pytest.mark.parametrize("first", [0, 128])
@pytest.mark.parametrize("n_rows,layer", [(64, None), (64, 2), (7, 1)])
def test_top_10_of_256_with_128_held(first, n_rows, layer):
    """A decode step of the laguna cell in small widths: 64 rows, 10
    choices each over 256 experts of which this chip holds 128 (either
    half), whole stacks and a layer index as the program passes them.
    The held choices, and only they, against the per-token loop; every
    choice is counted by exactly one of the two halves."""
    rng = np.random.default_rng(11)
    held, d, f = 128, 8, 6
    ids, gates = _top_k_routing(rng, n_rows, 256, 10)
    x = jnp.asarray(rng.normal(size=(n_rows, d)), jnp.float32)
    lead = (held,) if layer is None else (3, held)
    mk = lambda *s: jnp.asarray(rng.normal(size=lead + s) * 0.3, jnp.float32)
    w = mk(d, f), mk(d, f), mk(f, d)
    kw = {} if layer is None else {"layer": jnp.int32(layer)}
    with jax.default_matmul_precision("highest"):
        y, counts = jax.jit(token_choice_experts,
                            static_argnames="first_expert")(
            x, jnp.asarray(ids), jnp.asarray(gates, jnp.float32), *w,
            first_expert=first, **kw)
    here = (ids >= first) & (ids < first + held)
    want = np.zeros((n_rows, d))
    mine = [a if layer is None else a[layer]
            for a in (np.asarray(t, np.float64) for t in w)]
    for n in range(n_rows):
        for e, g in zip(ids[n][here[n]], gates[n][here[n]]):
            a = np.asarray(x[n], np.float64) @ mine[0][e - first]
            h = a / (1.0 + np.exp(-a)) * (np.asarray(x[n], np.float64)
                                          @ mine[1][e - first])
            want[n] += g * (h @ mine[2][e - first])
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    np.testing.assert_array_equal(
        np.asarray(counts),
        np.bincount(ids[here] - first, minlength=held))
    assert 0 < int(counts.sum()) < ids.size         # a share, not the whole
    assert int(counts.sum()) == int(here.sum())


# ------------------- top-10 of 512 at four shares: the qwen3-next cell's k

@pytest.mark.parametrize("n_rows,layer", [(128, None), (128, 5), (7, 1)])
def test_top_10_of_512_at_four_shares(n_rows, layer):
    """A decode step of the qwen3-next cell in small widths: 128 rows, 10
    choices each over 512 experts, of which a chip of the group holds 128
    (experts 0-127 ... 384-511), whole stacks and a layer index as the
    program passes them. The four shares' parts add up to the per-token
    loop over ALL experts, and every choice is counted by exactly one
    share."""
    rng = np.random.default_rng(12)
    held, d, f = 128, 8, 6
    ids, gates = _top_k_routing(rng, n_rows, 512, 10)
    gates = gates / 2.5                                # softmax gates sum to 1
    x = jnp.asarray(rng.normal(size=(n_rows, d)), jnp.float32)
    lead = (512,) if layer is None else (8, 512)
    mk = lambda *s: jnp.asarray(rng.normal(size=lead + s) * 0.3, jnp.float32)
    w = mk(d, f), mk(d, f), mk(f, d)
    kw = {} if layer is None else {"layer": jnp.int32(layer)}
    call = jax.jit(token_choice_experts, static_argnames="first_expert")
    total, counted = 0.0, 0
    with jax.default_matmul_precision("highest"):
        for first in (0, 128, 256, 384):
            share = tuple(t[..., first:first + held, :, :] for t in w)
            y, counts = call(x, jnp.asarray(ids),
                             jnp.asarray(gates, jnp.float32), *share,
                             first_expert=first, **kw)
            here = (ids >= first) & (ids < first + held)
            np.testing.assert_array_equal(
                np.asarray(counts),
                np.bincount(ids[here] - first, minlength=held))
            total, counted = total + np.asarray(y, np.float64), (
                counted + int(counts.sum()))
    assert counted == ids.size                          # every choice, once
    want = np.zeros((n_rows, d))
    mine = [a if layer is None else a[layer]
            for a in (np.asarray(t, np.float64) for t in w)]
    for n in range(n_rows):
        for e, g in zip(ids[n], gates[n]):
            a = np.asarray(x[n], np.float64) @ mine[0][e]
            h = a / (1.0 + np.exp(-a)) * (np.asarray(x[n], np.float64)
                                          @ mine[1][e])
            want[n] += g * (h @ mine[2][e])
    np.testing.assert_allclose(total, want, atol=4e-5)


# -------------- top-8 of 384 with 12 held: the kimi-k2.6 cell's k and share

@pytest.mark.parametrize("first", [0, 372])
@pytest.mark.parametrize("n_rows,layer", [(256, None), (256, 3), (7, 1)])
def test_top_8_of_384_with_12_held(first, n_rows, layer):
    """A decode step of the kimi-k2.6 cell in small widths: 256 rows, 8
    choices each over 384 experts of which this chip holds 12 (the first
    or the last of the group's 32 shares), gates that sum to 2.827, whole
    stacks and a layer index as the program passes them. The held
    choices, and only they (a thirty-second of them), against the
    per-token loop; a row none of whose choices is held gets exactly 0."""
    rng = np.random.default_rng(13)
    held, d, f = 12, 8, 6
    ids, gates = _top_k_routing(rng, n_rows, 384, 8)
    gates = gates * (2.827 / 2.5)
    x = jnp.asarray(rng.normal(size=(n_rows, d)), jnp.float32)
    lead = (held,) if layer is None else (4, held)
    mk = lambda *s: jnp.asarray(rng.normal(size=lead + s) * 0.3, jnp.float32)
    w = mk(d, f), mk(d, f), mk(f, d)
    kw = {} if layer is None else {"layer": jnp.int32(layer)}
    with jax.default_matmul_precision("highest"):
        y, counts = jax.jit(token_choice_experts,
                            static_argnames="first_expert")(
            x, jnp.asarray(ids), jnp.asarray(gates, jnp.float32), *w,
            first_expert=first, **kw)
    here = (ids >= first) & (ids < first + held)
    want = np.zeros((n_rows, d))
    mine = [a if layer is None else a[layer]
            for a in (np.asarray(t, np.float64) for t in w)]
    for n in range(n_rows):
        for e, g in zip(ids[n][here[n]], gates[n][here[n]]):
            a = np.asarray(x[n], np.float64) @ mine[0][e - first]
            h = a / (1.0 + np.exp(-a)) * (np.asarray(x[n], np.float64)
                                          @ mine[1][e - first])
            want[n] += g * (h @ mine[2][e - first])
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5)
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(ids[here] - first, minlength=held))
    assert int(counts.sum()) == int(here.sum()) < ids.size // 8
    unreached = ~here.any(axis=1)
    assert unreached.any() and not np.asarray(y)[unreached].any()


# ---- top-22 of 512, two-matrix relu2 experts in a latent: nemotron-h's

@pytest.mark.parametrize("held,first,n_routed,widths", [
    (128, 0, 512, (8, 6)), (128, 384, 512, (8, 6)), (512, 0, 512, (8, 6)),
    (512, 0, None, (8, 6)), (128, 384, 512, (128, 384))])
@pytest.mark.parametrize("n_rows,layer", [(48, None), (48, 3), (5, 1)])
def test_top_22_of_512_relu2_experts_in_a_latent(monkeypatch, held, first,
                                                 n_routed, widths, n_rows,
                                                 layer):
    """A decode step of the nemotron-3-super cell in small widths: 22
    choices a row over 512 experts of which the chip holds 128 (the
    first or the last share: the held-rows path, at 48 rows) or all 512
    (every choice a row), gates that sum to 5, TWO stacks an expert
    (W_down relu(W_up l)^2) whose width in and out is a latent's (8, of
    a model that the layer never sees), whole stacks and a layer index
    as the program passes them. The held choices, and only they, against
    the per-token float64 loop; a shape the gated form would refuse. At a
    latent of 128 under experts 384 wide both grouped matmuls are the
    repo's kernel's (widths it can tile; told it is on the TPU,
    interpreted here), through the same layer."""
    rng = np.random.default_rng(22)
    d_latent, f = widths
    scale = 0.3 if d_latent == 8 else 0.05
    if d_latent % 128 == 0 and f % 128 == 0:
        monkeypatch.setattr(moe, "_mixed_dot_default", lambda: True)
    ids, gates = _top_k_routing(rng, n_rows, 512, 22)
    gates = gates * 2.0                                 # sum to 5
    latent = jnp.asarray(rng.normal(size=(n_rows, d_latent)), jnp.float32)
    lead = (held,) if layer is None else (5, held)
    mk = lambda *s: jnp.asarray(rng.normal(size=lead + s) * scale,
                                jnp.float32)
    w = _stacks(mk, d_latent, f, "relu2")
    assert len(w) == 2
    kw = {} if layer is None else {"layer": jnp.int32(layer)}
    blocks_of_held = (n_routed == 512 and held == 128 and n_rows == 48)
    assert (moe.block_rows(ids.size, held, n_routed)
            < moe._pad_rows(ids.size + held)) == blocks_of_held
    with jax.default_matmul_precision("highest"):
        y, counts = jax.jit(token_choice_experts,
                            static_argnames=("first_expert", "n_routed"))(
            latent, jnp.asarray(ids), jnp.asarray(gates, jnp.float32), *w,
            first_expert=first, n_routed=n_routed, **kw)
    assert y.shape == (n_rows, d_latent)
    mine = w if layer is None else tuple(t[layer] for t in w)
    np.testing.assert_allclose(
        np.asarray(y), _held_loop(latent, ids, gates, mine, first), atol=4e-5)
    here = (ids >= first) & (ids < first + held)
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(ids[here] - first, minlength=held))
    assert int(counts.sum()) == (ids.size if held == 512 else int(here.sum()))
    with pytest.raises(ValueError, match="three stacks"):
        token_choice_experts(latent, jnp.asarray(ids), jnp.asarray(gates),
                             w[0])


# ---------- told the router's width, the layer carries the held rows only

# 64 rows x 4 choices over 64 experts of which 4 are held: an even router
# sends 16 choices here, so a block is 128 rows (4 zero rows + 124) where
# every choice a row would be 384.
B_N, B_K, B_HELD, B_ROUTED, B_D, B_F = 64, 4, 4, 64, 8, 6
B_ROOM = 124


def _block_case(routing, k=B_K, n_rows=B_N):
    """→ ids [n_rows, k] over B_ROUTED experts; experts 0..3 are held by
    the first share, 4..7 by the second."""
    rng = np.random.default_rng(21)
    away = lambda n: np.stack([8 + rng.permutation(B_ROUTED - 8)[:k]
                               for _ in range(n)])
    if routing == "even":
        ids = np.stack([rng.permutation(B_ROUTED)[:k] for _ in range(n_rows)])
    elif routing == "all_held":         # every choice on the first share
        ids = np.stack([rng.permutation(B_HELD)[:k] for _ in range(n_rows)])
    elif routing in ("room", "room_and_one"):
        ids = away(n_rows)
        ids[:B_ROOM // k] = np.arange(k)                    # 31 x 4 = 124
        if routing == "room_and_one":
            ids[-1, 0] = 2
    elif routing == "two_shares":       # every choice on experts 0..5
        ids = np.stack([rng.permutation(6)[:k] for _ in range(n_rows)])
    elif routing == "one_expert":       # k = 1: every row to expert 1
        ids = np.full((n_rows, 1), 1)
    else:
        raise ValueError(routing)
    return ids.astype(np.int32), rng


def _held_loop(x, ids, gates, w, first, valid=None):
    """The definition, over the choices that land on `first` .. + held."""
    mine = [np.asarray(t, np.float64) for t in w]
    held = mine[0].shape[0]
    want = np.zeros(np.asarray(x).shape)
    for n in range(len(ids)):
        if valid is not None and not valid[n]:
            continue
        for e, g in zip(ids[n], gates[n]):
            if first <= e < first + held:
                want[n] += g * _expert64(x[n], [t[e - first] for t in mine])
    return want


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("routing,k,layer,masked,over", [
    ("even", B_K, None, False, 0),
    ("even", B_K, 2, True, 0),
    ("all_held", B_K, None, False, B_N * B_K - B_ROOM),
    ("all_held", B_K, 1, False, B_N * B_K - B_ROOM),
    ("all_held", B_K, 1, True, None),
    ("room", B_K, None, False, 0),
    ("room_and_one", B_K, None, False, 1),
    ("room_and_one", B_K, 0, False, 1),
    ("one_expert", 1, None, False, B_N * B_K - B_ROOM),
    ("one_expert", 1, 2, True, None),
])
def test_blocks_of_held_rows_match_the_loop_for_any_routing(
        routing, k, layer, masked, over, form):
    """With `n_routed` the layer takes the held choices a block at a
    time: an even router's are one turn; a routing that sends every
    choice to the held experts (the one the loop exists for) takes
    several, at a plain stack and under a traced layer index; the held
    rows exactly fill a block, and pass it by one; rows that carry no
    token; k = 1. Every case against the per-token float64 loop, with the
    counts the full-width layer gives."""
    n_rows = B_N * B_K if k == 1 else B_N
    ids, rng = _block_case(routing, k, n_rows)
    gates = rng.uniform(0.1, 1.0, ids.shape).astype(np.float32)
    x = jnp.asarray(rng.normal(size=(n_rows, B_D)), jnp.float32)
    valid = rng.uniform(size=n_rows) < 0.6 if masked else None
    lead = (B_HELD,) if layer is None else (3, B_HELD)
    mk = lambda *s: jnp.asarray(rng.normal(size=lead + s) * 0.3, jnp.float32)
    w = _stacks(mk, B_D, B_F, form)
    kw = {} if layer is None else {"layer": jnp.int32(layer)}
    if masked:
        kw["valid"] = jnp.asarray(valid)
    assert moe.block_rows(ids.size, B_HELD, B_ROUTED) == 128 < moe._pad_rows(
        ids.size + B_HELD)
    call = jax.jit(token_choice_experts,
                   static_argnames=("first_expert", "n_routed"))
    with jax.default_matmul_precision("highest"):
        y, counts = call(x, jnp.asarray(ids), jnp.asarray(gates), *w,
                         n_routed=B_ROUTED, **kw)
        y_full, counts_full = call(x, jnp.asarray(ids), jnp.asarray(gates),
                                   *w, **kw)
    mine = w if layer is None else tuple(t[layer] for t in w)
    np.testing.assert_allclose(
        np.asarray(y), _held_loop(x, ids, gates, mine, 0, valid), atol=2e-5)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_full), atol=2e-5)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(counts_full))
    here = (ids < B_HELD) & (True if valid is None else valid[:, None])
    np.testing.assert_array_equal(
        np.asarray(counts), np.bincount(ids[here], minlength=B_HELD))
    got = int(moe.rows_over(counts, ids.size, B_ROUTED))
    assert got == max(0, int(here.sum()) - B_ROOM)
    if over is not None:
        assert got == over
    if valid is not None:
        assert not np.asarray(y)[~valid].any()


@pytest.mark.parametrize("form", sorted(FORMS))
@pytest.mark.parametrize("layer", [None, 1])
def test_two_shares_blocks_add_up_to_the_whole_layer(layer, form):
    """Every choice on experts 0..5, a share holding 0..3 (two thirds of
    the choices: more turns than one) and one holding 4..7, each told the
    router's 64: the two parts add up to the loop over all eight."""
    ids, rng = _block_case("two_shares")
    gates = rng.uniform(0.1, 1.0, ids.shape).astype(np.float32)
    x = jnp.asarray(rng.normal(size=(B_N, B_D)), jnp.float32)
    lead = (8,) if layer is None else (2, 8)
    mk = lambda *s: jnp.asarray(rng.normal(size=lead + s) * 0.3, jnp.float32)
    w = _stacks(mk, B_D, B_F, form)
    kw = {} if layer is None else {"layer": jnp.int32(layer)}
    call = jax.jit(token_choice_experts,
                   static_argnames=("first_expert", "n_routed"))
    total, counted = 0.0, 0
    with jax.default_matmul_precision("highest"):
        for first in (0, 4):
            share = tuple(t[..., first:first + 4, :, :] for t in w)
            y, counts = call(x, jnp.asarray(ids), jnp.asarray(gates), *share,
                             first_expert=first, n_routed=B_ROUTED, **kw)
            over = int(moe.rows_over(counts, ids.size, B_ROUTED))
            assert over > 0 if first == 0 else over == 0
            total, counted = (total + np.asarray(y, np.float64),
                              counted + int(counts.sum()))
    assert counted == ids.size
    mine = w if layer is None else tuple(t[layer] for t in w)
    np.testing.assert_allclose(total, _held_loop(x, ids, gates, mine, 0),
                               atol=4e-5)


@pytest.mark.parametrize("n_choices,held,routed,rows,full", [
    (256 * 8, 12, 384, 384, 2176),      # kimi-k2.6.longthink's decode step
    (128 * 8, 16, 256, 384, 1152),      # mimo-v2-flash.think's
    (128 * 10, 128, 512, 896, 1408),    # qwen3-next-80b-a3b.longform's
    (64 * 10, 128, 256, 896, 896),      # laguna-s-2.1.codegen's: every choice
    (64, 16, None, 128, 128),           # zaya1-8b.reason's: holds all
    (64, 16, 16, 128, 128),
    (1024 * 8, 12, 384, 640, 8320),     # a kimi-k2.6 chunk program of 8 rows
    # nemotron-3-super-120b-a12b.subagents' decode step: 8.25 rows an expert
    (192 * 22, 128, 512, 2432, 4480),
    (1024 * 22, 128, 512, 11392, 22656),   # ... and its chunk program of 8 rows
])
def test_block_rows_at_the_cells_shapes(n_choices, held, routed, rows, full):
    assert moe.block_rows(n_choices, held, routed) == rows
    assert moe._pad_rows(n_choices + held) == full
    assert rows % 256 == 128 and rows <= full


# ------- the grouped matmul of the repo's own, and where the layer takes it

def _group_sizes(case, n_layers, live):
    """→ sizes [n_layers * groups] int32, a layer's groups non-empty only
    in layer `live`: groups of 0, 1 and many rows."""
    one = {"few": [0, 1, 5, 0, 3, 1],               # 10 rows in one tile
           "many": [1, 140, 0, 17, 131, 60],        # 349: groups across tiles
           "full": [100, 0, 28, 200, 50, 6],        # 384: no row past them
           "none": [0] * 6,
           # a zero row an expert and little else, as a decode step's are:
           "ones": [1] * 13 + [9, 1, 2],            # 16 groups in one tile
           "mostly_one": ([1] * 5 + [4, 1, 1, 17, 1] + [1] * 6) * 8,  # 128
           }[case]
    sizes = np.zeros(n_layers * len(one), np.int32)
    sizes[live * len(one):(live + 1) * len(one)] = one
    return sizes


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case,n_layers,live", [
    ("few", 1, 0), ("many", 1, 0), ("full", 1, 0), ("none", 1, 0),
    ("few", 3, 1), ("many", 3, 1),
    ("ones", 1, 0),             # 16 groups at 128 rows (zaya's decode step)
    ("ones", 4, 2),             # ... one layer's of a stack, as `_layer_groups`
    ("mostly_one", 2, 1)])      # 128 groups, most of one row (qwen3_next's)
@pytest.mark.parametrize("K,N", [(128, 384), (384, 128), (256, 640)])
def test_the_repos_grouped_matmul_is_ragged_dot(monkeypatch, K, N, case,
                                                n_layers, live, dtype):
    """`moe_grouped_matmul`, interpreted, against `jax.lax.ragged_dot` in
    float32 on the same values: groups of 0, 1 and many rows, groups
    that lie across row tiles, a stack of layers of which only one's
    groups have rows (handed as `moe._layer_groups` hands them), 16
    groups in one tile of 128 rows, 128 groups most of one row; rows
    past the last group may come back holding anything and are not
    compared. A plane wider than the kernel's block budget (here made
    128 x 128 values) is cut along N: the same bits, a part at a time."""
    from ray_tpu.ops import grouped_matmul as gm

    rng = np.random.default_rng(K + N + n_layers)
    sizes = _group_sizes(case, n_layers, live)
    n = int(sizes.sum())
    M = 128 if case == "ones" else 384
    if n_layers > 1:
        stack = jnp.zeros((n_layers, len(sizes) // n_layers, 1, 1))
        one = sizes[sizes.size // n_layers * live:][:stack.shape[1]]
        np.testing.assert_array_equal(
            moe._layer_groups(jnp.asarray(one), jnp.int32(live), stack),
            sizes)
    lhs = jnp.asarray(rng.normal(size=(M, K)), dtype)
    rhs = jnp.asarray(rng.normal(size=(len(sizes), K, N)) * 0.1, dtype)
    with jax.default_matmul_precision("highest"):
        want = jax.lax.ragged_dot(lhs.astype(jnp.float32),
                                  rhs.astype(jnp.float32), jnp.asarray(sizes))
        got = gm.moe_grouped_matmul(lhs, rhs, jnp.asarray(sizes),
                                    interpret=True)
        assert gm._n_tile(K, N, rhs.dtype.itemsize) == N
        monkeypatch.setattr(gm, "_PLANE_BYTES", K * 128 * rhs.dtype.itemsize)
        assert gm._n_tile(K, N, rhs.dtype.itemsize) == 128
        # (the function under its `jit`: a new trace under the new budget)
        in_parts = gm.moe_grouped_matmul.__wrapped__(
            lhs, rhs, jnp.asarray(sizes), interpret=True)
    assert got.shape == (M, N) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got)[:n], np.asarray(want)[:n],
                               atol=2e-5)
    np.testing.assert_array_equal(np.asarray(in_parts)[:n],
                                  np.asarray(got)[:n])


@pytest.mark.parametrize("K,N,parts", [
    (7168, 2048, 4), (2048, 7168, 4),       # kimi-k2.6: 29.4 MB a plane
    (4096, 2048, 2), (2048, 4096, 2),       # mimo-v2-flash: 16.8 MB
    (2048, 2048, 1),                        # zaya1-8b: 8 MB, the budget
    (3072, 1024, 1), (1024, 3072, 1),       # laguna-s-2.1
    (2048, 512, 1), (512, 2048, 1),         # qwen3-next-80b-a3b: 2 MB
    (1024, 2688, 1), (2688, 1024, 1)])      # nemotron-3-super
def test_a_plane_over_the_block_budget_is_cut_along_n(K, N, parts):
    """The weight block the kernel takes at every served plane, in bf16:
    the whole plane where `_PLANE_BYTES` holds it, else the fewest equal
    parts of N, each a multiple of 128 lanes, that it holds."""
    from ray_tpu.ops import grouped_matmul as gm

    tn = gm._n_tile(K, N, 2)
    assert tn * parts == N and tn % 128 == 0
    assert K * tn * 2 <= gm._PLANE_BYTES
    # (no coarser equal cut would have fitted)
    assert all(K * (N // cut) * 2 > gm._PLANE_BYTES
               for cut in range(1, parts) if (N // 128) % cut == 0)


def test_the_kernel_cut_along_n_at_a_plane_over_the_budget():
    """A plane over `_PLANE_BYTES` as the budget stands (a quarter of
    kimi-k2.6's in every dimension but N: [256, 16,384] float32 is 16.8
    MB, cut in 2), interpreted, against `ragged_dot`: twelve groups in
    one tile, a zero row an expert and a handful of others, one layer's
    of a stack of two."""
    from ray_tpu.ops import grouped_matmul as gm

    K, N = 256, 16384
    assert gm._n_tile(K, N, 4) == N // 2
    rng = np.random.default_rng(12)
    one = rng.multinomial(64, np.ones(12) / 12) + 1
    sizes = np.concatenate([np.zeros(12, np.int32), one]).astype(np.int32)
    n = int(sizes.sum())
    lhs = jnp.asarray(rng.normal(size=(128, K)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(24, K, N)) * 0.1, jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = jax.lax.ragged_dot(lhs, rhs, jnp.asarray(sizes))
        got = gm.moe_grouped_matmul(lhs, rhs, jnp.asarray(sizes),
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(got)[:n], np.asarray(want)[:n],
                               atol=2e-5)


def test_the_repos_grouped_matmul_walks_no_empty_group():
    """The kernel's walk: a visit for every (group, row tile) pair with a
    row in it, in row order, none for an empty group or for a tile past
    the last group; rows and shapes it cannot tile are refused."""
    from ray_tpu.ops import grouped_matmul as gm

    sizes = _group_sizes("many", 3, 1)
    group, tile, start, end, count = (
        np.asarray(a) for a in gm.visits(jnp.asarray(sizes), 512))
    assert len(group) == 512 // 128 + len(sizes)
    seen = [(g, t, s, e) for g, t, s, e
            in zip(group, tile, start, end)][:int(count)]
    assert seen == [(6, 0, 0, 1), (7, 0, 1, 128), (7, 1, 128, 141),
                    (9, 1, 141, 158), (10, 1, 158, 256), (10, 2, 256, 289),
                    (11, 2, 289, 349)]
    assert int(gm.visits(jnp.zeros(4, jnp.int32), 256)[-1]) == 0
    bf16 = lambda *shape: jnp.zeros(shape, jnp.bfloat16)
    for lhs, rhs in [(bf16(200, 128), bf16(6, 128, 384)),      # M % 128
                     (bf16(256, 128), bf16(6, 128, 200)),      # N % 128
                     (bf16(256, 64), bf16(6, 64, 384)),        # K % 128
                     (bf16(256, 128), bf16(6, 256, 384))]:     # K != K
        with pytest.raises(ValueError, match="moe_grouped_matmul"):
            gm.moe_grouped_matmul(lhs, rhs, jnp.zeros(6, jnp.int32),
                                  interpret=True)


def _grouped_calls(K, N, rows, groups):
    """The grouped matmuls `_grouped_dot`'s jaxpr holds for a [K, N]
    plane, by shapes alone (no plane is made)."""
    # (a fresh function: the trace of one backend's answer is not the other's)
    text = str(jax.make_jaxpr(lambda *a: moe._grouped_dot(*a))(
        jax.ShapeDtypeStruct((rows, K), jnp.bfloat16),
        jax.ShapeDtypeStruct((groups, K, N), jnp.bfloat16),
        jax.ShapeDtypeStruct((groups,), jnp.int32)))
    return {name for name in ("ragged_dot", "moe_grouped_matmul")
            if name in text}


@pytest.mark.parametrize("K,N,rows,groups,kernel", [
    (1024, 2688, 2432, 640, True),      # nemotron-3-super's up plane
    (2688, 1024, 2432, 640, True),      # ... and its down plane
    (1024, 2688, 11392, 640, True),     # ... in its chunk program
    (128, 384, 384, 6, True), (384, 128, 384, 6, True),
    (256, 640, 384, 6, True),
    (2048, 2048, 128, 16, True),        # zaya1-8b
    (3072, 1024, 896, 128, True), (1024, 3072, 896, 128, True),  # laguna
    (2048, 512, 896, 128, True), (512, 2048, 896, 128, True),  # qwen3_next
    (4096, 2048, 384, 16, True), (2048, 4096, 384, 16, True),  # mimo_v2
    (7168, 2048, 384, 12, True), (2048, 7168, 384, 12, True),  # kimi_k2
    (8, 6, 128, 8, False), (16, 12, 128, 8, False),     # the tiny families'
    (64, 128, 128, 8, False), (1024, 2560, 384, 6, True)])
def test_the_plane_says_which_grouped_matmul_runs(monkeypatch, K, N, rows,
                                                  groups, kernel):
    """`_grouped_dot` on the TPU: the repo's kernel wherever it can tile
    the shapes (rows, K and N multiples of 128): every plane of the six
    served configurations with experts, whatever its width;
    `ragged_dot` at the tiny test families' planes; off the TPU,
    `ragged_dot` whatever the plane."""
    from ray_tpu.ops import grouped_matmul as gm

    assert gm.tiles((rows, K), (groups, K, N)) == kernel
    assert not moe._mixed_dot_default()
    assert _grouped_calls(K, N, rows, groups) == {"ragged_dot"}
    monkeypatch.setattr(moe, "_mixed_dot_default", lambda: True)
    assert _grouped_calls(K, N, rows, groups) == {
        "moe_grouped_matmul" if kernel else "ragged_dot"}
