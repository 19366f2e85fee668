"""Mixture-of-experts layers: the two this repository has.

**`moe_mlp`: the TRAINING layer, with a capacity** (models/moe_gpt.py).
GShard/Switch-style top-2 token-choice routing:

    gates = softmax(x @ wg)            [tokens, E]
    top-2 experts per token, renormalized; tokens beyond an expert's
    capacity C are dropped (their combine weight is 0 → residual passthrough
    at the call site).
    dispatch [G, E, C] one-hot  → expert inputs  [E, C, D]  (einsum)
    expert MLP (stacked weights [E, D, F] / [E, F, D], GELU, biases)
    combine  [G, E, C] weighted → outputs        [G, D]     (einsum)

Everything is dense einsum under jit: the expert axis carries the
logical "expert" sharding (→ `ep` mesh axis, parallel/mesh.py), so XLA
partitions expert compute across `ep` and derives the token all-to-all
from the dispatch/combine einsums' shardings; no hand-written a2a.

**`token_choice_experts`: the SERVING layer, as deployed** (every
served family with experts): no capacity, no dropped token, no
[N, E, C] one-hot. The router is the family's (a softmax or a sigmoid,
a biased choice or not: models/blocks.py and the family modules); this
layer takes its choices and gates, is told which experts of the
router's width this chip HOLDS, sorts the held choices by expert and
runs them as grouped matmuls, returning the held experts' part of the
result. Which grouped matmul, `_grouped_dot` decides by the shapes it is
handed and the backend, nothing else:

    on the TPU; rows, K and N   `moe_grouped_matmul` (ops/grouped_matmul.py),
    multiples of 128            the repo's kernel: a held plane (or the
                                part of its columns 8 MB hold) a DMA, the
                                next in flight while this one multiplies;
                                every served family's planes, 85-90 %
                                of the HBM peak over the held planes in a
                                decode step (PERF.md section 6, PR 65)
    any other plane (the tiny   `jax.lax.ragged_dot`: XLA's own grouped
    test families'); every      matmul, `ragged-dot` in a TPU's trace
    plane off the TPU           (59-74 % there, 27-31 % at 2,688 wide)

An expert is one of two forms, told apart by how many stacks it is
handed:

    three matrices   W_down(silu(W_gate x) * W_up x)   gated SiLU
                     (zaya, laguna, qwen3_next, mimo_v2, kimi_k2)
    two matrices     W_down relu(W_up x)^2             squared ReLU,
                     ungated (nemotron_h)

Either form's width in and out is whatever the stacks say, not the
model's: nemotron_h's experts live in a LATENT of 1,024 under a model
of 4,096 (the projection into the latent before and out of it after is
shared by all experts and is the caller's), so rows enter and leave the
grouped matmuls at 1,024 lanes.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.ops import grouped_matmul, scopes


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int
    n_experts: int
    capacity_factor: float = 1.5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    def capacity(self, n_tokens: int) -> int:
        # top-2 routing: each token lands in up to 2 experts.
        return max(1, math.ceil(
            2 * n_tokens / self.n_experts * self.capacity_factor))


def moe_param_specs(cfg: MoEConfig) -> dict[str, dict[str, Any]]:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "wg": {"shape": (D, E), "axes": ("embed", None),
               "init": "normal", "scale": 0.02},
        "w_up": {"shape": (E, D, F), "axes": ("expert", "embed", "mlp"),
                 "init": "normal", "scale": 0.02},
        "b_up": {"shape": (E, F), "axes": ("expert", "mlp"),
                 "init": "zeros"},
        "w_down": {"shape": (E, F, D), "axes": ("expert", "mlp", "embed"),
                   "init": "normal", "scale": 0.02},
        "b_down": {"shape": (E, D), "axes": ("expert", "embed"),
                   "init": "zeros"},
    }


def init_moe_params(cfg: MoEConfig, rng: jax.Array) -> dict[str, jax.Array]:
    specs = moe_param_specs(cfg)
    keys = jax.random.split(rng, len(specs))
    out = {}
    for key, (name, s) in zip(keys, sorted(specs.items())):
        if s["init"] == "normal":
            out[name] = jax.random.normal(
                key, s["shape"], cfg.param_dtype) * s["scale"]
        else:
            out[name] = jnp.zeros(s["shape"], cfg.param_dtype)
    return out


def moe_logical_axes(cfg: MoEConfig) -> dict[str, tuple]:
    return {k: v["axes"] for k, v in moe_param_specs(cfg).items()}


def _top2_dispatch(gates: jax.Array, capacity: int):
    """gates [G, E] fp32 → (dispatch [G, E, C] bool-ish, combine [G, E, C]).

    Classic GShard construction: per-expert arrival order via cumsum of the
    one-hot assignment; tokens whose slot ≥ capacity are dropped.
    """
    G, E = gates.shape
    idx1 = jnp.argmax(gates, axis=-1)                       # [G]
    mask1 = jax.nn.one_hot(idx1, E, dtype=gates.dtype)      # [G, E]
    gates2 = gates * (1.0 - mask1)
    idx2 = jnp.argmax(gates2, axis=-1)
    mask2 = jax.nn.one_hot(idx2, E, dtype=gates.dtype)

    w1 = jnp.sum(gates * mask1, axis=-1)
    w2 = jnp.sum(gates * mask2, axis=-1)
    denom = jnp.maximum(w1 + w2, 1e-9)
    w1, w2 = w1 / denom, w2 / denom

    # Slot index = arrival position within the expert (top-1 routes fill
    # before top-2 routes, matching GShard).
    pos1 = jnp.cumsum(mask1, axis=0) - mask1                # [G, E]
    pos2 = jnp.cumsum(mask2, axis=0) - mask2 + jnp.sum(mask1, axis=0)
    slot1 = jnp.sum(pos1 * mask1, axis=-1)                  # [G]
    slot2 = jnp.sum(pos2 * mask2, axis=-1)
    keep1 = slot1 < capacity
    keep2 = slot2 < capacity

    oh_slot1 = jax.nn.one_hot(slot1, capacity, dtype=gates.dtype)
    oh_slot2 = jax.nn.one_hot(slot2, capacity, dtype=gates.dtype)
    d1 = mask1[:, :, None] * oh_slot1[:, None, :] * keep1[:, None, None]
    d2 = mask2[:, :, None] * oh_slot2[:, None, :] * keep2[:, None, None]
    dispatch = d1 + d2                                      # [G, E, C]
    combine = d1 * w1[:, None, None] + d2 * w2[:, None, None]
    return dispatch, combine


def moe_mlp(x: jax.Array, params: dict[str, jax.Array],
            cfg: MoEConfig) -> tuple[jax.Array, jax.Array]:
    """x [B, S, D] → (y [B, S, D], aux_loss scalar).

    aux_loss is the standard load-balancing loss (mean fraction routed ×
    mean gate prob per expert × E) — add `aux * coef` to the model loss.
    """
    B, S, D = x.shape
    G = B * S
    xf = x.reshape(G, D)
    gates = jax.nn.softmax(
        jnp.einsum("gd,de->ge", xf.astype(jnp.float32),
                   params["wg"].astype(jnp.float32)), axis=-1)
    C = cfg.capacity(G)
    dispatch, combine = _top2_dispatch(gates, C)
    # Token → expert slots (XLA turns the resharding from token-sharded xf
    # to expert-sharded slots into the a2a).
    expert_in = jnp.einsum(
        "gec,gd->ecd", dispatch.astype(cfg.dtype), xf.astype(cfg.dtype))
    up = jnp.einsum("ecd,edf->ecf", expert_in,
                    params["w_up"].astype(cfg.dtype))
    up = jax.nn.gelu(up + params["b_up"].astype(cfg.dtype)[:, None, :])
    down = jnp.einsum("ecf,efd->ecd", up,
                      params["w_down"].astype(cfg.dtype))
    down = down + params["b_down"].astype(cfg.dtype)[:, None, :]
    y = jnp.einsum("gec,ecd->gd", combine.astype(cfg.dtype), down)
    # Load-balance aux loss (Switch Transformer eq. 4).
    frac_routed = jnp.mean(
        jax.nn.one_hot(jnp.argmax(gates, -1), cfg.n_experts), axis=0)
    mean_gate = jnp.mean(gates, axis=0)
    aux = cfg.n_experts * jnp.sum(frac_routed * mean_gate)
    return y.reshape(B, S, D), aux


# --------------------------------------------------------------------------
# Token-choice experts as deployed: no capacity, no dropped token.
#
# The serving form of an expert layer. Every (row, choice) assignment is
# kept: rows are sorted by expert, the experts THIS chip holds run as one
# grouped matmul over their contiguous row groups (`_grouped_dot`: the
# repo's `moe_grouped_matmul` on the TPU, `jax.lax.ragged_dot` off it
# and for a plane the kernel cannot tile), the result is unsorted and
# scaled by the gate. No [N, E, C]
# one-hot exists and nothing depends on a capacity. The layer is told
# which experts it holds (`first_expert` .. + the weights' leading axis)
# and returns only their part, so the parts of chips holding disjoint
# ranges add up to the whole layer (tests/test_moe_token_choice.py).
# Told the router's width too (`n_routed`), it CARRIES only their part:
# a chip with 12 of 384 experts gathers, multiplies and combines the
# ~3 % of the choices that land on them, a block of rows at a time
# (`block_rows`), and not a row for every choice.

def _mixed_dot_default() -> bool:
    """Whether the backend is the TPU: it multiplies bf16 groups into a
    float32 result (XLA:CPU has no such thunk for a ragged dot), and the
    repo's grouped matmul is compiled for it."""
    return jax.default_backend() == "tpu"


def _pad_rows(n: int) -> int:
    """The smallest odd multiple of 128 that holds n rows."""
    tiles = -(-n // 128)
    return 128 * (tiles + 1 - tiles % 2)


def _grouped_dot(lhs, rhs, sizes):
    """Rows of `lhs` [M, K], in contiguous groups of `sizes`, each
    against its group's `rhs[g]` [K, N], accumulated to float32. On the
    TPU the repo's kernel (`moe_grouped_matmul`) wherever it can tile
    the shapes (rows, K and N multiples of 128: every served family's);
    any other plane, and every plane off the TPU, goes to
    `jax.lax.ragged_dot`. Off-TPU, narrower operands go up to float32
    first: the same values and exact products, so the same sums."""
    if _mixed_dot_default() and grouped_matmul.tiles(lhs.shape, rhs.shape):
        return grouped_matmul.moe_grouped_matmul(lhs, rhs, sizes)
    if lhs.dtype != jnp.float32 and not _mixed_dot_default():
        lhs, rhs = lhs.astype(jnp.float32), rhs.astype(jnp.float32)
    return jax.lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32)


# What a block holds beyond its zero rows, in the rows an even router
# sends a chip's share (N * k * E / n_routed): 2. A trained router's load
# on one share swings by tens of percent between steps, not by a factor
# (`expert_rows_max`, the fullest expert over the mean, reads 1.8 where
# an expert's mean is 5 rows); a share that takes over twice its part is
# served exactly, in one more turn over its experts.
_BLOCK_OF_EVEN = 2


def block_rows(n_choices: int, n_held: int, n_routed: int | None) -> int:
    """Rows of one block of `token_choice_experts`: the rows its grouped
    matmuls take in one turn, for `n_choices` (rows x k) choices over
    `n_routed` experts of which `n_held` are held. `n_routed` None, or
    no more than `n_held`: every choice may be held, and the block is
    every choice and a zero row an expert. Else `_BLOCK_OF_EVEN` times
    the held choices of an even router and a zero row an expert, never
    more than every choice. An odd multiple of 128 either way."""
    full = _pad_rows(n_choices + n_held)
    if n_routed is None or n_routed <= n_held:
        return full
    even = -(-n_choices * n_held // n_routed)
    return min(full, _pad_rows(n_held + _BLOCK_OF_EVEN * even))


def rows_over(counts: jax.Array, n_choices: int,
              n_routed: int | None) -> jax.Array:
    """The held choices `token_choice_experts`' first block did not take
    (0: one turn sufficed), for its `counts` [E_held]. → uint32."""
    E = counts.shape[0]
    room = block_rows(n_choices, E, n_routed) - E
    return jnp.maximum(jnp.sum(counts) - room, 0).astype(jnp.uint32)


def token_choice_experts(x: jax.Array, expert_ids: jax.Array,
                         gates: jax.Array, *weights: jax.Array,
                         first_expert: int = 0, layer=None, valid=None,
                         n_routed: int | None = None):
    """Experts over token-choice routing, the held part.

    x [N, D]; expert_ids [N] or [N, k] int32 (global expert ids, a row's
    k choices); gates like expert_ids (float32 weights); `weights` the
    held experts' stacks, experts ``first_expert`` .. ``first_expert +
    E_held - 1``: THREE, w_gate / w_up [E_held, D, F] and w_down
    [E_held, F, D], are gated-SiLU experts; TWO, w_up [E_held, D, F] and
    w_down [E_held, F, D], are squared-ReLU experts without a gate. D is
    the experts' own width in and out (a latent's, where the caller
    projects into one). `valid` [N] bool (optional): rows that
    carry a token; the others reach no expert. `n_routed` (static): the
    experts the router chose among, of which these are the held; None:
    every choice may be held.

    With `layer` (an index, traced inside a layer scan) the weights are
    the WHOLE stacks [L, E_held, ...]: the stack is handed to the grouped
    matmul as L * E_held groups of which only this layer's have rows, so
    no layer's experts are ever sliced out of it (a custom call's operand
    would be a copy of them, a layer's worth of bytes a step).

    Every held expert gets one extra all-zero row, so each group is
    non-empty and the grouped matmul reads every held expert's weights
    whatever the routing: a decode step's time does not move with how
    many experts a handful of rows happened to reach (a trained router
    reaches nearly all of them). The zero rows contribute exactly 0.

    Only a choice that lands on a held expert becomes a row. The held
    choices, sorted by expert, are taken `block_rows` at a time: where
    the block is every choice (a chip that holds every expert, or half
    of them under top-10) the layer is one straight pass; where it is
    less, as many turns of ONE loop body as the held rows need (one, for
    any routing near an even one), each over its own part of every
    expert's group, summed in float32. No routing drops a row.

    → (y [N, D] in x.dtype: Σ over a row's choices that land on a held
    expert of gate · Expert(x), zero for the rest;
    counts [E_held] int32: rows each held expert received)."""
    if len(weights) not in (2, 3):
        raise ValueError("an expert is three stacks (gated SiLU) or two "
                         f"(squared ReLU); got {len(weights)}")
    N, D = x.shape
    E = weights[0].shape[0 if layer is None else 1]
    with jax.named_scope(scopes.MOE_ROUTE):
        ids = expert_ids.reshape(N, -1)
    k = ids.shape[1]
    block = block_rows(N * k, E, n_routed)
    kw = dict(first_expert=first_expert, layer=layer, valid=valid)
    if block == _pad_rows(N * k + E):
        return _every_choice_a_row(x, ids, gates, weights, **kw)
    return _held_rows_in_blocks(x, ids, gates, weights, block=block, **kw)


def _held_choices(ids, n_held: int, first_expert: int, valid):
    """ids [N, k] → (local [N * k] int32: a choice's expert among the
    held, `n_held` for a choice that reaches none of them, which sorts
    last; counts [n_held] int32: rows each held expert received)."""
    E, k = n_held, ids.shape[1]
    local = ids.reshape(-1).astype(jnp.int32) - first_expert
    held = (local >= 0) & (local < E)
    if valid is not None:
        held &= jnp.repeat(valid, k)
    local = jnp.where(held, local, E)
    counts = jnp.zeros(E + 1, jnp.int32).at[local].add(1)[:E]
    return local, counts


def _layer_groups(sizes, layer, stack):
    """A layer's group sizes among the whole `stack`'s L * E groups."""
    if layer is None:
        return sizes
    n_layers, E = stack.shape[:2]
    return jax.lax.dynamic_update_slice(
        jnp.zeros(n_layers * E, jnp.int32), sizes, (layer * E,))


def _experts(rows, sizes, weights, layer):
    """rows [M, D] in contiguous groups of `sizes` → [M, D] float32:
    three stacks a gated-SiLU expert, two a squared-ReLU one."""
    with jax.named_scope(scopes.MOE_EXPERTS):
        if layer is not None:
            weights = tuple(w.reshape((-1,) + w.shape[2:]) for w in weights)
        dot = functools.partial(_grouped_dot, sizes=sizes)
        if len(weights) == 2:
            w_up, w_down = weights
            h = jnp.square(jax.nn.relu(dot(rows, w_up))).astype(rows.dtype)
            return dot(h, w_down)
        w_gate, w_up, w_down = weights
        h = (jax.nn.silu(dot(rows, w_gate))
             * dot(rows, w_up)).astype(rows.dtype)
        return dot(h, w_down)


def _every_choice_a_row(x, ids, gates, weights, *, first_expert, layer,
                        valid):
    """`token_choice_experts` where any choice may be held: one pass over
    every choice, the ones that are not held sorted past the groups."""
    (N, D), k = x.shape, ids.shape[1]
    E = weights[0].shape[0 if layer is None else 1]
    with jax.named_scope(scopes.MOE_ROUTE):
        local, counts = _held_choices(ids, E, first_expert, valid)
        rows = jnp.repeat(x, k, axis=0) if k > 1 else x
        local = jnp.concatenate([local, jnp.arange(E, dtype=jnp.int32)])
        rows = jnp.concatenate([rows, jnp.zeros((E, D), x.dtype)])
        sizes = counts + 1
        # XLA:TPU tiles the rows of a grouped matmul by the largest power
        # of two that divides their number: 16 for a decode step's 80
        # rows, so an expert's group straddles tiles and its weights are
        # read for each (1.25x the bytes; 2x at a chunk's 272 rows). An
        # ODD multiple of 128 rows keeps the tile at 128, the MXU's
        # width: empty rows that belong to no group are appended to get
        # there.
        pad = _pad_rows(local.shape[0]) - local.shape[0]
        local = jnp.concatenate([local, jnp.full(pad, E, jnp.int32)])
        rows = jnp.concatenate([rows, jnp.zeros((pad, D), x.dtype)])
        order = jnp.argsort(local, stable=True)
        rows = rows[order]
        sizes = _layer_groups(sizes, layer, weights[0])
    out = _experts(rows, sizes, weights, layer)          # [M, D] fp32
    with jax.named_scope(scopes.MOE_ROUTE):
        # Rows past the held groups belong to no group: whatever the
        # grouped matmul left there is dropped here.
        out = jnp.where((local[order] < E)[:, None], out, 0.0)
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.size))
        out = out[inverse[:N * k]]                          # unsort
        y = jnp.sum(out.reshape(N, k, D)
                    * gates.reshape(N, k, 1).astype(jnp.float32), axis=1)
        return y.astype(x.dtype), counts


def _held_rows_in_blocks(x, ids, gates, weights, *, first_expert, layer,
                         valid, block: int):
    """`token_choice_experts` where most choices are held elsewhere: the
    held choices alone, sorted by expert with every expert's zero row
    behind its own, `block` sorted rows a turn.

    A turn gathers its rows of `x` by token, runs the grouped matmuls
    over the part of every expert's group that lies in it (the group
    ends clipped to the block), scales a row by its choice's gate and
    adds it to its token's sum as a [N, block] one-hot times the rows on
    the MXU, in float32 at the highest precision. Any routing near an
    even one is ONE turn; one that sends this share more than the block
    holds takes the turns it needs over the same body, each streaming
    the experts whose rows it carries."""
    (N, D), k = x.shape, ids.shape[1]
    E, R, n = weights[0].shape[0 if layer is None else 1], block, N * k
    with jax.named_scope(scopes.MOE_ROUTE):
        local, counts = _held_choices(ids, E, first_expert, valid)
        order = jnp.argsort(
            jnp.concatenate([local, jnp.arange(E, dtype=jnp.int32)]),
            stable=True)                # sorted row → choice; >= n: zero row
        # A whole number of blocks, so that no turn's slice is clamped.
        order = jnp.concatenate(
            [order, jnp.full(-(n + E) % R, n, order.dtype)])
        ends = jnp.cumsum(counts + 1)
        starts, in_groups = ends - (counts + 1), ends[-1]
        gate_of = gates.reshape(-1).astype(jnp.float32)

    def turn(t, y):
        with jax.named_scope(scopes.MOE_ROUTE):
            lo = t * R
            choice = jax.lax.dynamic_slice(order, (lo,), (R,))
            live = (lo + jnp.arange(R) < in_groups) & (choice < n)
            choice = jnp.where(live, choice, 0)
            token = choice // k
            rows = jnp.where(live[:, None], x[token], 0)
            sizes = _layer_groups(
                jnp.clip(ends, lo, lo + R) - jnp.clip(starts, lo, lo + R),
                layer, weights[0])
        out = _experts(rows, sizes, weights, layer)      # [R, D] fp32
        with jax.named_scope(scopes.MOE_ROUTE):
            # A zero row, and a row past the groups (whatever the grouped
            # matmul left there), add nothing.
            out = jnp.where(live[:, None],
                            out * gate_of[choice][:, None], 0.0)
            onto = (token == jnp.arange(N)[:, None]) & live     # [N, R]
            return y + jnp.dot(onto.astype(jnp.float32), out,
                               precision=jax.lax.Precision.HIGHEST)

    with jax.named_scope(scopes.MOE_ROUTE):
        turns, none = -(-in_groups // R), jnp.zeros((N, D), jnp.float32)
    y = jax.lax.fori_loop(0, turns, turn, none)
    with jax.named_scope(scopes.MOE_ROUTE):
        return y.astype(x.dtype), counts
