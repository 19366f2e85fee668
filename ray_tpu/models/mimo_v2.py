"""MiMo-V2-class decoder: K heads wider than V heads, a learned sink in
the window layers' softmax, a KV head count a layer kind, and a router
that chooses by a biased score and gates by the unbiased one.

A block of its own beside models/laguna.py, whose ring of window pages,
pool builder and paged walk it imports (one rule for both families).
Source: the model's config.json (`model_type: mimo_v2_flash`);
benchmarks/configs/mimo-v2-flash.json lists what it fixes and what is
assumed. D model width, H query heads, Kq the K (and q) head size, Kv
the V head size; layer l is a FULL layer (G = `n_kv_heads` KV heads,
every earlier key) or a WINDOW layer (G = `n_kv_heads_window`, the last
`window` keys, the query's own position counted), by `layer_types`:

  x <- x + Attn(RMSNorm(x));  x <- x + MLP(RMSNorm(x));  no bias anywhere
  Attn   u the normed input;  q = u W_q (H x Kq), k = u W_k (G x Kq),
         v = `value_scale` * u W_v (G x Kv);  rotate-half rope on the
         first `rotary_dim` dims of q and k, theta `rope_theta` (full) or
         `rope_theta_window`;  query head h reads KV head h // (H / G);
         scores a_ij = q_i . k_j * Kq^-1/2;  full: p = softmax(a);
         window: p_ij = exp(a_ij) / (exp(s_h) + sum_j' exp(a_ij')), s_h
         one learned logit a query head (`w_sink`): in the denominator,
         with no value row;  o_i = sum_j p_ij v_j (Kv wide);  then W_o
         (H x Kv -> D).
  MLP    a dense layer (`dense_layers`): W_down(silu(W_gate u) * W_up u).
         a sparse layer: s = sigmoid(u W_r) in float32 over all
         `n_experts_routed` experts; the `top_k` largest of s + b CHOOSE
         (b the `router_bias`, used for the choice only);
         gate_e = s_e / sum of the chosen s;
         MLP(u) = sum over the chosen e of gate_e Expert_e(u), every one
         a gated-SiLU MLP; no shared expert.
  final RMSNorm, then an untied head.

**One chip's share**, as models/laguna.py: the weights hold `n_experts`
of the routed experts (`first_expert` ..) and `vocab_size` rows of the
vocabulary; the router scores and chooses over all `n_experts_routed`,
`ops.moe.token_choice_experts` returns the held experts' part, and no
exchange is built (the routed parts of all shares summed ARE the whole
layer: tests/test_mimo_v2.py).

**Four planes of four widths in one pool pytree** (`laguna.ring_pool`):
``pool["k"]`` ``[n_full, P+1, page, G_f*Kq]`` and ``pool["v"]``
``[.., G_f*Kv]`` by the engine's page tables; ``pool["k_win"]``
``[n_window, (n_slots+1)*R, page, G_w*Kq]`` and ``pool["v_win"]``
``[.., G_w*Kv]`` in a ring of R pages a slot. A K head's lanes lie head
after head, unpadded (192 a head: 1.5 lane tiles), which the kernels
take (ops/paged_attention.py).

The decode programs' counters are laguna's five and `rows_bias_moved`:
choices among the top-k by s + b that are not among the top-k by s.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, ClassVar

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import blocks, laguna
from ray_tpu.models.blocks import (gated_mlp, last_token_logits, rms_norm,
                                   untied_head, write_kv)
from ray_tpu.models.laguna import PLANES, ring_pages
from ray_tpu.models.paged_kv import paged_programs
from ray_tpu.ops import scopes
from ray_tpu.ops.moe import token_choice_experts

_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class MiMoV2Config:
    vocab_size: int = 152576         # rows of embedding and head held here
    d_model: int = 4096
    n_layers: int = 48
    n_heads: int = 64                # query heads, both layer kinds
    n_kv_heads: int = 4              # KV heads of a full layer
    n_kv_heads_window: int = 8       # KV heads of a window layer
    head_dim: int = 192              # q and K
    v_head_dim: int = 128
    value_scale: float = 0.707
    d_ff_dense: int = 16384          # the dense layers' MLP width
    n_experts: int = 256             # routed experts HELD here
    n_experts_routed: int = 256      # the router's outputs
    first_expert: int = 0            # the first held expert's global id
    top_k: int = 8
    d_ff: int = 2048                 # one routed expert's width
    window: int = 128
    # "full" / "window" a layer; () is the model's own pattern: full at
    # layer 0 and at every sixth from layer 5.
    layer_types: tuple = ()
    dense_layers: tuple = (0,)
    # Layer kinds whose softmax carries a learned sink a query head.
    sink_kinds: tuple = ("window",)
    rope_theta: float = 5_000_000.0  # full layers
    rope_theta_window: float = 10_000.0
    rotary_dim: int = 64             # dims of q and k that get rotary
    norm_eps: float = 1e-5
    max_seq: int = 6144
    dtype: Any = jnp.bfloat16        # activation/compute dtype
    param_dtype: Any = jnp.float32

    family: ClassVar[str] = "mimo_v2"  # models/serving.py

    @classmethod
    def tiny(cls, **kw) -> "MiMoV2Config":
        """CPU-test size that keeps the pattern: dense layer 0 (full),
        window layers, a full one; K heads 1.5 x V heads; 2 and 4 KV
        heads under 8 query heads; 8 experts top-3 with 4 held."""
        base = dict(vocab_size=256, d_model=64, n_layers=5, n_heads=8,
                    n_kv_heads=2, n_kv_heads_window=4, head_dim=24,
                    v_head_dim=16, d_ff_dense=128, n_experts=4,
                    n_experts_routed=8, top_k=3, d_ff=32, window=32,
                    layer_types=("full", "window", "window", "full",
                                 "window"),
                    rope_theta=50_000.0, rotary_dim=8, max_seq=256)
        return cls(**{**base, **kw})

    @property
    def kinds(self) -> tuple:
        """"full" or "window" for each of the n_layers layers."""
        if self.layer_types:
            return tuple(self.layer_types[:self.n_layers])
        return tuple("full" if l == 0 or l % 6 == 5 else "window"
                     for l in range(self.n_layers))

    def kv_heads(self, kind: str) -> int:
        return self.n_kv_heads if kind == "full" else self.n_kv_heads_window

    count = laguna.LagunaConfig.count
    index = laguna.LagunaConfig.index


# What the sinks and the router's bias are seeded at (normal): a sink of
# that size is a visible share of a window's denominator at test size,
# and the bias moves ~3 % of the top-8 choices of 256 at the published
# widths (benchmarks/configs/mimo-v2-flash.json `assumed`).
_SINK_SCALE, _ROUTER_BIAS_SCALE = 1.0, 0.002

# The experts' stacks, handed whole to the grouped matmul. A layer kind's
# attention leaves carry the prefixes "f_" (full) and "w_" (window).
_EXPERT_KEYS = ("w_gate", "w_up", "w_down")


def param_specs(cfg: MiMoV2Config) -> dict[str, dict[str, Any]]:
    """name → {shape, init[, scale]}: one stack a layer kind and MLP
    kind, in layer order within the kind. The sinks (`<kind>_sink`, one
    logit a query head) and the router's bias are seeded normal, so that
    a program that drops either is visibly wrong."""
    D, H, Kq, Kv, V, L = (cfg.d_model, cfg.n_heads, cfg.head_dim,
                          cfg.v_head_dim, cfg.vocab_size, cfg.n_layers)
    norm = lambda *s, scale=0.02: {"init": "normal", "scale": scale,
                                   "shape": s}
    resid = lambda *s: norm(*s, scale=0.02 / math.sqrt(2 * L))
    ones = lambda *s: {"init": "ones", "shape": s}
    specs = {"wte": norm(V, D), "lm_head": norm(D, V), "ln_f_scale": ones(D),
             "ln1_scale": ones(L, D), "ln2_scale": ones(L, D)}
    for kind in ("full", "window"):
        n, G, p = cfg.count(kind), cfg.kv_heads(kind), kind[0] + "_"
        specs.update({
            p + "wq": norm(n, D, H * Kq), p + "wk": norm(n, D, G * Kq),
            p + "wv": norm(n, D, G * Kv), p + "wo": resid(n, H * Kv, D)})
        if kind in cfg.sink_kinds:
            specs[p + "sink"] = norm(n, H, scale=_SINK_SCALE)
    nd, ns = cfg.count("dense"), cfg.count("sparse")
    E, F, Fd = cfg.n_experts, cfg.d_ff, cfg.d_ff_dense
    specs.update({
        "d_gate": norm(nd, D, Fd), "d_up": norm(nd, D, Fd),
        "d_down": resid(nd, Fd, D),
        "router": norm(ns, D, cfg.n_experts_routed),
        "router_bias": norm(ns, cfg.n_experts_routed,
                            scale=_ROUTER_BIAS_SCALE),
        "w_gate": norm(ns, E, D, F), "w_up": norm(ns, E, D, F),
        "w_down": resid(ns, E, F, D)})
    return specs


partition_rules = laguna.partition_rules


def init_params(cfg: MiMoV2Config, rng: jax.Array) -> dict[str, jax.Array]:
    return laguna.init_from_specs(param_specs(cfg), rng, cfg.param_dtype)


# ------------------------------------------------------------- the block

def _inv_freq(cfg: MiMoV2Config, kind: str) -> np.ndarray:
    """A layer kind's rotary frequencies [rotary_dim / 2]."""
    theta = cfg.rope_theta if kind == "full" else cfg.rope_theta_window
    d = cfg.rotary_dim
    return float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)


def _rope(x, pos, inv_freq: np.ndarray, head_dim: int):
    """Rotate-half rotary on the first 2 * len(inv_freq) dims of each
    head, on the heads as they leave the projection: x [N, C, h*K]
    float32, pos [N, C] absolute positions. No head is cut out of the
    minor axis (a 192-wide head is 1.5 lane tiles: cutting `[.., h, 192]`
    into its rotary halves cost 0.38 ms a layer of a 128-row decode step
    on the chip, a seventh of the step: PERF.md, PR 48): a lane's partner
    is half a rotary width to its right or left, so the rotation is two
    rolls of the whole row, and a lane past the rotary dims turns by an
    angle of 0."""
    half = len(inv_freq)
    d = np.arange(x.shape[-1]) % head_dim
    freq = np.where(d < 2 * half, np.tile(inv_freq, 2)[d % (2 * half)], 0.0)
    sign = np.where(d < half, -1.0, np.where(d < 2 * half, 1.0, 0.0))
    ang = pos.astype(_F32)[..., None] * jnp.asarray(freq, _F32)
    partner = jnp.where(jnp.asarray(d < half), jnp.roll(x, -half, axis=-1),
                        jnp.roll(x, half, axis=-1))
    return x * jnp.cos(ang) + partner * (jnp.asarray(sign, _F32)
                                         * jnp.sin(ang))


def _sink(cfg: MiMoV2Config, params, kind: str, i: int):
    """The [H] float32 sink logits of a kind's layer i, or None."""
    if kind not in cfg.sink_kinds:
        return None
    return params[kind[0] + "_sink"][i].astype(_F32)


@jax.named_scope(scopes.ATTN_IN)
def _attn_inputs(cfg: MiMoV2Config, params, l: int, x, pos):
    """Layer l's attention sublayer up to q, k, v. x [N, C, D], pos
    [N, C] → (q [N, C, H, Kq], k [N, C, G, Kq], v [N, C, G, Kv], all in
    cfg.dtype; v already scaled)."""
    N, C, _D = x.shape
    kind, i, _mlp, _j = cfg.index(l)
    H, G, dt = cfg.n_heads, cfg.kv_heads(kind), cfg.dtype
    w = lambda name: params[kind[0] + "_" + name][i].astype(dt)
    u = rms_norm(x, params["ln1_scale"][l], cfg.norm_eps)
    inv_freq = _inv_freq(cfg, kind)
    rope = lambda t, h: _rope(t.astype(_F32), pos, inv_freq,
                              cfg.head_dim).astype(dt).reshape(
        N, C, h, cfg.head_dim)
    q, k = rope(u @ w("wq"), H), rope(u @ w("wk"), G)
    v = ((u @ w("wv")).astype(_F32) * cfg.value_scale).astype(dt)
    return q, k, v.reshape(N, C, G, cfg.v_head_dim)


# The router that chooses by s + b and gates by s (models/kimi_k2.py is
# its other user).
_route = blocks.biased_route


def _finish_block(cfg: MiMoV2Config, params, l: int, x, attn, valid):
    """From the attention output to layer l's end. attn [N, C, H, Kv],
    valid [N, C] bool (rows that carry a token: the others reach no
    expert).
    → (x, (counts [n_experts] int32 rows each held expert received,
    the valid rows' choices the bias moved) or None in a dense layer)."""
    N, C, D = x.shape
    dt = cfg.dtype
    kind, i, mlp, j = cfg.index(l)
    with jax.named_scope(scopes.ATTN_OUT):
        x = x + attn.reshape(N, C, -1) @ params[kind[0] + "_wo"][i].astype(dt)
    with jax.named_scope(scopes.MLP):
        u = rms_norm(x, params["ln2_scale"][l],
                     cfg.norm_eps).reshape(N * C, D)
        if mlp == "dense":
            f = gated_mlp(u, params["d_gate"][j], params["d_up"][j],
                          params["d_down"][j])
            return x + f.astype(dt).reshape(N, C, D), None
    chosen, gates, moved = _route(cfg, params["router"][j],
                                  params["router_bias"][j], u)
    with jax.named_scope(scopes.MOE_EXPERTS):
        experts = tuple(params[k].astype(dt) for k in _EXPERT_KEYS)
    routed, counts = token_choice_experts(
        u, chosen, gates, *experts,
        first_expert=cfg.first_expert, layer=j, valid=valid.reshape(-1),
        n_routed=cfg.n_experts_routed)
    with jax.named_scope(scopes.COUNTERS):
        moved = jnp.sum(jnp.where(valid.reshape(-1), moved, 0))
    with jax.named_scope(scopes.MLP):
        return x + routed.astype(dt).reshape(N, C, D), (counts, moved)


_head = functools.partial(untied_head, rms_norm)


# ------------------------------------------ full sequence (tests, no cache)

def forward(cfg: MiMoV2Config, params, tokens):
    """tokens [B, S] → logits [B, S, V] float32: every row a whole
    sequence from position 0, plain masked attention, no pool."""
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    ahead = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]     # i - j
    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens]
    for l, kind in enumerate(cfg.kinds):
        q, k, v = _attn_inputs(cfg, params, l, x, pos)
        with jax.named_scope(scopes.ATTN_KERNEL):
            g = cfg.n_heads // cfg.kv_heads(kind)
            k, v = (jnp.repeat(t, g, axis=2) for t in (k, v))
            seen = ahead >= 0
            if kind == "window":
                seen &= ahead < cfg.window
            s = jnp.einsum("bshk,bthk->bhst", q, k,
                           preferred_element_type=_F32)
            s = jnp.where(seen[None, None],
                          s / math.sqrt(cfg.head_dim), -1e30)
            sink = _sink(cfg, params, kind, cfg.index(l)[1])
            if sink is not None:    # a key with no value row
                s = jnp.concatenate(
                    [s, jnp.broadcast_to(sink[None, :, None, None],
                                         (B, cfg.n_heads, S, 1))], axis=-1)
            p = jax.nn.softmax(s, axis=-1)[..., :S].astype(cfg.dtype)
            attn = jnp.einsum("bhst,bthk->bshk", p, v)
        x, _counts = _finish_block(cfg, params, l, x, attn,
                                   jnp.ones((B, S), bool))
    return _head(cfg, params, x)


# --------------------------------------------------------- the paged pool

# Running totals over decode steps, wrapping uint32 (the host takes
# differences): laguna's five, and the choices the router's bias moved.
COUNTERS = blocks.COUNTERS_BIASED


def init_paged_kv(cfg: MiMoV2Config, n_pages: int, page_size: int,
                  n_slots: int, kv_dtype: str | None = None, *,
                  dispatch_tokens: int):
    """`laguna.ring_pool` at this family's four widths: a kind's KV
    heads x the K or the V head size."""
    if kv_dtype not in (None, "bf16"):
        raise ValueError(
            f"the mimo_v2 family's pool is bf16, got {kv_dtype!r}")
    lanes = {name: cfg.kv_heads(kind) * size
             for kind, names in PLANES.items()
             for name, size in zip(names, (cfg.head_dim, cfg.v_head_dim))}
    return laguna.ring_pool(cfg, n_pages, page_size, n_slots,
                            dispatch_tokens, lanes, len(COUNTERS))


def _paged_layers(cfg: MiMoV2Config, params, x, pos, valid, pool, attend,
                  full, ring):
    """`laguna._paged_layers` for this block: a window layer's reader is
    also handed its sinks."""
    ps = pool["k"].shape[2]
    offs = (pos % ps).reshape(-1)
    counts = []
    for l, kind in enumerate(cfg.kinds):
        i = cfg.index(l)[1]
        pages, table, kw = full if kind == "full" else ring
        q, k, v = _attn_inputs(cfg, params, l, x, pos)
        kn, vn = PLANES[kind]
        pool = write_kv(pool, i, pages, offs, k, v, (kn, vn))
        with jax.named_scope(scopes.ATTN_KERNEL):
            attn = attend(q, pool[kn], pool[vn], i, table,
                          sink=_sink(cfg, params, kind, i), **kw)
        x, n = _finish_block(cfg, params, l, x, attn, valid)
        if n is not None:
            counts.append(n)
    return x, pool, counts


_count = blocks.counter_row_biased


# laguna's two forwards over this block's walk and counter row.
(prefill_chunk_paged, decode_step_paged, _decode_sample_paged,
 decode_multi_paged) = paged_programs(
    functools.partial(laguna.chunk_forward, layers=_paged_layers),
    functools.partial(laguna.decode_once, layers=_paged_layers, count=_count),
    last_token_logits(_head), COUNTERS)


__all__ = [
    "MiMoV2Config", "param_specs", "partition_rules", "init_params",
    "forward", "init_paged_kv", "ring_pages", "prefill_chunk_paged",
    "decode_step_paged", "decode_multi_paged",
]
