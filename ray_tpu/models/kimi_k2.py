"""Kimi-K2-class decoder (DeepSeek-V3's block): multi-head LATENT
attention whose cache is ONE compressed row a token, and a sigmoid
router that chooses by a biased score over more experts than this chip
holds, beside a shared expert.

A block of its own beside the other families' (models/blocks.py has what
it shares with them). Source: the model's config.json (`model_type:
kimi_k2`, which follows HF `modeling_deepseek.py`);
benchmarks/configs/kimi-k2.6.json lists what it fixes and what is
assumed. D model width, H query heads, Rq the query's rank, R the
latent's (`kv_lora_rank`), Kn / Kr the head's no-position and rotary
parts, Kv the V head size; no bias anywhere:

  x <- x + Attn(RMSNorm(x));  x <- x + MLP(RMSNorm(x))
  Attn   u the normed input;  c_q = RMSNorm(u W_qa) (Rq);  q = c_q W_qb
         (H x (Kn + Kr)), a head [q_nope ; q_pe], q_pe = rope(q_pe).
         THE CACHED ROW: [c ; k_pe] = u W_kva (R + Kr), c = RMSNorm(c),
         k_pe = rope(k_pe): one rotary key for all heads. R + Kr values a
         token and layer (576: 1,152 B in bf16) are all that is cached.
         plain form (`forward`, and the reference's): [k_nope_h ; v_h] =
         c W_kvb (Kn + Kv a head); a_h = [q_nope_h ; q_pe_h] . [k_nope_h
         ; k_pe] * scale; o_h = softmax(a_h) v_h; then W_o (H x Kv -> D).
         ABSORBED form (both paged programs): q~_h = q_nope_h W_UK,h (R);
         a_h = (q~_h . c + q_pe_h . k_pe) * scale = [q~_h ; q_pe_h] . row;
         o-_h = sum_j p_j c_j (R), the row's first R lanes; o_h = o-_h
         W_UV,h. The same function: the cache is read once, as it lies,
         and never expanded. W_UK [H, Kn, R] and W_UV [H, R, Kv] a
         layer are W_kvb cut once at load (`lay_out`).
  rope   rotate-half on the Kr dims in their stored order, YaRN
         frequencies (`blocks.yarn_inv_freq`), cos and sin times
         mscale(factor, `mscale`) / mscale(factor, `mscale_all_dim`);
         scale = (Kn + Kr)^-1/2 * mscale(factor, `mscale_all_dim`)^2,
         mscale(f, m) = 0.1 m ln f + 1.
  MLP    a dense layer (the first `first_k_dense`): gated SiLU of
         `d_ff_dense`. A sparse layer: s = sigmoid(u W_r) in float32 over
         all `n_experts_routed`; the `top_k` largest of s + b choose;
         gate_e = `routed_scale` * s_e / sum of the chosen s;
         MLP(u) = Shared(u) + sum over the chosen e of gate_e Expert_e(u),
         every one a gated-SiLU MLP, the shared one ungated.
  final RMSNorm, then an untied head.

**One chip's share**, as models/laguna.py: the weights hold `n_experts`
of the routed experts (`first_expert` ..) and `vocab_size` rows of the
vocabulary; the router scores and chooses over all `n_experts_routed`,
`ops.moe.token_choice_experts` returns the held experts' part, and no
exchange is built (the routed parts of all shares plus the shared expert
once ARE the whole layer: tests/test_kimi_k2.py).

**One plane in the pool**: ``pool["kv"]`` ``[L, P+1, page, lanes]``,
lanes = R + Kr rounded up to whole lane tiles (640 for 576: the chip
stores it so either way, `KimiK2Config.head_dim`), addressed by the
engine's page tables; nothing by the slot and no ring, so every cached
byte is a `PagePool` page. Both paged kernels read it in their latent
form (ops/paged_attention.py `latent=`): a page is fetched once and
stands in the score and the value matmul.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, ClassVar

import jax
import jax.numpy as jnp

from ray_tpu.models import blocks
# The pool reader's choice as a global of THIS module, which the walk
# reads at trace time (benchmarks/tools/probe_kimi_k2.py rebinds it).
from ray_tpu.models.blocks import attend_fn as _attend_fn
from ray_tpu.models.blocks import (gated_mlp, init_from_specs,
                                   last_token_logits, rms_norm, untied_head,
                                   write_row, yarn_inv_freq)
from ray_tpu.models.paged_kv import paged_programs
from ray_tpu.ops import scopes
from ray_tpu.ops.moe import token_choice_experts

_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class KimiK2Config:
    vocab_size: int = 163840         # rows of embedding and head held here
    d_model: int = 7168
    n_layers: int = 61
    n_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512          # R: the latent, and the value read
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff_dense: int = 18432          # the dense layers' MLP width
    first_k_dense: int = 1           # layers 0 .. first_k_dense - 1 are dense
    n_experts: int = 384             # routed experts HELD here
    n_experts_routed: int = 384      # the router's outputs
    first_expert: int = 0            # the first held expert's global id
    top_k: int = 8
    d_ff: int = 2048                 # one routed expert's width
    d_ff_shared: int = 2048
    routed_scale: float = 2.827
    rope_theta: float = 50_000.0
    yarn_factor: float = 64.0
    yarn_orig: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0
    norm_eps: float = 1e-5
    max_seq: int = 4608
    dtype: Any = jnp.bfloat16        # activation/compute dtype
    param_dtype: Any = jnp.float32

    family: ClassVar[str] = "kimi_k2"  # models/serving.py

    @classmethod
    def tiny(cls, **kw) -> "KimiK2Config":
        """CPU-test size that keeps the pattern: a dense layer, two
        sparse ones; a row of 128 + 16 lanes under 4 heads of 32 + 16;
        8 experts top-3 with 4 held; YaRN's ramp inside the positions a
        test reaches."""
        base = dict(vocab_size=256, d_model=64, n_layers=3, n_heads=4,
                    q_lora_rank=48, kv_lora_rank=128, qk_nope_head_dim=32,
                    qk_rope_head_dim=16, v_head_dim=32, d_ff_dense=128,
                    n_experts=4, n_experts_routed=8, top_k=3, d_ff=32,
                    d_ff_shared=32, rope_theta=10_000.0, yarn_factor=8.0,
                    yarn_orig=32, max_seq=256)
        return cls(**{**base, **kw})

    @property
    def head_dim(self) -> int:
        """A query head as the paged kernels see it, and the cached row
        as it lies: the latent, the rotary part, and zeros up to whole
        lane tiles of 128 (576 values in 640 lanes at the published
        sizes). The chip's tiled layout stores a 576-lane row in 640
        lanes whatever the array says, and the kernels' DMAs take whole
        tiles only, so the plane is declared as it is stored."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    rotary_dim = property(lambda self: self.qk_rope_head_dim)

    def count(self, kind: str) -> int:
        """Layers of an MLP kind ("dense", "sparse")."""
        dense = min(self.first_k_dense, self.n_layers)
        return dense if kind == "dense" else self.n_layers - dense

    def index(self, l: int) -> tuple[str, int]:
        """(MLP kind, index in its stack) of layer l."""
        if l < self.first_k_dense:
            return "dense", l
        return "sparse", l - self.first_k_dense


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(cfg: KimiK2Config) -> float:
    """(Kn + Kr)^-1/2 times YaRN's mscale over all dims, squared."""
    return ((cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
            * _mscale(cfg.yarn_factor, cfg.mscale_all_dim) ** 2)


def _rope_factor(cfg: KimiK2Config) -> float:
    return (_mscale(cfg.yarn_factor, cfg.mscale)
            / _mscale(cfg.yarn_factor, cfg.mscale_all_dim))


# What the router's bias is seeded at (normal): as models/mimo_v2.py, it
# moves a few percent of the top-k choices at the published widths
# (benchmarks/configs/kimi-k2.6.json `assumed`).
_ROUTER_BIAS_SCALE = 0.002

# The experts' stacks, handed whole to the grouped matmul.
_EXPERT_KEYS = ("w_gate", "w_up", "w_down")


def param_specs(cfg: KimiK2Config) -> dict[str, dict[str, Any]]:
    """name → {shape, init[, scale]}: the attention a stack over all
    layers, the MLPs a stack an MLP kind. `wkv_b` is the published
    `kv_b_proj` ([R, H x (Kn + Kv)], a head's no-position key then its
    value); the router's bias is seeded normal, so that a program that
    drops it is visibly wrong."""
    D, H, V, L = cfg.d_model, cfg.n_heads, cfg.vocab_size, cfg.n_layers
    Rq, R = cfg.q_lora_rank, cfg.kv_lora_rank
    Kn, Kr, Kv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    norm = lambda *s, scale=0.02: {"init": "normal", "scale": scale,
                                   "shape": s}
    resid = lambda *s: norm(*s, scale=0.02 / math.sqrt(2 * L))
    ones = lambda *s: {"init": "ones", "shape": s}
    nd, ns = cfg.count("dense"), cfg.count("sparse")
    E, F, Fs, Fd = cfg.n_experts, cfg.d_ff, cfg.d_ff_shared, cfg.d_ff_dense
    return {
        "wte": norm(V, D), "lm_head": norm(D, V), "ln_f_scale": ones(D),
        "ln1_scale": ones(L, D), "ln2_scale": ones(L, D),
        "wq_a": norm(L, D, Rq), "q_norm": ones(L, Rq),
        "wq_b": norm(L, Rq, H * (Kn + Kr)),
        "wkv_a": norm(L, D, R + Kr), "kv_norm": ones(L, R),
        "wkv_b": norm(L, R, H * (Kn + Kv)), "wo": resid(L, H * Kv, D),
        "d_gate": norm(nd, D, Fd), "d_up": norm(nd, D, Fd),
        "d_down": resid(nd, Fd, D),
        "router": norm(ns, D, cfg.n_experts_routed),
        "router_bias": norm(ns, cfg.n_experts_routed,
                            scale=_ROUTER_BIAS_SCALE),
        "s_gate": norm(ns, D, Fs), "s_up": norm(ns, D, Fs),
        "s_down": resid(ns, Fs, D),
        "w_gate": norm(ns, E, D, F), "w_up": norm(ns, E, D, F),
        "w_down": resid(ns, E, F, D)}


def partition_rules() -> tuple:
    """Every leaf replicated: the family serves at tp = 1 only
    (models/serving.py refuses more)."""
    from jax.sharding import PartitionSpec

    return ((r".*", PartitionSpec()),)


def init_params(cfg: KimiK2Config, rng: jax.Array) -> dict[str, jax.Array]:
    return init_from_specs(param_specs(cfg), rng, cfg.param_dtype)


def lay_out(cfg: KimiK2Config, params) -> dict:
    """The tree as the paged programs want it (models/serving.py
    `lay_out`), made once at load: `wkv_b` leaves the tree as the two
    stacks the absorbed form multiplies by, `w_uk` [L, H, Kn, R] (a
    head's no-position key map, transposed: q_nope -> the latent's space)
    and `w_uv` [L, H, R, Kv] (the latent -> a head's value): 17 MB a
    layer, cut and transposed here and not in every step. Every other
    leaf stays the stack it came as: the harness keeps the tree it made
    for its reference, and a copy a layer of each plane (2.2 GB) beside
    it does not fit the chip next to this family's pool; the compiled
    programs read a layer's plane where it lies in its stack
    (tests/test_chip_compile.py). Idempotent."""
    if "w_uk" in params:
        return params
    H, R = cfg.n_heads, cfg.kv_lora_rank
    Kn = cfg.qk_nope_head_dim
    out = {name: a for name, a in params.items() if name != "wkv_b"}
    kvb = params["wkv_b"].reshape(cfg.n_layers, R, H, Kn + cfg.v_head_dim)
    out["w_uk"] = kvb[..., :Kn].transpose(0, 2, 3, 1)
    out["w_uv"] = kvb[..., Kn:].transpose(0, 2, 1, 3)
    return out


# ------------------------------------------------------------- the block

def _rope(cfg: KimiK2Config, x, pos):
    """Rotate-half rotary on the last axis (Kr wide), YaRN frequencies.
    x [N, C, ..., Kr] float32, pos [N, C] absolute positions."""
    inv_freq, factor = yarn_inv_freq(cfg), _rope_factor(cfg)
    half = len(inv_freq)
    ang = (pos.astype(_F32).reshape(pos.shape + (1,) * (x.ndim - 2))
           * jnp.asarray(inv_freq, _F32))
    sin, cos = jnp.sin(ang) * factor, jnp.cos(ang) * factor
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


@jax.named_scope(scopes.ATTN_IN)
def _attn_inputs(cfg: KimiK2Config, params, l: int, x, pos):
    """Layer l's attention sublayer up to the head's two query parts and
    the row it caches. x [N, C, D], pos [N, C] → (q_nope [N, C, H, Kn],
    q_pe [N, C, H, Kr] rotated, row [N, C, `head_dim`]: the normed
    latent, the rotated rotary key and the zero lanes), all in
    cfg.dtype."""
    N, C, _D = x.shape
    H, R, dt = cfg.n_heads, cfg.kv_lora_rank, cfg.dtype
    Kn, Kr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    w = lambda name: params[name][l].astype(dt)
    u = rms_norm(x, params["ln1_scale"][l], cfg.norm_eps)
    c_q = rms_norm(u @ w("wq_a"), params["q_norm"][l], cfg.norm_eps)
    q = (c_q @ w("wq_b")).reshape(N, C, H, Kn + Kr)
    q_pe = _rope(cfg, q[..., Kn:].astype(_F32), pos).astype(dt)
    kv = u @ w("wkv_a")
    c = rms_norm(kv[..., :R], params["kv_norm"][l], cfg.norm_eps)
    k_pe = _rope(cfg, kv[..., R:].astype(_F32), pos).astype(dt)
    return q[..., :Kn], q_pe, _lanes(cfg, c, k_pe)


def _lanes(cfg: KimiK2Config, lat, pe):
    """[lat ; pe ; 0] on the last axis, `head_dim` lanes wide."""
    pad = cfg.head_dim - lat.shape[-1] - pe.shape[-1]
    return jnp.concatenate(
        [lat, pe, jnp.zeros(lat.shape[:-1] + (pad,), lat.dtype)], axis=-1)


@jax.named_scope(scopes.ATTN_ABSORB)
def _absorb_query(cfg: KimiK2Config, w_uk, q_nope, q_pe):
    """[q_nope W_UK ; q_pe ; 0]: the query against the cached row as it
    lies. q_nope [N, C, H, Kn], w_uk [H, Kn, R] → [N, C, H, `head_dim`]."""
    q_lat = jnp.einsum("nchd,hdr->nchr", q_nope, w_uk.astype(cfg.dtype))
    return _lanes(cfg, q_lat, q_pe)


@jax.named_scope(scopes.ATTN_ABSORB)
def _absorb_value(cfg: KimiK2Config, w_uv, o_lat):
    """A head's attended latent to its value: o_lat [N, C, H, R], w_uv
    [H, R, Kv] → [N, C, H, Kv]."""
    return jnp.einsum("nchr,hrv->nchv", o_lat, w_uv.astype(cfg.dtype))


# The router that chooses by s + b and gates by `routed_scale` s
# (models/mimo_v2.py is its other user).
_route = blocks.biased_route


def _finish_block(cfg: KimiK2Config, params, l: int, x, attn, valid):
    """From the attention output to layer l's end. attn [N, C, H, Kv],
    valid [N, C] bool (rows that carry a token: the others reach no
    expert).
    → (x, (counts [n_experts] int32 rows each held expert received,
    the valid rows' choices the bias moved) or None in a dense layer)."""
    N, C, D = x.shape
    dt = cfg.dtype
    mlp, j = cfg.index(l)
    with jax.named_scope(scopes.ATTN_OUT):
        x = x + attn.reshape(N, C, -1) @ params["wo"][l].astype(dt)
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
        shared = gated_mlp(u, params["s_gate"][j], params["s_up"][j],
                           params["s_down"][j])
        f = (shared + routed.astype(_F32)).astype(dt)
        return x + f.reshape(N, C, D), (counts, moved)


_head = functools.partial(untied_head, rms_norm)


# ------------------------------------------ full sequence (tests, no cache)

def forward(cfg: KimiK2Config, params, tokens):
    """tokens [B, S] → logits [B, S, V] float32: every row a whole
    sequence from position 0, the PLAIN form (K and V expanded from the
    latent through `wkv_b`), masked attention, no pool. `params` as
    `param_specs` shapes them."""
    B, S = tokens.shape
    H, Kn, Kv, R = (cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim,
                    cfg.kv_lora_rank)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    seen = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens]
    for l in range(cfg.n_layers):
        q_nope, q_pe, row = _attn_inputs(cfg, params, l, x, pos)
        with jax.named_scope(scopes.ATTN_KERNEL):
            kv = (row[..., :R] @ params["wkv_b"][l].astype(cfg.dtype)
                  ).reshape(B, S, H, Kn + Kv)
            k = jnp.concatenate(
                [kv[..., :Kn], jnp.broadcast_to(
                    row[:, :, None, R:R + cfg.qk_rope_head_dim],
                    (B, S, H, cfg.qk_rope_head_dim))], axis=-1)
            s = jnp.einsum("bshk,bthk->bhst",
                           jnp.concatenate([q_nope, q_pe], axis=-1), k,
                           preferred_element_type=_F32)
            s = jnp.where(seen[None, None], s * softmax_scale(cfg), -1e30)
            attn = jnp.einsum("bhst,bthk->bshk",
                              jax.nn.softmax(s, axis=-1).astype(cfg.dtype),
                              kv[..., Kn:])
        x, _counts = _finish_block(cfg, params, l, x, attn,
                                   jnp.ones((B, S), bool))
    return _head(cfg, params, x)


# --------------------------------------------------------- the paged pool

# Running totals over decode steps, wrapping uint32 (the host takes
# differences): the expert families' five, and the choices the router's
# bias moved.
COUNTERS = blocks.COUNTERS_BIASED


def init_paged_kv(cfg: KimiK2Config, n_pages: int, page_size: int,
                  _n_slots: int, kv_dtype: str | None = None):
    """The pool pytree, donated to the paged programs: ONE plane `kv`
    ``[L, P+1, page_size, head_dim]`` (row 0 the null page) and the
    decode steps' running expert counters. Nothing by the slot."""
    if kv_dtype not in (None, "bf16"):
        raise ValueError(
            f"the kimi_k2 family's pool is bf16, got {kv_dtype!r}")
    return {"kv": jnp.zeros((cfg.n_layers, n_pages + 1, page_size,
                             cfg.head_dim), cfg.dtype),
            "moe_counters": jnp.zeros(len(COUNTERS), jnp.uint32)}


def _paged_layers(cfg: KimiK2Config, params, x, pos, valid, pool, attend,
                  pages):
    """The layers over the pool, for a chunk dispatch and a decode step
    alike. x [N, C, D]; pos, valid [N, C]; `attend(q, kv_pool, l)` the
    bound pool reader (q [N, C, H, head_dim] → [N, C, H, R]); `pages`
    [N*C] the page each token's row is written to; `params` the tree
    `lay_out` makes. → (x, pool, counts of each sparse layer)."""
    offs = (pos % pool["kv"].shape[2]).reshape(-1)
    counts = []
    for l in range(cfg.n_layers):
        q_nope, q_pe, row = _attn_inputs(cfg, params, l, x, pos)
        pool = write_row(pool, l, pages, offs, row, "kv")
        q = _absorb_query(cfg, params["w_uk"][l], q_nope, q_pe)
        with jax.named_scope(scopes.ATTN_KERNEL):
            o_lat = attend(q, pool["kv"], l)
        attn = _absorb_value(cfg, params["w_uv"][l], o_lat)
        x, n = _finish_block(cfg, params, l, x, attn, valid)
        if n is not None:
            counts.append(n)
    return x, pool, counts


def chunk_forward(cfg: KimiK2Config, params, tokens, pool, tables, offsets,
                  n_valid, attn_impl: str):
    """N chunk rows written into their slots' pages, each at its own
    offset, and attended in the absorbed form.
    → (hidden states [N, C, D], updated pool)."""
    _N, C = tokens.shape
    ps = pool["kv"].shape[2]
    rel = jnp.arange(C)
    pos = offsets[:, None] + rel[None, :]
    valid = rel[None, :] < n_valid[:, None]
    kv_lens = offsets + n_valid
    with jax.named_scope(scopes.ATTN_KV_WRITE):
        page_idx = jnp.minimum(pos // ps, tables.shape[1] - 1)
        pages = jnp.where(valid, jnp.take_along_axis(tables, page_idx,
                                                     axis=1), 0).reshape(-1)
    attend = _attend_fn(attn_impl, chunk=True)
    reader = lambda q, kv, l: attend(
        q, kv, None, l, tables, offsets, kv_lens,
        sm_scale=softmax_scale(cfg), latent=cfg.kv_lora_rank)
    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens]
    x, pool, _counts = _paged_layers(cfg, params, x, pos, valid, pool,
                                     reader, pages)
    return x, pool


def decode_once(cfg: KimiK2Config, params, tokens, pool, positions, tables,
                attn_impl: str):
    """All B slots advance one token: row b IS slot b. A row whose table
    is all null (an idle slot, or one still mid-prefill) writes the null
    page, reaches no expert and counts nowhere.
    → (logits [B, V] fp32, updated pool)."""
    ps = pool["kv"].shape[2]
    active = tables[:, 0] > 0
    with jax.named_scope(scopes.ATTN_KV_WRITE):
        pages = jnp.take_along_axis(
            tables,
            jnp.minimum(positions // ps, tables.shape[1] - 1)[:, None],
            axis=1)[:, 0]
    attend = _attend_fn(attn_impl, chunk=False)
    reader = lambda q, kv, l: attend(
        q[:, 0], kv, None, l, tables, positions + 1,
        sm_scale=softmax_scale(cfg), latent=cfg.kv_lora_rank)[:, None]
    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens[:, None]]
    x, pool, counts = _paged_layers(cfg, params, x, positions[:, None],
                                    active[:, None], pool, reader, pages)
    with jax.named_scope(scopes.COUNTERS):
        n_live = jnp.sum(active)
        counters = pool["moe_counters"] + sum(
            blocks.counter_row_biased(cfg, n, n_live, tokens.shape[0])
            for n in counts)
    return _head(cfg, params, x[:, 0]), {**pool, "moe_counters": counters}


(prefill_chunk_paged, decode_step_paged, _decode_sample_paged,
 decode_multi_paged) = paged_programs(
    chunk_forward, decode_once, last_token_logits(_head), COUNTERS)


__all__ = [
    "KimiK2Config", "param_specs", "partition_rules", "init_params",
    "lay_out", "forward", "softmax_scale", "init_paged_kv",
    "prefill_chunk_paged", "decode_step_paged", "decode_multi_paged",
]
