"""Laguna-class decoder: window layers beside full ones over one engine's
cache, and a sigmoid top-k router over more experts than this chip holds.

A block of its own beside models/gpt.py and models/zaya.py (neither gets
a switch for any of this). Source: the model's config.json (`model_type:
laguna`); benchmarks/configs/laguna-s-2.1.json lists what it fixes and
what is assumed. D model width, K head size, G KV heads; layer l is a
FULL layer (H = `n_heads` query heads, every earlier key) or a WINDOW
layer (H = `n_heads_window`, the last `window` keys, the query's own
position counted), by `layer_types`:

  x <- x + Attn(RMSNorm(x));  x <- x + MLP(RMSNorm(x));  no bias anywhere
  Attn   u the normed input;  q = u W_q (H x K), k = u W_k, v = u W_v
         (G x K);  rope on q and k;  query head h reads KV head
         h // (H / G);  softmax at scale K^-1/2;  g = sigmoid(u W_g), one
         gate a head, multiplies head h's output before W_o.
  rope   window layers: all K dims, rotate-half, theta `rope_theta_window`.
         full layers: the first `rotary_dim` dims, theta `rope_theta`,
         YaRN frequencies (`yarn_inv_freq`: computed once on the host in
         float64), cos and sin multiplied by `attention_factor`.
  MLP    a dense layer (`dense_layers`): W_down(silu(W_gate u) * W_up u).
         a sparse layer: s = sigmoid(u W_r) in float32 over all
         `n_experts_routed` experts; the `top_k` largest choose;
         gate_e = `routed_scale` * s_e / sum of the chosen s;
         MLP(u) = Shared(u) + sum over the chosen e of gate_e Expert_e(u),
         every one a gated-SiLU MLP, the shared one ungated.
  final RMSNorm, then an untied head.

**One chip's share.** The weights hold `n_experts` of the routed experts
(`first_expert` .. + `n_experts` - 1) and `vocab_size` rows of the
vocabulary: the share one chip of an expert-parallel pair holds. The
router still scores every expert and the gates are normalised over all
`top_k` choices; `ops.moe.token_choice_experts` returns the held
experts' part and what the absent ones would have added is the other
chip's (no exchange is built; two shares' routed parts plus the shared
expert once ARE the whole layer: tests/test_laguna.py).

**Two kinds of K/V in one pool pytree.** Full layers keep
``pool["k"], pool["v"]`` ``[n_full, P+1, page, G*K]``, addressed by the
engine's page tables like every other family's. Window layers keep
``pool["k_win"], pool["v_win"]`` ``[n_window, (n_slots+1)*R, page, G*K]``:
a RING of R pages a slot, needing no allocator. Logical page j of slot s
lives at row ``ring_rows[s, j % R]`` (`ring_rows` ``[n_slots+1, R]`` int32
is in the pool pytree so that both programs read R and the rows off one
array; the last ring is the null slot's, which idle rows write). The
programs hand the kernels that `[rows, R]` table and `col_page`: the
logical page each column holds NOW, from the row's own length alone
(`_ring_view`), so a reused or re-prefilled slot never reads what its
predecessor left: a column its own tokens have not reached holds no
page (-1). R = window pages + the pages one chunk dispatch writes + 1
(`ring_pages`): chunk programs write every row's K/V before any row
attends, and two rows of a dispatch may be consecutive chunks of one
prompt, so the later row's pages must not land on those the earlier row
still reads.

The four paged programs are `paged_kv.paged_programs` over
`chunk_forward` and `decode_once` (names, donation and the decode window
are its); models/mimo_v2.py builds its own over the same two with another
walk (`layers=`) and counter row (`count=`). Five or so layers of three
different shapes are walked in Python, each kind indexing its own stack;
the experts' stack goes to the grouped matmul whole (`layer=`).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, ClassVar

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import scopes
# The pool reader's choice as a global of THIS module, which the walk
# below reads at trace time: benchmarks/tools/probe_laguna.py and
# probe_mimo_v2.py put their window faults in by rebinding it.
from ray_tpu.models.blocks import attend_fn as _attend_fn
from ray_tpu.models.blocks import (COUNTERS, counter_row, gated_mlp,
                                   init_from_specs, last_token_logits,
                                   rms_norm, untied_head, write_kv,
                                   yarn_inv_freq)
from ray_tpu.models.paged_kv import paged_programs
from ray_tpu.ops.moe import token_choice_experts

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int = 100352         # rows of embedding and head held here
    d_model: int = 3072
    n_layers: int = 48
    n_heads: int = 48                # query heads of a full layer
    n_heads_window: int = 72         # query heads of a window layer
    n_kv_heads: int = 8
    head_dim: int = 128
    d_ff_dense: int = 12288          # the dense layers' MLP width
    n_experts: int = 256             # routed experts HELD here
    n_experts_routed: int = 256      # the router's outputs
    first_expert: int = 0            # the first held expert's global id
    top_k: int = 10
    d_ff: int = 1024                 # one routed expert's width
    d_ff_shared: int = 1024
    routed_scale: float = 2.5
    window: int = 512
    # "full" / "window" a layer; () is the model's own pattern, a full
    # layer every fourth starting at layer 0.
    layer_types: tuple = ()
    dense_layers: tuple = (0,)
    rope_theta: float = 500_000.0    # full layers
    rotary_dim: int = 64             # full layers: dims that get rotary
    yarn_factor: float = 128.0
    yarn_orig: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.4852030263919618
    rope_theta_window: float = 10_000.0
    norm_eps: float = 1e-6
    max_seq: int = 4096
    dtype: Any = jnp.bfloat16        # activation/compute dtype
    param_dtype: Any = jnp.float32

    family: ClassVar[str] = "laguna"  # models/serving.py

    @classmethod
    def tiny(cls, **kw) -> "LagunaConfig":
        """CPU-test size that keeps the pattern: dense layer 0, three
        window layers, a full one; unequal head counts over 2 KV heads;
        8 experts top-3 with 4 held; YaRN's ramp inside the positions a
        test reaches."""
        base = dict(vocab_size=256, d_model=64, n_layers=5, n_heads=4,
                    n_heads_window=6, n_kv_heads=2, head_dim=16,
                    d_ff_dense=128, n_experts=4, n_experts_routed=8,
                    top_k=3, d_ff=32, d_ff_shared=32, window=32,
                    rope_theta=10_000.0, rotary_dim=8, yarn_factor=8.0,
                    yarn_orig=32, max_seq=256)
        return cls(**{**base, **kw})

    @property
    def kinds(self) -> tuple:
        """"full" or "window" for each of the n_layers layers."""
        if self.layer_types:
            return tuple(self.layer_types[:self.n_layers])
        return tuple("full" if l % 4 == 0 else "window"
                     for l in range(self.n_layers))

    def heads(self, kind: str) -> int:
        return self.n_heads if kind == "full" else self.n_heads_window

    def count(self, kind: str) -> int:
        """Layers of an attention kind ("full", "window") or of an MLP
        kind ("dense", "sparse")."""
        if kind in ("full", "window"):
            return sum(k == kind for k in self.kinds)
        dense = sum(l in self.dense_layers for l in range(self.n_layers))
        return dense if kind == "dense" else self.n_layers - dense

    def index(self, l: int) -> tuple[str, int, str, int]:
        """(attention kind, index in its stack, MLP kind, index in its
        stack) of layer l."""
        kind = self.kinds[l]
        mlp = "dense" if l in self.dense_layers else "sparse"
        return (kind, sum(k == kind for k in self.kinds[:l]), mlp,
                sum((m in self.dense_layers) == (mlp == "dense")
                    for m in range(l)))


def _rope_of(cfg: LagunaConfig, kind: str) -> tuple[np.ndarray, float]:
    """(frequencies, the factor on cos and sin) of a layer kind."""
    if kind == "full":
        return yarn_inv_freq(cfg), float(cfg.attention_factor)
    K = cfg.head_dim
    return (float(cfg.rope_theta_window)
            ** (-np.arange(0, K, 2, dtype=np.float64) / K), 1.0)


# The experts' stacks, handed whole to the grouped matmul. A layer kind's
# attention leaves carry the prefixes "f_" (full) and "w_" (window).
_EXPERT_KEYS = ("w_gate", "w_up", "w_down")


def param_specs(cfg: LagunaConfig) -> dict[str, dict[str, Any]]:
    """name → {shape, init[, scale]}: one stack a layer kind and MLP
    kind, in layer order within the kind."""
    D, G, K, V, L = (cfg.d_model, cfg.n_kv_heads, cfg.head_dim,
                     cfg.vocab_size, cfg.n_layers)
    norm = lambda *s, scale=0.02: {"init": "normal", "scale": scale,
                                   "shape": s}
    resid = lambda *s: norm(*s, scale=0.02 / math.sqrt(2 * L))
    ones = lambda *s: {"init": "ones", "shape": s}
    specs = {"wte": norm(V, D), "lm_head": norm(D, V), "ln_f_scale": ones(D),
             "ln1_scale": ones(L, D), "ln2_scale": ones(L, D)}
    for kind in ("full", "window"):
        n, H, p = cfg.count(kind), cfg.heads(kind), kind[0] + "_"
        specs.update({
            p + "wq": norm(n, D, H * K), p + "wk": norm(n, D, G * K),
            p + "wv": norm(n, D, G * K), p + "wg": norm(n, D, H),
            p + "wo": resid(n, H * K, D)})
    nd, ns = cfg.count("dense"), cfg.count("sparse")
    E, F, Fs, Fd = cfg.n_experts, cfg.d_ff, cfg.d_ff_shared, cfg.d_ff_dense
    specs.update({
        "d_gate": norm(nd, D, Fd), "d_up": norm(nd, D, Fd),
        "d_down": resid(nd, Fd, D),
        "router": norm(ns, D, cfg.n_experts_routed),
        "s_gate": norm(ns, D, Fs), "s_up": norm(ns, D, Fs),
        "s_down": resid(ns, Fs, D),
        "w_gate": norm(ns, E, D, F), "w_up": norm(ns, E, D, F),
        "w_down": resid(ns, E, F, D)})
    return specs


def partition_rules() -> tuple:
    """Every leaf replicated: the family serves at tp = 1 only
    (models/serving.py refuses more); the table exists so that the
    shared loaders find a rule for each leaf."""
    from jax.sharding import PartitionSpec

    return ((r".*", PartitionSpec()),)


def init_params(cfg: LagunaConfig, rng: jax.Array) -> dict[str, jax.Array]:
    return init_from_specs(param_specs(cfg), rng, cfg.param_dtype)


# ------------------------------------------------------------- the block

def _rope(x, pos, inv_freq: np.ndarray, factor: float):
    """Rotate-half rotary on the first 2 * len(inv_freq) dims of each
    head, cos and sin times `factor`. x [N, C, h, K] float32, pos [N, C]
    absolute positions."""
    half = len(inv_freq)
    ang = (pos.astype(_F32)[..., None, None]
           * jnp.asarray(inv_freq, _F32))                   # [N, C, 1, half]
    sin, cos = jnp.sin(ang) * factor, jnp.cos(ang) * factor
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


@jax.named_scope(scopes.ATTN_IN)
def _attn_inputs(cfg: LagunaConfig, params, l: int, x, pos):
    """Layer l's attention sublayer up to q, k, v and the heads' gates.
    x [N, C, D], pos [N, C] → (q [N, C, H, K], k, v [N, C, G, K] in
    cfg.dtype, gate [N, C, H] float32)."""
    N, C, _D = x.shape
    kind, i, _mlp, _j = cfg.index(l)
    H, G, K, dt = cfg.heads(kind), cfg.n_kv_heads, cfg.head_dim, cfg.dtype
    w = lambda name: params[kind[0] + "_" + name][i].astype(dt)
    u = rms_norm(x, params["ln1_scale"][l], cfg.norm_eps)
    inv_freq, factor = _rope_of(cfg, kind)
    rope = lambda t, h: _rope(t.reshape(N, C, h, K).astype(_F32), pos,
                              inv_freq, factor).astype(dt)
    q, k = rope(u @ w("wq"), H), rope(u @ w("wk"), G)
    v = (u @ w("wv")).reshape(N, C, G, K)
    gate = jax.nn.sigmoid((u @ w("wg")).astype(_F32))
    return q, k, v, gate


@jax.named_scope(scopes.MOE_ROUTE)
def _route(cfg: LagunaConfig, w_router, u):
    """The router, float32 throughout. u [M, D] → (experts [M, k] int32
    global ids, gates [M, k] float32: `routed_scale` times the chosen
    scores, normalised over all k choices, held here or not)."""
    s = jax.nn.sigmoid(jnp.matmul(u.astype(_F32), w_router.astype(_F32),
                                  precision=_HIGHEST))
    top, chosen = jax.lax.top_k(s, cfg.top_k)
    return (chosen.astype(jnp.int32),
            cfg.routed_scale * top / jnp.sum(top, axis=-1, keepdims=True))


def _finish_block(cfg: LagunaConfig, params, l: int, x, attn, gate, valid):
    """From the attention output to layer l's end. attn [N, C, H, K],
    gate [N, C, H] float32, valid [N, C] bool (rows that carry a token:
    the others reach no expert).
    → (x, counts [n_experts] int32 rows each held expert received, or
    None in a dense layer)."""
    N, C, D = x.shape
    dt = cfg.dtype
    kind, i, mlp, j = cfg.index(l)
    with jax.named_scope(scopes.ATTN_OUT):
        o = (attn.astype(_F32) * gate[..., None]).astype(dt)
        x = x + o.reshape(N, C, -1) @ params[kind[0] + "_wo"][i].astype(dt)
    with jax.named_scope(scopes.MLP):
        u = rms_norm(x, params["ln2_scale"][l],
                     cfg.norm_eps).reshape(N * C, D)
        if mlp == "dense":
            f = gated_mlp(u, params["d_gate"][j], params["d_up"][j],
                          params["d_down"][j])
            return x + f.astype(dt).reshape(N, C, D), None
    chosen, gates = _route(cfg, params["router"][j], u)
    with jax.named_scope(scopes.MOE_EXPERTS):
        experts = tuple(params[k].astype(dt) for k in _EXPERT_KEYS)
    routed, counts = token_choice_experts(
        u, chosen, gates, *experts,
        first_expert=cfg.first_expert, layer=j, valid=valid.reshape(-1),
        n_routed=cfg.n_experts_routed)
    with jax.named_scope(scopes.MLP):
        shared = gated_mlp(u, params["s_gate"][j], params["s_up"][j],
                           params["s_down"][j])
        f = (shared + routed.astype(_F32)).astype(dt)
        return x + f.reshape(N, C, D), counts


_head = functools.partial(untied_head, rms_norm)


# ------------------------------------------ full sequence (tests, no cache)

def forward(cfg: LagunaConfig, params, tokens):
    """tokens [B, S] → logits [B, S, V] float32: every row a whole
    sequence from position 0, plain masked attention, no pool."""
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    ahead = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]     # i - j
    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens]
    for l, kind in enumerate(cfg.kinds):
        q, k, v, gate = _attn_inputs(cfg, params, l, x, pos)
        with jax.named_scope(scopes.ATTN_KERNEL):
            g = cfg.heads(kind) // cfg.n_kv_heads
            k, v = (jnp.repeat(t, g, axis=2) for t in (k, v))
            seen = ahead >= 0
            if kind == "window":
                seen &= ahead < cfg.window
            s = jnp.einsum("bshk,bthk->bhst", q, k,
                           preferred_element_type=_F32)
            s = jnp.where(seen[None, None],
                          s / math.sqrt(cfg.head_dim), -1e30)
            attn = jnp.einsum("bhst,bthk->bshk",
                              jax.nn.softmax(s, axis=-1).astype(cfg.dtype), v)
        x, _counts = _finish_block(cfg, params, l, x, attn, gate,
                                   jnp.ones((B, S), bool))
    return _head(cfg, params, x)


# --------------------------------------------------------- the paged pool

def ring_pages(window: int, page_size: int, dispatch_tokens: int) -> int:
    """Pages in a slot's ring: those a query's window can reach, those
    one dispatch can write of one prompt, and one for a window or a
    chunk that starts inside a page."""
    return -(-window // page_size) + -(-dispatch_tokens // page_size) + 1


def ring_pool(cfg, n_pages: int, page_size: int, n_slots: int,
              dispatch_tokens: int, lanes: dict, n_counters: int):
    """The pool pytree of a family with full and window layers, donated
    to its paged programs: the full layers' pages ``[n_full, P+1,
    page_size, lanes]`` (row 0 the null page), the window layers' rings
    ``[n_window, (n_slots+1)*R, page_size, lanes]`` with their row ids
    `ring_rows` ``[n_slots+1, R]`` (the last ring the null slot's; a
    ring is R CONSECUTIVE rows of the plane, ``ring_rows[s, c] ==
    ring_rows[s, 0] + c``: the window decode call fetches a slot's window
    as a run of the plane's rows and is owed that,
    ops/paged_attention.py `_check_ring`), and
    the decode steps' `n_counters` running expert counters. `lanes`: the
    minor width of each plane ("k", "v", "k_win", "v_win"): a kind's KV
    heads x its K or V head size. `dispatch_tokens`: the most tokens of
    one prompt a chunk dispatch carries (the engine's tallest program x
    prefill_chunk)."""
    R = ring_pages(cfg.window, page_size, dispatch_tokens)
    rows = {"full": (cfg.count("full"), n_pages + 1),
            "window": (cfg.count("window"), (n_slots + 1) * R)}
    planes = {name: jnp.zeros(rows[kind] + (page_size, lanes[name]),
                              cfg.dtype)
              for kind, names in PLANES.items() for name in names}
    return {**planes,
            "ring_rows": jnp.arange((n_slots + 1) * R, dtype=jnp.int32
                                    ).reshape(n_slots + 1, R),
            "moe_counters": jnp.zeros(n_counters, jnp.uint32)}


def init_paged_kv(cfg: LagunaConfig, n_pages: int, page_size: int,
                  n_slots: int, kv_dtype: str | None = None, *,
                  dispatch_tokens: int):
    """`ring_pool` at this family's widths: every plane G*K lanes, the
    counters `blocks.COUNTERS`."""
    if kv_dtype not in (None, "bf16"):
        raise ValueError(f"the laguna family's pool is bf16, got {kv_dtype!r}")
    GK = cfg.n_kv_heads * cfg.head_dim
    return ring_pool(cfg, n_pages, page_size, n_slots, dispatch_tokens,
                     dict.fromkeys(("k", "v", "k_win", "v_win"), GK),
                     len(COUNTERS))


@jax.named_scope(scopes.ATTN_KERNEL)
def _ring_view(pool, slots, lengths, page_size: int):
    """(table [N, R] of ring rows, col_page [N, R]) for rows that belong
    to `slots` [N] and have `lengths` [N] tokens written."""
    R = pool["ring_rows"].shape[1]
    last_page = ((lengths - 1) // page_size)[:, None]       # -1: no token
    col = jnp.arange(R, dtype=jnp.int32)[None, :]
    col_page = last_page - (last_page - col) % R
    return pool["ring_rows"][slots], jnp.where(col_page < 0, -1, col_page)


@jax.named_scope(scopes.ATTN_KV_WRITE)
def _ring_targets(pool, table, pos, live, page_size: int):
    """Ring rows the tokens at `pos` [N, C] are written to (the null
    slot's first row where `live` [N, C] is false), flat [N*C]."""
    R = table.shape[1]
    pages = jnp.take_along_axis(table, (pos // page_size) % R, axis=1)
    return jnp.where(live, pages, pool["ring_rows"][-1, 0]).reshape(-1)


# The pool's K and V planes of each cache kind.
PLANES = {"full": ("k", "v"), "window": ("k_win", "v_win")}


def _paged_layers(cfg: LagunaConfig, params, x, pos, valid, pool, attend,
                  full, ring):
    """The layers over the pool, for a chunk dispatch and a decode step
    alike. x [N, C, D]; pos, valid [N, C]; `attend(q, k_pool, v_pool, i,
    table, **kw)` the bound pool reader; `full` and `ring` each
    (write pages [N*C], table [N, n], the reader's keywords) of a cache
    kind. → (x, pool, counts of each sparse layer)."""
    ps = pool["k"].shape[2]
    offs = (pos % ps).reshape(-1)
    counts = []
    for l, kind in enumerate(cfg.kinds):
        i = cfg.index(l)[1]
        pages, table, kw = full if kind == "full" else ring
        q, k, v, gate = _attn_inputs(cfg, params, l, x, pos)
        kn, vn = PLANES[kind]
        pool = write_kv(pool, i, pages, offs, k, v, (kn, vn))
        with jax.named_scope(scopes.ATTN_KERNEL):
            attn = attend(q, pool[kn], pool[vn], i, table, **kw)
        x, n = _finish_block(cfg, params, l, x, attn, gate, valid)
        if n is not None:
            counts.append(n)
    return x, pool, counts


def chunk_forward(cfg, params, tokens, pool, tables, offsets, n_valid,
                  slots, attn_impl: str, layers=_paged_layers):
    """N chunk rows written into their slots' pages and rings, each at
    its own offset. Every row's K/V is written before any row attends
    (a row may continue the row above it), which is why a ring holds a
    dispatch's pages beyond the window (`ring_pages`). `layers`: the
    family's walk over its blocks (models/mimo_v2.py hands its own).
    → (hidden states [N, C, D], updated pool)."""
    N, C = tokens.shape
    ps, R = pool["k"].shape[2], pool["ring_rows"].shape[1]
    if R < ring_pages(cfg.window, ps, N * C):
        raise ValueError(
            f"a dispatch of {N} x {C} tokens needs a ring of "
            f"{ring_pages(cfg.window, ps, N * C)} pages, the pool's has {R}")
    rel = jnp.arange(C)
    pos = offsets[:, None] + rel[None, :]
    valid = rel[None, :] < n_valid[:, None]
    kv_lens = offsets + n_valid
    # The full kind's write targets, as models/paged_kv sets them.
    with jax.named_scope(scopes.ATTN_KV_WRITE):
        page_idx = jnp.minimum(pos // ps, tables.shape[1] - 1)
        full_pages = jnp.where(
            valid, jnp.take_along_axis(tables, page_idx, axis=1),
            0).reshape(-1)
    ring_table, col_page = _ring_view(pool, slots, kv_lens, ps)
    ring_targets = _ring_targets(pool, ring_table, pos, valid, ps)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    attend = _attend_fn(attn_impl, chunk=True)
    reader = lambda q, kp, vp, i, table, **kw: attend(
        q, kp, vp, i, table, offsets, kv_lens, sm_scale=scale, **kw)
    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens]
    x, pool, _counts = layers(
        cfg, params, x, pos, valid, pool, reader,
        (full_pages, tables, {}),
        (ring_targets, ring_table,
         {"window": cfg.window, "col_page": col_page}))
    return x, pool


def decode_once(cfg, params, tokens, pool, positions, tables,
                attn_impl: str, layers=_paged_layers, count=counter_row):
    """All B slots advance one token: row b IS slot b. A row whose table
    is all null (an idle slot, or one still mid-prefill) writes the null
    page and the null slot's ring, reaches no expert and counts nowhere,
    so a prompt's ring survives the decode windows between its chunks.
    `layers`, `count`: the family's walk over its blocks and what it
    adds to the running counters for one sparse layer's `counts`.
    → (logits [B, V] fp32, updated pool)."""
    B = tokens.shape[0]
    ps = pool["k"].shape[2]
    active = tables[:, 0] > 0
    pos = positions[:, None]
    with jax.named_scope(scopes.ATTN_KV_WRITE):
        full_pages = jnp.take_along_axis(
            tables,
            jnp.minimum(positions // ps, tables.shape[1] - 1)[:, None],
            axis=1)[:, 0]
    ring_table, col_page = _ring_view(pool, jnp.arange(B), positions + 1, ps)
    ring_targets = _ring_targets(pool, ring_table, pos, active[:, None], ps)
    scale = 1.0 / math.sqrt(cfg.head_dim)
    attend = _attend_fn(attn_impl, chunk=False)
    reader = lambda q, kp, vp, i, table, **kw: attend(
        q[:, 0], kp, vp, i, table, positions + 1, sm_scale=scale,
        **kw)[:, None]
    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens[:, None]]
    x, pool, counts = layers(
        cfg, params, x, pos, active[:, None], pool, reader,
        (full_pages, tables, {}),
        (ring_targets, ring_table,
         {"window": cfg.window, "col_page": col_page}))
    with jax.named_scope(scopes.COUNTERS):
        n_live = jnp.sum(active)
        counters = pool["moe_counters"] + sum(count(cfg, n, n_live, B)
                                              for n in counts)
    return _head(cfg, params, x[:, 0]), {**pool, "moe_counters": counters}


(prefill_chunk_paged, decode_step_paged, _decode_sample_paged,
 decode_multi_paged) = paged_programs(
    chunk_forward, decode_once, last_token_logits(_head), COUNTERS)


__all__ = [
    "LagunaConfig", "param_specs", "partition_rules", "init_params",
    "forward", "init_paged_kv", "ring_pages", "ring_pool",
    "yarn_inv_freq", "prefill_chunk_paged", "decode_step_paged",
    "decode_multi_paged",
]
