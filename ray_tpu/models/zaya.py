"""ZAYA1-class decoder: compressed convolutional attention (CCA, in its
grouped-query form) over a top-1 expert layer with an MLP router.

A block of its own beside models/gpt.py (which gets no switch for any of
this): RMSNorm, bias-free projections, rotary on part of each head
(rotate-half form), L2-normalised q/k with a temperature per KV head, a
learned scale and bias on both arms of every residual add, gated-SiLU
experts, a tied head. Sources: the model's config.json (`model_type:
zaya`), arXiv:2510.04476 (CCA) and arXiv:2511.17127 (the router, the
residual scaling); benchmarks/configs/zaya1-8b.json lists what each
fixes and what is assumed.

One layer, token t, everything before a sequence's first token zero:

  attention   u = RMSNorm(x);  z_t = [W_q u_t ; W_k u_t]   (H + G heads of K)
              c_t = a_0 * z_t + a_1 * z_{t-1} + b_a         depthwise conv, 2 taps
              y_t = B_0 c_t + B_1 c_{t-1} + b_B             one K x K group a head
              q = y[q part] + (q~ + k~ of its group) / 2    the q-k mean, taken
              k = y[k part] + (mean of its q~ + k~) / 2     before the convs
              q^ = sqrt(K) q/|q|;  k^ = tau_j sqrt(K) k/|k|;  rotary on both
              v_t = [W_v1 u_t ; W_v2 u_{t-1}]               half the value heads
                                                            read the token before
              o = softmax(q^ k^T / sqrt(K)) v  (causal, grouped);  f = W_o o
  experts     u = RMSNorm(x);  s = W_d u + b_d;  r_l = s + gamma_l * r_{l-1}
              logits = W_3 gelu(W_2 gelu(W_1 RMSNorm(r_l) + b_1) + b_2)
              p = softmax(logits);  e = argmax(p + beta);  gate p_e    (float32)
              f = p_e W_down^e (silu(W_gate^e u) * W_up^e u)
  residual    x <- (a * x + a') + (c * f + c') after each of the two

So a token's attention needs THREE things of the token before it, per
layer: z_{t-1}, c_{t-1} and W_v2 u_{t-1} (`ZayaConfig.state_width`
values). Inside a sequence they are a shift by one; at the start of a
prompt chunk or of a decode step they come from the **slot state**
``[L, n_slots + 1, state_width]``, a second kind of per-request state
beside the KV pages: read at a row's first token, written at its last
VALID token, indexed by slot (the last row is a null slot that idle
rows write, as page 0 is the null page), never paged. A row at offset 0
reads zeros, which is the reset: a slot reused after release, or
re-prefilled after a preemption, starts clean with nothing to clear.

The block is written once (`_attn_inputs`, `_finish_block`); what
differs between the full-sequence forward (tests, no cache), a prompt
chunk and a decode step is where the boundary values come from and how
attention reads K/V: the three callers below. The four paged programs
are `paged_kv.paged_programs` over the chunk forward and the decode step
(names, donation and the decode window are its), and carry the pool dict
``{"k", "v", "slot_state", "moe_counters"}``; the layer scan is
paged_kv's too.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, ClassVar

import jax
import jax.numpy as jnp

from ray_tpu.ops import scopes
from ray_tpu.models import blocks
# The norm under the name benchmarks/tools/probe_zaya.py's router calls
# it by, as an attribute of this module.
from ray_tpu.models.blocks import rms_norm as _rms_norm
from ray_tpu.models.paged_kv import paged_programs, scan_pool_layers
from ray_tpu.ops.moe import token_choice_experts

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class ZayaConfig:
    vocab_size: int = 262272
    d_model: int = 2048
    n_layers: int = 40
    n_heads: int = 8                 # query heads, inside the latent
    n_kv_heads: int = 2
    head_dim: int = 128
    n_experts: int = 16
    d_ff: int = 2048                 # one expert's width
    router_dim: int = 256
    rotary_dim: int = 64             # per-head dims that get rotary
    rope_theta: float = 5_000_000.0
    norm_eps: float = 1e-5
    max_seq: int = 2048
    dtype: Any = jnp.bfloat16        # activation/compute dtype
    param_dtype: Any = jnp.float32

    family: ClassVar[str] = "zaya"   # models/serving.py

    @classmethod
    def tiny(cls, **kw) -> "ZayaConfig":
        """CPU-test size: every mechanism, no width a lane would notice."""
        base = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                    n_kv_heads=2, head_dim=16, n_experts=4, d_ff=32,
                    router_dim=16, rotary_dim=8, max_seq=128)
        return cls(**{**base, **kw})

    @property
    def conv_channels(self) -> int:          # z and c: q~ then k~
        return (self.n_heads + self.n_kv_heads) * self.head_dim

    @property
    def v_shift_heads(self) -> int:          # value heads reading t-1
        return self.n_kv_heads // 2

    @property
    def state_width(self) -> int:            # z, c, W_v2 u of one token
        return 2 * self.conv_channels + self.v_shift_heads * self.head_dim


def _state_slices(cfg: ZayaConfig) -> dict[str, slice]:
    zc = cfg.conv_channels
    return {"z": slice(0, zc), "c": slice(zc, 2 * zc),
            "v": slice(2 * zc, cfg.state_width)}


# Leaves without a layers axis; every other leaf is stacked [L, ...].
_TOP_KEYS = ("wte", "ln_f_scale")
# Expert weights stay whole, outside the layer scan's xs: the grouped
# matmul takes the stack and the layer index (ops/moe.py), so no layer's
# 400 MB of experts is ever cut out of it.
_EXPERT_KEYS = ("w_gate", "w_up", "w_down")
# The router's arithmetic is float32 whatever the activations are.
_ROUTER_KEYS = ("r_down", "r_down_b", "r_gamma", "r_norm", "r_w1", "r_b1",
                "r_w2", "r_b2", "r_w3", "r_beta")


def param_specs(cfg: ZayaConfig) -> dict[str, dict[str, Any]]:
    """name → {shape, axes (logical), init}; block leaves carry a leading
    `layers` axis (scanned)."""
    D, H, G, K = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    E, F, R, L, V = (cfg.n_experts, cfg.d_ff, cfg.router_dim, cfg.n_layers,
                     cfg.vocab_size)
    ZC, VS = cfg.conv_channels, cfg.v_shift_heads * K
    norm = lambda *s, scale=0.02: {"init": "normal", "scale": scale,
                                   "shape": s}
    resid = lambda *s: norm(*s, scale=0.02 / math.sqrt(2 * L))
    ones = lambda *s: {"init": "ones", "shape": s}
    zeros = lambda *s: {"init": "zeros", "shape": s}
    lay = lambda spec, *axes: {**spec, "axes": ("layers",) + axes}
    specs = {
        "wte": {**norm(V, D), "axes": ("vocab", "embed")},
        "ln_f_scale": {**ones(D), "axes": ("embed",)},
        # attention sublayer
        "ln1_scale": lay(ones(L, D), "embed"),
        "wq": lay(norm(L, D, H * K), "embed", "heads"),
        "wk": lay(norm(L, D, G * K), "embed", "heads"),
        "wv1": lay(norm(L, D, G * K - VS), "embed", "heads"),
        "wv2": lay(norm(L, D, VS), "embed", "heads"),
        "conv0_w": lay(norm(L, 2, ZC, scale=0.5), None, "heads"),
        "conv0_b": lay(zeros(L, ZC), "heads"),
        "conv1_w": lay(norm(L, 2, H + G, K, K, scale=1 / math.sqrt(K)),
                       None, "heads", "kv", "kv"),
        "conv1_b": lay(zeros(L, ZC), "heads"),
        "k_temp": lay(ones(L, G), "heads"),
        "wo": lay(resid(L, H * K, D), "heads", "embed"),
        # expert sublayer
        "ln2_scale": lay(ones(L, D), "embed"),
        "r_down": lay(norm(L, D, R), "embed", None),
        "r_down_b": lay(zeros(L, R), None),
        "r_gamma": lay(ones(L, R), None),
        "r_norm": lay(ones(L, R), None),
        "r_w1": lay(norm(L, R, R, scale=1 / math.sqrt(R)), None, None),
        "r_b1": lay(zeros(L, R), None),
        "r_w2": lay(norm(L, R, R, scale=1 / math.sqrt(R)), None, None),
        "r_b2": lay(zeros(L, R), None),
        "r_w3": lay(norm(L, R, E, scale=1 / math.sqrt(R)), None, "expert"),
        "r_beta": lay(zeros(L, E), "expert"),
        "w_gate": lay(norm(L, E, D, F), "expert", "embed", "mlp"),
        "w_up": lay(norm(L, E, D, F), "expert", "embed", "mlp"),
        "w_down": lay(resid(L, E, F, D), "expert", "mlp", "embed"),
    }
    # x <- (a * x + a') + (c * f + c') after each sublayer.
    for i in (1, 2):
        specs[f"res{i}_a"] = lay(ones(L, D), "embed")
        specs[f"res{i}_a_b"] = lay(zeros(L, D), "embed")
        specs[f"res{i}_c"] = lay(ones(L, D), "embed")
        specs[f"res{i}_c_b"] = lay(zeros(L, D), "embed")
    return specs


def logical_axes(cfg: ZayaConfig) -> dict[str, tuple]:
    return {k: v["axes"] for k, v in param_specs(cfg).items()}


def partition_rules() -> tuple:
    """Every leaf replicated. The family serves at tp = 1 only
    (serve/llm_options.py refuses more: 2 KV heads, and experts want an
    expert-parallel dispatch, not a head split); the table exists so that
    the shared loaders find a rule for each leaf."""
    from jax.sharding import PartitionSpec

    return ((r".*", PartitionSpec()),)


def init_params(cfg: ZayaConfig, rng: jax.Array) -> dict[str, jax.Array]:
    specs = param_specs(cfg)
    keys = jax.random.split(rng, len(specs))
    params = {}
    for key, (name, spec) in zip(keys, sorted(specs.items())):
        if spec["init"] == "normal":
            params[name] = (jax.random.normal(key, spec["shape"],
                                              cfg.param_dtype) * spec["scale"])
        elif spec["init"] == "ones":
            params[name] = jnp.ones(spec["shape"], cfg.param_dtype)
        else:
            params[name] = jnp.zeros(spec["shape"], cfg.param_dtype)
    return params


def num_params(cfg: ZayaConfig) -> int:
    return sum(math.prod(s["shape"]) for s in param_specs(cfg).values())


# ------------------------------------------------------------- the block

def _l2_normalise(x):
    """x / |x| over the last axis, float32; a zero vector stays zero."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-12)


def _rotary_half(x, pos, rotary_dim: int, theta: float):
    """Rotate-half rotary on the first `rotary_dim` dims of each head.
    x [N, C, h, K] float32, pos [N, C] absolute positions."""
    half = rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=_F32) * 2.0 / rotary_dim)
    ang = pos.astype(_F32)[..., None, None] * inv_freq      # [N, C, 1, half]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _shift(full, first):
    """The value at t-1 for every token of each row: `full` [N, C, W]
    moved one token later, `first` [N, W] in front."""
    return jnp.concatenate([first[:, None].astype(full.dtype),
                            full[:, :-1]], axis=1)


def _residual(x, f, layer, arm: str):
    dt = x.dtype
    return ((layer[arm + "_a"].astype(dt) * x + layer[arm + "_a_b"].astype(dt))
            + (layer[arm + "_c"].astype(dt) * f.astype(dt)
               + layer[arm + "_c_b"].astype(dt)))


@jax.named_scope(scopes.ATTN_IN)
def _attn_inputs(cfg: ZayaConfig, layer, x, pos, boundary):
    """The attention sublayer up to its q^, k^ and v.

    x [N, C, D] (N rows of C consecutive tokens), pos [N, C];
    `boundary(name, full)` → [N, W]: for "z", "c" and "v", the value at
    the token BEFORE each row's first, given the row's own values `full`
    [N, C, W] (a chunk row may continue the row above it).
    → (q [N, C, H, K], k [N, C, G, K], v [N, C, G, K] in cfg.dtype, tails
    {"z", "c", "v"}: [N, C, W] each, what a later token's boundary reads)."""
    N, C, _D = x.shape
    H, G, K, dt = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.dtype
    g = H // G
    u = _rms_norm(x, layer["ln1_scale"], cfg.norm_eps)
    z = jnp.concatenate([u @ layer["wq"].astype(dt),
                         u @ layer["wk"].astype(dt)], axis=-1)   # [N, C, ZC]
    a = layer["conv0_w"].astype(_F32)
    c = (a[0] * z.astype(_F32) + a[1] * _shift(z, boundary("z", z)).astype(_F32)
         + layer["conv0_b"].astype(_F32)).astype(dt)
    heads = lambda t: t.reshape(N, C, H + G, K)
    # float32 operands (values of cfg.dtype: the products are the same):
    # XLA:CPU has no mixed-type thunk for a dot batched over a middle axis.
    group = lambda t, w: jnp.einsum("nchk,hkj->nchj", heads(t).astype(_F32),
                                    w.astype(dt).astype(_F32))
    y = (group(c, layer["conv1_w"][0])
         + group(_shift(c, boundary("c", c)), layer["conv1_w"][1])
         + layer["conv1_b"].astype(_F32).reshape(H + G, K))
    zh = heads(z.astype(_F32))
    q_t, k_t = zh[:, :, :H], zh[:, :, H:]
    q = y[:, :, :H] + 0.5 * (q_t + jnp.repeat(k_t, g, axis=2))
    k = y[:, :, H:] + 0.5 * (q_t.reshape(N, C, G, g, K).mean(axis=3) + k_t)
    q = math.sqrt(K) * _l2_normalise(q)
    k = (layer["k_temp"].astype(_F32)[:, None] * math.sqrt(K)
         * _l2_normalise(k))
    q = _rotary_half(q, pos, cfg.rotary_dim, cfg.rope_theta).astype(dt)
    k = _rotary_half(k, pos, cfg.rotary_dim, cfg.rope_theta).astype(dt)
    v2u = u @ layer["wv2"].astype(dt)
    v = jnp.concatenate([u @ layer["wv1"].astype(dt),
                         _shift(v2u, boundary("v", v2u))], axis=-1)
    return q, k, v.reshape(N, C, G, K), {"z": z, "c": c, "v": v2u}


@jax.named_scope(scopes.MOE_ROUTE)
def _route(cfg: ZayaConfig, layer, u, r):
    """The MLP router, float32 throughout. u [M, D], r [M, R] (the
    stream of the layer before) → (expert [M] int32, gate [M] f32, r_l)."""
    w = lambda name: layer[name].astype(_F32)
    dot = functools.partial(jnp.matmul, precision=_HIGHEST)
    r = dot(u.astype(_F32), w("r_down")) + w("r_down_b") + w("r_gamma") * r
    h = _rms_norm(r, w("r_norm"), cfg.norm_eps)
    gelu = functools.partial(jax.nn.gelu, approximate=False)
    h = gelu(dot(h, w("r_w1")) + w("r_b1"))
    h = gelu(dot(h, w("r_w2")) + w("r_b2"))
    p = jax.nn.softmax(dot(h, w("r_w3")), axis=-1)
    expert = jnp.argmax(p + w("r_beta"), axis=-1).astype(jnp.int32)
    gate = jnp.take_along_axis(p, expert[:, None], axis=-1)[:, 0]
    return expert, gate, r


def _finish_block(cfg: ZayaConfig, layer, experts, l, x, attn, r, valid):
    """From the attention output to the layer's end. attn [N, C, H, K];
    r [N, C, R] float32; valid [N, C] bool (rows that carry a token: the
    others reach no expert); `experts` the three whole expert stacks
    [L, E, ...] and `l` the layer index.
    → (x, r_l, counts [E] int32: rows each expert received)."""
    N, C, D = x.shape
    with jax.named_scope(scopes.ATTN_OUT):
        f = attn.reshape(N, C, -1) @ layer["wo"].astype(cfg.dtype)
        x = _residual(x, f, layer, "res1")
    with jax.named_scope(scopes.MOE_ROUTE):
        u = _rms_norm(x, layer["ln2_scale"], cfg.norm_eps).reshape(N * C, D)
    expert, gate, r = _route(cfg, layer, u, r.reshape(N * C, -1))
    y, counts = token_choice_experts(
        u, expert, gate, *experts, layer=l, valid=valid.reshape(-1))
    with jax.named_scope(scopes.MOE_ROUTE):
        x = _residual(x, y.reshape(N, C, D), layer, "res2")
    return x, r.reshape(N, C, -1), counts


def _stacked(cfg: ZayaConfig, params):
    """(scanned per-layer leaves in their compute type, expert stacks)."""
    cast = lambda k, v: v if k in _ROUTER_KEYS else v.astype(cfg.dtype)
    stacked = {k: cast(k, v) for k, v in params.items()
               if k not in _TOP_KEYS + _EXPERT_KEYS}
    return stacked, tuple(params[k].astype(cfg.dtype) for k in _EXPERT_KEYS)


@jax.named_scope(scopes.EMBED)
def _embed(cfg: ZayaConfig, params, tokens):
    x = params["wte"].astype(cfg.dtype)[tokens]
    return x, jnp.zeros(tokens.shape + (cfg.router_dim,), _F32)


_head = functools.partial(blocks.tied_head, _rms_norm)


# ------------------------------------------ full sequence (tests, no cache)

def forward(cfg: ZayaConfig, params, tokens):
    """tokens [B, S] → logits [B, S, V] float32: every row a whole
    sequence from position 0, plain causal attention, no pool."""
    B, S = tokens.shape
    g = cfg.n_heads // cfg.n_kv_heads
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    x, r = _embed(cfg, params, tokens)
    stacked, experts = _stacked(cfg, params)
    zero = lambda _name, full: jnp.zeros(
        (full.shape[0], full.shape[2]), full.dtype)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]

    def body(carry, inputs):
        x, r = carry
        l, layer = inputs
        q, k, v, _tails = _attn_inputs(cfg, layer, x, pos, zero)
        with jax.named_scope(scopes.ATTN_KERNEL):
            k, v = (jnp.repeat(t, g, axis=2) for t in (k, v))
            s = jnp.einsum("bshk,bthk->bhst", q, k,
                           preferred_element_type=_F32)
            s = jnp.where(causal[None, None],
                          s / math.sqrt(cfg.head_dim), -1e30)
            attn = jnp.einsum("bhst,bthk->bshk",
                              jax.nn.softmax(s, axis=-1).astype(cfg.dtype), v)
        x, r, _counts = _finish_block(cfg, layer, experts, l, x, attn, r,
                                      jnp.ones((B, S), bool))
        return (x, r), None

    (x, _r), _ = jax.lax.scan(body, (x, r),
                              (jnp.arange(cfg.n_layers), stacked))
    return _head(cfg, params, x)


# --------------------------------------------------------- the paged pool

def init_paged_kv(cfg: ZayaConfig, n_pages: int, page_size: int,
                  n_slots: int, kv_dtype: str | None = None):
    """The pool pytree the paged programs carry, donated: K and V pages
    ``[L, P+1, page_size, G*K]`` (row 0 the null page; G KV heads, as
    ops/paged_attention.py reads them), the slot state
    ``[L, n_slots+1, state_width]`` (the last row the null slot) and the
    decode steps' running expert counters (`COUNTERS`)."""
    if kv_dtype not in (None, "bf16"):
        raise ValueError(f"the zaya family's pool is bf16, got {kv_dtype!r}")
    shape = (cfg.n_layers, n_pages + 1, page_size,
             cfg.n_kv_heads * cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype),
            "slot_state": jnp.zeros(
                (cfg.n_layers, n_slots + 1, cfg.state_width), cfg.dtype),
            "moe_counters": jnp.zeros(len(COUNTERS), jnp.uint32)}


# `blocks.COUNTERS` without its last two, `rows_held` and `rows_over`:
# every expert is held here, a row has one choice, and the expert layer's
# block is every row.
COUNTERS = blocks.COUNTERS[:-2]


def _count(counts):
    return jnp.stack([jnp.uint32(1), jnp.sum(counts > 0).astype(jnp.uint32),
                      jnp.max(counts).astype(jnp.uint32),
                      jnp.sum(counts).astype(jnp.uint32)])


@jax.named_scope(scopes.SLOT_STATE)
def _write_state(pool, l, rows, tails):
    """tails {"z", "c", "v"}: [N, W] each → slot-state rows `rows` of
    layer l (several rows may name the null slot)."""
    new = jnp.concatenate([tails[n] for n in ("z", "c", "v")], axis=-1)
    return {**pool,
            "slot_state": pool["slot_state"].at[l, rows].set(new)}


def _chunk_forward(cfg: ZayaConfig, params, tokens, pool, tables, offsets,
                   n_valid, slots, attn_impl: str):
    """N chunk rows written into their slots' pages, each at its own
    offset, and the slot state carried: a row reads its boundary from
    the row of THIS dispatch that holds the same slot's chunk before it
    (the engine packs a prompt's consecutive chunks into one dispatch),
    else from the slot state, else (offset 0: a prompt's start) zeros;
    the LAST live row of a slot in the dispatch writes the state back.
    → (hidden states [N, C, D], updated pool)."""
    N, C = tokens.shape
    ps = pool["k"].shape[2]
    null_slot = pool["slot_state"].shape[1] - 1
    sl = _state_slices(cfg)
    rel, row = jnp.arange(C), jnp.arange(N)
    pos = offsets[:, None] + rel[None, :]
    valid = rel[None, :] < n_valid[:, None]
    with jax.named_scope(scopes.SLOT_STATE):
        live = n_valid > 0
        same = ((slots[:, None] == slots[None, :])
                & live[:, None] & live[None, :])
        chain = jnp.max(jnp.where(same & (row[None, :] < row[:, None]),
                                  row[None, :], -1), axis=1)      # [N]
        is_last = live & ~jnp.any(same & (row[None, :] > row[:, None]),
                                  axis=1)
        state_rows = jnp.where(is_last, slots, null_slot)
        last_tok = jnp.maximum(n_valid - 1, 0)[:, None, None]
    at_last = lambda full: jnp.take_along_axis(full, last_tok, axis=1)[:, 0]
    # K/V write targets, as models/paged_kv._chunk_paged_forward sets them.
    with jax.named_scope(scopes.ATTN_KV_WRITE):
        page_idx = jnp.minimum(pos // ps, tables.shape[1] - 1)
        write_pages = jnp.where(
            valid, jnp.take_along_axis(tables, page_idx, axis=1),
            0).reshape(-1)
        write_offs = (pos % ps).reshape(-1)
    kv_lens = offsets + n_valid
    attend = blocks.attend_fn(attn_impl, chunk=True)
    x, r = _embed(cfg, params, tokens)
    stacked, experts = _stacked(cfg, params)

    def body(carry, layer, l, pool):
        x, r = carry
        with jax.named_scope(scopes.SLOT_STATE):
            state = pool["slot_state"][l][slots]                  # [N, W]

        def boundary(name, full):
            before = jnp.where(chain[:, None] >= 0,
                               at_last(full)[jnp.maximum(chain, 0)],
                               state[:, sl[name]])
            return jnp.where((offsets == 0)[:, None], 0, before)

        q, k, v, tails = _attn_inputs(cfg, layer, x, pos, boundary)
        pool = blocks.write_kv(pool, l, write_pages, write_offs, k, v)
        with jax.named_scope(scopes.ATTN_KERNEL):
            attn = attend(q, pool["k"], pool["v"], l, tables, offsets,
                          kv_lens, sm_scale=1.0 / math.sqrt(cfg.head_dim))
        with jax.named_scope(scopes.SLOT_STATE):
            lasts = {n: at_last(t) for n, t in tails.items()}
        pool = _write_state(pool, l, state_rows, lasts)
        x, r, _counts = _finish_block(cfg, layer, experts, l, x, attn, r,
                                      valid)
        return (x, r), pool

    (x, _r), pool = scan_pool_layers(body, (x, r), stacked, pool)
    return x, pool


def _decode_once(cfg: ZayaConfig, params, tokens, pool, positions, tables,
                 attn_impl: str):
    """All B slots advance one token: row b IS slot b. A row whose table
    is all null (an idle slot, or one still mid-prefill) writes the null
    page and the null slot, reaches no expert and counts nowhere, so a
    prompt's state survives the decode windows between its chunks.
    → (logits [B, V] fp32, updated pool)."""
    B = tokens.shape[0]
    ps = pool["k"].shape[2]
    null_slot = pool["slot_state"].shape[1] - 1
    sl = _state_slices(cfg)
    active = tables[:, 0] > 0
    state_rows = jnp.where(active, jnp.arange(B), null_slot)
    pos = positions[:, None]
    with jax.named_scope(scopes.ATTN_KV_WRITE):
        write_page = jnp.take_along_axis(
            tables,
            jnp.minimum(positions // ps, tables.shape[1] - 1)[:, None],
            axis=1)[:, 0]
        write_off = positions % ps
    attend = blocks.attend_fn(attn_impl, chunk=False)
    x, r = _embed(cfg, params, tokens[:, None])
    stacked, experts = _stacked(cfg, params)

    def body(carry, layer, l, pool):
        x, r, counters = carry
        with jax.named_scope(scopes.SLOT_STATE):
            state = pool["slot_state"][l, :B]
        q, k, v, tails = _attn_inputs(
            cfg, layer, x, pos, lambda name, _full: state[:, sl[name]])
        pool = blocks.write_kv(pool, l, write_page, write_off, k, v)
        with jax.named_scope(scopes.ATTN_KERNEL):
            attn = attend(q[:, 0], pool["k"], pool["v"], l, tables,
                          positions + 1,
                          sm_scale=1.0 / math.sqrt(cfg.head_dim))
        pool = _write_state(pool, l, state_rows,
                            {n: t[:, 0] for n, t in tails.items()})
        x, r, counts = _finish_block(cfg, layer, experts, l, x,
                                     attn[:, None], r, active[:, None])
        with jax.named_scope(scopes.COUNTERS):
            counters = counters + _count(counts)
        return (x, r, counters), pool

    (x, _r, counters), pool = scan_pool_layers(
        body, (x, r, pool["moe_counters"]), stacked, pool)
    return _head(cfg, params, x[:, 0]), {**pool, "moe_counters": counters}


(prefill_chunk_paged, decode_step_paged, _decode_sample_paged,
 decode_multi_paged) = paged_programs(
    _chunk_forward, _decode_once, blocks.last_token_logits(_head), COUNTERS)


__all__ = [
    "ZayaConfig", "param_specs", "logical_axes", "partition_rules",
    "init_params", "num_params", "forward", "init_paged_kv",
    "prefill_chunk_paged", "decode_step_paged", "decode_multi_paged",
]
