"""Qwen3-Next-class decoder: Gated DeltaNet layers (a linear-attention
recurrence over a matrix state a head) beside gated softmax-attention
layers, under a softmax top-k router over more experts than this chip
holds and a gated shared expert.

A block of its own beside models/gpt.py, models/zaya.py and
models/laguna.py (none gets a switch for any of this). Source: the
model's config.json (`model_type: qwen3_next`) and HF
`modeling_qwen3_next.py`; benchmarks/configs/qwen3-next-80b-a3b.json
lists what each fixes and what is assumed. D model width; layer l is a
FULL layer when (l + 1) % `full_interval` == 0, else a LINEAR one:

  x <- x + Mixer(norm(x));  x <- x + MoE(norm(x));  no bias anywhere
  norm   x / sqrt(mean(x^2) + eps) * (1 + w), float32 (zero-centred)
  linear u the normed input; W_qkvz u viewed [Hk, dk + dk + r dv + r dv]
         (r = Hv / Hk) into q, k, v, z; W_ba u viewed [Hk, r + r] into b,
         a. q | k | v flattened pass a causal depthwise convolution of
         `conv_taps` taps, then SiLU; q, k repeated to Hv heads, L2
         normalised, q times dk^-1/2; beta = sigmoid(b),
         g = -exp(A_log) softplus(a + dt_bias); then the gated delta
         rule over a head's state S [dk, dv] (ops/gated_delta.py);
         o <- rmsnorm(o) w_norm silu(z) a head, then W_out.
  full   W_q u viewed [H, 2 K] into query and gate; zero-centred norm a
         head on q and k; rotate-half rope on the first `rotary_dim`
         dims; causal softmax at K^-1/2 over G KV heads; the output
         times sigmoid(gate); W_o.
  MoE    p = softmax(u W_r) in float32 over all `n_experts_routed`; the
         `top_k` largest choose; gate_e = p_e / sum of the chosen p;
         MoE(u) = sigmoid(u . w_sg) Shared(u) + sum gate_e Expert_e(u).
  final norm, then an untied head.

**One chip's share.** As models/laguna.py: the weights hold `n_experts`
of the routed experts (`first_expert` ..) and `vocab_size` rows of the
vocabulary; the router scores every expert, the gates are normalised
over all `top_k` choices, `ops.moe.token_choice_experts` returns the
held experts' part and no exchange is built.

**Two kinds of per-request memory in one pool pytree.** Full layers keep
``pool["k"], pool["v"]`` ``[n_full, P+1, page, G*K]``, addressed by the
engine's page tables like every family's. Linear layers keep a
RECURRENT STATE by the slot: ``pool["gdn_state"]`` ``[n_linear,
n_slots+1, Hv, dk, dv]`` float32 (the model's own precision for it; 2
MiB a layer and slot at the published sizes) and ``pool["gdn_conv"]``
``[n_linear, n_slots+1, taps-1, channels]``, the convolution's last
inputs. The last row is the null slot, which idle rows name. The state
is never gathered, scattered by page or copied: a decode step's batch IS
the slot array, and the pool is donated. A chunk row starts from zeros
at offset 0 (which is the reset: a reused or re-prefilled slot reads
nothing of its predecessor), else from the row of THIS dispatch that
holds the same slot's chunk before it, else from the slot's state: a
recurrence cannot read a chained row's boundary in parallel, as
models/zaya.py's one-token state can, so the state pass walks a
dispatch's rows in order (`ops.gated_delta.gdn_chunk_scan`). A row that
another row continues is a full chunk (only a prompt's LAST chunk is
short), which the convolution's tail relies on. The last live row of a
slot writes state and tail back.

The four paged programs are `paged_kv.paged_programs` over the chunk
forward and the decode step (names, donation and the decode window are
its). Eight or so layers of two shapes are walked in Python, each mixer kind
indexing its own stack; the MLP's leaves are one stack over all layers
and the experts' go to the grouped matmul whole (`layer=`).
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
from ray_tpu.models.blocks import (COUNTERS, attend_fn, counter_row,
                                   dispatch_order, gated_mlp,
                                   last_token_logits, untied_head, write_kv)
from ray_tpu.models.blocks import rms_norm_centred as _norm
from ray_tpu.models.paged_kv import paged_programs
from ray_tpu.ops.gated_delta import (
    gdn_chunk_scan, gdn_decode_step, reference_gdn_decode_step)
from ray_tpu.ops.moe import token_choice_experts

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936         # rows of embedding and head held here
    d_model: int = 2048
    n_layers: int = 48
    full_interval: int = 4           # every fourth layer is a full one
    n_heads: int = 16                # full layers: query heads
    n_kv_heads: int = 2
    head_dim: int = 256
    lin_k_heads: int = 16            # linear layers: key heads
    lin_v_heads: int = 32            # value heads (and states) a layer
    lin_k_dim: int = 128
    lin_v_dim: int = 128
    conv_taps: int = 4
    n_experts: int = 512             # routed experts HELD here
    n_experts_routed: int = 512      # the router's outputs
    first_expert: int = 0            # the first held expert's global id
    top_k: int = 10
    d_ff: int = 512                  # one routed expert's width
    d_ff_shared: int = 512
    rope_theta: float = 10_000_000.0
    rotary_dim: int = 64             # per-head dims that get rotary
    norm_eps: float = 1e-6
    scan_block: int = 64             # tokens a block of the chunked scan
    max_seq: int = 4096
    dtype: Any = jnp.bfloat16        # activation/compute dtype
    param_dtype: Any = jnp.float32

    family: ClassVar[str] = "qwen3_next"  # models/serving.py

    @classmethod
    def tiny(cls, **kw) -> "Qwen3NextConfig":
        """CPU-test size that keeps every ratio: two periods of 3 linear
        : 1 full; 2 value heads a key head; 8 query heads a KV head;
        rope on a quarter of a head; 8 experts top-3 with 4 held; a scan
        block a chunk row holds twice."""
        base = dict(vocab_size=256, d_model=64, n_layers=8, n_heads=16,
                    n_kv_heads=2, head_dim=16, lin_k_heads=2, lin_v_heads=4,
                    lin_k_dim=16, lin_v_dim=16, n_experts=4,
                    n_experts_routed=8, top_k=3, d_ff=32, d_ff_shared=32,
                    rope_theta=10_000.0, rotary_dim=4, scan_block=16,
                    max_seq=256)
        return cls(**{**base, **kw})

    @property
    def kinds(self) -> tuple:
        """"linear" or "full" for each of the n_layers layers."""
        return tuple("full" if (l + 1) % self.full_interval == 0 else "linear"
                     for l in range(self.n_layers))

    def count(self, kind: str) -> int:
        return sum(k == kind for k in self.kinds)

    def index(self, l: int) -> tuple[str, int]:
        """(mixer kind, index in its stack) of layer l."""
        kind = self.kinds[l]
        return kind, sum(k == kind for k in self.kinds[:l])

    @property
    def conv_channels(self) -> int:          # q | k | v of a linear layer
        return (2 * self.lin_k_heads * self.lin_k_dim
                + self.lin_v_heads * self.lin_v_dim)


# The experts' stacks, handed whole to the grouped matmul. A linear
# layer's leaves carry the prefix "g_", a full layer's "f_".
_EXPERT_KEYS = ("w_gate", "w_up", "w_down")
# The dense matrices a layer: each a matmul's right operand, picked out
# of its stack by the layer walk's static index (`params[k][i]`).
_PLANE_KEYS = ("g_qkvz", "g_ba", "g_out", "f_wq", "f_wk", "f_wv", "f_wo",
               "router", "s_gate", "s_up", "s_down")


def param_specs(cfg: Qwen3NextConfig) -> dict[str, dict[str, Any]]:
    """name → {shape, init[, scale]}: one stack a mixer kind, in layer
    order within the kind; the MLP's leaves one stack over all layers.
    Zero-centred norm weights start at 0 (a scale of 1). `g_dt_bias`
    ones and `g_A_log` the log of U(0, 16) are the model's own start
    (`init_params` makes the second; a loader that fills leaves from
    normal / ones / zeros alone overrides both, as
    benchmarks/families/qwen3_next.py does)."""
    D, V, L = cfg.d_model, cfg.vocab_size, cfg.n_layers
    H, G, K = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Hk, Hv, dk, dv = (cfg.lin_k_heads, cfg.lin_v_heads, cfg.lin_k_dim,
                      cfg.lin_v_dim)
    nl, nf = cfg.count("linear"), cfg.count("full")
    norm = lambda *s, scale=0.02: {"init": "normal", "scale": scale,
                                   "shape": s}
    resid = lambda *s: norm(*s, scale=0.02 / math.sqrt(2 * L))
    ones = lambda *s: {"init": "ones", "shape": s}
    zeros = lambda *s: {"init": "zeros", "shape": s}
    E, F, Fs = cfg.n_experts, cfg.d_ff, cfg.d_ff_shared
    return {
        "wte": norm(V, D), "lm_head": norm(D, V), "ln_f_scale": zeros(D),
        "ln1_scale": zeros(L, D), "ln2_scale": zeros(L, D),
        "g_qkvz": norm(nl, D, 2 * Hk * dk + 2 * Hv * dv),
        "g_ba": norm(nl, D, 2 * Hv),
        "g_conv": norm(nl, cfg.conv_taps, cfg.conv_channels, scale=0.5),
        "g_dt_bias": ones(nl, Hv),
        "g_A_log": {"init": "log_uniform", "high": 16.0, "shape": (nl, Hv)},
        "g_norm": ones(nl, dv), "g_out": resid(nl, Hv * dv, D),
        "f_wq": norm(nf, D, 2 * H * K), "f_wk": norm(nf, D, G * K),
        "f_wv": norm(nf, D, G * K), "f_qnorm": zeros(nf, K),
        "f_knorm": zeros(nf, K), "f_wo": resid(nf, H * K, D),
        "router": norm(L, D, cfg.n_experts_routed),
        "s_gate": norm(L, D, Fs), "s_up": norm(L, D, Fs),
        "s_down": resid(L, Fs, D), "s_gate_w": norm(L, D),
        "w_gate": norm(L, E, D, F), "w_up": norm(L, E, D, F),
        "w_down": resid(L, E, F, D)}


def partition_rules() -> tuple:
    """Every leaf replicated: the family serves at tp = 1 only
    (models/serving.py refuses more); the table exists so that the
    shared loaders find a rule for each leaf."""
    from jax.sharding import PartitionSpec

    return ((r".*", PartitionSpec()),)


def lay_out(_cfg: Qwen3NextConfig, params) -> dict:
    """The tree as the serving programs want it (models/serving.py
    `lay_out`): each dense matrix a layer (`_PLANE_KEYS`) a tuple of its
    stack's per-layer arrays, which `params[k][i]` reads as it reads the
    stack. Handed a stack, a program slices all of its layers out at
    its start and only one fits fast memory: the rest go back to HBM and
    each layer's matmul reads its plane a second time (at the 80B
    widths `g_qkvz` alone: 502 MB a step). The experts' stacks stay
    whole (the grouped matmul takes the stack and a layer), the vectors
    stay stacks (kilobytes). Idempotent."""
    return {
        name: tuple(a[i] for i in range(a.shape[0]))
        if name in _PLANE_KEYS and not isinstance(a, tuple) else a
        for name, a in params.items()}


def init_params(cfg: Qwen3NextConfig, rng: jax.Array) -> dict[str, jax.Array]:
    specs = param_specs(cfg)
    keys = jax.random.split(rng, len(specs))
    params = {}
    for key, (name, spec) in zip(keys, sorted(specs.items())):
        shape, dt = spec["shape"], cfg.param_dtype
        if spec["init"] == "normal":
            params[name] = jax.random.normal(key, shape, dt) * spec["scale"]
        elif spec["init"] == "log_uniform":
            params[name] = jnp.log(jax.random.uniform(
                key, shape, dt, 1e-3, spec["high"]))
        else:
            fill = jnp.ones if spec["init"] == "ones" else jnp.zeros
            params[name] = fill(shape, dt)
    return params


# ------------------------------------------------------------- the block

def _rope(cfg: Qwen3NextConfig, x, pos):
    """Rotate-half rotary on the first `rotary_dim` dims of each head.
    x [N, C, h, K] float32, pos [N, C] absolute positions."""
    d = cfg.rotary_dim
    half = d // 2
    inv_freq = float(cfg.rope_theta) ** (
        -np.arange(0, d, 2, dtype=np.float64) / d)
    ang = (pos.astype(_F32)[..., None, None]
           * jnp.asarray(inv_freq, _F32))                   # [N, C, 1, half]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2, rest = x[..., :half], x[..., half:d], x[..., d:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _unit(x):
    """x / |x| over the last axis, float32, as the model computes it."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


@jax.named_scope(scopes.GDN_IN)
def _gdn_inputs(cfg: Qwen3NextConfig, params, l: int, x, valid, boundary):
    """Linear layer l up to what the delta rule takes. x [N, C, D]; valid
    [N, C] bool (a token that is none leaves the state alone: g = 0,
    beta = 0); `boundary(mixed)` → [N, taps-1, channels]: the
    convolution's inputs BEFORE each row's first token, given the rows'
    own `mixed` [N, C, channels].
    → (q, k [N, C, Hk, dk] float32 for the KEY heads, v [N, C, Hv, dv] in
    cfg.dtype, z likewise, g, beta [N, C, Hv] float32, ext [N, taps-1+C,
    channels]: the convolution's inputs with the boundary in front)."""
    N, C, _D = x.shape
    i, dt = cfg.index(l)[1], cfg.dtype
    Hk, Hv, dk, dv = (cfg.lin_k_heads, cfg.lin_v_heads, cfg.lin_k_dim,
                      cfg.lin_v_dim)
    r = Hv // Hk
    u = _norm(x, params["ln1_scale"][l], cfg.norm_eps)
    qkvz = (u @ params["g_qkvz"][i].astype(dt)).reshape(
        N, C, Hk, 2 * dk + 2 * r * dv)
    q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
    ba = (u @ params["g_ba"][i].astype(dt)).astype(_F32).reshape(
        N, C, Hk, 2 * r)
    b, a = ba[..., :r].reshape(N, C, Hv), ba[..., r:].reshape(N, C, Hv)
    flat = lambda t: t.reshape(N, C, -1)
    mixed = jnp.concatenate([flat(q), flat(k), flat(v)], axis=-1)
    ext = jnp.concatenate([boundary(mixed).astype(dt), mixed], axis=1)
    taps = params["g_conv"][i].astype(dt).astype(_F32)      # [taps, ch]
    conv = sum(taps[j] * ext[:, j:j + C].astype(_F32)
               for j in range(cfg.conv_taps))
    mixed = jax.nn.silu(conv).astype(dt)
    q, k, v = jnp.split(mixed, [Hk * dk, 2 * Hk * dk], axis=-1)
    heads = lambda t: t.astype(_F32).reshape(N, C, Hk, dk)
    q, k = _unit(heads(q)) / math.sqrt(dk), _unit(heads(k))
    beta = jax.nn.sigmoid(b)
    g = (-jnp.exp(params["g_A_log"][i].astype(_F32))
         * jax.nn.softplus(a + params["g_dt_bias"][i].astype(_F32)))
    live = valid[..., None]
    return (q, k, v.reshape(N, C, Hv, dv), z.reshape(N, C, Hv, dv),
            jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0), ext)


@jax.named_scope(scopes.GDN_OUT)
def _gdn_output(cfg: Qwen3NextConfig, params, l: int, x, o, z):
    """From the delta rule's output o [N, C, Hv, dv] float32 to the
    sublayer's end: the gated norm a head, W_out, the residual."""
    N, C, _D = x.shape
    i, dt = cfg.index(l)[1], cfg.dtype
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + cfg.norm_eps)
    o = (o * params["g_norm"][i].astype(_F32)
         * jax.nn.silu(z.astype(_F32))).astype(dt)
    return x + o.reshape(N, C, -1) @ params["g_out"][i].astype(dt)


@jax.named_scope(scopes.ATTN_IN)
def _attn_inputs(cfg: Qwen3NextConfig, params, l: int, x, pos):
    """Full layer l's attention up to q, k, v and the output's gate.
    → (q [N, C, H, K], k, v [N, C, G, K] in cfg.dtype, gate [N, C, H*K]
    float32)."""
    N, C, _D = x.shape
    i, dt = cfg.index(l)[1], cfg.dtype
    H, G, K = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    u = _norm(x, params["ln1_scale"][l], cfg.norm_eps)
    qg = (u @ params["f_wq"][i].astype(dt)).reshape(N, C, H, 2 * K)
    q, gate = qg[..., :K], qg[..., K:]
    k = (u @ params["f_wk"][i].astype(dt)).reshape(N, C, G, K)
    v = (u @ params["f_wv"][i].astype(dt)).reshape(N, C, G, K)
    q = _norm(q, params["f_qnorm"][i], cfg.norm_eps).astype(_F32)
    k = _norm(k, params["f_knorm"][i], cfg.norm_eps).astype(_F32)
    return (_rope(cfg, q, pos).astype(dt), _rope(cfg, k, pos).astype(dt), v,
            jax.nn.sigmoid(gate.astype(_F32)).reshape(N, C, H * K))


@jax.named_scope(scopes.ATTN_OUT)
def _attn_output(cfg: Qwen3NextConfig, params, l: int, x, attn, gate):
    N, C, _D = x.shape
    i, dt = cfg.index(l)[1], cfg.dtype
    o = (attn.astype(_F32).reshape(N, C, -1) * gate).astype(dt)
    return x + o @ params["f_wo"][i].astype(dt)


@jax.named_scope(scopes.MOE_ROUTE)
def _route(cfg: Qwen3NextConfig, w_router, u):
    """The router, float32 throughout. u [M, D] → (experts [M, k] int32
    global ids, gates [M, k] float32: the chosen probabilities
    normalised over all k choices, held here or not)."""
    p = jax.nn.softmax(jnp.matmul(u.astype(_F32), w_router.astype(_F32),
                                  precision=_HIGHEST), axis=-1)
    top, chosen = jax.lax.top_k(p, cfg.top_k)
    return (chosen.astype(jnp.int32),
            top / jnp.sum(top, axis=-1, keepdims=True))


def _shared_gate(u, w):
    """sigmoid(u . w) [M, 1] float32: the shared expert's gate."""
    return jax.nn.sigmoid(jnp.matmul(u, w.astype(u.dtype)[:, None],
                                     preferred_element_type=_F32))


def _moe(cfg: Qwen3NextConfig, params, l: int, x, valid):
    """Layer l's sparse MLP with its norm and residual. valid [N, C]
    bool (rows that carry a token: the others reach no expert).
    → (x, counts [n_experts] int32 rows each held expert received)."""
    N, C, D = x.shape
    dt = cfg.dtype
    with jax.named_scope(scopes.MLP):
        u = _norm(x, params["ln2_scale"][l], cfg.norm_eps).reshape(N * C, D)
    chosen, gates = _route(cfg, params["router"][l], u)
    with jax.named_scope(scopes.MOE_EXPERTS):
        experts = tuple(params[k].astype(dt) for k in _EXPERT_KEYS)
    routed, counts = token_choice_experts(
        u, chosen, gates, *experts,
        first_expert=cfg.first_expert, layer=l, valid=valid.reshape(-1),
        n_routed=cfg.n_experts_routed)
    with jax.named_scope(scopes.MLP):
        shared = gated_mlp(u, params["s_gate"][l], params["s_up"][l],
                           params["s_down"][l])
        f = (_shared_gate(u, params["s_gate_w"][l]) * shared
             + routed.astype(_F32)).astype(dt)
        return x + f.reshape(N, C, D), counts


_head = functools.partial(untied_head, _norm)


def _repeat_heads(cfg: Qwen3NextConfig, t):
    """Key heads [N, C, Hk, dk] → one a value head [N, C, Hv, dk]."""
    return jnp.repeat(t, cfg.lin_v_heads // cfg.lin_k_heads, axis=2)


# ------------------------------------------ full sequence (tests, no cache)

def forward(cfg: Qwen3NextConfig, params, tokens):
    """tokens [B, S] → logits [B, S, V] float32: every row a whole
    sequence from position 0 and a zero state, plain masked attention,
    no pool. The linear layers run the chunked scan over the row (padded
    to whole blocks with tokens that leave the state alone)."""
    B, S = tokens.shape
    pad = -S % cfg.scan_block
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    valid = jnp.ones((B, S), bool)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    zeros = lambda mixed: jnp.zeros(
        (B, cfg.conv_taps - 1, mixed.shape[-1]), mixed.dtype)
    padded = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens]
    for l, kind in enumerate(cfg.kinds):
        if kind == "linear":
            q, k, v, z, g, beta, _ext = _gdn_inputs(cfg, params, l, x, valid,
                                                    zeros)
            with jax.named_scope(scopes.GDN_SCAN):
                o, _finals = gdn_chunk_scan(
                    *(padded(t) for t in (_repeat_heads(cfg, q),
                                          _repeat_heads(cfg, k), v, g, beta)),
                    jnp.zeros((B, cfg.lin_v_heads, cfg.lin_k_dim,
                               cfg.lin_v_dim), _F32),
                    jnp.full(B, -1, jnp.int32), jnp.ones(B, bool),
                    block=cfg.scan_block)
            x = _gdn_output(cfg, params, l, x, o[:, :S], z)
        else:
            q, k, v, gate = _attn_inputs(cfg, params, l, x, pos)
            with jax.named_scope(scopes.ATTN_KERNEL):
                rep = cfg.n_heads // cfg.n_kv_heads
                k, v = (jnp.repeat(t, rep, axis=2) for t in (k, v))
                s = jnp.einsum("bshk,bthk->bhst", q, k,
                               preferred_element_type=_F32)
                s = jnp.where(causal[None, None],
                              s / math.sqrt(cfg.head_dim), -1e30)
                attn = jnp.einsum(
                    "bhst,bthk->bshk",
                    jax.nn.softmax(s, axis=-1).astype(cfg.dtype), v)
            x = _attn_output(cfg, params, l, x, attn, gate)
        x, _counts = _moe(cfg, params, l, x, valid)
    return _head(cfg, params, x)


# --------------------------------------------------------- the paged pool

# The pool's leaves that are a state by the slot (models/serving.py).
SLOT_STATE_LEAVES = ("gdn_state", "gdn_conv")


def init_paged_kv(cfg: Qwen3NextConfig, n_pages: int, page_size: int,
                  n_slots: int, kv_dtype: str | None = None):
    """The pool pytree the paged programs carry, donated: the full
    layers' pages ``[n_full, P+1, page_size, G*K]`` (row 0 the null
    page), the linear layers' recurrent state ``[n_linear, n_slots+1,
    Hv, dk, dv]`` float32 and convolution tail ``[n_linear, n_slots+1,
    taps-1, channels]`` (the last row the null slot), and the decode
    steps' running expert counters (`blocks.COUNTERS`)."""
    if kv_dtype not in (None, "bf16"):
        raise ValueError(
            f"the qwen3_next family's pool is bf16, got {kv_dtype!r}")
    nl = cfg.count("linear")
    pages = (cfg.count("full"), n_pages + 1, page_size,
             cfg.n_kv_heads * cfg.head_dim)
    return {"k": jnp.zeros(pages, cfg.dtype), "v": jnp.zeros(pages, cfg.dtype),
            "gdn_state": jnp.zeros(
                (nl, n_slots + 1, cfg.lin_v_heads, cfg.lin_k_dim,
                 cfg.lin_v_dim), _F32),
            "gdn_conv": jnp.zeros(
                (nl, n_slots + 1, cfg.conv_taps - 1, cfg.conv_channels),
                cfg.dtype),
            "moe_counters": jnp.zeros(len(COUNTERS), jnp.uint32)}


def _chunk_forward(cfg: Qwen3NextConfig, params, tokens, pool, tables,
                   offsets, n_valid, slots, attn_impl: str):
    """N chunk rows written into their slots' pages, each at its own
    offset, and the linear layers' state carried through the dispatch's
    rows in order (the module's docstring has the rule).
    → (hidden states [N, C, D], updated pool)."""
    N, C = tokens.shape
    ps = pool["k"].shape[2]
    null_slot = pool["gdn_state"].shape[1] - 1
    n_tail = cfg.conv_taps - 1
    rel = jnp.arange(C)
    pos = offsets[:, None] + rel[None, :]
    valid = rel[None, :] < n_valid[:, None]
    kv_lens = offsets + n_valid
    chain, state_rows, fresh = dispatch_order(slots, offsets, n_valid,
                                              null_slot)
    with jax.named_scope(scopes.SLOT_STATE):
        # The last taps-1 inputs of a row, as indices into its `ext`.
        tail_at = (n_valid[:, None] + jnp.arange(n_tail)[None, :])[..., None]
    with jax.named_scope(scopes.ATTN_KV_WRITE):
        page_idx = jnp.minimum(pos // ps, tables.shape[1] - 1)
        write_pages = jnp.where(
            valid, jnp.take_along_axis(tables, page_idx, axis=1),
            0).reshape(-1)
        write_offs = (pos % ps).reshape(-1)
    attend = attend_fn(attn_impl, chunk=True)
    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens]
    for l, kind in enumerate(cfg.kinds):
        i = cfg.index(l)[1]
        if kind == "linear":
            def boundary(mixed, i=i):
                # A chained row's predecessor is a full chunk: its last
                # inputs are its own last tokens.
                before = jnp.where((chain >= 0)[:, None, None],
                                   mixed[jnp.maximum(chain, 0), C - n_tail:],
                                   pool["gdn_conv"][i, slots])
                return jnp.where(fresh[:, None, None], 0, before)

            q, k, v, z, g, beta, ext = _gdn_inputs(cfg, params, l, x, valid,
                                                   boundary)
            with jax.named_scope(scopes.GDN_IN):
                tails = jnp.take_along_axis(ext, tail_at, axis=1)
                pool = {**pool, "gdn_conv":
                        pool["gdn_conv"].at[i, state_rows].set(tails)}
            with jax.named_scope(scopes.GDN_SCAN):
                o, finals = gdn_chunk_scan(
                    _repeat_heads(cfg, q), _repeat_heads(cfg, k), v, g, beta,
                    pool["gdn_state"][i, slots], chain, fresh,
                    block=cfg.scan_block)
                pool = {**pool, "gdn_state":
                        pool["gdn_state"].at[i, state_rows].set(finals)}
            x = _gdn_output(cfg, params, l, x, o, z)
        else:
            q, k, v, gate = _attn_inputs(cfg, params, l, x, pos)
            pool = write_kv(pool, i, write_pages, write_offs, k, v)
            with jax.named_scope(scopes.ATTN_KERNEL):
                attn = attend(q, pool["k"], pool["v"], i, tables, offsets,
                              kv_lens, sm_scale=1.0 / math.sqrt(cfg.head_dim))
            x = _attn_output(cfg, params, l, x, attn, gate)
        x, _counts = _moe(cfg, params, l, x, valid)
    return x, pool


def _decode_once(cfg: Qwen3NextConfig, params, tokens, pool, positions,
                 tables, attn_impl: str):
    """All B slots advance one token: row b IS slot b. A row whose table
    is all null (an idle slot, or one still mid-prefill) writes the null
    page, leaves its slot's state and tail as they are, reaches no
    expert and counts nowhere, so a prompt's state survives the decode
    windows between its chunks.
    → (logits [B, V] fp32, updated pool)."""
    B = tokens.shape[0]
    ps = pool["k"].shape[2]
    active = tables[:, 0] > 0
    pos = positions[:, None]
    live = active[:, None]
    with jax.named_scope(scopes.ATTN_KV_WRITE):
        write_page = jnp.take_along_axis(
            tables,
            jnp.minimum(positions // ps, tables.shape[1] - 1)[:, None],
            axis=1)[:, 0]
        write_off = positions % ps
    attend = attend_fn(attn_impl, chunk=False)
    step = (gdn_decode_step if attn_impl == "kernel"
            else reference_gdn_decode_step)
    repeat = cfg.lin_v_heads // cfg.lin_k_heads
    counts = []
    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens[:, None]]
    for l, kind in enumerate(cfg.kinds):
        i = cfg.index(l)[1]
        if kind == "linear":
            q, k, v, z, g, beta, ext = _gdn_inputs(
                cfg, params, l, x, live,
                lambda _mixed, i=i: pool["gdn_conv"][i, :B])
            with jax.named_scope(scopes.GDN_IN):
                tails = jnp.where(live[..., None], ext[:, 1:],
                                  pool["gdn_conv"][i, :B])
                pool = {**pool,
                        "gdn_conv": pool["gdn_conv"].at[i, :B].set(tails)}
            with jax.named_scope(scopes.GDN_SCAN):
                o, state = step(pool["gdn_state"], i, q[:, 0], k[:, 0],
                                v[:, 0].astype(_F32), g[:, 0], beta[:, 0],
                                active, repeat=repeat)
                pool = {**pool, "gdn_state": state}
            x = _gdn_output(cfg, params, l, x, o[:, None], z)
        else:
            q, k, v, gate = _attn_inputs(cfg, params, l, x, pos)
            pool = write_kv(pool, i, write_page, write_off, k, v)
            with jax.named_scope(scopes.ATTN_KERNEL):
                attn = attend(q[:, 0], pool["k"], pool["v"], i, tables,
                              positions + 1,
                              sm_scale=1.0 / math.sqrt(cfg.head_dim))
            x = _attn_output(cfg, params, l, x, attn[:, None], gate)
        x, n = _moe(cfg, params, l, x, live)
        counts.append(n)
    with jax.named_scope(scopes.COUNTERS):
        n_live = jnp.sum(active)
        counters = pool["moe_counters"] + sum(
            counter_row(cfg, n, n_live, tokens.shape[0]) for n in counts)
    return _head(cfg, params, x[:, 0]), {**pool, "moe_counters": counters}


(prefill_chunk_paged, decode_step_paged, _decode_sample_paged,
 decode_multi_paged) = paged_programs(
    _chunk_forward, _decode_once, last_token_logits(_head), COUNTERS)


__all__ = [
    "Qwen3NextConfig", "param_specs", "partition_rules", "init_params",
    "forward", "init_paged_kv", "prefill_chunk_paged", "decode_step_paged",
    "decode_multi_paged", "SLOT_STATE_LEAVES",
]
