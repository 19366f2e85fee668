"""Nemotron-H-class decoder (Nemotron-3): layers of ONE sublayer each,
whose mixer a pattern string names: a Mamba-2 mixer (a matrix state a
head under one scalar decay), a grouped-query softmax attention WITHOUT
positions, or a LATENT expert layer (two-matrix squared-ReLU experts in
a latent narrower than the model, under a sigmoid router that chooses by
a biased score, beside a shared expert); an untied head.

A block of its own beside the other families' (models/blocks.py has what
it shares with them; none gets a switch for any of this). Every other
family pairs a mixer with an MLP a layer; here the MLP IS a layer.
Source: the model's config.json (`model_type: nemotron_h`) and HF
`modeling_nemotron_h.py`; benchmarks/configs/nemotron-3-super-120b-a12b
.json lists what each fixes and what is assumed. D model width; Hm
Mamba-2 heads of P values over Ns states in Gm groups, Dn = Hm P;
H query heads over G KV heads of K; Dl the experts' latent, F their
width, Fs the shared expert's; no bias but the convolution's:

  x <- x + Mixer(norm(x)) a layer;  final norm;  untied head
  norm   x / sqrt(mean(x^2) + eps) * w, float32
  M      [z | xBC | dt] = u W_in (Dn | Dn + 2 Gm Ns | Hm); xBC through a
         causal depthwise convolution of `d_conv` taps with a bias, then
         SiLU; xBC = x [Hm, P], B [Gm, Ns], C [Gm, Ns], head h in group
         h // (Hm / Gm); dt = softplus(dt + dt_bias), a = exp(-exp(A_log)
         dt), float32; S <- a S + dt x B^T, y = S C + D x
         (ops/ssd.py); y <- norm(y * silu(z)) over each group's Dn / Gm
         values (the gate first, then the norm); W_out.
  *      q = W_q u (H x K), k, v = W_k u, W_v u (G x K); NO rope and no
         other position; causal softmax at K^-1/2; W_o.
  E      s = sigmoid(u W_r) in float32 over all `n_experts_routed`; the
         `top_k` largest of s + b choose; gate_e = `routed_scale` s_e /
         sum of the chosen s; l = u W_lat_in (D -> Dl);
         r = sum over the chosen e of gate_e W2_e relu(W1_e l)^2;
         Mixer = r W_lat_out + W_s2 relu(W_s1 u)^2: router and shared
         expert read the model's width, only the routed experts the
         latent.

**One chip's share**, as models/kimi_k2.py: the weights hold `n_experts`
of the routed experts (`first_expert` ..) and `vocab_size` rows of the
vocabulary; the router scores and chooses over all `n_experts_routed`,
`ops.moe.token_choice_experts` returns the held experts' part IN THE
LATENT, and no exchange is built (each share's r W_lat_out, plus the
shared expert once, add up to the whole layer: tests/test_nemotron_h.py).

**Two kinds of per-request memory in one pool pytree**, as
models/jamba.py: the attention layers' pages ``pool["k"], pool["v"]``
``[n_attn, P+1, page, G*K]`` addressed by the engine's page tables, and
by the slot ``pool["ssm_state"]`` ``[n_mamba, n_slots+1, Hm / p, Ns,
p P]`` float32 (a head's state transposed, p heads side by side along
the lanes: ops/ssd.py says why; 4.19 MB a layer and slot at the
published sizes) and ``pool["ssm_conv"]`` ``[n_mamba, taps-1,
n_slots+1, Dn + 2 Gm Ns]``, the convolution's last inputs, a plane of
slots a tap. The last slot is the null slot. Idle slots, a reused slot's
reset at offset 0, the rows of one dispatch that continue each other and
a prompt whose chunks are split over dispatches behave as jamba's: a
decode step's batch IS the slot array and the pool is donated; a chunk
row starts from zeros at offset 0, else from the row of THIS dispatch
that holds the same slot's chunk before it, else from the slot's state
(`blocks.dispatch_order`); the last live row of a slot writes state and
tail back.

The weights are stacks over the layers of their kind. Where a stretch of
the pattern repeats, it is ONE `lax.fori_loop` whose body reads its
layers' planes out of the stacks by the loop's index, where they lie, as
models/jamba.py's runs: the published period's first six layers are "ME"
three times, so a program is a loop body of two sublayers and five
sublayers alone to the compiler, not eleven (a cell warms twelve
programs before its window opens). The four paged programs are
`paged_kv.paged_programs` over the chunk forward and the decode step.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, ClassVar

import jax
import jax.numpy as jnp

from ray_tpu.models import blocks
from ray_tpu.models.blocks import (attend_fn, causal_conv, dispatch_order,
                                   last_token_logits, rms_norm, untied_head,
                                   write_kv)
from ray_tpu.models.paged_kv import paged_programs
from ray_tpu.ops import scopes
from ray_tpu.ops.moe import token_choice_experts
from ray_tpu.ops.selective_scan import (reference_ssm_conv_step,
                                        ssm_conv_step)
from ray_tpu.ops.ssd import (pack_state, packed_heads,
                             reference_ssd_decode_step, reference_ssd_scan,
                             ssd_chunk_scan, ssd_decode_step, unpack_state)

_F32 = jnp.float32

# The published pattern's first period (88 layers: 40 M, 40 E, 8 *).
_PERIOD = "MEMEMEM*EME"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072         # rows of embedding and head held here
    d_model: int = 4096
    pattern: str = _PERIOD           # a layer's mixer: M, * or E
    m_heads: int = 128               # Mamba-2 heads
    m_head_dim: int = 64
    d_state: int = 128
    m_groups: int = 8
    d_conv: int = 4
    chunk_size: int = 128            # tokens a block of the chunk scan
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    n_experts: int = 512             # routed experts HELD here
    n_experts_routed: int = 512      # the router's outputs
    first_expert: int = 0            # the first held expert's global id
    top_k: int = 22
    d_latent: int = 1024             # the routed experts' width in and out
    d_ff: int = 2688                 # one routed expert's width
    d_ff_shared: int = 5376
    routed_scale: float = 5.0
    norm_eps: float = 1e-5
    max_seq: int = 4096
    dtype: Any = jnp.bfloat16        # activation/compute dtype
    param_dtype: Any = jnp.float32

    family: ClassVar[str] = "nemotron_h"  # models/serving.py

    @classmethod
    def tiny(cls, **kw) -> "NemotronHConfig":
        """CPU-test size that keeps the pattern's three mixers and a
        stretch that repeats ("ME" twice, one loop): three Mamba-2, three
        expert layers and one attention layer; 8 heads of 16 over 16
        states in 2 groups; 4 query heads over 2 KV heads; 16 experts
        top-4 with 8 held, in a latent of 32 under a model of 64."""
        base = dict(vocab_size=256, d_model=64, pattern="MEMEM*E", m_heads=8,
                    m_head_dim=16, d_state=16, m_groups=2, chunk_size=16,
                    n_heads=4, n_kv_heads=2, head_dim=16, n_experts=8,
                    n_experts_routed=16, top_k=4, d_latent=32, d_ff=48,
                    d_ff_shared=96, max_seq=256)
        return cls(**{**base, **kw})

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def d_inner(self) -> int:
        return self.m_heads * self.m_head_dim

    @property
    def conv_channels(self) -> int:          # x | B | C
        return self.d_inner + 2 * self.m_groups * self.d_state

    def __post_init__(self):
        if set(self.pattern) - set("ME*"):
            raise ValueError(f"the pattern {self.pattern!r} names a mixer "
                             "that is not M, * or E")

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    @property
    def runs(self) -> tuple:
        """The pattern as stretches (unit, its first layer, repeats): at
        each layer the unit whose consecutive repeats cover the most
        layers, else the layer alone ("MEMEMEM*EME": "ME" x 3, then M,
        *, E, M, E one by one)."""
        runs, p, n = [], 0, len(self.pattern)
        while p < n:
            unit, repeats = self.pattern[p], 1
            for w in range(1, (n - p) // 2 + 1):
                k = 1
                while (self.pattern[p + k * w:p + (k + 1) * w]
                       == self.pattern[p:p + w]):
                    k += 1
                if k > 1 and k * w > len(unit) * repeats:
                    unit, repeats = self.pattern[p:p + w], k
            runs.append((unit, p, repeats))
            p += len(unit) * repeats
        return tuple(runs)

    def index(self, l: int) -> int:
        """Layer l's index among the layers of its kind."""
        return self.pattern[:l].count(self.pattern[l])


# What the router's bias is seeded at (normal), as models/kimi_k2.py.
_ROUTER_BIAS_SCALE = 0.002


def param_specs(cfg: NemotronHConfig) -> dict[str, dict[str, Any]]:
    """name → {shape, init[, ...]}: every leaf a stack over the layers of
    its kind (a Mamba-2 layer's carry the prefix "m_", an attention
    layer's "a_", the rest are an expert layer's; the one norm a layer
    runs over all). The model's own start (`init_params` makes it):
    `A_log` the log of U(1, 16) a head, `D` ones, `dt_bias` the inverse
    softplus of a step log-uniform in [1e-3, 1e-1]."""
    D, V, L = cfg.d_model, cfg.vocab_size, cfg.n_layers
    H, G, K = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Hm, Dn, Dc = cfg.m_heads, cfg.d_inner, cfg.conv_channels
    E, Dl, F, Fs = cfg.n_experts, cfg.d_latent, cfg.d_ff, cfg.d_ff_shared
    nm, na, ne = cfg.count("M"), cfg.count("*"), cfg.count("E")
    norm = lambda *s, scale=0.02: {"init": "normal", "scale": scale,
                                   "shape": s}
    resid = lambda *s: norm(*s, scale=0.02 / math.sqrt(L))
    ones = lambda *s: {"init": "ones", "shape": s}
    return {
        "wte": norm(V, D), "lm_head": norm(D, V), "ln_f_scale": ones(D),
        "ln_scale": ones(L, D),
        "m_in": norm(nm, D, Dn + Dc + Hm),
        "m_conv": norm(nm, cfg.d_conv, Dc, scale=0.5),
        "m_conv_b": norm(nm, Dc, scale=0.1),
        "m_dt_b": {"init": "dt_bias", "low": 1e-3, "high": 1e-1,
                   "shape": (nm, Hm)},
        "m_A_log": {"init": "log_uniform", "low": 1.0, "high": 16.0,
                    "shape": (nm, Hm)},
        "m_D": ones(nm, Hm), "m_norm": ones(nm, Dn),
        "m_out": resid(nm, Dn, D),
        "a_wq": norm(na, D, H * K), "a_wk": norm(na, D, G * K),
        "a_wv": norm(na, D, G * K), "a_wo": resid(na, H * K, D),
        "router": norm(ne, D, cfg.n_experts_routed),
        "router_bias": norm(ne, cfg.n_experts_routed,
                            scale=_ROUTER_BIAS_SCALE),
        "lat_in": norm(ne, D, Dl), "lat_out": resid(ne, Dl, D),
        "w_up": norm(ne, E, Dl, F), "w_down": norm(ne, E, F, Dl),
        "s_up": norm(ne, D, Fs), "s_down": resid(ne, Fs, D)}


def partition_rules() -> tuple:
    """Every leaf replicated: the family serves at tp = 1 only
    (models/serving.py refuses more)."""
    from jax.sharding import PartitionSpec

    return ((r".*", PartitionSpec()),)


def init_params(cfg: NemotronHConfig, rng: jax.Array) -> dict[str, jax.Array]:
    specs = param_specs(cfg)
    keys = jax.random.split(rng, len(specs))
    params = {}
    for key, (name, spec) in zip(keys, sorted(specs.items())):
        shape, dt = spec["shape"], cfg.param_dtype
        if spec["init"] == "normal":
            params[name] = jax.random.normal(key, shape, dt) * spec["scale"]
        elif spec["init"] == "ones":
            params[name] = jnp.ones(shape, dt)
        elif spec["init"] == "dt_bias":
            # the inverse softplus of a step log-uniform in [low, high]
            step = jnp.exp(jax.random.uniform(
                key, shape, _F32, math.log(spec["low"]),
                math.log(spec["high"])))
            params[name] = (step + jnp.log(-jnp.expm1(-step))).astype(dt)
        else:
            # "log_uniform": the log of a rate uniform in [low, high]
            params[name] = jnp.log(jax.random.uniform(
                key, shape, _F32, spec["low"], spec["high"])).astype(dt)
    return params


# ------------------------------------------------------------- the block
# l is a layer's index among all layers, i its index among the layers of
# its kind; inside a repeated stretch's loop both are traced and a
# stack's `[i]` is a dynamic slice.

@jax.named_scope(scopes.SSM_IN)
def _ssm_inputs(cfg: NemotronHConfig, params, l, i, x, valid, conv):
    """Mamba-2 layer l up to what the scan takes. x [N, C, D]; valid
    [N, C] bool (a token that is none leaves the state alone: dt = 0); a
    decode step passes planes without the token axis, x [N, D] and valid
    [N]; `conv(xbc, taps, bias)` → (the activated xBC, anything): the
    convolution, `blocks.causal_conv(boundary, taps)` over a row's own
    tokens or a decode step's, which keeps the tail in the pool.
    → (xs [.., Hm, P] float32: the scan's input, z [.., Dn] in cfg.dtype:
    the gate, dt [.., Hm], B, C [.., Gm, Ns] float32, and what `conv`
    returned besides)."""
    dt_ = cfg.dtype
    Hm, P, Gm, Ns = cfg.m_heads, cfg.m_head_dim, cfg.m_groups, cfg.d_state
    Dn, Dc = cfg.d_inner, cfg.conv_channels
    u = rms_norm(x, params["ln_scale"][l], cfg.norm_eps)
    # One matmul, accumulated to float32 and cut there: dt stays float32
    # and no column block of W_in is sliced out of its stack.
    proj = jnp.matmul(u, params["m_in"][i].astype(dt_),
                      preferred_element_type=_F32)
    z = proj[..., :Dn].astype(dt_)
    # The convolution's inputs as the tail keeps them (cfg.dtype), handed
    # over in float32 so that what it returns stays float32: x, B and C
    # go into a float32 recurrence, and a rounding here is one the plain
    # forward does not make.
    xbc, ext = conv(
        proj[..., Dn:Dn + Dc].astype(dt_).astype(_F32),
        params["m_conv"][i].astype(dt_).astype(_F32),           # [taps, Dc]
        params["m_conv_b"][i].astype(_F32))
    step = jax.nn.softplus(proj[..., Dn + Dc:]
                           + params["m_dt_b"][i].astype(_F32))
    lead = xbc.shape[:-1]
    xs = xbc[..., :Dn].reshape(lead + (Hm, P))
    B = xbc[..., Dn:Dn + Gm * Ns].reshape(lead + (Gm, Ns))
    C_ = xbc[..., Dn + Gm * Ns:].reshape(lead + (Gm, Ns))
    return xs, z, jnp.where(valid[..., None], step, 0.0), B, C_, ext


def _rate(params, i):
    """A [Hm] = -exp(A_log) of Mamba-2 layer i, float32."""
    return -jnp.exp(params["m_A_log"][i].astype(_F32))


@jax.named_scope(scopes.SSM_OUT)
def _ssm_output(cfg: NemotronHConfig, params, i, x, y, xs, z):
    """From the scan's output y [.., Hm, P] float32 (without the skip) to
    the sublayer's end: the skip D x, the gate, the norm over each
    group's values, W_out, the residual."""
    dt_ = cfg.dtype
    lead = z.shape[:-1]
    y = y + params["m_D"][i].astype(_F32)[:, None] * xs.astype(_F32)
    y = y.reshape(z.shape) * jax.nn.silu(z.astype(_F32))
    grouped = y.reshape(lead + (cfg.m_groups, -1))
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(grouped * grouped, axis=-1, keepdims=True) + cfg.norm_eps)
    y = (grouped.reshape(z.shape)
         * params["m_norm"][i].astype(_F32)).astype(dt_)
    return x + y @ params["m_out"][i].astype(dt_)


@jax.named_scope(scopes.ATTN_IN)
def _attn_inputs(cfg: NemotronHConfig, params, l, i, x):
    """Attention layer l up to q [N, C, H, K], k, v [N, C, G, K] in
    cfg.dtype. No position enters: the Mamba-2 layers carry the order."""
    N, C, _D = x.shape
    dt_ = cfg.dtype
    H, G, K = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    u = rms_norm(x, params["ln_scale"][l], cfg.norm_eps)
    q = (u @ params["a_wq"][i].astype(dt_)).reshape(N, C, H, K)
    k = (u @ params["a_wk"][i].astype(dt_)).reshape(N, C, G, K)
    v = (u @ params["a_wv"][i].astype(dt_)).reshape(N, C, G, K)
    return q, k, v


@jax.named_scope(scopes.ATTN_OUT)
def _attn_output(cfg: NemotronHConfig, params, i, x, attn):
    N, C, _D = x.shape
    dt_ = cfg.dtype
    return x + (attn.astype(dt_).reshape(N, C, -1)
                @ params["a_wo"][i].astype(dt_))


def _relu2_mlp(u, w_up, w_down):
    """W_down relu(W_up u)^2, accumulated to float32."""
    dt = u.dtype
    h = jnp.matmul(u, w_up.astype(dt), preferred_element_type=_F32)
    return jnp.matmul(jnp.square(jax.nn.relu(h)).astype(dt),
                      w_down.astype(dt), preferred_element_type=_F32)


# The experts' stacks, handed whole to the grouped matmul: two matrices,
# so squared-ReLU experts (ops/moe.py).
_EXPERT_KEYS = ("w_up", "w_down")


def _expert_layer(cfg: NemotronHConfig, params, l, j, x, valid):
    """Expert layer l (the j-th of its kind). x [N, C, D]; valid [N, C]
    bool (rows that carry a token: the others reach no expert).
    → (x, (counts [n_experts] int32 rows each held expert received, the
    valid rows' choices the bias moved))."""
    N, C, D = x.shape
    dt = cfg.dtype
    with jax.named_scope(scopes.MLP):
        u = rms_norm(x, params["ln_scale"][l], cfg.norm_eps).reshape(N * C, D)
    chosen, gates, moved = blocks.biased_route(
        cfg, params["router"][j], params["router_bias"][j], u)
    with jax.named_scope(scopes.MOE_LATENT):
        latent = u @ params["lat_in"][j].astype(dt)             # [M, Dl]
    with jax.named_scope(scopes.MOE_EXPERTS):
        experts = tuple(params[k].astype(dt) for k in _EXPERT_KEYS)
    routed, counts = token_choice_experts(
        latent, chosen, gates, *experts,
        first_expert=cfg.first_expert, layer=j, valid=valid.reshape(-1),
        n_routed=cfg.n_experts_routed)
    with jax.named_scope(scopes.COUNTERS):
        moved = jnp.sum(jnp.where(valid.reshape(-1), moved, 0))
    with jax.named_scope(scopes.MOE_LATENT):
        routed = jnp.matmul(routed, params["lat_out"][j].astype(dt),
                            preferred_element_type=_F32)
    with jax.named_scope(scopes.MLP):
        shared = _relu2_mlp(u, params["s_up"][j], params["s_down"][j])
        f = (shared + routed).astype(dt)
        return x + f.reshape(N, C, D), (counts, moved)


_head = functools.partial(untied_head, rms_norm)


def _walk(cfg: NemotronHConfig, params, x, pool, valid, mamba, attn,
          tally=None):
    """Every layer in order. `mamba(l, i, x, pool)` and `attn(l, i, x,
    pool)` → (x, pool) are a layer's mixer with its residual; the expert
    layer is the same for every program. x [N, C, D], valid [N, C];
    `tally(counted)` → what an expert layer's counts add to the running
    counters (None: nothing is counted). A stretch of the pattern that
    repeats (`cfg.runs`: "ME" three times at the published period) is ONE
    `lax.fori_loop` over its repeats, l and i traced and a stack's `[i]`
    a dynamic slice; the rest stands alone, l and i static.
    → (x, pool, the counters' sum [len(COUNTERS)] uint32)."""
    def layer(kind, l, i, x, pool, total):
        if kind == "M":
            x, pool = mamba(l, i, x, pool)
        elif kind == "*":
            x, pool = attn(l, i, x, pool)
        else:
            x, counted = _expert_layer(cfg, params, l, i, x, valid)
            if tally is not None:
                with jax.named_scope(scopes.COUNTERS):
                    total = total + tally(counted)
        return x, pool, total

    carry = x, pool, jnp.zeros(len(COUNTERS), jnp.uint32)
    before = dict.fromkeys("ME*", 0)    # layers of a kind before this run
    for unit, first, repeats in cfg.runs:
        def turn(k, carry, unit=unit, first=first, at=dict(before)):
            for o, kind in enumerate(unit):
                carry = layer(
                    kind, first + k * len(unit) + o,
                    at[kind] + k * unit.count(kind) + unit[:o].count(kind),
                    *carry)
            return carry

        carry = (turn(0, carry) if repeats == 1 else
                 jax.lax.fori_loop(0, repeats, turn, carry))
        for kind in before:
            before[kind] += repeats * unit.count(kind)
    return carry


# ------------------------------------------ full sequence (tests, no cache)

def forward(cfg: NemotronHConfig, params, tokens):
    """tokens [B, S] → logits [B, S, V] float32: every row a whole
    sequence from position 0 and a zero state, plain masked attention,
    the recurrence token by token; no pool."""
    B, S = tokens.shape
    valid = jnp.ones((B, S), bool)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    conv = causal_conv(
        lambda xs: (jnp.zeros((B, xs.shape[-1]), xs.dtype),) * (
            cfg.d_conv - 1), cfg.d_conv)
    zeros = jnp.zeros((B, cfg.m_heads, cfg.d_state, cfg.m_head_dim), _F32)

    def mamba(l, i, x, pool):
        xs, z, dt, Bm, Cm, _ext = _ssm_inputs(cfg, params, l, i, x, valid,
                                              conv)
        with jax.named_scope(scopes.SSM_SCAN):
            y, _final = jax.vmap(reference_ssd_scan,
                                 in_axes=(0, 0, None, 0, 0, 0))(
                xs, dt, _rate(params, i), Bm, Cm, zeros)
        return _ssm_output(cfg, params, i, x, y, xs, z), pool

    def attn(l, i, x, pool):
        q, k, v = _attn_inputs(cfg, params, l, i, x)
        with jax.named_scope(scopes.ATTN_KERNEL):
            rep = cfg.n_heads // cfg.n_kv_heads
            k, v = (jnp.repeat(t, rep, axis=2) for t in (k, v))
            s = jnp.einsum("bshk,bthk->bhst", q, k,
                           preferred_element_type=_F32)
            s = jnp.where(causal[None, None],
                          s / math.sqrt(cfg.head_dim), -1e30)
            o = jnp.einsum("bhst,bthk->bshk",
                           jax.nn.softmax(s, axis=-1).astype(cfg.dtype), v)
        return _attn_output(cfg, params, i, x, o), pool

    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens]
    x, _none, _total = _walk(cfg, params, x, (), valid, mamba, attn)
    return _head(cfg, params, x)


# --------------------------------------------------------- the paged pool

# The pool's leaves that are a state by the slot (models/serving.py).
SLOT_STATE_LEAVES = ("ssm_state", "ssm_conv")

# Running totals over decode steps, wrapping uint32 (the host takes
# differences): the expert families' five, and the choices the router's
# bias moved.
COUNTERS = blocks.COUNTERS_BIASED


def init_paged_kv(cfg: NemotronHConfig, n_pages: int, page_size: int,
                  n_slots: int, kv_dtype: str | None = None):
    """The pool pytree the paged programs carry, donated: the attention
    layers' pages ``[n_attn, P+1, page_size, G*K]`` (row 0 the null
    page), the Mamba-2 layers' state ``[n_mamba, n_slots+1, Hm / p, Ns,
    p P]`` float32 (ops/ssd.py) and convolution tail ``[n_mamba, taps-1,
    n_slots+1, Dn + 2 Gm Ns]`` (the last slot the null slot), and the
    decode steps' running expert counters."""
    if kv_dtype not in (None, "bf16"):
        raise ValueError(
            f"the nemotron_h family's pool is bf16, got {kv_dtype!r}")
    nm = cfg.count("M")
    p = packed_heads(cfg.m_heads, cfg.m_head_dim)
    pages = (cfg.count("*"), n_pages + 1, page_size,
             cfg.n_kv_heads * cfg.head_dim)
    return {"k": jnp.zeros(pages, cfg.dtype), "v": jnp.zeros(pages, cfg.dtype),
            "ssm_state": jnp.zeros(
                (nm, n_slots + 1, cfg.m_heads // p, cfg.d_state,
                 p * cfg.m_head_dim), _F32),
            "ssm_conv": jnp.zeros(
                (nm, cfg.d_conv - 1, n_slots + 1, cfg.conv_channels),
                cfg.dtype),
            "moe_counters": jnp.zeros(len(COUNTERS), jnp.uint32)}


def _chunk_forward(cfg: NemotronHConfig, params, tokens, pool, tables,
                   offsets, n_valid, slots, attn_impl: str):
    """N chunk rows written into their slots' pages, each at its own
    offset, and the Mamba-2 layers' state carried through the dispatch's
    rows in order (`blocks.dispatch_order`).
    → (hidden states [N, C, D], updated pool)."""
    _N, C = tokens.shape
    ps = pool["k"].shape[2]
    n_tail = cfg.d_conv - 1
    rel = jnp.arange(C)
    pos = offsets[:, None] + rel[None, :]
    valid = rel[None, :] < n_valid[:, None]
    kv_lens = offsets + n_valid
    chain, state_rows, fresh = dispatch_order(
        slots, offsets, n_valid, pool["ssm_state"].shape[1] - 1)
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

    def mamba(l, i, x, pool):
        def boundary(xs):
            # A chained row's predecessor is a full chunk: its last
            # inputs are its own last tokens.
            return tuple(
                jnp.where(fresh[:, None], 0, jnp.where(
                    (chain >= 0)[:, None],
                    xs[jnp.maximum(chain, 0), C - n_tail + j],
                    pool["ssm_conv"][i, j, slots]))
                for j in range(n_tail))

        xs, z, dt, Bm, Cm, ext = _ssm_inputs(
            cfg, params, l, i, x, valid, causal_conv(boundary, cfg.d_conv))
        with jax.named_scope(scopes.SSM_IN):
            tails = jnp.take_along_axis(ext, tail_at, axis=1)
            conv = pool["ssm_conv"]
            for j in range(n_tail):
                conv = conv.at[i, j, state_rows].set(
                    tails[:, j].astype(conv.dtype))
            pool = {**pool, "ssm_conv": conv}
        with jax.named_scope(scopes.SSM_SCAN):
            y, finals = ssd_chunk_scan(
                xs, dt, _rate(params, i), Bm, Cm,
                unpack_state(pool["ssm_state"][i, slots], cfg.m_head_dim),
                chain, fresh, block=cfg.chunk_size)
            packed = pack_state(
                finals, pool["ssm_state"].shape[-1] // cfg.m_head_dim)
            pool = {**pool, "ssm_state":
                    pool["ssm_state"].at[i, state_rows].set(packed)}
        return _ssm_output(cfg, params, i, x, y, xs, z), pool

    def attn(l, i, x, pool):
        q, k, v = _attn_inputs(cfg, params, l, i, x)
        pool = write_kv(pool, i, write_pages, write_offs, k, v)
        with jax.named_scope(scopes.ATTN_KERNEL):
            o = attend(q, pool["k"], pool["v"], i, tables, offsets, kv_lens,
                       sm_scale=1.0 / math.sqrt(cfg.head_dim))
        return _attn_output(cfg, params, i, x, o), pool

    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens]
    x, pool, _total = _walk(cfg, params, x, pool, valid, mamba, attn)
    return x, pool


def _decode_once(cfg: NemotronHConfig, params, tokens, pool, positions,
                 tables, attn_impl: str):
    """All B slots advance one token: row b IS slot b. A row whose table
    is all null (an idle slot, or one still mid-prefill) writes the null
    page, reaches no expert, counts nowhere and leaves its slot's state
    and tail as they are, so a prompt's state survives the decode windows
    between its chunks.
    → (logits [B, V] fp32, updated pool)."""
    ps = pool["k"].shape[2]
    active = tables[:, 0] > 0
    with jax.named_scope(scopes.ATTN_KV_WRITE):
        write_page = jnp.take_along_axis(
            tables,
            jnp.minimum(positions // ps, tables.shape[1] - 1)[:, None],
            axis=1)[:, 0]
        write_off = positions % ps
    attend = attend_fn(attn_impl, chunk=False)
    step, conv_step = ((ssd_decode_step, ssm_conv_step)
                       if attn_impl == "kernel" else
                       (reference_ssd_decode_step, reference_ssm_conv_step))

    def mamba(l, i, x, pool):
        # The sublayer on planes [B, ..], a slot a row.
        def conv(xbc, taps, bias):
            return conv_step(pool["ssm_conv"], i, xbc, taps, bias, active)

        x = x[:, 0]
        xs, z, dt, Bm, Cm, tail = _ssm_inputs(cfg, params, l, i, x, active,
                                              conv)
        pool = {**pool, "ssm_conv": tail}
        with jax.named_scope(scopes.SSM_SCAN):
            y, state = step(pool["ssm_state"], i, xs, dt, _rate(params, i),
                            Bm, Cm, active)
            pool = {**pool, "ssm_state": state}
        return _ssm_output(cfg, params, i, x, y, xs, z)[:, None], pool

    def attn(l, i, x, pool):
        q, k, v = _attn_inputs(cfg, params, l, i, x)
        pool = write_kv(pool, i, write_page, write_off, k, v)
        with jax.named_scope(scopes.ATTN_KERNEL):
            o = attend(q[:, 0], pool["k"], pool["v"], i, tables,
                       positions + 1, sm_scale=1.0 / math.sqrt(cfg.head_dim))
        return _attn_output(cfg, params, i, x, o[:, None]), pool

    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens[:, None]]
    with jax.named_scope(scopes.COUNTERS):
        n_live = jnp.sum(active)
    x, pool, total = _walk(
        cfg, params, x, pool, active[:, None], mamba, attn,
        tally=lambda counted: blocks.counter_row_biased(
            cfg, counted, n_live, tokens.shape[0]))
    with jax.named_scope(scopes.COUNTERS):
        counters = pool["moe_counters"] + total
    return _head(cfg, params, x[:, 0]), {**pool, "moe_counters": counters}


(prefill_chunk_paged, decode_step_paged, _decode_sample_paged,
 decode_multi_paged) = paged_programs(
    _chunk_forward, _decode_once, last_token_logits(_head), COUNTERS)


__all__ = [
    "NemotronHConfig", "param_specs", "partition_rules", "init_params",
    "forward", "init_paged_kv", "prefill_chunk_paged", "decode_step_paged",
    "decode_multi_paged", "SLOT_STATE_LEAVES", "COUNTERS",
]
