"""Jamba-class decoder: Mamba-1 selective-scan layers (a diagonal
state-space recurrence, 16 float32 values a channel) beside a few
multi-query softmax-attention layers WITHOUT positions, every layer's
feed-forward a dense gated-SiLU MLP, the embedding tied to the head.

A block of its own beside models/gpt.py, zaya.py, laguna.py and
qwen3_next.py (none gets a switch for any of this), and the first served
family without experts. Source: the model's config.json (`model_type:
jamba`) and HF `modeling_jamba.py`; benchmarks/configs/ai21-jamba2-3b.json
lists what each fixes and what is assumed. D model width, Dn = `expand`
x D the mixer's channels, S the state's values a channel, R the step's
rank; layer l is an ATTENTION layer when l % `attn_period` ==
`attn_offset`, else a MAMBA one:

  x <- x + Mixer(norm(x));  x <- x + MLP(norm(x));  final norm; tied head
  norm   x / sqrt(mean(x^2) + eps) * w, float32 (w starts at 1)
  mamba  u the normed input; [xs, z] = W_in u; xs through a causal
         depthwise convolution of `d_conv` taps with a bias, then SiLU;
         [r, B, C] = W_x xs, each through an RMSNorm of its own;
         dt = softplus(W_dt r + b_dt); then the selective scan over the
         channel's state h [S] with A = -exp(A_log) (ops/selective_scan.py);
         y <- y silu(z), then W_out.
  attn   q = W_q u a head, k, v = W_k u, W_v u over `n_kv_heads` heads;
         NO rope and no other position; causal softmax at K^-1/2; W_o.
  MLP    W_down(silu(W_gate u) * W_up u).

**Two kinds of per-request memory in one pool pytree.** Attention layers
keep ``pool["k"], pool["v"]`` ``[n_attn, P+1, page, G*K]``, addressed by
the engine's page tables like every family's (at the published sizes
ONE KV head: a token is 1 KB over both layers). Mamba layers keep a
STATE by the slot: ``pool["ssm_state"]`` ``[n_mamba, n_slots+1, S, Dn]``
float32 (channels on the lanes) and ``pool["ssm_conv"]`` ``[n_mamba,
taps-1, n_slots+1, Dn]``, the convolution's last inputs, a plane of
slots a tap (with the three taps on the second-minor axis the chip pads
them to a tile of their own; a decode step reads and shifts a block of
slots' planes in place, `ops.selective_scan.ssm_conv_step`): 9.3 MB a
slot at the published sizes, whatever the context. The last slot is the
null slot, which a chunk row that writes nothing back names. A decode
step's mamba sublayer runs on planes without the token axis, ``[B, Dn]``
and ``[B, D]``, a slot a row, and both of its kernels take the slots in
blocks (`ssm_decode_step` 8 a grid step, the state's ``[8, S, Dn]`` read
and written in place; `ssm_conv_step` 32): an idle slot inside a live
block keeps every bit, a block of idle slots moves nothing of the state.
Idle slots, a reused slot's reset at
offset 0, the rows of one dispatch that continue each other and a prompt whose chunks
are split over dispatches with decode windows between behave as
models/qwen3_next.py states for its recurrence: a decode step's batch IS
the slot array and the pool is donated; a chunk row starts from zeros at
offset 0, else from the row of THIS dispatch that holds the same slot's
chunk before it, else from the slot's state (`blocks.dispatch_order`);
a row that another row continues is a full chunk, which the
convolution's tail relies on; the last live row of a slot writes state
and tail back.

**The layers are walked by loops, not unrolled.** The weights are stacks
over the layers of their kind (`m_in` ``[n_mamba, D, 2 Dn]``, `w_up`
``[L, D, F]``) and a run of consecutive mamba layers (7, 13 and 6 of them
at the published sizes, between and around the two attention layers) is
ONE `lax.fori_loop` whose body reads its layer's planes out of the
stacks by the loop's index, where they lie (a matmul's fusion slices its
own plane; tests/test_chip_compile.py holds that no plane is copied out)
and updates the layer's rows of the donated pool in place. A program is
then three loop bodies and two attention layers to the compiler, not 28
layers: the cell warms ten programs of this depth before its window
opens, and unrolled they took four minutes to compile.

The four paged programs are `paged_kv.paged_programs` over the chunk
forward and the decode step (names, donation and the decode window are
its), with no counters: nothing here routes.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, ClassVar

import jax
import jax.numpy as jnp

from ray_tpu.ops import scopes
from ray_tpu.models.blocks import (attend_fn, dispatch_order, gated_mlp,
                                   last_token_logits, tied_head, write_kv)
from ray_tpu.models.blocks import causal_conv as _causal_conv
from ray_tpu.models.blocks import rms_norm as _norm
from ray_tpu.models.paged_kv import paged_programs
from ray_tpu.ops.selective_scan import (
    reference_ssm_chunk_scan, reference_ssm_conv_step,
    reference_ssm_decode_step, reference_ssm_scan, ssm_chunk_scan,
    ssm_conv_step, ssm_decode_step)

_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    d_model: int = 2560
    n_layers: int = 28
    attn_period: int = 14            # layer l attends when
    attn_offset: int = 7             #   l % attn_period == attn_offset
    n_heads: int = 20
    n_kv_heads: int = 1
    head_dim: int = 128
    d_ff: int = 8192
    expand: int = 2                  # d_inner = expand x d_model
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    norm_eps: float = 1e-6
    max_seq: int = 4096
    dtype: Any = jnp.bfloat16        # activation/compute dtype
    param_dtype: Any = jnp.float32

    family: ClassVar[str] = "jamba"  # models/serving.py

    @classmethod
    def tiny(cls, **kw) -> "JambaConfig":
        """CPU-test size that keeps every ratio: two periods of 3 mamba :
        1 attention with the attention layer mid-period; 4 query heads
        over ONE KV head; channels twice the width; a state of 8 values;
        a chunk row of 16 tokens or more holds the convolution's tail."""
        base = dict(vocab_size=256, d_model=64, n_layers=8, attn_period=4,
                    attn_offset=2, n_heads=4, n_kv_heads=1, head_dim=16,
                    d_ff=96, expand=2, d_state=8, d_conv=4, dt_rank=8,
                    max_seq=256)
        return cls(**{**base, **kw})

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def kinds(self) -> tuple:
        """"mamba" or "attn" for each of the n_layers layers."""
        return tuple("attn" if l % self.attn_period == self.attn_offset
                     else "mamba" for l in range(self.n_layers))

    def count(self, kind: str) -> int:
        return sum(k == kind for k in self.kinds)

    @property
    def runs(self) -> tuple:
        """The layers as runs of one kind: (kind, the run's first layer,
        that layer's index among the layers of its kind, how many)."""
        runs, at = [], {"mamba": 0, "attn": 0}
        for l, kind in enumerate(self.kinds):
            if runs and runs[-1][0] == kind:
                runs[-1][3] += 1
            else:
                runs.append([kind, l, at[kind], 1])
            at[kind] += 1
        return tuple(map(tuple, runs))


def param_specs(cfg: JambaConfig) -> dict[str, dict[str, Any]]:
    """name → {shape, init[, ...]}: every leaf a stack over the layers of
    its kind (a mamba layer's carry the prefix "m_", an attention
    layer's "a_", the MLP's and the two norms' run over all layers).
    `m_A_log` is laid out as the state is, [S, Dn] a layer. The model's
    own start (`init_params` makes it): `A_log` = log(1..S) a channel,
    `D` ones, `b_dt` the inverse softplus of a step log-uniform in
    [1e-3, 1e-1]; a loader that fills leaves from normal / ones / zeros
    alone overrides the first and the last, as
    benchmarks/families/jamba.py does."""
    D, V, L, F = cfg.d_model, cfg.vocab_size, cfg.n_layers, cfg.d_ff
    H, G, K = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Dn, S, R = cfg.d_inner, cfg.d_state, cfg.dt_rank
    nm, na = cfg.count("mamba"), cfg.count("attn")
    norm = lambda *s, scale=0.02: {"init": "normal", "scale": scale,
                                   "shape": s}
    resid = lambda *s: norm(*s, scale=0.02 / math.sqrt(2 * L))
    ones = lambda *s: {"init": "ones", "shape": s}
    return {
        "wte": norm(V, D), "ln_f_scale": ones(D),
        "ln1_scale": ones(L, D), "ln2_scale": ones(L, D),
        "m_in": norm(nm, D, 2 * Dn),
        "m_conv": norm(nm, cfg.d_conv, Dn, scale=0.5),
        "m_conv_b": norm(nm, Dn, scale=0.1),
        "m_x": norm(nm, Dn, R + 2 * S),
        "m_dt_norm": ones(nm, R), "m_b_norm": ones(nm, S),
        "m_c_norm": ones(nm, S),
        "m_dt": norm(nm, R, Dn, scale=R ** -0.5),
        "m_dt_b": {"init": "dt_bias", "low": 1e-3, "high": 1e-1,
                   "shape": (nm, Dn)},
        "m_A_log": {"init": "log_arange", "shape": (nm, S, Dn)},
        "m_D": ones(nm, Dn),
        "m_out": resid(nm, Dn, D),
        "a_wq": norm(na, D, H * K), "a_wk": norm(na, D, G * K),
        "a_wv": norm(na, D, G * K), "a_wo": resid(na, H * K, D),
        "w_gate": norm(L, D, F), "w_up": norm(L, D, F),
        "w_down": resid(L, F, D)}


def partition_rules() -> tuple:
    """Every leaf replicated: the family serves at tp = 1 only
    (models/serving.py refuses more); the table exists so that the
    shared loaders find a rule for each leaf."""
    from jax.sharding import PartitionSpec

    return ((r".*", PartitionSpec()),)


def init_params(cfg: JambaConfig, rng: jax.Array) -> dict[str, jax.Array]:
    specs = param_specs(cfg)
    keys = jax.random.split(rng, len(specs))
    params = {}
    for key, (name, spec) in zip(keys, sorted(specs.items())):
        shape, dt = spec["shape"], cfg.param_dtype
        if spec["init"] == "normal":
            params[name] = jax.random.normal(key, shape, dt) * spec["scale"]
        elif spec["init"] == "dt_bias":
            step = jnp.exp(jax.random.uniform(
                key, shape, _F32, math.log(spec["low"]),
                math.log(spec["high"])))
            params[name] = (step + jnp.log(-jnp.expm1(-step))).astype(dt)
        elif spec["init"] == "log_arange":
            values = jnp.log(jnp.arange(1, shape[-2] + 1, dtype=_F32))
            params[name] = jnp.broadcast_to(values[:, None], shape).astype(dt)
        else:
            params[name] = jnp.ones(shape, dt)
    return params


# ------------------------------------------------------------- the block
# l is a layer's index among all layers, i its index among the layers of
# its kind; inside a run's loop both are traced and a stack's `[i]` is a
# dynamic slice.

def _unit_rms(x, w, eps):
    """One of the mixer's three inner norms, float32 in and out."""
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * w.astype(_F32))


@jax.named_scope(scopes.SSM_IN)
def _ssm_inputs(cfg: JambaConfig, params, l, i, x, valid, conv):
    """Mamba layer l up to what the scan takes. x [N, C, D]; valid [N, C]
    bool (a token that is none leaves the state alone: dt = 0); a decode
    step passes planes without the token axis, x [N, D] and valid [N];
    `conv(xs, taps, bias)` → (the activated xs, anything): the
    convolution, `_causal_conv(boundary, taps)` over a row's own tokens
    or a decode step's, which keeps the tail in the pool.
    → (xs [N, C, Dn] in cfg.dtype: the scan's input, z likewise: the
    gate, dt [N, C, Dn], B, C [N, C, S] float32, and what `conv` returned
    besides: `_causal_conv`'s ext [N, taps-1+C, Dn], the convolution's
    inputs with the boundary in front)."""
    dt_ = cfg.dtype
    Dn, S, R = cfg.d_inner, cfg.d_state, cfg.dt_rank
    u = _norm(x, params["ln1_scale"][l], cfg.norm_eps)
    xz = u @ params["m_in"][i].astype(dt_)
    xs, z = xz[..., :Dn], xz[..., Dn:]
    xs, ext = conv(
        xs, params["m_conv"][i].astype(dt_).astype(_F32),       # [taps, Dn]
        params["m_conv_b"][i].astype(_F32))
    rbc = jnp.matmul(xs, params["m_x"][i].astype(dt_),
                     preferred_element_type=_F32)
    r, B, C_ = jnp.split(rbc, [R, R + S], axis=-1)
    r = _unit_rms(r, params["m_dt_norm"][i], cfg.norm_eps).astype(dt_)
    B = _unit_rms(B, params["m_b_norm"][i], cfg.norm_eps)
    C_ = _unit_rms(C_, params["m_c_norm"][i], cfg.norm_eps)
    step = jax.nn.softplus(
        jnp.matmul(r, params["m_dt"][i].astype(dt_),
                   preferred_element_type=_F32)
        + params["m_dt_b"][i].astype(_F32))
    return xs, z, jnp.where(valid[..., None], step, 0.0), B, C_, ext


def _decay(params, i):
    """(A [S, Dn] = -exp(A_log), D [Dn]) of mamba layer i, float32."""
    return (-jnp.exp(params["m_A_log"][i].astype(_F32)),
            params["m_D"][i].astype(_F32))


@jax.named_scope(scopes.SSM_OUT)
def _ssm_output(cfg: JambaConfig, params, i, x, y, z):
    """From the scan's output y [N, C, Dn] float32 to the sublayer's
    end: the gate, W_out, the residual (a decode step's y is [N, Dn] and
    its x [N, D])."""
    dt_ = cfg.dtype
    y = (y * jax.nn.silu(z.astype(_F32))).astype(dt_)
    return x + y @ params["m_out"][i].astype(dt_)


@jax.named_scope(scopes.ATTN_IN)
def _attn_inputs(cfg: JambaConfig, params, l, i, x):
    """Attention layer l up to q [N, C, H, K], k, v [N, C, G, K] in
    cfg.dtype. No position enters: the mamba layers carry the order."""
    N, C, _D = x.shape
    dt_ = cfg.dtype
    H, G, K = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    u = _norm(x, params["ln1_scale"][l], cfg.norm_eps)
    q = (u @ params["a_wq"][i].astype(dt_)).reshape(N, C, H, K)
    k = (u @ params["a_wk"][i].astype(dt_)).reshape(N, C, G, K)
    v = (u @ params["a_wv"][i].astype(dt_)).reshape(N, C, G, K)
    return q, k, v


@jax.named_scope(scopes.ATTN_OUT)
def _attn_output(cfg: JambaConfig, params, i, x, attn):
    N, C, _D = x.shape
    dt_ = cfg.dtype
    return x + (attn.astype(dt_).reshape(N, C, -1)
                @ params["a_wo"][i].astype(dt_))


@jax.named_scope(scopes.MLP)
def _mlp(cfg: JambaConfig, params, l, x):
    u = _norm(x, params["ln2_scale"][l], cfg.norm_eps)
    f = gated_mlp(u, params["w_gate"][l], params["w_up"][l],
                  params["w_down"][l])
    return x + f.astype(cfg.dtype)


_head = functools.partial(tied_head, _norm)


def _walk(cfg: JambaConfig, params, x, pool, mamba, attn):
    """Every layer in order. `mamba(l, i, x, pool)` and `attn(l, i, x,
    pool)` → (x, pool) are a layer's mixer with its residual; the MLP
    follows here. A run of mamba layers is one `fori_loop` (l and i
    traced), an attention layer stands alone (l and i static).
    → (x, pool)."""
    for kind, l, i, n in cfg.runs:
        if kind == "attn":
            for k in range(n):
                x, pool = attn(l + k, i + k, x, pool)
                x = _mlp(cfg, params, l + k, x)
            continue

        def layer(k, carry, l=l, i=i):
            x, pool = mamba(l + k, i + k, *carry)
            return _mlp(cfg, params, l + k, x), pool

        x, pool = jax.lax.fori_loop(0, n, layer, (x, pool))
    return x, pool


# ------------------------------------------ full sequence (tests, no cache)

def forward(cfg: JambaConfig, params, tokens):
    """tokens [B, S] → logits [B, S, V] float32: every row a whole
    sequence from position 0 and a zero state, plain masked attention,
    the recurrence token by token; no pool."""
    B, S = tokens.shape
    valid = jnp.ones((B, S), bool)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    conv = _causal_conv(
        lambda xs: (jnp.zeros((B, xs.shape[-1]), xs.dtype),) * (
            cfg.d_conv - 1), cfg.d_conv)

    def mamba(l, i, x, pool):
        xs, z, dt, Bm, Cm, _ext = _ssm_inputs(cfg, params, l, i, x, valid,
                                              conv)
        with jax.named_scope(scopes.SSM_SCAN):
            rows = lambda t: jnp.swapaxes(t, 0, 1)           # tokens first
            y, _final = reference_ssm_scan(
                rows(xs), rows(dt), rows(Bm), rows(Cm), *_decay(params, i),
                jnp.zeros((B, cfg.d_state, cfg.d_inner), _F32))
        return _ssm_output(cfg, params, i, x, rows(y), z), pool

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
    x, _none = _walk(cfg, params, x, (), mamba, attn)
    return _head(cfg, params, x)


# --------------------------------------------------------- the paged pool

# The pool's leaves that are a state by the slot (models/serving.py).
SLOT_STATE_LEAVES = ("ssm_state", "ssm_conv")


def init_paged_kv(cfg: JambaConfig, n_pages: int, page_size: int,
                  n_slots: int, kv_dtype: str | None = None):
    """The pool pytree the paged programs carry, donated: the attention
    layers' pages ``[n_attn, P+1, page_size, G*K]`` (row 0 the null
    page), the mamba layers' state ``[n_mamba, n_slots+1, S, Dn]``
    float32 and convolution tail ``[n_mamba, taps-1, n_slots+1, Dn]``
    (the last slot the null slot)."""
    if kv_dtype not in (None, "bf16"):
        raise ValueError(
            f"the jamba family's pool is bf16, got {kv_dtype!r}")
    nm = cfg.count("mamba")
    pages = (cfg.count("attn"), n_pages + 1, page_size,
             cfg.n_kv_heads * cfg.head_dim)
    return {"k": jnp.zeros(pages, cfg.dtype), "v": jnp.zeros(pages, cfg.dtype),
            "ssm_state": jnp.zeros(
                (nm, n_slots + 1, cfg.d_state, cfg.d_inner), _F32),
            "ssm_conv": jnp.zeros(
                (nm, cfg.d_conv - 1, n_slots + 1, cfg.d_inner), cfg.dtype)}


def _chunk_forward(cfg: JambaConfig, params, tokens, pool, tables, offsets,
                   n_valid, slots, attn_impl: str):
    """N chunk rows written into their slots' pages, each at its own
    offset, and the mamba layers' state carried through the dispatch's
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
    scan = (ssm_chunk_scan if attn_impl == "kernel"
            else reference_ssm_chunk_scan)

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
            cfg, params, l, i, x, valid, _causal_conv(boundary, cfg.d_conv))
        with jax.named_scope(scopes.SSM_IN):
            tails = jnp.take_along_axis(ext, tail_at, axis=1)
            conv = pool["ssm_conv"]
            for j in range(n_tail):
                conv = conv.at[i, j, state_rows].set(tails[:, j])
            pool = {**pool, "ssm_conv": conv}
        with jax.named_scope(scopes.SSM_SCAN):
            y, finals = scan(xs, dt, Bm, Cm, *_decay(params, i),
                             pool["ssm_state"][i, slots], chain, fresh)
            pool = {**pool, "ssm_state":
                    pool["ssm_state"].at[i, state_rows].set(finals)}
        return _ssm_output(cfg, params, i, x, y, z), pool

    def attn(l, i, x, pool):
        q, k, v = _attn_inputs(cfg, params, l, i, x)
        pool = write_kv(pool, i, write_pages, write_offs, k, v)
        with jax.named_scope(scopes.ATTN_KERNEL):
            o = attend(q, pool["k"], pool["v"], i, tables, offsets, kv_lens,
                       sm_scale=1.0 / math.sqrt(cfg.head_dim))
        return _attn_output(cfg, params, i, x, o), pool

    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens]
    return _walk(cfg, params, x, pool, mamba, attn)


def _decode_once(cfg: JambaConfig, params, tokens, pool, positions, tables,
                 attn_impl: str):
    """All B slots advance one token: row b IS slot b. A row whose table
    is all null (an idle slot, or one still mid-prefill) writes the null
    page and leaves its slot's state and tail as they are, so a prompt's
    state survives the decode windows between its chunks.
    → (logits [B, V] fp32, updated pool)."""
    B = tokens.shape[0]
    ps = pool["k"].shape[2]
    active = tables[:, 0] > 0
    with jax.named_scope(scopes.ATTN_KV_WRITE):
        write_page = jnp.take_along_axis(
            tables,
            jnp.minimum(positions // ps, tables.shape[1] - 1)[:, None],
            axis=1)[:, 0]
        write_off = positions % ps
    attend = attend_fn(attn_impl, chunk=False)
    step, conv_step = ((ssm_decode_step, ssm_conv_step)
                       if attn_impl == "kernel" else
                       (reference_ssm_decode_step, reference_ssm_conv_step))

    def mamba(l, i, x, pool):
        # The sublayer on planes [B, ..], a slot a row: the token axis is
        # put back for the residual stream alone, so that the kernels'
        # operands and what is fused to them lie a slot a sublane.
        def conv(xs, taps, bias):
            return conv_step(pool["ssm_conv"], i, xs, taps, bias, active)

        x = x[:, 0]
        xs, z, dt, Bm, Cm, tail = _ssm_inputs(cfg, params, l, i, x, active,
                                              conv)
        pool = {**pool, "ssm_conv": tail}
        with jax.named_scope(scopes.SSM_SCAN):
            y, state = step(pool["ssm_state"], i, xs, dt, Bm, Cm,
                            *_decay(params, i), active)
            pool = {**pool, "ssm_state": state}
        return _ssm_output(cfg, params, i, x, y, z)[:, None], pool

    def attn(l, i, x, pool):
        q, k, v = _attn_inputs(cfg, params, l, i, x)
        pool = write_kv(pool, i, write_page, write_off, k, v)
        with jax.named_scope(scopes.ATTN_KERNEL):
            o = attend(q[:, 0], pool["k"], pool["v"], i, tables,
                       positions + 1, sm_scale=1.0 / math.sqrt(cfg.head_dim))
        return _attn_output(cfg, params, i, x, o[:, None]), pool

    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens[:, None]]
    x, pool = _walk(cfg, params, x, pool, mamba, attn)
    return _head(cfg, params, x[:, 0]), pool


(prefill_chunk_paged, decode_step_paged, _decode_sample_paged,
 decode_multi_paged) = paged_programs(
    _chunk_forward, _decode_once, last_token_logits(_head))


__all__ = [
    "JambaConfig", "param_specs", "partition_rules", "init_params",
    "forward", "init_paged_kv", "prefill_chunk_paged", "decode_step_paged",
    "decode_multi_paged", "SLOT_STATE_LEAVES",
]
