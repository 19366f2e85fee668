"""Olmo-Hybrid-class decoder: Gated DeltaNet layers whose write strength
reaches 2 (a linear-attention recurrence over a matrix state a head,
96 keys by 192 values) beside MULTI-head softmax-attention layers with no
positions, every sublayer's OUTPUT normed, a dense gated MLP in every
layer.

A block of its own beside the seven other families' (none gets a switch
for any of this). Source: the model's config.json (`model_type:
olmo_hybrid`) and HF `modeling_olmo_hybrid.py`, which builds its linear
layer from the `fla` library's GatedDeltaNet;
benchmarks/configs/olmo-hybrid-7b.json lists what each fixes and what is
assumed. D model width; layer l is a FULL layer when
(l + 1) % `full_interval` == 0, else a LINEAR one:

  h <- x + norm(Mixer(x));  x <- h + norm(MLP(h));  no bias anywhere:
  the norm sits on each sublayer's OUTPUT (the Olmo 2 / Olmo 3 order),
  the sublayer reads the residual stream as it is.
  norm   x / sqrt(mean(x^2) + eps) * w, float32 (w starts at 1)
  linear x W_qkvz cut into q, k [Hk dk], v, z [Hv dv] (four projections
         side by side); b, a = x W_ba [Hv each]. q | k | v pass a causal
         depthwise convolution of `conv_taps` taps, then SiLU; q, k L2
         normalised a head, q times dk^-1/2; beta = 2 sigmoid(b)
         (`allow_neg_eigval`: I - beta k k^T may reflect),
         g = -exp(A_log) softplus(a + dt_bias); then the gated delta
         rule over a head's state S [dk, dv] (ops/gated_delta.py);
         o <- rmsnorm(o) w_norm silu(z) a head, then W_out.
  full   x W_qkv cut into q [H K], k, v [G K]; norm over q's and k's
         WHOLE width (not a head's); NO rotary position (the recurrent
         layers order the tokens); causal softmax at K^-1/2 over G = H
         KV heads; W_o.
  MLP    W_down(silu(W_gate h) * W_up h), width `d_ff`.
  final norm, then an untied head.

**The tree is the served tree.** `param_specs` names the projections as
the programs multiply by them, the published separate q / k / v / gate
(and b / a, and the three convolutions) side by side in ONE leaf each: a
matmul against [W_q | W_k | W_v | W_g] IS the four matmuls, column for
column, so the fusion is a naming of columns and not arithmetic, and no
`lay_out` cuts or copies anything at load. Every leaf is a stack over its
kind's layers, read where it lies (`params[k][i]`, a static index): a
dense 7B model's planes are most of its bytes, the benchmark's harness
keeps the tree it made for its reference, and a copy a layer of each
plane beside it (3.3 GB at the benchmark's eight layers) does not fit
the chip next to this family's pool. tests/test_chip_compile.py holds
that the compiled programs make no plane-sized copy.

**Two kinds of per-request memory in one pool pytree**, as
models/qwen3_next.py: full layers keep ``pool["k"], pool["v"]``
``[n_full, P+1, page, G*K]`` (3,840 lanes a row at the published sizes:
the decode kernel attends them a page of 64 keys a block,
ops/paged_attention.py `decode_block_pages`); linear layers keep a RECURRENT STATE by the slot,
``pool["gdn_state"]`` ``[n_linear, n_slots+1, Hv / p, dk, p dv]`` float32
with p = `ops.gated_delta.packed_heads` heads side by side (2 at dv =
192: [15, 96, 384], dense in the chip's tiles where [30, 96, 192] would
be padded by a third), and ``pool["gdn_conv"]`` ``[n_linear, n_slots+1,
taps-1, channels]``. The last row is the null slot. A decode step's batch
IS the slot array, the pool is donated and the state never copied; a
chunk row starts from zeros at offset 0, else from the row of THIS
dispatch that holds the same slot's chunk before it, else from the
slot's state (`blocks.dispatch_order`), and the last live row of a slot
writes state and tail back.

The four paged programs are `paged_kv.paged_programs` over the chunk
forward and the decode step. Both walk the layers as ONE loop over the
pattern's periods (`_walk_periods`: a loop over a period's linear layers,
then its full layer), every stack and the pool's leaves read at the
loop's counters where they lie; `forward` walks them in Python.
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
                                   init_from_specs, last_token_logits,
                                   rms_norm, untied_head, write_kv)
from ray_tpu.models.paged_kv import paged_programs
from ray_tpu.ops.gated_delta import (
    gdn_chunk_scan, gdn_decode_step, pack_state, packed_heads,
    reference_gdn_decode_step, unpack_state)

_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    d_model: int = 3840
    n_layers: int = 32
    full_interval: int = 4           # every fourth layer is a full one
    n_heads: int = 30                # full layers: query heads
    n_kv_heads: int = 30             # multi-head: a KV head a query head
    head_dim: int = 128
    lin_k_heads: int = 30            # linear layers: key heads
    lin_v_heads: int = 30            # value heads (and states) a layer
    lin_k_dim: int = 96
    lin_v_dim: int = 192
    conv_taps: int = 4
    allow_neg_eigval: bool = True    # beta = 2 sigmoid(b), in (0, 2)
    d_ff: int = 11008
    norm_eps: float = 1e-6
    scan_block: int = 64             # tokens a block of the chunked scan
    max_seq: int = 4096
    dtype: Any = jnp.bfloat16        # activation/compute dtype
    param_dtype: Any = jnp.float32

    family: ClassVar[str] = "olmo_hybrid"  # models/serving.py

    @classmethod
    def tiny(cls, **kw) -> "OlmoHybridConfig":
        """CPU-test size that keeps every ratio: two periods of 3 linear
        : 1 full; 6 heads of each kind, a key head a value head; values
        twice as wide as keys and HALF a lane tile too many (64: the
        pool's state packs two heads side by side, as at 192); a scan
        block a chunk row holds twice."""
        base = dict(vocab_size=256, d_model=64, n_layers=8, n_heads=6,
                    n_kv_heads=6, head_dim=16, lin_k_heads=6, lin_v_heads=6,
                    lin_k_dim=32, lin_v_dim=64, d_ff=96, scan_block=16,
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

    @property
    def state_pack(self) -> int:
        """Heads the pool's state keeps side by side along the lanes."""
        return packed_heads(self.lin_v_heads, self.lin_v_dim)


def param_specs(cfg: OlmoHybridConfig) -> dict[str, dict[str, Any]]:
    """name → {shape, init[, scale]}: one stack a mixer kind, in layer
    order within the kind; the MLP's leaves and the two output norms one
    stack over all layers. A linear layer's leaves carry the prefix
    "g_", a full layer's "f_". `g_qkvz` is W_q | W_k | W_v | W_g side by
    side, `g_ba` W_b | W_a, `g_conv` the three convolutions' taps,
    `f_qkv` W_q | W_k | W_v. Norm weights start at 1. `g_dt_bias` ones
    and `g_A_log` the log of U(0, 16) are the `fla` layer's own start (a
    loader that fills leaves from normal / ones / zeros alone overrides
    both, as benchmarks/families/olmo_hybrid.py does)."""
    D, V, L = cfg.d_model, cfg.vocab_size, cfg.n_layers
    H, G, K = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Hk, Hv, dk, dv = (cfg.lin_k_heads, cfg.lin_v_heads, cfg.lin_k_dim,
                      cfg.lin_v_dim)
    nl, nf, F = cfg.count("linear"), cfg.count("full"), cfg.d_ff
    norm = lambda *s, scale=0.02: {"init": "normal", "scale": scale,
                                   "shape": s}
    resid = lambda *s: norm(*s, scale=0.02 / math.sqrt(2 * L))
    ones = lambda *s: {"init": "ones", "shape": s}
    return {
        "wte": norm(V, D), "lm_head": norm(D, V), "ln_f_scale": ones(D),
        "ln1_scale": ones(L, D), "ln2_scale": ones(L, D),
        "g_qkvz": norm(nl, D, 2 * Hk * dk + 2 * Hv * dv),
        "g_ba": norm(nl, D, 2 * Hv),
        "g_conv": norm(nl, cfg.conv_taps, cfg.conv_channels, scale=0.5),
        "g_dt_bias": ones(nl, Hv),
        "g_A_log": {"init": "log_uniform", "high": 16.0, "shape": (nl, Hv)},
        "g_norm": ones(nl, dv), "g_out": resid(nl, Hv * dv, D),
        "f_qkv": norm(nf, D, (H + 2 * G) * K),
        "f_qnorm": ones(nf, H * K), "f_knorm": ones(nf, G * K),
        "f_wo": resid(nf, H * K, D),
        "w_gate": norm(L, D, F), "w_up": norm(L, D, F),
        "w_down": resid(L, F, D)}


def partition_rules() -> tuple:
    """Every leaf replicated: the family serves at tp = 1 only
    (models/serving.py refuses more); the table exists so that the
    shared loaders find a rule for each leaf."""
    from jax.sharding import PartitionSpec

    return ((r".*", PartitionSpec()),)


def init_params(cfg: OlmoHybridConfig, rng: jax.Array) -> dict[str, jax.Array]:
    return init_from_specs(param_specs(cfg), rng, cfg.param_dtype)


# ------------------------------------------------------------- the block

def _unit(x):
    """x / |x| over the last axis, float32, as the model computes it."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


@jax.named_scope(scopes.GDN_IN)
def _gdn_inputs(cfg: OlmoHybridConfig, params, i, x, valid, boundary):
    """The i-th linear layer (an int, or a loop's counter) up to what
    the delta rule takes. x [N, C, D]: the
    residual stream as it is (no norm before a sublayer); valid [N, C]
    bool (a token that is none leaves the state alone: g = 0, beta = 0);
    `boundary(mixed)` → [N, taps-1, channels]: the convolution's inputs
    BEFORE each row's first token, given the rows' own `mixed`
    [N, C, channels].
    → (q, k [N, C, Hk, dk], v, z [N, C, Hv, dv], g, beta [N, C, Hv], ext
    [N, taps-1+C, channels]: the convolution's inputs with the boundary
    in front), all float32."""
    N, C, _D = x.shape
    dt = cfg.dtype
    Hk, Hv, dk, dv = (cfg.lin_k_heads, cfg.lin_v_heads, cfg.lin_k_dim,
                      cfg.lin_v_dim)
    ch = cfg.conv_channels
    # The wide projections' sums are handed on as the MXU accumulated
    # them, float32: everything that reads them (the convolution, SiLU,
    # the L2 norms, the gate) computes in float32, and a round to bf16
    # between a matmul and float32 arithmetic is one the plain bf16
    # forward does not make either (XLA folds the pair of converts
    # away there). What is KEPT is bf16: the pool's tail.
    wide = functools.partial(jnp.matmul, preferred_element_type=_F32)
    qkvz = wide(x, params["g_qkvz"][i].astype(dt))
    mixed, z = qkvz[..., :ch], qkvz[..., ch:]
    ba = wide(x, params["g_ba"][i].astype(dt))
    b, a = ba[..., :Hv], ba[..., Hv:]
    ext = jnp.concatenate([boundary(mixed).astype(_F32), mixed], axis=1)
    taps = params["g_conv"][i].astype(dt).astype(_F32)      # [taps, ch]
    conv = sum(taps[j] * ext[:, j:j + C] for j in range(cfg.conv_taps))
    q, k, v = jnp.split(jax.nn.silu(conv), [Hk * dk, 2 * Hk * dk], axis=-1)
    heads = lambda t: t.reshape(N, C, Hk, dk)
    q, k = _unit(heads(q)) / math.sqrt(dk), _unit(heads(k))
    beta = jax.nn.sigmoid(b) * (2.0 if cfg.allow_neg_eigval else 1.0)
    g = (-jnp.exp(params["g_A_log"][i].astype(_F32))
         * jax.nn.softplus(a + params["g_dt_bias"][i].astype(_F32)))
    live = valid[..., None]
    return (q, k, v.reshape(N, C, Hv, dv), z.reshape(N, C, Hv, dv),
            jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0), ext)


@jax.named_scope(scopes.GDN_OUT)
def _gdn_output(cfg: OlmoHybridConfig, params, l, i, x, o, z):
    """From the delta rule's output o [N, C, Hv, dv] float32 to the end
    of layer l's mixer (the i-th linear one): the gated norm a head,
    W_out, the OUTPUT norm, the residual."""
    N, C, _D = x.shape
    dt = cfg.dtype
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + cfg.norm_eps)
    o = (o * params["g_norm"][i].astype(_F32)
         * jax.nn.silu(z.astype(_F32))).astype(dt)
    return x + rms_norm(o.reshape(N, C, -1) @ params["g_out"][i].astype(dt),
                        params["ln1_scale"][l], cfg.norm_eps)


@jax.named_scope(scopes.ATTN_IN)
def _attn_inputs(cfg: OlmoHybridConfig, params, i, x):
    """The i-th full layer's attention up to q, k, v: the norm is over
    the WHOLE projected width of q and of k, and nothing turns with the
    position. → (q [N, C, H, K], k, v [N, C, G, K] in cfg.dtype)."""
    N, C, _D = x.shape
    dt = cfg.dtype
    H, G, K = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    qkv = jnp.matmul(x, params["f_qkv"][i].astype(dt),
                     preferred_element_type=_F32)    # as `_gdn_inputs`
    q, k, v = jnp.split(qkv, [H * K, (H + G) * K], axis=-1)
    q = rms_norm(q, params["f_qnorm"][i], cfg.norm_eps).astype(dt)
    k = rms_norm(k, params["f_knorm"][i], cfg.norm_eps).astype(dt)
    return (q.reshape(N, C, H, K), k.reshape(N, C, G, K),
            v.astype(dt).reshape(N, C, G, K))


@jax.named_scope(scopes.ATTN_OUT)
def _attn_output(cfg: OlmoHybridConfig, params, l, i, x, attn):
    N, C, _D = x.shape
    dt = cfg.dtype
    return x + rms_norm(attn.reshape(N, C, -1) @ params["f_wo"][i].astype(dt),
                        params["ln1_scale"][l], cfg.norm_eps)


@jax.named_scope(scopes.MLP)
def _mlp(cfg: OlmoHybridConfig, params, l, x):
    """Layer l's dense MLP on the residual stream as it is, its OUTPUT
    normed, and the residual."""
    N, C, D = x.shape
    f = gated_mlp(x.reshape(N * C, D), params["w_gate"][l],
                  params["w_up"][l], params["w_down"][l])
    return x + rms_norm(f.astype(cfg.dtype).reshape(N, C, D),
                        params["ln2_scale"][l], cfg.norm_eps)


_head = functools.partial(untied_head, rms_norm)


def _repeat_heads(cfg: OlmoHybridConfig, t):
    """Key heads [N, C, Hk, dk] → one a value head [N, C, Hv, dk] (the
    published sizes have as many of each)."""
    r = cfg.lin_v_heads // cfg.lin_k_heads
    return t if r == 1 else jnp.repeat(t, r, axis=2)


# ------------------------------------------ full sequence (tests, no cache)

def forward(cfg: OlmoHybridConfig, params, tokens):
    """tokens [B, S] → logits [B, S, V] float32: every row a whole
    sequence from position 0 and a zero state, plain masked attention,
    no pool. The linear layers run the chunked scan over the row (padded
    to whole blocks with tokens that leave the state alone)."""
    B, S = tokens.shape
    pad = -S % cfg.scan_block
    valid = jnp.ones((B, S), bool)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    zeros = lambda mixed: jnp.zeros(
        (B, cfg.conv_taps - 1, mixed.shape[-1]), mixed.dtype)
    padded = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens]
    for l, kind in enumerate(cfg.kinds):
        i = cfg.index(l)[1]
        if kind == "linear":
            q, k, v, z, g, beta, _ext = _gdn_inputs(cfg, params, i, x, valid,
                                                    zeros)
            with jax.named_scope(scopes.GDN_SCAN):
                o, _finals = gdn_chunk_scan(
                    *(padded(t) for t in (_repeat_heads(cfg, q),
                                          _repeat_heads(cfg, k), v, g, beta)),
                    jnp.zeros((B, cfg.lin_v_heads, cfg.lin_k_dim,
                               cfg.lin_v_dim), _F32),
                    jnp.full(B, -1, jnp.int32), jnp.ones(B, bool),
                    block=cfg.scan_block)
            x = _gdn_output(cfg, params, l, i, x, o[:, :S], z)
        else:
            q, k, v = _attn_inputs(cfg, params, i, x)
            with jax.named_scope(scopes.ATTN_KERNEL):
                rep = cfg.n_heads // cfg.n_kv_heads
                if rep > 1:
                    k, v = (jnp.repeat(t, rep, axis=2) for t in (k, v))
                s = jnp.einsum("bshk,bthk->bhst", q, k,
                               preferred_element_type=_F32)
                s = jnp.where(causal[None, None],
                              s / math.sqrt(cfg.head_dim), -1e30)
                attn = jnp.einsum(
                    "bhst,bthk->bshk",
                    jax.nn.softmax(s, axis=-1).astype(cfg.dtype), v)
            x = _attn_output(cfg, params, l, i, x, attn)
        x = _mlp(cfg, params, l, x)
    return _head(cfg, params, x)


# --------------------------------------------------------- the paged pool

# The pool's leaves that are a state by the slot (models/serving.py).
SLOT_STATE_LEAVES = ("gdn_state", "gdn_conv")


def init_paged_kv(cfg: OlmoHybridConfig, n_pages: int, page_size: int,
                  n_slots: int, kv_dtype: str | None = None):
    """The pool pytree the paged programs carry, donated: the full
    layers' pages ``[n_full, P+1, page_size, G*K]`` (row 0 the null
    page), the linear layers' recurrent state ``[n_linear, n_slots+1,
    Hv / p, dk, p dv]`` float32 (`state_pack` heads side by side) and
    convolution tail ``[n_linear, n_slots+1, taps-1, channels]`` (the
    last row the null slot)."""
    if kv_dtype not in (None, "bf16"):
        raise ValueError(
            f"the olmo_hybrid family's pool is bf16, got {kv_dtype!r}")
    nl, p = cfg.count("linear"), cfg.state_pack
    pages = (cfg.count("full"), n_pages + 1, page_size,
             cfg.n_kv_heads * cfg.head_dim)
    return {"k": jnp.zeros(pages, cfg.dtype), "v": jnp.zeros(pages, cfg.dtype),
            "gdn_state": jnp.zeros(
                (nl, n_slots + 1, cfg.lin_v_heads // p, cfg.lin_k_dim,
                 p * cfg.lin_v_dim), _F32),
            "gdn_conv": jnp.zeros(
                (nl, n_slots + 1, cfg.conv_taps - 1, cfg.conv_channels),
                cfg.dtype)}


def _walk_periods(cfg: OlmoHybridConfig, x, pool, linear_layer, full_layer):
    """x and the pool through every period of the pattern: ONE loop over
    the periods whose body is a loop over the period's linear layers and
    its full layer, so a program holds one linear layer, one full layer
    and two MLPs whatever the depth (walked in Python, the eight layers
    of the benchmark's stage were sixteen programs of ~10 s each to
    compile). `linear_layer(l, i, x, pool)` / `full_layer(l, i, x, pool)`
    → (x, pool): layer l, the i-th of its kind, MLP and all; both
    indices are loop counters, and a stack is read at them where it
    lies."""
    n_lin = cfg.full_interval - 1
    if cfg.n_layers % cfg.full_interval:
        raise ValueError(
            f"the olmo_hybrid programs walk whole periods of "
            f"{cfg.full_interval} layers; n_layers is {cfg.n_layers}")

    def period(p, carry):
        first = p * cfg.full_interval
        carry = jax.lax.fori_loop(
            0, n_lin, lambda j, c: linear_layer(first + j, p * n_lin + j, *c),
            carry)
        return full_layer(first + n_lin, p, *carry)

    return jax.lax.fori_loop(0, cfg.n_layers // cfg.full_interval, period,
                             (x, pool))


def _chunk_forward(cfg: OlmoHybridConfig, params, tokens, pool, tables,
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

    def linear_layer(l, i, x, pool):
        def boundary(mixed):
            # A chained row's predecessor is a full chunk: its last
            # inputs are its own last tokens.
            before = jnp.where((chain >= 0)[:, None, None],
                               mixed[jnp.maximum(chain, 0), C - n_tail:],
                               pool["gdn_conv"][i, slots])
            return jnp.where(fresh[:, None, None], 0, before)

        q, k, v, z, g, beta, ext = _gdn_inputs(cfg, params, i, x, valid,
                                               boundary)
        with jax.named_scope(scopes.GDN_IN):
            tails = jnp.take_along_axis(ext, tail_at, axis=1)
            pool = {**pool, "gdn_conv": pool["gdn_conv"].at[
                i, state_rows].set(tails.astype(cfg.dtype))}
        with jax.named_scope(scopes.GDN_SCAN):
            # A row's state is cut out of the stack where it lies, a
            # dynamic slice a row: a gather over a leaf whose rows are
            # three lane tiles wide is compiled as three slices of the
            # WHOLE stack ahead of it (429 MB each at the published
            # sizes: a copy of the stack a layer).
            held = pool["gdn_state"]
            rows = jnp.concatenate([jax.lax.dynamic_slice(
                held, (i, slots[n], 0, 0, 0), (1, 1) + held.shape[2:])[0]
                for n in range(N)])
            o, finals = gdn_chunk_scan(
                _repeat_heads(cfg, q), _repeat_heads(cfg, k), v, g, beta,
                unpack_state(rows, cfg.lin_v_dim), chain, fresh,
                block=cfg.scan_block)
            pool = {**pool, "gdn_state": held.at[i, state_rows].set(
                pack_state(finals, cfg.state_pack))}
        x = _gdn_output(cfg, params, l, i, x, o, z)
        return _mlp(cfg, params, l, x), pool

    def full_layer(l, i, x, pool):
        q, k, v = _attn_inputs(cfg, params, i, x)
        pool = write_kv(pool, i, write_pages, write_offs, k, v)
        with jax.named_scope(scopes.ATTN_KERNEL):
            attn = attend(q, pool["k"], pool["v"], i, tables, offsets,
                          kv_lens, sm_scale=1.0 / math.sqrt(cfg.head_dim))
        x = _attn_output(cfg, params, l, i, x, attn)
        return _mlp(cfg, params, l, x), pool

    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens]
    return _walk_periods(cfg, x, pool, linear_layer, full_layer)


def _decode_once(cfg: OlmoHybridConfig, params, tokens, pool, positions,
                 tables, attn_impl: str):
    """All B slots advance one token: row b IS slot b. A row whose table
    is all null (an idle slot, or one still mid-prefill) writes the null
    page and leaves its slot's state and tail as they are, so a prompt's
    state survives the decode windows between its chunks.
    → (logits [B, V] fp32, updated pool)."""
    B = tokens.shape[0]
    ps = pool["k"].shape[2]
    active = tables[:, 0] > 0
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

    def linear_layer(l, i, x, pool):
        held = pool["gdn_conv"][i, :B]
        q, k, v, z, g, beta, ext = _gdn_inputs(cfg, params, i, x, live,
                                               lambda _mixed: held)
        with jax.named_scope(scopes.GDN_IN):
            tails = jnp.where(live[..., None], ext[:, 1:].astype(cfg.dtype),
                              held)
            pool = {**pool,
                    "gdn_conv": pool["gdn_conv"].at[i, :B].set(tails)}
        with jax.named_scope(scopes.GDN_SCAN):
            o, state = step(pool["gdn_state"], i, q[:, 0], k[:, 0],
                            v[:, 0].astype(_F32), g[:, 0], beta[:, 0],
                            active, repeat=repeat)
            pool = {**pool, "gdn_state": state}
        x = _gdn_output(cfg, params, l, i, x, o[:, None], z)
        return _mlp(cfg, params, l, x), pool

    def full_layer(l, i, x, pool):
        q, k, v = _attn_inputs(cfg, params, i, x)
        pool = write_kv(pool, i, write_page, write_off, k, v)
        with jax.named_scope(scopes.ATTN_KERNEL):
            attn = attend(q[:, 0], pool["k"], pool["v"], i, tables,
                          positions + 1,
                          sm_scale=1.0 / math.sqrt(cfg.head_dim))
        x = _attn_output(cfg, params, l, i, x, attn[:, None])
        return _mlp(cfg, params, l, x), pool

    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens[:, None]]
    x, pool = _walk_periods(cfg, x, pool, linear_layer, full_layer)
    return _head(cfg, params, x[:, 0]), pool


(prefill_chunk_paged, decode_step_paged, _decode_sample_paged,
 decode_multi_paged) = paged_programs(
    _chunk_forward, _decode_once, last_token_logits(_head))


__all__ = [
    "OlmoHybridConfig", "param_specs", "partition_rules", "init_params",
    "forward", "init_paged_kv", "prefill_chunk_paged", "decode_step_paged",
    "decode_multi_paged", "SLOT_STATE_LEAVES",
]
