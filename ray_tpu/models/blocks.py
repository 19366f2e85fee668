"""Parts that two or more served families are built from.

A family module (models/zaya.py, laguna.py, qwen3_next.py, mimo_v2.py,
jamba.py, kimi_k2.py, olmo_hybrid.py, nemotron_h.py) writes what is its own: the configuration, the
attention inputs, the router, the ropes, the pool. What several of them
compute the same way lives here under public names, so that no family
imports a sibling to get it: the imports of `ray_tpu/models/` point from a family to this module
and to the program builder (models/paged_kv.py `paged_programs`), never
across. (models/mimo_v2.py imports the module models/laguna.py: it IS
laguna's paged walk and ring with other parts, which is kinship, not
borrowing.)

Nothing here looks at a configuration class: a function takes the few
fields it reads (`cfg.dtype`, `cfg.norm_eps`, `cfg.top_k`,
`cfg.n_experts_routed`) off whatever it is handed.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import moe, scopes

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


# ---------------------------------------------------------------- weights

def init_from_specs(specs: dict, rng: jax.Array, dtype) -> dict:
    """Seeded leaves from a `param_specs` table: normal at the spec's
    scale, the log of U(1e-3, `high`) (a decay's A_log), or ones; a key
    a leaf, in the names' order."""
    keys = jax.random.split(rng, len(specs))
    params = {}
    for key, (name, spec) in zip(keys, sorted(specs.items())):
        if spec["init"] == "normal":
            params[name] = (jax.random.normal(key, spec["shape"], dtype)
                            * spec["scale"])
        elif spec["init"] == "log_uniform":
            params[name] = jnp.log(jax.random.uniform(
                key, spec["shape"], dtype, 1e-3, spec["high"]))
        else:
            params[name] = jnp.ones(spec["shape"], dtype)
    return params


# ------------------------------------------------------------------ norms

def _unit_rms(x, eps):
    x32 = x.astype(_F32)
    return x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                               + eps)


def rms_norm(x, scale, eps):
    """RMSNorm, float32 inside; back to x's type."""
    return (_unit_rms(x, eps) * scale.astype(_F32)).astype(x.dtype)


def rms_norm_centred(x, w, eps):
    """Zero-centred RMSNorm (a weight of 0 is a scale of 1), float32
    inside; back to x's type."""
    return (_unit_rms(x, eps) * (1.0 + w.astype(_F32))).astype(x.dtype)


# -------------------------------------------------------------------- MLP

def gated_mlp(u, w_gate, w_up, w_down):
    """W_down(silu(W_gate u) * W_up u), accumulated to float32."""
    dt = u.dtype
    gate = jnp.matmul(u, w_gate.astype(dt), preferred_element_type=_F32)
    up = jnp.matmul(u, w_up.astype(dt), preferred_element_type=_F32)
    return jnp.matmul((jax.nn.silu(gate) * up).astype(dt), w_down.astype(dt),
                      preferred_element_type=_F32)


# ------------------------------------------------------------ convolution

def causal_conv(boundary, n_taps: int):
    """A state-space layer's causal depthwise convolution over a row's
    own tokens in plain XLA (models/jamba.py, nemotron_h.py).
    `boundary(xs)` → taps-1 planes [N, Dn]: the inputs BEFORE each row's
    first token, oldest first, given the rows' own `xs` [N, C, Dn].
    → conv(xs, taps, bias) → (silu(conv + bias) [N, C, Dn] in xs.dtype,
    ext [N, taps-1+C, Dn]: the inputs with the boundary in front)."""
    def conv(xs, taps, bias):
        C = xs.shape[1]
        ext = jnp.concatenate(
            [b[:, None].astype(xs.dtype) for b in boundary(xs)] + [xs],
            axis=1)
        acc = sum(taps[j] * ext[:, j:j + C].astype(_F32)
                  for j in range(n_taps))
        return jax.nn.silu(acc + bias).astype(xs.dtype), ext
    return conv


# ------------------------------------------------------------------- rope

def yarn_inv_freq(cfg):
    """YaRN rotary frequencies [rotary_dim / 2], float64 on the host:
    plain frequencies where a dim turns more than `beta_fast` times over
    the original context (`yarn_orig`), divided by `yarn_factor` where it
    turns fewer than `beta_slow`, a linear ramp between."""
    d, theta = cfg.rotary_dim, float(cfg.rope_theta)
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    bound = lambda beta: (d * math.log(cfg.yarn_orig / (beta * 2 * math.pi))
                          / (2 * math.log(theta)))
    low = max(math.floor(bound(cfg.beta_fast)), 0)
    high = min(math.ceil(bound(cfg.beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return f * (1.0 - ramp) + (f / cfg.yarn_factor) * ramp


# ----------------------------------------------------------------- router

@jax.named_scope(scopes.MOE_ROUTE)
def biased_route(cfg, w_router, bias, u):
    """A sigmoid router that chooses by a biased score and gates by the
    unbiased one, float32 throughout. u [M, D] → (experts [M, k] int32
    global ids, chosen by s + bias; gates [M, k] float32, the chosen
    experts' UNBIASED scores normalised over all k choices, held here or
    not, times `cfg.routed_scale` where the configuration has one; moved
    [M] int32, the choices that are not among the k largest of s
    alone)."""
    s = jax.nn.sigmoid(jnp.matmul(u.astype(_F32), w_router.astype(_F32),
                                  precision=_HIGHEST))
    _top, chosen = jax.lax.top_k(s + bias.astype(_F32), cfg.top_k)
    own = jnp.take_along_axis(s, chosen, axis=-1)
    # A choice's rank by s alone: the experts that score higher.
    above = jnp.sum(s[:, None, :] > own[:, :, None], axis=-1)
    chosen = chosen.astype(jnp.int32)
    gates = own / jnp.sum(own, axis=-1, keepdims=True)
    if hasattr(cfg, "routed_scale"):
        gates = gates * cfg.routed_scale
    return (chosen, gates,
            jnp.sum(above >= cfg.top_k, axis=-1).astype(jnp.int32))


# ------------------------------------------------------------------ heads

@jax.named_scope(scopes.HEAD)
def tied_head(norm, cfg, params, x):
    """Final `norm` and the embedding as the head → float32 logits
    [..., V]."""
    h = norm(x, params["ln_f_scale"], cfg.norm_eps)
    return jnp.einsum("...d,vd->...v", h, params["wte"].astype(cfg.dtype),
                      preferred_element_type=_F32)


@jax.named_scope(scopes.HEAD)
def untied_head(norm, cfg, params, x):
    """Final `norm` and the untied head → float32 logits [..., V]."""
    h = norm(x, params["ln_f_scale"], cfg.norm_eps)
    return jnp.matmul(h, params["lm_head"].astype(cfg.dtype),
                      preferred_element_type=_F32)


def last_token_logits(head):
    """`head(cfg, params, x)` as the program builder's `chunk_logits`:
    run on each chunk row's last valid hidden state only ([N, C, V] at a
    262k vocabulary is not a tensor to make; an inert row clamps to
    token 0, garbage the engine ignores)."""

    def chunk_logits(cfg, params, x, n_valid):
        with jax.named_scope(scopes.HEAD):
            last = jnp.take_along_axis(
                x, jnp.maximum(n_valid - 1, 0)[:, None, None], axis=1)[:, 0]
        return head(cfg, params, last)

    return chunk_logits


# --------------------------------------------------------- the paged pool

def attend_fn(attn_impl: str, chunk: bool):
    """The pool reader of a chunk row or of a decode step. "kernel": the
    Pallas ragged paged-attention kernel, which reads K/V pages in place
    from the pool at (layer, page); no [B, T, H, K] timeline ever hits
    HBM. "gather": the reference that reconstitutes the contiguous
    timeline: ONE implementation shared with the kernel's test oracle,
    so engine-gather and oracle can never diverge."""
    from ray_tpu.ops.paged_attention import (
        paged_attention, paged_prefill_attention, reference_paged_attention,
        reference_paged_prefill_attention)

    if attn_impl not in ("gather", "kernel"):
        raise ValueError(
            f"attn_impl must be gather|kernel, got {attn_impl!r}")
    kernel, oracle = ((paged_prefill_attention,
                       reference_paged_prefill_attention) if chunk else
                      (paged_attention, reference_paged_attention))
    return kernel if attn_impl == "kernel" else oracle


@jax.named_scope(scopes.ATTN_KV_WRITE)
def write_kv(pool, l, pages, offs, k, v, planes=("k", "v")):
    """K/V [.., G, K] as rows [M, G*K] → (l, pages[m], offs[m]) of the
    carried pool's K and V `planes`."""
    rows = lambda t: t.reshape(-1, t.shape[-2] * t.shape[-1])
    kn, vn = planes
    return {**pool, kn: pool[kn].at[l, pages, offs].set(rows(k)),
            vn: pool[vn].at[l, pages, offs].set(rows(v))}


@jax.named_scope(scopes.ATTN_KV_WRITE)
def write_row(pool, l, pages, offs, row, plane):
    """A latent cache's ONE row a token, [.., K] as rows [M, K] →
    (l, pages[m], offs[m]) of the carried pool's `plane`."""
    rows = row.reshape(-1, row.shape[-1])
    return {**pool, plane: pool[plane].at[l, pages, offs].set(rows)}


@jax.named_scope(scopes.SLOT_STATE)
def dispatch_order(slots, offsets, n_valid, null_slot: int):
    """Where each chunk row of one dispatch finds the state before its
    first token, for a family whose per-slot state is a RECURRENCE (a
    row cannot read a chained row's boundary in parallel, so the state
    pass walks the rows in order). slots, offsets, n_valid [N]: a row's
    slot, the position of its first token, its tokens (0: an inert row).
    → (chain [N] int32: the row ABOVE that holds the same slot's chunk
    before this one, -1 for none (the row then starts from the slot's
    state in the pool); state_rows [N]: the slot a row writes its final
    state back to, `null_slot` unless it is its slot's last live row of
    the dispatch; fresh [N] bool: the row starts a prompt, from zeros,
    which is the reset of a reused or re-prefilled slot)."""
    row = jnp.arange(slots.shape[0])
    live = n_valid > 0
    same = ((slots[:, None] == slots[None, :])
            & live[:, None] & live[None, :])
    chain = jnp.max(jnp.where(same & (row[None, :] < row[:, None]),
                              row[None, :], -1), axis=1)
    is_last = live & ~jnp.any(same & (row[None, :] > row[:, None]), axis=1)
    return chain, jnp.where(is_last, slots, null_slot), offsets == 0


# Running totals over decode steps in `pool["moe_counters"]`, wrapping
# uint32 (the host takes differences): (sparse layer, step) pairs, held
# experts that had a row, the fullest held expert's rows, choices routed
# (k a live row), the choices that landed on a held expert, and of those
# the ones the expert layer's first block did not take (`moe.rows_over`;
# 0 unless the routing sent this share over twice an even router's part).
COUNTERS = ("layer_steps", "experts_touched", "rows_max", "rows_routed",
            "rows_held", "rows_over")


# ... and, behind a router that chooses by a biased score, the choices
# the bias moved.
COUNTERS_BIASED = COUNTERS + ("rows_bias_moved",)


def counter_row_biased(cfg, counted, n_live, n_rows):
    """`counter_row` for `COUNTERS_BIASED`: `counted` a sparse layer's
    (counts, the live rows' choices the bias moved)."""
    counts, moved = counted
    return jnp.concatenate([counter_row(cfg, counts, n_live, n_rows),
                            moved.astype(jnp.uint32)[None]])


def counter_row(cfg, counts, n_live, n_rows: int):
    """What one sparse layer of one decode step adds to `COUNTERS`:
    `counts` [n_experts] the rows each held expert received, `n_live` the
    rows that carried a token, `n_rows` the rows the layer was handed."""
    return jnp.stack([jnp.uint32(1), jnp.sum(counts > 0).astype(jnp.uint32),
                      jnp.max(counts).astype(jnp.uint32),
                      (n_live * cfg.top_k).astype(jnp.uint32),
                      jnp.sum(counts).astype(jnp.uint32),
                      moe.rows_over(counts, n_rows * cfg.top_k,
                                    cfg.n_experts_routed)])
