"""The plain reference for the `olmo_hybrid` family (Olmo-Hybrid-7B:
three Gated DeltaNet layers whose write strength reaches 2 to one
multi-head softmax-attention layer with no positions, every sublayer's
OUTPUT normed, a dense gated MLP in every layer): the yardstick
`correct` appeals to. Straightforward `jax.numpy` over ONE whole
sequence: no kernel, no cache, no paging, no chunked scan, and NO import
from `ray_tpu`. The description followed is the model's config.json
(`model_type: olmo_hybrid`) and HF `modeling_olmo_hybrid.py`, which
builds its linear layer from the `fla` library's GatedDeltaNet at
`head_dim` 96, `expand_v` 2.

Two arithmetics, chosen by `dtype`, as in gpt_ref.py. float32 (under
`jax.default_matmul_precision("highest")`) is the truth. bfloat16 is the
arithmetic the model is SERVED in, laid out plainly: bf16 weights and
activations; norms, softmax, the gate, the decay, `beta` and the WHOLE
delta-rule recurrence (its state and its sums) in float32, matmuls
accumulated to float32.

Layer l is a full-attention layer when (l + 1) % `full_interval` == 0,
else a Gated DeltaNet layer; x is a layer's input:

  h = x + norm(Mixer(x));  out = h + norm(MLP(h))
  norm(x) = x / sqrt(mean(x^2) + eps) * w, float32; it sits on each
  sublayer's OUTPUT and the sublayer reads the residual stream as it is.

  Gated DeltaNet (H heads; keys dk wide, values dv): q = silu(conv(x W_q)),
  k = silu(conv(x W_k)) [H dk], v = silu(conv(x W_v)) [H dv], each a
  causal depthwise convolution of `taps` taps with no bias
  (c_t = sum_j w_j m_{t-taps+1+j}, zeros before the sequence); q, k L2
  normalised a head (x rsqrt(sum x^2 + 1e-6)), q times dk^-1/2;
  beta = 2 sigmoid(x W_b) (`linear_allow_neg_eigval`; sigmoid alone
  without it), g = -exp(A_log) softplus(x W_a + dt_bias) in float32. A
  head's state S [dk, dv] starts at zero and, token by token (THE
  definition: a `lax.scan`):

      S <- exp(g_t) S;  d_t = beta_t (v_t - S^T k_t);
      S <- S + k_t d_t^T;  o_t = S^T q_t

  o <- o rsqrt(mean(o^2) + eps) w_o silu(x W_g) a head (plain RMSNorm
  over a head's dv values), heads flattened, W_out.

  Full attention (H heads over G = H KV heads of size K): q = norm(x W_q)
  and k = norm(x W_k) over the WHOLE projected width (H K values, not a
  head's), v = x W_v; NO rotary position; causal softmax at K^-1/2; W_o.

  MLP: W_down(silu(W_gate h) * W_up h).

  final norm; logits x W_head (untied).

Departures from the published model, each also in the configuration
file: the norm's placement on the linear layer's output and the
whole-width q / k norm are the Olmo 2 / Olmo 3 convention, read here as
holding for both kinds of layer (config.json does not say);
`rope_parameters.rope_theta` null is read as no rotary position; the
state is float32; weights are seeded.

Parameters are the program's own pytree (models/olmo_hybrid.py), one
stack a mixer kind in layer order within the kind, the MLP's leaves and
the output norms one stack over all layers, and the published separate
projections SIDE BY SIDE in one leaf (cut apart here, so that each runs
as the matmul of its own it is published as): wte [V,D], lm_head [D,V],
ln_f_scale [D]; ln1_scale, ln2_scale [L,D]; g_qkvz [nl,D,2 H dk + 2 H dv]
= W_q | W_k | W_v | W_g, g_ba [nl,D,2 H] = W_b | W_a, g_conv
[nl,taps,2 H dk + H dv] = the three convolutions' taps, g_dt_bias,
g_A_log [nl,H], g_norm [nl,dv], g_out [nl,H dv,D]; f_qkv [nf,D,(H + 2 G) K]
= W_q | W_k | W_v, f_qnorm [nf,H K], f_knorm [nf,G K], f_wo [nf,H K,D];
w_gate, w_up [L,D,F], w_down [L,F,D].

`rc` is a hashable static value (families/olmo_hybrid.py
`reference_config`): `n_layers`, `full_interval`, `n_heads`,
`n_kv_heads`, `lin_heads`, `lin_k_dim`, `neg_eigval`, `norm_eps`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_HEAD_ROWS = 256        # rows of the head computed at a time (paired_rows)
_QUERY_ROWS = 256       # query rows attended at a time


def _norm(x, w, eps):
    """RMSNorm, float32 inside; back to x's type."""
    x32 = x.astype(_F32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(_F32)).astype(x.dtype)


def _short_conv(m, taps):
    """m [S, ch] -> silu of its causal depthwise convolution by `taps`
    [t, ch] (zeros before the sequence), computed in float32 from
    operands of m's type, back in m's type."""
    S, dt = m.shape[0], m.dtype
    n = taps.shape[0]
    padded = jnp.concatenate([jnp.zeros((n - 1, m.shape[1]), _F32),
                              m.astype(_F32)])
    conv = sum(taps[j].astype(dt).astype(_F32) * padded[j:j + S]
               for j in range(n))
    return jax.nn.silu(conv).astype(dt)


def _delta_net(x, w, rc):
    """x [S, D] -> the Gated DeltaNet sublayer's output [S, D] (before
    the output norm)."""
    S, dt = x.shape[0], x.dtype
    H, dk = rc.lin_heads, rc.lin_k_dim
    dv = w["out"].shape[0] // H
    cut = [H * dk, 2 * H * dk, 2 * H * dk + H * dv]
    w_q, w_k, w_v, w_g = jnp.split(w["qkvz"], cut, axis=1)
    w_b, w_a = jnp.split(w["ba"], 2, axis=1)
    c_q, c_k, c_v = jnp.split(w["conv"], cut[:2], axis=1)
    proj = lambda m: x @ m.astype(dt)
    q = _short_conv(proj(w_q), c_q).astype(_F32).reshape(S, H, dk)
    k = _short_conv(proj(w_k), c_k).astype(_F32).reshape(S, H, dk)
    v = _short_conv(proj(w_v), c_v).astype(_F32).reshape(S, H, dv)
    unit = lambda t: t * jax.lax.rsqrt(
        jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)
    q, k = unit(q) / math.sqrt(dk), unit(k)
    beta = jax.nn.sigmoid(proj(w_b).astype(_F32))
    if rc.neg_eigval:
        beta = 2.0 * beta
    g = (-jnp.exp(w["A_log"].astype(_F32))
         * jax.nn.softplus(proj(w_a).astype(_F32)
                           + w["dt_bias"].astype(_F32)))      # [S, H]

    def token(state, inputs):                         # state [H, dk, dv]
        q_t, k_t, v_t, g_t, beta_t = inputs
        state = state * jnp.exp(g_t)[:, None, None]
        d = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * d[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((H, dk, dv), _F32),
                        (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + rc.norm_eps)
    gate = jax.nn.silu(proj(w_g).astype(_F32)).reshape(S, H, dv)
    o = (o * w["norm"].astype(_F32) * gate).astype(dt)
    return o.reshape(S, H * dv) @ w["out"].astype(dt)


def _attention(x, w, rc):
    """x [S, D] -> the full-attention sublayer's output [S, D] (before
    the output norm)."""
    S, dt = x.shape[0], x.dtype
    H, G = rc.n_heads, rc.n_kv_heads
    K = w["wo"].shape[0] // H
    w_q, w_k, w_v = jnp.split(w["qkv"], [H * K, (H + G) * K], axis=1)
    q = _norm(x @ w_q.astype(dt), w["qnorm"], rc.norm_eps).reshape(S, H, K)
    k = _norm(x @ w_k.astype(dt), w["knorm"], rc.norm_eps).reshape(S, G, K)
    v = (x @ w_v.astype(dt)).reshape(S, G, K)
    k, v = jnp.repeat(k, H // G, axis=1), jnp.repeat(v, H // G, axis=1)
    block = _QUERY_ROWS if S % _QUERY_ROWS == 0 else S
    j = jnp.arange(S)

    def rows(args):
        i, q_rows = args                                   # [b], [b, H, K]
        scores = jnp.einsum("shk,thk->hst", q_rows, k,
                            preferred_element_type=_F32) / math.sqrt(K)
        seen = j[None, :] <= i[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                               axis=-1).astype(dt)
        return jnp.einsum("hst,thk->shk", probs, v)

    split = lambda a: a.reshape((S // block, block) + a.shape[1:])
    o = jax.lax.map(rows, (split(j), split(q))).reshape(S, H * K)
    return o @ w["wo"].astype(dt)


def _gated_mlp(u, w_gate, w_up, w_down):
    """W_down(silu(W_gate u) * W_up u) -> float32 [S, D]."""
    dt = u.dtype
    hid = (jax.nn.silu((u @ w_gate.astype(dt)).astype(_F32))
           * (u @ w_up.astype(dt)).astype(_F32)).astype(dt)
    return (hid @ w_down.astype(dt)).astype(_F32)


_MIXER = {"linear": ("qkvz", "ba", "conv", "dt_bias", "A_log", "norm", "out"),
          "full": ("qkv", "qnorm", "knorm", "wo")}


def _kinds(rc) -> tuple:
    return tuple("full" if (l + 1) % rc.full_interval == 0 else "linear"
                 for l in range(rc.n_layers))


def hidden(params, tokens, rc, dtype=_F32):
    """tokens [S] int32 -> final-norm hidden states [S, D] in `dtype`.
    Layers are walked in order and query rows attended a block at a
    time, so the reference fits beside bf16 weights."""
    x = params["wte"][tokens].astype(dtype)
    at = {"linear": 0, "full": 0}
    for l, kind in enumerate(_kinds(rc)):
        i, p = at[kind], "g_" if kind == "linear" else "f_"
        at[kind] += 1
        w = {k: params[p + k][i] for k in _MIXER[kind]}
        mixer = _delta_net if kind == "linear" else _attention
        h = x + _norm(mixer(x, w, rc).astype(dtype), params["ln1_scale"][l],
                      rc.norm_eps)
        f = _gated_mlp(h, params["w_gate"][l], params["w_up"][l],
                       params["w_down"][l])
        x = h + _norm(f.astype(dtype), params["ln2_scale"][l], rc.norm_eps)
    return _norm(x, params["ln_f_scale"], rc.norm_eps)


def _head(params, h, dtype):
    return jnp.einsum("sd,dv->sv", h, params["lm_head"].astype(dtype),
                      preferred_element_type=_F32)


def logits(params, tokens, rc, dtype=_F32):
    """tokens [S] -> logits [S, V] float32 (accumulated to float32 from
    `dtype` operands). Whole: for tests and short sequences."""
    with jax.default_matmul_precision("highest"):
        return _head(params, hidden(params, tokens, rc, dtype), dtype)


def loss(params, tokens, targets, rc):
    """Mean next-token cross-entropy of a batch [B, S], float32."""
    with jax.default_matmul_precision("highest"):
        def one(toks, tgt):
            lg = _head(params, hidden(params, toks, rc), _F32)
            logz = jax.nn.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, tgt[:, None], axis=-1)[:, 0]
            return jnp.sum(logz - gold)

        total = sum(one(t, g) for t, g in zip(tokens, targets))
        return total / (tokens.shape[0] * tokens.shape[1])


def paired_rows(params, seq, rc):
    """For a padded stream `seq` [S], per position and all measured in the
    FLOAT32 reference's logits: the row's best logit and its argmax, the
    logit of the token that actually follows (what was served), and the
    logit of the token a plain bfloat16 forward of the same weights would
    have chosen there (gpt_ref.paired_rows has the why). The head runs
    `_HEAD_ROWS` rows at a time."""
    S = seq.shape[0]
    block = _HEAD_ROWS if S % _HEAD_ROWS == 0 else S
    with jax.default_matmul_precision("highest"):
        h32 = hidden(params, seq, rc, _F32)
        h16 = hidden(params, seq, rc, jnp.bfloat16)

        def rows(args):
            a32, a16, served = args
            lg32 = _head(params, a32, _F32)
            plain = _head(params, a16, jnp.bfloat16).argmax(axis=1)
            pick = lambda t: jnp.take_along_axis(lg32, t[:, None],
                                                 axis=1)[:, 0]
            return (lg32.max(axis=1), lg32.argmax(axis=1), pick(served),
                    pick(plain))

        split = lambda a: a.reshape((S // block, block) + a.shape[1:])
        out = jax.lax.map(rows, (split(h32), split(h16),
                                 split(jnp.roll(seq, -1))))
    return tuple(a.reshape(S) for a in out)
