"""The plain reference for the `qwen3_next` family
(Qwen3-Next-80B-A3B-Instruct: three Gated DeltaNet layers to one gated
softmax-attention layer, a softmax top-k router over more experts than
this chip holds, a gated shared expert): the yardstick `correct` appeals
to. Straightforward `jax.numpy` over ONE whole sequence: no kernel, no
cache, no paging, no chunked scan, no sorting of rows by expert, and NO
import from `ray_tpu`. The description followed is the model's
config.json and HF `modeling_qwen3_next.py`.

Two arithmetics, chosen by `dtype`, as in gpt_ref.py. float32 (under
`jax.default_matmul_precision("highest")`) is the truth. bfloat16 is the
arithmetic the model is SERVED in, laid out plainly: bf16 weights and
activations; norms, softmax, the gates, the decay, `beta` and the WHOLE
delta-rule recurrence (its state and its sums) in float32, matmuls
accumulated to float32; the ROUTER in float32 in both.

Layer l is a full-attention layer when (l + 1) % `full_interval` == 0,
else a Gated DeltaNet layer; every layer's MLP is sparse:

  x <- x + Mixer(norm(x));  x <- x + MoE(norm(x))
  norm(x) = x / sqrt(mean(x^2) + eps) * (1 + w), float32 (zero-centred)

  Gated DeltaNet (Hk key heads, Hv value heads of size dk = dv; u the
  normed input): W_qkvz u viewed [Hk, dk + dk + r dv + r dv] (r = Hv /
  Hk) and split a key head into q, k, v, z; W_ba u viewed [Hk, r + r]
  into b, a (one scalar a value head). q | k | v flattened pass a causal
  depthwise convolution of `taps` taps (c_t = sum_j w_j m_{t-taps+1+j},
  zeros before the sequence), then SiLU. q, k are repeated to Hv heads
  (key head h serves value heads r h .. r h + r - 1), L2-normalised
  (x rsqrt(sum x^2 + 1e-6)), q times dk^-1/2.  beta = sigmoid(b),
  g = -exp(A_log) softplus(a + dt_bias). A head's state S [dk, dv]
  starts at zero and, token by token (THE definition: a `lax.scan`):

      S <- exp(g_t) S;  d_t = beta_t (v_t - S^T k_t);
      S <- S + k_t d_t^T;  o_t = S^T q_t

  o <- o rsqrt(mean(o^2) + eps) w_norm silu(z) a head (plain RMSNorm),
  heads flattened, W_out.

  Gated attention (H query heads over G KV heads of size K): W_q u
  viewed [H, 2 K] and split a head into query and gate; per-head
  zero-centred norm on q and k; rotate-half rope on the first
  `rotary_dim` dims, theta `rope_theta`; causal softmax at K^-1/2; the
  output, flattened, times sigmoid(gate); W_o.

  MoE: p = softmax(u W_r) in float32 over ALL experts; the top_k largest
  choose; gate_e = p_e / sum of the chosen p; the layer is
  sigmoid(u . w_sg) Shared(u) + sum over the chosen e THIS SHARE HOLDS
  of gate_e Expert_e(u), all gated-SiLU MLPs. The share holds experts
  first_expert .. first_expert + E_held - 1 (the weights' own leading
  axis); what the others would have added is another chip's, left out
  here as in the program.

  final norm; logits x W_head (untied; this share's slice of the
  vocabulary: the leaves' own shapes).

Parameters are the program's own pytree (models/qwen3_next.py), one
stack a mixer kind in layer order within the kind, the MLP's leaves one
stack over all layers: wte [V,D], lm_head [D,V], ln_f_scale [D];
ln1_scale, ln2_scale [L,D]; g_qkvz [nl,D,2 Hk dk + 2 Hv dv], g_ba
[nl,D,2 Hv], g_conv [nl,taps,2 Hk dk + Hv dv], g_dt_bias, g_A_log
[nl,Hv], g_norm [nl,dv], g_out [nl,Hv dv,D]; f_wq [nf,D,2 H K], f_wk,
f_wv [nf,D,G K], f_qnorm, f_knorm [nf,K], f_wo [nf,H K,D]; router
[L,D,E]; s_gate, s_up [L,D,Fs], s_down [L,Fs,D], s_gate_w [L,D];
w_gate, w_up [L,E_held,D,F], w_down [L,E_held,F,D].

`rc` is a hashable static value (families/qwen3_next.py
`reference_config`): `n_layers`, `full_interval`, `n_heads`,
`n_kv_heads`, `lin_k_heads`, `lin_v_heads`, `top_k`, `first_expert`,
`norm_eps`, `rope_theta`, `rotary_dim`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32
_HEAD_ROWS = 256        # rows of the head computed at a time (paired_rows)
_QUERY_ROWS = 256       # query rows attended at a time


def _norm(x, w, eps):
    """Zero-centred RMSNorm, float32 inside; back to x's type."""
    x32 = x.astype(_F32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(_F32))).astype(x.dtype)


def _rope(x, rotary_dim: int, theta: float):
    """x [S, h, K] float32; rotate-half on the first `rotary_dim` dims."""
    S, half = x.shape[0], rotary_dim // 2
    inv_freq = float(theta) ** (
        -np.arange(0, rotary_dim, 2, dtype=np.float64) / rotary_dim)
    ang = (jnp.arange(S, dtype=_F32)[:, None, None]
           * jnp.asarray(inv_freq, _F32))                     # [S, 1, half]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _delta_net(x, w, rc):
    """x [S, D] -> the Gated DeltaNet sublayer's output [S, D]."""
    S, dt = x.shape[0], x.dtype
    Hk, Hv = rc.lin_k_heads, rc.lin_v_heads
    r = Hv // Hk
    dv = w["out"].shape[0] // Hv
    dk = (w["qkvz"].shape[1] - 2 * Hv * dv) // (2 * Hk)
    u = _norm(x, w["ln1"], rc.norm_eps)
    qkvz = (u @ w["qkvz"].astype(dt)).reshape(S, Hk, 2 * dk + 2 * r * dv)
    q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + r * dv], axis=-1)
    ba = (u @ w["ba"].astype(dt)).reshape(S, Hk, 2 * r).astype(_F32)
    b, a = ba[..., :r].reshape(S, Hv), ba[..., r:].reshape(S, Hv)
    mixed = jnp.concatenate([q.reshape(S, -1), k.reshape(S, -1),
                             v.reshape(S, -1)], axis=-1).astype(_F32)
    taps = w["conv"].shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, mixed.shape[1]), _F32), mixed])
    conv = sum(w["conv"][j].astype(dt).astype(_F32) * padded[j:j + S]
               for j in range(taps))                  # four shifted products
    mixed = jax.nn.silu(conv).astype(dt).astype(_F32)
    q, k, v = jnp.split(mixed, [Hk * dk, 2 * Hk * dk], axis=-1)
    unit = lambda t: t * jax.lax.rsqrt(
        jnp.sum(t * t, axis=-1, keepdims=True) + 1e-6)
    q = jnp.repeat(unit(q.reshape(S, Hk, dk)), r, axis=1) / math.sqrt(dk)
    k = jnp.repeat(unit(k.reshape(S, Hk, dk)), r, axis=1)
    v = v.reshape(S, Hv, dv)
    beta = jax.nn.sigmoid(b)
    g = (-jnp.exp(w["A_log"].astype(_F32))
         * jax.nn.softplus(a + w["dt_bias"].astype(_F32)))    # [S, Hv]

    def token(state, inputs):                         # state [Hv, dk, dv]
        q_t, k_t, v_t, g_t, beta_t = inputs
        state = state * jnp.exp(g_t)[:, None, None]
        d = beta_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * d[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((Hv, dk, dv), _F32),
                        (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + rc.norm_eps)
    o = (o * w["norm"].astype(_F32)
         * jax.nn.silu(z.reshape(S, Hv, dv).astype(_F32))).astype(dt)
    return o.reshape(S, Hv * dv) @ w["out"].astype(dt)


def _attention(x, w, rc):
    """x [S, D] -> the gated attention sublayer's output [S, D]."""
    S, dt = x.shape[0], x.dtype
    H, G = rc.n_heads, rc.n_kv_heads
    K = w["wo"].shape[0] // H
    u = _norm(x, w["ln1"], rc.norm_eps)
    qg = (u @ w["wq"].astype(dt)).reshape(S, H, 2 * K)
    q, gate = qg[..., :K], qg[..., K:]
    k = (u @ w["wk"].astype(dt)).reshape(S, G, K)
    v = (u @ w["wv"].astype(dt)).reshape(S, G, K)
    q = _norm(q, w["qnorm"], rc.norm_eps).astype(_F32)
    k = _norm(k, w["knorm"], rc.norm_eps).astype(_F32)
    q = _rope(q, rc.rotary_dim, rc.rope_theta).astype(dt)
    k = _rope(k, rc.rotary_dim, rc.rope_theta).astype(dt)
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
    o = jax.lax.map(rows, (split(j), split(q))).reshape(S, H, K)
    o = (o.astype(_F32) * jax.nn.sigmoid(gate.astype(_F32))).astype(dt)
    return o.reshape(S, H * K) @ w["wo"].astype(dt)


def _gated_mlp(u, w_gate, w_up, w_down):
    """W_down(silu(W_gate u) * W_up u) -> float32 [S, D]."""
    dt = u.dtype
    hid = (jax.nn.silu((u @ w_gate.astype(dt)).astype(_F32))
           * (u @ w_up.astype(dt)).astype(_F32)).astype(dt)
    return (hid @ w_down.astype(dt)).astype(_F32)


def _sparse_mlp(u, w, rc, expert, held: int):
    """u [S, D] (normed) -> the gated shared expert + this share's routed
    part, float32. `w`: the layer's router, shared expert and its gate;
    `expert(e)` -> held expert e's three matrices, cut out of wherever
    they lie one expert at a time."""
    with jax.default_matmul_precision("highest"):            # the router
        p = jax.nn.softmax(u.astype(_F32) @ w["router"].astype(_F32),
                           axis=-1)
    top, chosen = jax.lax.top_k(p, rc.top_k)                 # [S, k]
    gates = top / jnp.sum(top, axis=-1, keepdims=True)
    share = jax.nn.sigmoid(
        (u @ w["s_gate_w"].astype(u.dtype)[:, None]).astype(_F32))  # [S, 1]

    def one_expert(f, e):
        gate = jnp.sum(jnp.where(chosen == rc.first_expert + e, gates, 0.0),
                       axis=-1)
        return f + gate[:, None] * _gated_mlp(u, *expert(e)), None

    f, _ = jax.lax.scan(
        one_expert,
        share * _gated_mlp(u, w["s_gate"], w["s_up"], w["s_down"]),
        jnp.arange(held))
    return f


_MIXER = {"linear": ("qkvz", "ba", "conv", "dt_bias", "A_log", "norm", "out"),
          "full": ("wq", "wk", "wv", "qnorm", "knorm", "wo")}
_MLP = ("router", "s_gate", "s_up", "s_down", "s_gate_w")
_EXPERTS = ("w_gate", "w_up", "w_down")


def _kinds(rc) -> tuple:
    return tuple("full" if (l + 1) % rc.full_interval == 0 else "linear"
                 for l in range(rc.n_layers))


def hidden(params, tokens, rc, dtype=_F32):
    """tokens [S] int32 -> final-norm hidden states [S, D] in `dtype`.
    Layers are walked in order; experts are cut out of their stack one
    at a time and query rows attended a block at a time, so the
    reference fits beside bf16 weights."""
    x = params["wte"][tokens].astype(dtype)
    at = {"linear": 0, "full": 0}
    for l, kind in enumerate(_kinds(rc)):
        i, p = at[kind], kind[0] + "_"
        at[kind] += 1
        w = {"ln1": params["ln1_scale"][l],
             **{k: params[("g_" if kind == "linear" else p) + k][i]
                for k in _MIXER[kind]},
             **{k: params[k][l] for k in _MLP}}
        mixer = _delta_net if kind == "linear" else _attention
        x = x + mixer(x, w, rc).astype(dtype)
        u = _norm(x, params["ln2_scale"][l], rc.norm_eps)
        f = _sparse_mlp(
            u, w, rc, lambda e, l=l: tuple(params[k][l, e] for k in _EXPERTS),
            params["w_gate"].shape[1])
        x = x + f.astype(dtype)
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
