"""The plain reference for the `nemotron_h` family (Nemotron-3-Super:
layers of ONE sublayer each, a Mamba-2 mixer, a grouped-query attention
without positions or a LATENT expert layer by a pattern string): the
yardstick `correct` appeals to. Straightforward `jax.numpy` over ONE
whole sequence: the recurrence token by token (`lax.scan`) on a state in
the equations' own [P, N] layout, the experts one at a time over every
token, no chunked form, no kernel, no cache, no paging, no sorting of
rows by expert, and NO import from `ray_tpu`.

Departures from the published description (config.json and HF
`modeling_nemotron_h.py`), each also in
benchmarks/configs/nemotron-3-super-120b-a12b.json:
  - the multi-token-prediction head (`mtp_hybrid_override_pattern`) is
    left out: a draft for speculation, without which the served tokens
    are the same;
  - one chip's share: only the experts this share holds add to an expert
    layer's routed part (the others' part is another chip's), the latent
    projections and the shared expert are whole, and the vocabulary is
    the held slice (the leaves' own shapes);
  - assumed: no rotary position in the attention layers (the modelling
    code applies none); the router and the shared expert read the
    model's width and only the routed experts the latent; the latent
    projections carry no bias and no norm; the state is float32.

Two arithmetics, chosen by `dtype`, as in gpt_ref.py. float32 (under
`jax.default_matmul_precision("highest")`) is the truth. bfloat16 is the
arithmetic the model is SERVED in, laid out plainly: bf16 weights and
activations, norms, softmax, the step, the decay, the STATE and the
gates in float32, matmuls accumulated to float32, and the ROUTER in
float32 in both.

Layer l, token t of a sequence (D model width; Hm heads of P values over
N states in G groups, Dn = Hm P; H query heads over Gk KV heads of K):

  x <- x + Mixer_l(norm(x));  norm = x / sqrt(mean(x^2) + eps) * w
  M  [z | xBC | dt] = u W_in (Dn | Dn + 2 G N | Hm);
     xBC <- silu(conv(xBC) + b): causal, depthwise, `taps` taps, zeros
     before the sequence; xBC = x [Hm, P], B [G, N], C [G, N]; head h in
     group h // (Hm / G); dt_h = softplus(dt_h + dt_bias_h);
     a_h = exp(-exp(A_log_h) dt_h); S_h [P, N] from zeros:
     S_h <- a_h S_h + dt_h x_h B_g^T;  y_h = S_h C_g + D_h x_h;
     y <- y * silu(z), then the norm over each group's Dn / G values
     times w; Mixer = y W_out.
  *  q = u W_q (H x K), k = u W_k, v = u W_v (Gk x K), no position;
     o_t = softmax over j <= t of q_t . k_j K^-1/2, times v; W_o.
  E  s = sigmoid(u W_r) in float32 over ALL experts; the top_k largest
     of s + b choose (b: `router_bias`); gate_e = routed_scale * s_e /
     sum of the chosen s; l = u W_lat_in;
     r = sum over the chosen e THIS SHARE HOLDS of
         gate_e W2_e relu(W1_e l)^2;
     Mixer = r W_lat_out + W_s2 relu(W_s1 u)^2.
  final norm; logits x W_head (untied).

Parameters are the program's own pytree as `param_specs` shapes it
(models/nemotron_h.py), every leaf a stack over the layers of its kind
(nm Mamba-2, na attention, ne expert layers; L all): wte [V,D], lm_head
[D,V], ln_f_scale [D], ln_scale [L,D]; m_in [nm,D,2Dn+2GN+Hm], m_conv
[nm,taps,Dn+2GN], m_conv_b [nm,Dn+2GN], m_dt_b, m_A_log, m_D [nm,Hm],
m_norm [nm,Dn], m_out [nm,Dn,D]; a_wq [na,D,HK], a_wk, a_wv [na,D,GkK],
a_wo [na,HK,D]; router [ne,D,E], router_bias [ne,E]; lat_in [ne,D,Dl],
lat_out [ne,Dl,D]; w_up [ne,E_held,Dl,F], w_down [ne,E_held,F,Dl]; s_up
[ne,D,Fs], s_down [ne,Fs,D].

`rc` is a hashable static value (families/nemotron_h.py
`reference_config`): `pattern`, `m_heads`, `m_groups`, `n_heads`,
`n_kv_heads`, `top_k`, `routed_scale`, `first_expert`, `norm_eps`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_HEAD_ROWS = 256        # rows of the head computed at a time (paired_rows)
_QUERY_ROWS = 256       # query rows attended at a time


def _rms(x32, eps):
    return x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)


def _norm(x, w, eps):
    """RMSNorm, float32 inside; back to x's type."""
    return (_rms(x.astype(_F32), eps) * w.astype(_F32)).astype(x.dtype)


def _mm(a, b):
    """a @ b in a's type, accumulated to float32."""
    return jnp.matmul(a, b.astype(a.dtype), preferred_element_type=_F32)


def _mamba2(u, w, rc):
    """u [T, D] (normed) -> the Mamba-2 mixer's output [T, D] float32."""
    T, dt_ = u.shape[0], u.dtype
    Hm, G = rc.m_heads, rc.m_groups
    Dn = w["out"].shape[0]
    P, Dc = Dn // Hm, w["conv_b"].shape[0]
    N = (Dc - Dn) // (2 * G)
    proj = _mm(u, w["in"])                                  # [T, 2Dn+2GN+Hm]
    z, xbc, dt = proj[:, :Dn], proj[:, Dn:Dn + Dc], proj[:, Dn + Dc:]
    xbc = xbc.astype(dt_).astype(_F32)
    taps = w["conv"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, Dc), _F32), xbc])
    conv = sum(w["conv"][j].astype(dt_).astype(_F32) * padded[j:j + T]
               for j in range(taps)) + w["conv_b"].astype(_F32)
    xbc = jax.nn.silu(conv).astype(dt_).astype(_F32)
    x = xbc[:, :Dn].reshape(T, Hm, P)
    B = xbc[:, Dn:Dn + G * N].reshape(T, G, N)
    C = xbc[:, Dn + G * N:].reshape(T, G, N)
    dt = jax.nn.softplus(dt + w["dt_b"].astype(_F32))       # [T, Hm]
    a = jnp.exp(-jnp.exp(w["A_log"].astype(_F32)) * dt)

    def token(S, inputs):                                   # S [Hm, P, N]
        x_t, dt_t, a_t, b_t, c_t = inputs
        b_t, c_t = (jnp.repeat(t, Hm // G, axis=0) for t in (b_t, c_t))
        S = (a_t[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, c_t)

    _, y = jax.lax.scan(token, jnp.zeros((Hm, P, N), _F32), (x, dt, a, B, C))
    y = (y + w["D"].astype(_F32)[:, None] * x).reshape(T, Dn)
    y = y * jax.nn.silu(z.astype(dt_).astype(_F32))
    y = _rms(y.reshape(T, G, Dn // G), rc.norm_eps).reshape(T, Dn)
    return _mm((y * w["norm"].astype(_F32)).astype(dt_), w["out"])


def _attention(u, w, rc):
    """u [T, D] (normed) -> the attention mixer's output [T, D] float32."""
    T, dt_ = u.shape[0], u.dtype
    H, G = rc.n_heads, rc.n_kv_heads
    K = w["wo"].shape[0] // H
    q = _mm(u, w["wq"]).astype(dt_).reshape(T, H, K)
    k = _mm(u, w["wk"]).astype(dt_).reshape(T, G, K)
    v = _mm(u, w["wv"]).astype(dt_).reshape(T, G, K)
    k, v = jnp.repeat(k, H // G, axis=1), jnp.repeat(v, H // G, axis=1)
    block = _QUERY_ROWS if T % _QUERY_ROWS == 0 else T
    j = jnp.arange(T)

    def rows(args):
        i, q_rows = args                                   # [b], [b, H, K]
        scores = jnp.einsum("shk,thk->hst", q_rows, k,
                            preferred_element_type=_F32) / math.sqrt(K)
        seen = j[None, :] <= i[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                               axis=-1).astype(dt_)
        return jnp.einsum("hst,thk->shk", probs, v)

    split = lambda a: a.reshape((T // block, block) + a.shape[1:])
    o = jax.lax.map(rows, (split(j), split(q))).reshape(T, H * K)
    return _mm(o, w["wo"])


def _relu2(u, w_up, w_down):
    """W_down relu(W_up u)^2 -> float32."""
    return _mm(jnp.square(jax.nn.relu(_mm(u, w_up))).astype(u.dtype), w_down)


def _experts(u, w, rc, expert, held: int):
    """u [T, D] (normed) -> the expert layer's output [T, D] float32:
    this share's routed part out of the latent, plus the shared expert.
    `expert(e)` -> held expert e's two matrices, cut out of wherever
    they lie one expert at a time."""
    dt_ = u.dtype
    with jax.default_matmul_precision("highest"):            # the router
        s = jax.nn.sigmoid(u.astype(_F32) @ w["router"].astype(_F32))
    _, chosen = jax.lax.top_k(s + w["router_bias"].astype(_F32), rc.top_k)
    own = jnp.take_along_axis(s, chosen, axis=-1)            # unbiased
    gates = rc.routed_scale * own / jnp.sum(own, axis=-1, keepdims=True)
    latent = _mm(u, w["lat_in"]).astype(dt_)                 # [T, Dl]

    def one_expert(r, e):
        gate = jnp.sum(jnp.where(chosen == rc.first_expert + e, gates, 0.0),
                       axis=-1)
        return r + gate[:, None] * _relu2(latent, *expert(e)), None

    r, _ = jax.lax.scan(one_expert, jnp.zeros(latent.shape, _F32),
                        jnp.arange(held))
    return (_mm(r.astype(dt_), w["lat_out"])
            + _relu2(u, w["s_up"], w["s_down"]))


_LEAVES = {
    "M": ("m_", ("in", "conv", "conv_b", "dt_b", "A_log", "D", "norm",
                 "out")),
    "*": ("a_", ("wq", "wk", "wv", "wo")),
    "E": ("", ("router", "router_bias", "lat_in", "lat_out", "s_up",
               "s_down")),
}


def hidden(params, tokens, rc, dtype=_F32):
    """tokens [T] int32 -> final-norm hidden states [T, D] in `dtype`,
    the layers one after the other as the pattern names them."""
    x = params["wte"][tokens].astype(dtype)
    for l, kind in enumerate(rc.pattern):
        i = rc.pattern[:l].count(kind)
        prefix, names = _LEAVES[kind]
        w = {name: params[prefix + name][i] for name in names}
        u = _norm(x, params["ln_scale"][l], rc.norm_eps)
        if kind == "M":
            f = _mamba2(u, w, rc)
        elif kind == "*":
            f = _attention(u, w, rc)
        else:
            f = _experts(
                u, w, rc,
                lambda e, i=i: (params["w_up"][i, e], params["w_down"][i, e]),
                params["w_up"].shape[1])
        x = x + f.astype(dtype)
    return _norm(x, params["ln_f_scale"], rc.norm_eps)


def _head(params, h, dtype):
    return jnp.einsum("sd,dv->sv", h, params["lm_head"].astype(dtype),
                      preferred_element_type=_F32)


def logits(params, tokens, rc, dtype=_F32):
    """tokens [T] -> logits [T, V] float32 (accumulated to float32 from
    `dtype` operands). Whole: for tests and short sequences."""
    with jax.default_matmul_precision("highest"):
        return _head(params, hidden(params, tokens, rc, dtype), dtype)


def loss(params, tokens, targets, rc):
    """Mean next-token cross-entropy of a batch [B, T], float32."""
    with jax.default_matmul_precision("highest"):
        def one(toks, tgt):
            lg = _head(params, hidden(params, toks, rc), _F32)
            logz = jax.nn.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, tgt[:, None], axis=-1)[:, 0]
            return jnp.sum(logz - gold)

        total = sum(one(t, g) for t, g in zip(tokens, targets))
        return total / (tokens.shape[0] * tokens.shape[1])


def paired_rows(params, seq, rc):
    """For a padded stream `seq` [T], per position and all measured in the
    FLOAT32 reference's logits: the row's best logit and its argmax, the
    logit of the token that actually follows (what was served), and the
    logit of the token a plain bfloat16 forward of the same weights would
    have chosen there (gpt_ref.paired_rows has the why). The head runs
    `_HEAD_ROWS` rows at a time."""
    T = seq.shape[0]
    block = _HEAD_ROWS if T % _HEAD_ROWS == 0 else T
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

        split = lambda a: a.reshape((T // block, block) + a.shape[1:])
        out = jax.lax.map(rows, (split(h32), split(h16),
                                 split(jnp.roll(seq, -1))))
    return tuple(a.reshape(T) for a in out)
