"""The plain reference for the `zaya` family (ZAYA1: compressed
convolutional attention, grouped-query, over a top-1 expert layer with an
MLP router): the yardstick `correct` appeals to. Straightforward
`jax.numpy` over ONE whole sequence: no kernel, no cache, no paging, no
slot state, no sorting of rows by expert, and NO import from `ray_tpu`.

Two arithmetics, chosen by `dtype`, as in gpt_ref.py. float32 (under
`jax.default_matmul_precision("highest")`) is the truth. bfloat16 is the
arithmetic the model is SERVED in, laid out plainly: bf16 weights and
activations, norms, softmax and the convolution's sums in float32,
matmuls accumulated to float32 — and the ROUTER in float32 in both (it
is 0.3 % of a layer and a flipped top-1 moves a token's logits by
units; the program does the same).

The layer, token t of a sequence, everything at t < 0 zero (d model
width, H query heads and G KV heads of K, g = H/G, E experts):

  attention  u = RMSNorm(x)
      q~_t = W_q u_t (H x K),  k~_t = W_k u_t (G x K),  z_t = [q~_t ; k~_t]
      c_t = a_0 * z_t + a_1 * z_{t-1} + b_a              depthwise, 2 taps
      y_t^(h) = B_0^(h) c_t^(h) + B_1^(h) c_{t-1}^(h) + b_B^(h)   K x K a head
      m^q_{t,h} = (q~_{t,h} + k~_{t,h//g}) / 2
      m^k_{t,j} = (mean_{h in group j} q~_{t,h} + k~_{t,j}) / 2
      q = y[q part] + m^q,  k = y[k part] + m^k
      q^ = sqrt(K) q / |q|,  k^ = tau_j sqrt(K) k / |k|
      v_t = [W_v1 u_t ; W_v2 u_{t-1}]      the last G//2 KV heads read t-1
      rotary (rotate-half, base theta) on the first `rotary_dim` dims of
      every head of q^ and k^
      o_{t,h} = sum_{s<=t} softmax_s(q^_{t,h} . k^_{s,h//g} / sqrt(K)) v_{s,h//g}
      f = W_o [o_{t,0..H-1}]
  x <- (a * x + a') + (c * f + c')
  experts    u = RMSNorm(x)
      s = W_d u + b_d;  r_l = s + gamma_l * r_{l-1}  (r_{-1} = 0)
      h = RMSNorm(r_l);  h1 = gelu(W_1 h + b_1);  h2 = gelu(W_2 h1 + b_2)
      p = softmax(W_3 h2);  e* = argmax_e (p_e + beta_e);  gate p_{e*}
      f = p_{e*} W_down^{e*} ( silu(W_gate^{e*} u) * W_up^{e*} u )
  x <- (a * x + a') + (c * f + c')
  final RMSNorm; logits x W_emb^T (tied).  gelu is the exact (erf) form.

Parameters are the program's own pytree, block leaves stacked on a
leading layers axis: wte [V,D], ln_f_scale [D]; ln1_scale, ln2_scale
[L,D]; wq [L,D,H*K], wk [L,D,G*K], wv1 [L,D,(G-G//2)*K], wv2
[L,D,(G//2)*K]; conv0_w [L,2,(H+G)*K] (a_0, a_1), conv0_b; conv1_w
[L,2,H+G,K,K] (B_0, B_1; `c^(h) @ B^(h)`), conv1_b; k_temp [L,G];
wo [L,H*K,D]; res{1,2}_{a,a_b,c,c_b} [L,D]; r_down [L,D,R], r_down_b,
r_gamma, r_norm, r_b1, r_b2 [L,R], r_w1, r_w2 [L,R,R], r_w3 [L,R,E],
r_beta [L,E]; w_gate, w_up [L,E,D,F], w_down [L,E,F,D].

`rc` is a hashable static value with the fields `n_heads`,
`n_kv_heads`, `rotary_dim`, `rope_theta`, `norm_eps`
(families/zaya.py `reference_config`); the rest is read off the shapes.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_HEAD_ROWS = 256        # rows of the head computed at a time (paired_rows)


def _rms_norm(x, scale, eps):
    """In float32 whatever x is; the result goes back to x's type."""
    x32 = x.astype(_F32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(_F32)).astype(x.dtype)


def _unit(x):
    """x / |x| along the last axis (float32); 0 stays 0."""
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-12)


def _previous(x):
    """x [S, ...] -> the same with row t holding x[t-1], row 0 zeros."""
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], axis=0)


def _rotary(x, rotary_dim: int, theta: float):
    """x [S, h, K] float32; rotate-half on the first rotary_dim dims."""
    S, half = x.shape[0], rotary_dim // 2
    inv_freq = 1.0 / (theta ** (jnp.arange(half, dtype=_F32) * 2.0
                                / rotary_dim))
    ang = jnp.arange(S, dtype=_F32)[:, None, None] * inv_freq    # [S,1,half]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _gelu(x):
    return 0.5 * x * (1.0 + jax.scipy.special.erf(x / math.sqrt(2.0)))


def _mix(x, f, w, arm: str):
    dt = x.dtype
    return ((w[arm + "_a"].astype(dt) * x + w[arm + "_a_b"].astype(dt))
            + (w[arm + "_c"].astype(dt) * f.astype(dt)
               + w[arm + "_c_b"].astype(dt)))


def _attention(x, w, rc):
    """x [S, D] -> the attention sublayer's f [S, D]."""
    S, dt = x.shape[0], x.dtype
    H, G = rc.n_heads, rc.n_kv_heads
    K = w["wq"].shape[-1] // H
    g = H // G
    u = _rms_norm(x, w["ln1_scale"], rc.norm_eps)
    q_t = (u @ w["wq"].astype(dt)).reshape(S, H, K)
    k_t = (u @ w["wk"].astype(dt)).reshape(S, G, K)
    z = jnp.concatenate([q_t, k_t], axis=1)                  # [S, H+G, K]
    a = w["conv0_w"].astype(_F32).reshape(2, H + G, K)
    c = (a[0] * z.astype(_F32) + a[1] * _previous(z).astype(_F32)
         + w["conv0_b"].astype(_F32).reshape(H + G, K)).astype(dt)
    # Operands of `dt`'s values, multiplied and summed in float32.
    b = w["conv1_w"].astype(dt).astype(_F32)
    y = (jnp.einsum("shk,hkj->shj", c.astype(_F32), b[0])
         + jnp.einsum("shk,hkj->shj", _previous(c).astype(_F32), b[1])
         + w["conv1_b"].astype(_F32).reshape(H + G, K))
    q32, k32 = q_t.astype(_F32), k_t.astype(_F32)
    m_q = (q32 + jnp.repeat(k32, g, axis=1)) / 2
    m_k = (q32.reshape(S, G, g, K).mean(axis=2) + k32) / 2
    q = math.sqrt(K) * _unit(y[:, :H] + m_q)
    k = (w["k_temp"].astype(_F32)[:, None] * math.sqrt(K)
         * _unit(y[:, H:] + m_k))
    q = _rotary(q, rc.rotary_dim, rc.rope_theta).astype(dt)
    k = _rotary(k, rc.rotary_dim, rc.rope_theta).astype(dt)
    v = jnp.concatenate([u @ w["wv1"].astype(dt),
                         _previous(u @ w["wv2"].astype(dt))],
                        axis=-1).reshape(S, G, K)
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    scores = jnp.einsum("shk,thk->hst", q, k,
                        preferred_element_type=_F32) / math.sqrt(K)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf),
                           axis=-1).astype(dt)
    o = jnp.einsum("hst,thk->shk", probs, v)
    return o.reshape(S, H * K) @ w["wo"].astype(dt)


def _experts(x, r, w, rc):
    """x [S, D], r [S, R] float32 -> (the expert sublayer's f, r_l)."""
    dt = x.dtype
    u = _rms_norm(x, w["ln2_scale"], rc.norm_eps)
    w32 = lambda name: w[name].astype(_F32)
    with jax.default_matmul_precision("highest"):            # the router
        r = u.astype(_F32) @ w32("r_down") + w32("r_down_b") \
            + w32("r_gamma") * r
        h = _rms_norm(r, w32("r_norm"), rc.norm_eps)
        h = _gelu(h @ w32("r_w1") + w32("r_b1"))
        h = _gelu(h @ w32("r_w2") + w32("r_b2"))
        p = jax.nn.softmax(h @ w32("r_w3"), axis=-1)
    chosen = jnp.argmax(p + w32("r_beta"), axis=-1)
    gate = jnp.take_along_axis(p, chosen[:, None], axis=-1)[:, 0]

    def one_expert(f, e_w):
        e, w_gate, w_up, w_down = e_w
        hid = (jax.nn.silu((u @ w_gate.astype(dt)).astype(_F32))
               * (u @ w_up.astype(dt)).astype(_F32)).astype(dt)
        out = (hid @ w_down.astype(dt)).astype(_F32)
        return f + jnp.where(chosen == e, gate, 0.0)[:, None] * out, None

    n_experts = w["w_gate"].shape[0]
    f, _ = jax.lax.scan(one_expert, jnp.zeros(x.shape, _F32),
                        (jnp.arange(n_experts), w["w_gate"], w["w_up"],
                         w["w_down"]))
    return f.astype(dt), r


_TOP_KEYS = ("wte", "ln_f_scale")


def hidden(params, tokens, rc, dtype=_F32):
    """tokens [S] int32 -> final-norm hidden states [S, D] in `dtype`. The
    layers are walked with `lax.scan` over the stacked weights, one layer
    (and inside it one expert) cast at a time, so the reference fits
    beside bf16 weights."""
    x = params["wte"][tokens].astype(dtype)
    r = jnp.zeros((tokens.shape[0], params["r_down"].shape[-1]), _F32)
    stacked = {k: v for k, v in params.items() if k not in _TOP_KEYS}

    def layer(carry, w):
        x, r = carry
        x = _mix(x, _attention(x, w, rc), w, "res1")
        f, r = _experts(x, r, w, rc)
        return (_mix(x, f, w, "res2"), r), None

    (x, _r), _ = jax.lax.scan(layer, (x, r), stacked)
    return _rms_norm(x, params["ln_f_scale"], rc.norm_eps)


def _head(params, h, dtype):
    return jnp.einsum("sd,vd->sv", h, params["wte"].astype(dtype),
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
    `_HEAD_ROWS` rows at a time: [2,048 x 262,272] float32 never exists."""
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
