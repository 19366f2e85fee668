"""The plain reference for the `jamba` family (AI21-Jamba2-3B: Mamba-1
selective-scan layers, thirteen to one multi-query attention layer
without positions, a dense gated-SiLU MLP in every layer, a tied head):
the yardstick `correct` appeals to. Straightforward `jax.numpy` over ONE
whole sequence: no kernel, no cache, no paging, no chunking, and NO
import from `ray_tpu`. The description followed is the model's
config.json and HF `modeling_jamba.py`.

Two arithmetics, chosen by `dtype`, as in gpt_ref.py. float32 (under
`jax.default_matmul_precision("highest")`) is the truth. bfloat16 is the
arithmetic the model is SERVED in, laid out plainly: bf16 weights and
activations; norms, softmax, the convolution's sum, softplus, the gate
and the WHOLE recurrence (its state, its decay and its sums) in float32,
matmuls accumulated to float32.

Layer l is an attention layer when l % `attn_period` == `attn_offset`,
else a mamba layer (D the width, Dn the mixer's channels, S the state's
values a channel, R the step's rank, all read off the leaves' shapes):

  x <- x + Mixer(norm(x));  x <- x + MLP(norm(x))
  norm(x) = x / sqrt(mean(x^2) + eps) * w, float32

  Mamba (u the normed input): [xs, z] = W_in u; xs passes a causal
  depthwise convolution of `taps` taps with a bias
  (c_t = b + sum_j w_j xs_{t-taps+1+j}, zeros before the sequence), then
  SiLU. [r, B, C] = W_x xs (R, S and S wide), each through an RMSNorm of
  its own (no centring, its own weight); dt = softplus(W_dt r + b_dt).
  With A = -exp(A_log) [S, Dn], a channel's state h [S] starts at zero
  and, token by token (THE definition: a `lax.scan`):

      h <- exp(dt_t A) h + (dt_t xs_t) B_t;   y_t = h . C_t + D xs_t

  y <- y silu(z); W_out.

  Attention (H query heads over G KV heads of size K): q = W_q u,
  k = W_k u, v = W_v u; NO rope, no position of any kind; causal softmax
  at K^-1/2; W_o.

  MLP: W_down(silu(W_gate u) * W_up u).

  final norm; logits = x wte^T (the embedding IS the head).

Parameters are the program's own pytree (models/jamba.py), every leaf a
stack over the layers of its kind (nm mamba layers, na attention layers;
the MLP's and the two norms' over all L): m_in [nm, D, 2 Dn], m_x
[nm, Dn, R + 2 S], m_dt [nm, R, Dn], m_out [nm, Dn, D]; a_wq
[na, D, H K], a_wk, a_wv [na, D, G K], a_wo [na, H K, D]; w_gate, w_up
[L, D, F], w_down [L, F, D]; m_conv [nm, taps, Dn], m_conv_b, m_dt_b,
m_D [nm, Dn], m_A_log [nm, S, Dn], m_dt_norm [nm, R], m_b_norm, m_c_norm
[nm, S]; ln1_scale, ln2_scale [L, D]; ln_f_scale [D]; wte [V, D].

`rc` is a hashable static value (families/jamba.py `reference_config`):
`n_layers`, `attn_period`, `attn_offset`, `n_heads`, `n_kv_heads`,
`norm_eps`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_F32 = jnp.float32
_HEAD_ROWS = 256        # rows of the head computed at a time (paired_rows)
_QUERY_ROWS = 256       # query rows attended at a time


def _rms(x32, w, eps):
    return (x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
            * w.astype(_F32))


def _norm(x, w, eps):
    """RMSNorm, float32 inside; back to x's type."""
    return _rms(x.astype(_F32), w, eps).astype(x.dtype)


def _mamba(x, w, rc):
    """x [T, D] -> the mamba sublayer's output [T, D]."""
    T, dt_ = x.shape[0], x.dtype
    R, Dn = w["dt"].shape
    S = w["A_log"].shape[0]
    u = _norm(x, w["ln1"], rc.norm_eps)
    xz = u @ w["in"].astype(dt_)
    xs, z = xz[:, :Dn].astype(_F32), xz[:, Dn:].astype(_F32)
    taps = w["conv"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, Dn), _F32), xs])
    conv = sum(w["conv"][j].astype(dt_).astype(_F32) * padded[j:j + T]
               for j in range(taps)) + w["conv_b"].astype(_F32)
    xs = jax.nn.silu(conv).astype(dt_)
    rbc = jnp.matmul(xs, w["x"].astype(dt_), preferred_element_type=_F32)
    r = _rms(rbc[:, :R], w["dt_norm"], rc.norm_eps).astype(dt_)
    B = _rms(rbc[:, R:R + S], w["b_norm"], rc.norm_eps)
    C = _rms(rbc[:, R + S:], w["c_norm"], rc.norm_eps)
    step = jax.nn.softplus(
        jnp.matmul(r, w["dt"].astype(dt_), preferred_element_type=_F32)
        + w["dt_b"].astype(_F32))                               # [T, Dn]
    A = -jnp.exp(w["A_log"].astype(_F32))                       # [S, Dn]
    xs32 = xs.astype(_F32)

    def token(h, inputs):                                       # h [S, Dn]
        x_t, dt_t, b_t, c_t = inputs
        h = (jnp.exp(dt_t[None, :] * A) * h
             + (dt_t * x_t)[None, :] * b_t[:, None])
        return h, jnp.sum(h * c_t[:, None], axis=0)

    _, y = jax.lax.scan(token, jnp.zeros((S, Dn), _F32), (xs32, step, B, C))
    y = y + w["D"].astype(_F32) * xs32
    return (y * jax.nn.silu(z)).astype(dt_) @ w["out"].astype(dt_)


def _attention(x, w, rc):
    """x [T, D] -> the attention sublayer's output [T, D]."""
    T, dt_ = x.shape[0], x.dtype
    H, G = rc.n_heads, rc.n_kv_heads
    K = w["wo"].shape[0] // H
    u = _norm(x, w["ln1"], rc.norm_eps)
    q = (u @ w["wq"].astype(dt_)).reshape(T, H, K)
    k = (u @ w["wk"].astype(dt_)).reshape(T, G, K)
    v = (u @ w["wv"].astype(dt_)).reshape(T, G, K)
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
    return o @ w["wo"].astype(dt_)


def _mlp(u, w_gate, w_up, w_down):
    """W_down(silu(W_gate u) * W_up u) -> float32 [T, D]."""
    dt_ = u.dtype
    mm = lambda a, b: jnp.matmul(a, b.astype(dt_), preferred_element_type=_F32)
    return mm((jax.nn.silu(mm(u, w_gate)) * mm(u, w_up)).astype(dt_), w_down)


_MAMBA_LEAVES = ("in", "x", "dt", "out", "conv", "conv_b", "dt_norm", "b_norm",
                 "c_norm", "dt_b", "A_log", "D")
_ATTN_LEAVES = ("wq", "wk", "wv", "wo")


def _runs(rc) -> list:
    """The layers as runs of one kind: (kind, first layer, its index
    among the layers of its kind, how many)."""
    kinds = ["attn" if l % rc.attn_period == rc.attn_offset else "mamba"
             for l in range(rc.n_layers)]
    runs, at = [], {"mamba": 0, "attn": 0}
    for l, kind in enumerate(kinds):
        if runs and runs[-1][0] == kind:
            runs[-1][3] += 1
        else:
            runs.append([kind, l, at[kind], 1])
        at[kind] += 1
    return runs


def hidden(params, tokens, rc, dtype=_F32):
    """tokens [T] int32 -> final-norm hidden states [T, D] in `dtype`.
    A run of layers of one kind is one loop over its layers (28 layers
    written out take the compiler minutes)."""
    x = params["wte"][tokens].astype(dtype)
    for kind, l0, i0, n in _runs(rc):
        mixer, prefix, leaves = (
            (_mamba, "m_", _MAMBA_LEAVES) if kind == "mamba"
            else (_attention, "a_", _ATTN_LEAVES))

        def layer(k, x, l0=l0, i0=i0, mixer=mixer, prefix=prefix,
                  leaves=leaves):
            l, i = l0 + k, i0 + k
            w = {"ln1": params["ln1_scale"][l],
                 **{name: params[prefix + name][i] for name in leaves}}
            x = x + mixer(x, w, rc).astype(dtype)
            u = _norm(x, params["ln2_scale"][l], rc.norm_eps)
            f = _mlp(u, *(params[name][l]
                          for name in ("w_gate", "w_up", "w_down")))
            return x + f.astype(dtype)

        x = jax.lax.fori_loop(0, n, layer, x)
    return _norm(x, params["ln_f_scale"], rc.norm_eps)


def _head(params, h, dtype):
    return jnp.einsum("sd,vd->sv", h, params["wte"].astype(dtype),
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
