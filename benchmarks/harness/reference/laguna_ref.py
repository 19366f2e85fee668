"""The plain reference for the `laguna` family (Laguna-S-2.1: window
layers beside full ones, unequal query head counts over one set of KV
heads, a per-head output gate, YaRN on half a head in full layers, a
sigmoid top-k router over more experts than this chip holds, one shared
expert): the yardstick `correct` appeals to. Straightforward `jax.numpy`
over ONE whole sequence: no kernel, no cache, no paging, no ring, no
sorting of rows by expert, and NO import from `ray_tpu`.

Two arithmetics, chosen by `dtype`, as in gpt_ref.py. float32 (under
`jax.default_matmul_precision("highest")`) is the truth. bfloat16 is the
arithmetic the model is SERVED in, laid out plainly: bf16 weights and
activations, norms, softmax and the gates in float32, matmuls
accumulated to float32 — and the ROUTER in float32 in both (a flipped
choice moves a token's logits by far more than rounding does; the
program does the same).

Layer l, token i of a sequence (D model width, K head size, G KV heads,
H_l query heads: `heads_full` in a full layer, `heads_window` in a
window layer; W the window):

  x <- x + Attn(RMSNorm(x));  x <- x + MLP(RMSNorm(x))
  Attn  u the normed input;  q = u W_q (H_l x K), k = u W_k, v = u W_v
        (G x K each);  rope on q and k (below);  query head h reads KV
        head h // (H_l / G);  softmax over keys j <= i at scale K^-1/2,
        and in a window layer only i - j < W (the query's own position
        counts);  g = sigmoid(u W_g) (H_l gates);  head h's output is
        multiplied by g_h;  then W_o.
  rope  window layers: all K dims, rotate-half, theta `theta_window`.
        full layers: the first `rotary_dim` dims, theta `theta_full`,
        YaRN: d = rotary_dim, f_i = theta^(-2i/d),
        low, high = floor, ceil of d ln(orig / (beta 2 pi)) / (2 ln theta)
        at beta_fast and beta_slow, clipped to [0, d-1],
        ramp_i = clip((i - low) / (high - low), 0, 1),
        inv_freq_i = f_i (1 - ramp_i) + (f_i / factor) ramp_i,
        cos and sin multiplied by `attention_factor`. The frequencies
        are computed on the host in float64.
  MLP   dense layers: W_down(silu(W_gate u) * W_up u).
        sparse layers: s = sigmoid(u W_r) in float32 over ALL experts;
        the top_k largest choose; gate_e = scale * s_e / sum of the
        chosen s;  MLP(u) = Shared(u) + sum over the chosen e THIS SHARE
        HOLDS of gate_e Expert_e(u). The share holds experts
        first_expert .. first_expert + E_held - 1 (E_held: the weights'
        own leading axis); what the others would have added is another
        chip's, left out here as in the program. Shared and every
        expert are gated-SiLU MLPs; the shared one carries no gate.
  final RMSNorm; logits x W_head (untied, this share's slice of the
  vocabulary: the leaves' own shapes).

Parameters are the program's own pytree (models/laguna.py), one stack a
layer kind and MLP kind, in layer order within the kind:
wte [V,D], lm_head [D,V], ln_f_scale [D]; ln1_scale, ln2_scale [L,D];
f_wq [nf,D,Hf*K], f_wk, f_wv [nf,D,G*K], f_wg [nf,D,Hf], f_wo
[nf,Hf*K,D] (full layers); w_wq ... w_wo likewise (window layers);
d_gate, d_up [nd,D,Fd], d_down [nd,Fd,D] (dense MLPs); router
[ns,D,E]; s_gate, s_up [ns,D,Fs], s_down [ns,Fs,D] (shared experts);
w_gate, w_up [ns,E_held,D,F], w_down [ns,E_held,F,D].

`rc` is a hashable static value (families/laguna.py
`reference_config`): `layer_types` (a tuple of "full" / "window"),
`dense_layers` (a tuple of layer indices), `heads_full`, `heads_window`,
`n_kv_heads`, `window`, `top_k`, `routed_scale`, `first_expert`,
`norm_eps`, `theta_window`, `theta_full`, `rotary_dim`, `yarn_factor`,
`yarn_orig`, `beta_fast`, `beta_slow`, `attention_factor`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32
_HEAD_ROWS = 256        # rows of the head computed at a time (paired_rows)
_QUERY_ROWS = 256       # query rows attended at a time


def _rms_norm(x, scale, eps):
    """In float32 whatever x is; the result goes back to x's type."""
    x32 = x.astype(_F32)
    y = x32 / jnp.sqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(_F32)).astype(x.dtype)


def yarn_inv_freq(rc) -> np.ndarray:
    """The full layers' rotary frequencies, float64 on the host."""
    d, theta = rc.rotary_dim, float(rc.theta_full)
    f = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    bound = lambda beta: (d * math.log(rc.yarn_orig / (beta * 2 * math.pi))
                          / (2 * math.log(theta)))
    low = max(math.floor(bound(rc.beta_fast)), 0)
    high = min(math.ceil(bound(rc.beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return f * (1.0 - ramp) + (f / rc.yarn_factor) * ramp


def _rope(x, inv_freq, factor: float):
    """x [S, h, K] float32; rotate-half on the first 2 * len(inv_freq)
    dims, cos and sin scaled by `factor`."""
    S, half = x.shape[0], len(inv_freq)
    ang = (jnp.arange(S, dtype=_F32)[:, None, None]
           * jnp.asarray(inv_freq, _F32))                     # [S, 1, half]
    sin, cos = jnp.sin(ang) * factor, jnp.cos(ang) * factor
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _attention(x, w, kind: str, rc):
    """x [S, D] -> the attention sublayer's output [S, D]. `w`: this
    layer's ln1 scale and its kind's five matrices."""
    S, dt = x.shape[0], x.dtype
    G = rc.n_kv_heads
    H = rc.heads_full if kind == "full" else rc.heads_window
    K = w["wq"].shape[-1] // H
    u = _rms_norm(x, w["ln1"], rc.norm_eps)
    q = (u @ w["wq"].astype(dt)).reshape(S, H, K).astype(_F32)
    k = (u @ w["wk"].astype(dt)).reshape(S, G, K).astype(_F32)
    v = (u @ w["wv"].astype(dt)).reshape(S, G, K)
    if kind == "full":
        inv_freq, factor = yarn_inv_freq(rc), rc.attention_factor
    else:
        inv_freq = float(rc.theta_window) ** (
            -np.arange(0, K, 2, dtype=np.float64) / K)
        factor = 1.0
    q, k = _rope(q, inv_freq, factor).astype(dt), \
        _rope(k, inv_freq, factor).astype(dt)
    k, v = jnp.repeat(k, H // G, axis=1), jnp.repeat(v, H // G, axis=1)
    gate = jax.nn.sigmoid((u @ w["wg"].astype(dt)).astype(_F32))   # [S, H]
    block = _QUERY_ROWS if S % _QUERY_ROWS == 0 else S
    j = jnp.arange(S)

    def rows(args):
        i, q_rows = args                                   # [b], [b, H, K]
        scores = jnp.einsum("shk,thk->hst", q_rows, k,
                            preferred_element_type=_F32) / math.sqrt(K)
        seen = j[None, :] <= i[:, None]
        if kind == "window":
            seen &= i[:, None] - j[None, :] < rc.window
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf),
                               axis=-1).astype(dt)
        return jnp.einsum("hst,thk->shk", probs, v)

    split = lambda a: a.reshape((S // block, block) + a.shape[1:])
    o = jax.lax.map(rows, (split(j), split(q))).reshape(S, H, K)
    o = (o.astype(_F32) * gate[:, :, None]).astype(dt)
    return o.reshape(S, H * K) @ w["wo"].astype(dt)


def _gated_mlp(u, w_gate, w_up, w_down):
    """W_down(silu(W_gate u) * W_up u) -> float32 [S, D]."""
    dt = u.dtype
    hid = (jax.nn.silu((u @ w_gate.astype(dt)).astype(_F32))
           * (u @ w_up.astype(dt)).astype(_F32)).astype(dt)
    return (hid @ w_down.astype(dt)).astype(_F32)


def _sparse_mlp(u, w, rc, expert, held: int):
    """u [S, D] (normed) -> shared expert + this share's routed part,
    float32. `w`: the layer's router and shared expert; `expert(e)` ->
    held expert e's three matrices, cut out of wherever they lie one
    expert at a time (a layer's 128 are 2.4 GB in bf16: never copied)."""
    with jax.default_matmul_precision("highest"):            # the router
        s = jax.nn.sigmoid(u.astype(_F32) @ w["router"].astype(_F32))
    top, chosen = jax.lax.top_k(s, rc.top_k)                 # [S, k]
    gates = rc.routed_scale * top / jnp.sum(top, axis=-1, keepdims=True)

    def one_expert(f, e):
        gate = jnp.sum(jnp.where(chosen == rc.first_expert + e, gates, 0.0),
                       axis=-1)
        return f + gate[:, None] * _gated_mlp(u, *expert(e)), None

    f, _ = jax.lax.scan(
        one_expert, _gated_mlp(u, w["s_gate"], w["s_up"], w["s_down"]),
        jnp.arange(held))
    return f


_ATTN = ("wq", "wk", "wv", "wg", "wo")
_MLP = {"dense": ("d_gate", "d_up", "d_down"),
        "sparse": ("router", "s_gate", "s_up", "s_down")}
_EXPERTS = ("w_gate", "w_up", "w_down")


def _runs(rc):
    """The layers as runs of neighbours of one shape: [(attention kind,
    MLP kind, first layer, index of the first in its attention stack,
    index of the first in its MLP stack, how many)]."""
    runs, at = [], {"full": 0, "window": 0, "dense": 0, "sparse": 0}
    for l, kind in enumerate(rc.layer_types):
        mlp = "dense" if l in rc.dense_layers else "sparse"
        if runs and runs[-1][:2] == (kind, mlp):
            runs[-1][-1] += 1
        else:
            runs.append([kind, mlp, l, at[kind], at[mlp], 1])
        at[kind] += 1
        at[mlp] += 1
    return runs


def hidden(params, tokens, rc, dtype=_F32):
    """tokens [S] int32 -> final-norm hidden states [S, D] in `dtype`.
    Neighbouring layers of one shape are walked with `lax.scan` over
    their slice of each stack (the three window layers of a period
    compile once); experts are cut out of their stack one at a time and
    query rows attended a block at a time, so the reference fits beside
    bf16 weights."""
    x = params["wte"][tokens].astype(dtype)
    for kind, mlp, l0, a0, m0, n in _runs(rc):
        cut = lambda name, i0: params[name][i0:i0 + n]
        stacks = {"ln1": cut("ln1_scale", l0), "ln2": cut("ln2_scale", l0),
                  **{k: cut(kind[0] + "_" + k, a0) for k in _ATTN},
                  **{k: cut(k, m0) for k in _MLP[mlp]}}

        def layer(x, inputs, kind=kind, mlp=mlp, m0=m0):
            t, w = inputs
            x = x + _attention(x, w, kind, rc).astype(dtype)
            u = _rms_norm(x, w["ln2"], rc.norm_eps)
            if mlp == "dense":
                f = _gated_mlp(u, w["d_gate"], w["d_up"], w["d_down"])
            else:
                f = _sparse_mlp(
                    u, w, rc,
                    lambda e: tuple(params[k][m0 + t, e] for k in _EXPERTS),
                    params["w_gate"].shape[1])
            return x + f.astype(dtype), None

        x, _ = jax.lax.scan(layer, x, (jnp.arange(n), stacks))
    return _rms_norm(x, params["ln_f_scale"], rc.norm_eps)


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
