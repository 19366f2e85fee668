"""The plain reference for the `mimo_v2` family (MiMo-V2-Flash: K heads
of 192 beside V heads of 128, a learned sink in the window layers'
softmax, 4 and 8 KV heads by layer kind, rotary on a third of a head at
two thetas, a sigmoid router that chooses by a biased score and gates by
the unbiased one over more experts than this chip holds): the yardstick
`correct` appeals to. Straightforward `jax.numpy` over ONE whole
sequence: no kernel, no cache, no paging, no ring, no sorting of rows by
expert, and NO import from `ray_tpu`.

Departures from the published description (config.json and the model
card), each also in benchmarks/configs/mimo-v2-flash.json:
  - the 3 multi-token-prediction layers are left out (no key of
    config.json describes them);
  - one chip's share: only the experts this share holds add to a sparse
    layer's output (the others' part is another chip's), and the
    vocabulary is the held slice (the leaves' own shapes);
  - assumed, where config.json is silent: the softmax scale is
    head_dim^-1/2 (192^-1/2); `attention_value_scale` multiplies V;
    the window counts the query's own position; no q/k norm; the sink
    logit joins the scaled scores as they are (not scaled itself).

Two arithmetics, chosen by `dtype`, as in gpt_ref.py. float32 (under
`jax.default_matmul_precision("highest")`) is the truth. bfloat16 is the
arithmetic the model is SERVED in, laid out plainly: bf16 weights and
activations, norms, softmax, the value scale and the gates in float32,
matmuls accumulated to float32 — and the ROUTER in float32 in both.

Layer l, token i of a sequence (D model width, H query heads, Kq = the
q and K head size, Kv the V head size, G_c KV heads of layer kind c, W
the window):

  x <- x + Attn(RMSNorm(x));  x <- x + MLP(RMSNorm(x))
  Attn  u the normed input;  q = u W_q (H x Kq), k = u W_k (G_c x Kq),
        v = value_scale * u W_v (G_c x Kv);  rotate-half rope on the
        first `rotary_dim` dims of q and k, theta `theta_full` or
        `theta_window`, frequencies theta^(-2i/rotary_dim) computed on
        the host in float64;  query head h reads KV head h // (H / G_c);
        a_ij = q_i . k_j / sqrt(Kq) over keys j <= i, and in a window
        layer only i - j < W;  full: p = softmax(a);  window (a kind in
        `sink_kinds`): p_ij = exp(a_ij) / (exp(s_h) + sum_j' exp(a_ij')),
        s_h the head's learned sink logit;  o_i = sum_j p_ij v_j;  W_o.
  MLP   dense layers: W_down(silu(W_gate u) * W_up u).
        sparse layers: s = sigmoid(u W_r) in float32 over ALL experts;
        the top_k largest of s + b choose (b: `router_bias`);
        gate_e = s_e / sum of the chosen s;
        MLP(u) = sum over the chosen e THIS SHARE HOLDS of
        gate_e Expert_e(u). The share holds experts first_expert ..
        first_expert + E_held - 1 (E_held: the weights' own leading
        axis). Every expert is a gated-SiLU MLP; no shared expert.
  final RMSNorm; logits x W_head (untied).

Parameters are the program's own pytree (models/mimo_v2.py), one stack a
layer kind and MLP kind, in layer order within the kind:
wte [V,D], lm_head [D,V], ln_f_scale [D]; ln1_scale, ln2_scale [L,D];
f_wq [nf,D,H*Kq], f_wk [nf,D,Gf*Kq], f_wv [nf,D,Gf*Kv], f_wo
[nf,H*Kv,D] (full layers); w_wq ... w_wo likewise at Gw, and w_sink
[nw,H] (window layers); d_gate, d_up [nd,D,Fd], d_down [nd,Fd,D];
router [ns,D,E], router_bias [ns,E]; w_gate, w_up [ns,E_held,D,F],
w_down [ns,E_held,F,D].

`rc` is a hashable static value (families/mimo_v2.py
`reference_config`): `layer_types` (a tuple of "full" / "window"),
`dense_layers`, `n_heads`, `kv_heads_full`, `kv_heads_window`,
`head_dim`, `v_head_dim`, `value_scale`, `window`, `sink_kinds`, `top_k`,
`first_expert`, `norm_eps`, `theta_full`, `theta_window`, `rotary_dim`.
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


def _rope(x, inv_freq):
    """x [S, h, K] float32; rotate-half on the first 2 * len(inv_freq)
    dims."""
    S, half = x.shape[0], len(inv_freq)
    ang = (jnp.arange(S, dtype=_F32)[:, None, None]
           * jnp.asarray(inv_freq, _F32))                     # [S, 1, half]
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _attention(x, w, kind: str, rc):
    """x [S, D] -> the attention sublayer's output [S, D]. `w`: this
    layer's ln1 scale, its kind's four matrices and (a kind with a sink)
    its sink logits."""
    S, dt = x.shape[0], x.dtype
    H, Kq, Kv = rc.n_heads, rc.head_dim, rc.v_head_dim
    G = rc.kv_heads_full if kind == "full" else rc.kv_heads_window
    u = _rms_norm(x, w["ln1"], rc.norm_eps)
    q = (u @ w["wq"].astype(dt)).reshape(S, H, Kq).astype(_F32)
    k = (u @ w["wk"].astype(dt)).reshape(S, G, Kq).astype(_F32)
    v = ((u @ w["wv"].astype(dt)).astype(_F32) * rc.value_scale).astype(
        dt).reshape(S, G, Kv)
    theta = rc.theta_full if kind == "full" else rc.theta_window
    inv_freq = float(theta) ** (
        -np.arange(0, rc.rotary_dim, 2, dtype=np.float64) / rc.rotary_dim)
    q, k = _rope(q, inv_freq).astype(dt), _rope(k, inv_freq).astype(dt)
    k, v = jnp.repeat(k, H // G, axis=1), jnp.repeat(v, H // G, axis=1)
    block = _QUERY_ROWS if S % _QUERY_ROWS == 0 else S
    j = jnp.arange(S)

    def rows(args):
        i, q_rows = args                                   # [b], [b, H, Kq]
        scores = jnp.einsum("shk,thk->hst", q_rows, k,
                            preferred_element_type=_F32) / math.sqrt(Kq)
        seen = j[None, :] <= i[:, None]
        if kind == "window":
            seen &= i[:, None] - j[None, :] < rc.window
        scores = jnp.where(seen[None], scores, -jnp.inf)
        if kind in rc.sink_kinds:       # one more logit, with no value row
            sink = jnp.broadcast_to(w["sink"].astype(_F32)[:, None, None],
                                    scores.shape[:2] + (1,))
            scores = jnp.concatenate([scores, sink], axis=-1)
        probs = jax.nn.softmax(scores, axis=-1)[..., :S].astype(dt)
        return jnp.einsum("hst,thk->shk", probs, v)

    split = lambda a: a.reshape((S // block, block) + a.shape[1:])
    o = jax.lax.map(rows, (split(j), split(q))).reshape(S, H * Kv)
    return o @ w["wo"].astype(dt)


def _gated_mlp(u, w_gate, w_up, w_down):
    """W_down(silu(W_gate u) * W_up u) -> float32 [S, D]."""
    dt = u.dtype
    hid = (jax.nn.silu((u @ w_gate.astype(dt)).astype(_F32))
           * (u @ w_up.astype(dt)).astype(_F32)).astype(dt)
    return (hid @ w_down.astype(dt)).astype(_F32)


def _sparse_mlp(u, w, rc, expert, held: int):
    """u [S, D] (normed) -> this share's routed part, float32. `w`: the
    layer's router and its bias; `expert(e)` -> held expert e's three
    matrices, cut out of wherever they lie one expert at a time."""
    with jax.default_matmul_precision("highest"):            # the router
        s = jax.nn.sigmoid(u.astype(_F32) @ w["router"].astype(_F32))
    _, chosen = jax.lax.top_k(s + w["router_bias"].astype(_F32), rc.top_k)
    own = jnp.take_along_axis(s, chosen, axis=-1)            # unbiased
    gates = own / jnp.sum(own, axis=-1, keepdims=True)

    def one_expert(f, e):
        gate = jnp.sum(jnp.where(chosen == rc.first_expert + e, gates, 0.0),
                       axis=-1)
        return f + gate[:, None] * _gated_mlp(u, *expert(e)), None

    f, _ = jax.lax.scan(one_expert, jnp.zeros(u.shape, _F32),
                        jnp.arange(held))
    return f


_ATTN = ("wq", "wk", "wv", "wo")
_MLP = {"dense": ("d_gate", "d_up", "d_down"),
        "sparse": ("router", "router_bias")}
_EXPERTS = ("w_gate", "w_up", "w_down")


def _runs(rc):
    """The layers as runs of neighbours of one shape: [(attention kind,
    MLP kind, first layer, index of the first in its attention stack,
    index of the first in its MLP stack, how many)]."""
    runs, at = [], {"full": 0, "window": 0, "dense": 0, "sparse": 0}
    for l, kind in enumerate(rc.layer_types):
        mlp = "dense" if l in rc.dense_layers else "sparse"
        if runs and runs[-1][:2] == [kind, mlp]:
            runs[-1][-1] += 1
        else:
            runs.append([kind, mlp, l, at[kind], at[mlp], 1])
        at[kind] += 1
        at[mlp] += 1
    return runs


def hidden(params, tokens, rc, dtype=_F32):
    """tokens [S] int32 -> final-norm hidden states [S, D] in `dtype`.
    Neighbouring layers of one shape are walked with `lax.scan` over
    their slice of each stack (the four window layers of a period
    compile once); experts are cut out of their stack one at a time and
    query rows attended a block at a time, so the reference fits beside
    bf16 weights at 6,144 positions."""
    x = params["wte"][tokens].astype(dtype)
    for kind, mlp, l0, a0, m0, n in _runs(rc):
        cut = lambda name, i0: params[name][i0:i0 + n]
        attn = _ATTN + (("sink",) if kind in rc.sink_kinds else ())
        stacks = {"ln1": cut("ln1_scale", l0), "ln2": cut("ln2_scale", l0),
                  **{k: cut(kind[0] + "_" + k, a0) for k in attn},
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
