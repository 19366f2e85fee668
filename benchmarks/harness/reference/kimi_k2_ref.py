"""The plain reference for the `kimi_k2` family (Kimi-K2.6: DeepSeek-V3's
block; multi-head LATENT attention whose cache would be one compressed
row a token, a sigmoid router that chooses by a biased score and gates
by the unbiased one over more experts than this chip holds, a shared
expert): the yardstick `correct` appeals to. Straightforward `jax.numpy`
over ONE whole sequence in the PLAIN (expanded) form: every token's
latent is expanded to a key and a value a head through `kv_b_proj`; no
absorbed weights, no kernel, no cache, no paging, no sorting of rows by
expert, and NO import from `ray_tpu`.

Departures from the published description (config.json and HF
`modeling_deepseek.py`, which `model_type: kimi_k2` follows), each also
in benchmarks/configs/kimi-k2.6.json:
  - the vision tower is left out (no key of the language model's config
    describes it): the cell serves text;
  - one chip's share: only the experts this share holds add to a sparse
    layer's routed part (the others' part is another chip's), the shared
    expert is whole, and the vocabulary is the held slice (the leaves'
    own shapes);
  - assumed: HF de-interleaves q_pe and k_pe before rotate-half; with
    seeded weights rotate-half on the stored order is the same function
    up to a fixed permutation of W_qb's and W_kva's rope columns, and is
    what is computed here.

Two arithmetics, chosen by `dtype`, as in gpt_ref.py. float32 (under
`jax.default_matmul_precision("highest")`) is the truth. bfloat16 is the
arithmetic the model is SERVED in, laid out plainly: bf16 weights and
activations, norms, softmax and the gates in float32, matmuls accumulated
to float32 — and the ROUTER in float32 in both.

Layer l, token i of a sequence (D model width, H heads, Rq / R the
query's and the latent's rank, Kn / Kr a head's no-position and rotary
parts, Kv the V head size):

  x <- x + Attn(RMSNorm(x));  x <- x + MLP(RMSNorm(x));  no bias anywhere
  Attn  u the normed input;  c_q = RMSNorm(u W_qa);  q = c_q W_qb
        (H x (Kn + Kr)), a head [q_nope ; q_pe];  [c ; k_pe] = u W_kva
        (R + Kr);  c = RMSNorm(c);  rope on q_pe and on k_pe (ONE rotary
        key for all heads): rotate-half, YaRN frequencies (plain
        theta^(-2i/Kr) where a dim turns more than `beta_fast` times
        over `yarn_orig` positions, divided by `yarn_factor` where fewer
        than `beta_slow`, a linear ramp between; float64 on the host),
        cos and sin times mscale(factor, `mscale`) / mscale(factor,
        `mscale_all_dim`), mscale(f, m) = 0.1 m ln f + 1;
        [k_nope_h ; v_h] = c W_kvb (Kn + Kv a head);
        a_ij = [q_nope ; q_pe]_i . [k_nope ; k_pe]_j * (Kn + Kr)^-1/2 *
        mscale(factor, `mscale_all_dim`)^2 over keys j <= i;
        o_i = softmax(a_i) v;  then W_o (H x Kv -> D).
  MLP   dense layers (the first `first_k_dense`):
        W_down(silu(W_gate u) * W_up u).
        sparse layers: s = sigmoid(u W_r) in float32 over ALL experts;
        the top_k largest of s + b choose (b: `router_bias`);
        gate_e = routed_scale * s_e / sum of the chosen s;
        MLP(u) = Shared(u) + sum over the chosen e THIS SHARE HOLDS of
        gate_e Expert_e(u). The share holds experts first_expert ..
        first_expert + E_held - 1 (E_held: the weights' own leading
        axis). Every expert is a gated-SiLU MLP.
  final RMSNorm; logits x W_head (untied).

Parameters are the program's own pytree as `param_specs` shapes it
(models/kimi_k2.py; NOT the absorbed tree its engine lays out):
wte [V,D], lm_head [D,V], ln_f_scale [D]; ln1_scale, ln2_scale [L,D];
wq_a [L,D,Rq], q_norm [L,Rq], wq_b [L,Rq,H*(Kn+Kr)], wkv_a [L,D,R+Kr],
kv_norm [L,R], wkv_b [L,R,H*(Kn+Kv)], wo [L,H*Kv,D]; d_gate, d_up
[nd,D,Fd], d_down [nd,Fd,D]; router [ns,D,E], router_bias [ns,E];
s_gate, s_up [ns,D,Fs], s_down [ns,Fs,D]; w_gate, w_up [ns,E_held,D,F],
w_down [ns,E_held,F,D].

`rc` is a hashable static value (families/kimi_k2.py
`reference_config`): `n_heads`, `nope_dim`, `rope_dim`, `v_head_dim`,
`first_k_dense`, `top_k`, `routed_scale`, `first_expert`, `norm_eps`,
`rope_theta`, `yarn_factor`, `yarn_orig`, `beta_fast`, `beta_slow`,
`mscale`, `mscale_all_dim`.
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


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def _inv_freq(rc) -> np.ndarray:
    """YaRN frequencies [rope_dim / 2], float64."""
    d, theta = rc.rope_dim, float(rc.rope_theta)
    plain = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    turns = lambda n: (d * math.log(rc.yarn_orig / (n * 2 * math.pi))
                       / (2 * math.log(theta)))
    low = max(math.floor(turns(rc.beta_fast)), 0)
    high = min(math.ceil(turns(rc.beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return plain * (1.0 - ramp) + plain / rc.yarn_factor * ramp


def _rope(x, rc):
    """x [S, ..., rope_dim] float32, position = the row; rotate-half."""
    S, half = x.shape[0], rc.rope_dim // 2
    factor = (_mscale(rc.yarn_factor, rc.mscale)
              / _mscale(rc.yarn_factor, rc.mscale_all_dim))
    ang = (jnp.arange(S, dtype=_F32).reshape((S,) + (1,) * (x.ndim - 1))
           * jnp.asarray(_inv_freq(rc), _F32))
    sin, cos = jnp.sin(ang) * factor, jnp.cos(ang) * factor
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _attention(x, w, rc):
    """x [S, D] -> the attention sublayer's output [S, D], the plain
    form. `w`: this layer's ln1 scale, its five matrices and two inner
    norms."""
    S, dt = x.shape[0], x.dtype
    H, Kn, Kr, Kv = rc.n_heads, rc.nope_dim, rc.rope_dim, rc.v_head_dim
    R = w["kv_norm"].shape[0]
    u = _rms_norm(x, w["ln1"], rc.norm_eps)
    c_q = _rms_norm(u @ w["wq_a"].astype(dt), w["q_norm"], rc.norm_eps)
    q = (c_q @ w["wq_b"].astype(dt)).reshape(S, H, Kn + Kr)
    q_pe = _rope(q[..., Kn:].astype(_F32), rc).astype(dt)
    kv_a = u @ w["wkv_a"].astype(dt)
    c = _rms_norm(kv_a[:, :R], w["kv_norm"], rc.norm_eps)
    k_pe = _rope(kv_a[:, R:].astype(_F32), rc).astype(dt)        # [S, Kr]
    kv = (c @ w["wkv_b"].astype(dt)).reshape(S, H, Kn + Kv)
    q = jnp.concatenate([q[..., :Kn], q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :Kn], jnp.broadcast_to(k_pe[:, None, :], (S, H, Kr))],
        axis=-1)
    v = kv[..., Kn:]
    scale = ((Kn + Kr) ** -0.5
             * _mscale(rc.yarn_factor, rc.mscale_all_dim) ** 2)
    block = _QUERY_ROWS if S % _QUERY_ROWS == 0 else S
    j = jnp.arange(S)

    def rows(args):
        i, q_rows = args                               # [b], [b, H, Kn+Kr]
        scores = jnp.einsum("shk,thk->hst", q_rows, k,
                            preferred_element_type=_F32) * scale
        scores = jnp.where((j[None, :] <= i[:, None])[None], scores,
                           -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(dt)
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
    """u [S, D] (normed) -> the shared expert plus this share's routed
    part, float32. `w`: the layer's router, its bias and the shared
    expert; `expert(e)` -> held expert e's three matrices, cut out of
    wherever they lie one expert at a time."""
    with jax.default_matmul_precision("highest"):            # the router
        s = jax.nn.sigmoid(u.astype(_F32) @ w["router"].astype(_F32))
    _, chosen = jax.lax.top_k(s + w["router_bias"].astype(_F32), rc.top_k)
    own = jnp.take_along_axis(s, chosen, axis=-1)            # unbiased
    gates = rc.routed_scale * own / jnp.sum(own, axis=-1, keepdims=True)

    def one_expert(f, e):
        gate = jnp.sum(jnp.where(chosen == rc.first_expert + e, gates, 0.0),
                       axis=-1)
        return f + gate[:, None] * _gated_mlp(u, *expert(e)), None

    f, _ = jax.lax.scan(one_expert, jnp.zeros(u.shape, _F32),
                        jnp.arange(held))
    return f + _gated_mlp(u, w["s_gate"], w["s_up"], w["s_down"])


_ATTN = ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo")
_MLP = {"dense": ("d_gate", "d_up", "d_down"),
        "sparse": ("router", "router_bias", "s_gate", "s_up", "s_down")}
_EXPERTS = ("w_gate", "w_up", "w_down")


def hidden(params, tokens, rc, dtype=_F32):
    """tokens [S] int32 -> final-norm hidden states [S, D] in `dtype`.
    The dense layers and the sparse layers are each walked with
    `lax.scan` over their slice of each stack (a run compiles once);
    experts are cut out of their stack one at a time and query rows
    attended a block at a time, so the reference fits beside bf16
    weights at the cell's longest stream."""
    x = params["wte"][tokens].astype(dtype)
    L = params["ln1_scale"].shape[0]
    n_dense = min(rc.first_k_dense, L)
    for mlp, l0, n in (("dense", 0, n_dense), ("sparse", n_dense,
                                               L - n_dense)):
        if n == 0:
            continue
        cut = lambda name, i0: params[name][i0:i0 + n]
        stacks = {"ln1": cut("ln1_scale", l0), "ln2": cut("ln2_scale", l0),
                  **{k: cut(k, l0) for k in _ATTN},
                  **{k: cut(k, 0) for k in _MLP[mlp]}}

        def layer(x, inputs, mlp=mlp):
            t, w = inputs
            x = x + _attention(x, w, rc).astype(dtype)
            u = _rms_norm(x, w["ln2"], rc.norm_eps)
            if mlp == "dense":
                f = _gated_mlp(u, w["d_gate"], w["d_up"], w["d_down"])
            else:
                f = _sparse_mlp(
                    u, w, rc,
                    lambda e: tuple(params[k][t, e] for k in _EXPERTS),
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
