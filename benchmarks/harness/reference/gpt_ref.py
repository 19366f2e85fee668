"""The plain reference for the GPT-class configurations: the yardstick
`correct` appeals to. Straightforward `jax.numpy`: no kernel, no cache,
no paging, no batching tricks, and NO import from `ray_tpu.models`.

One block, two arithmetics, chosen by `dtype`. float32 (the default,
under `jax.default_matmul_precision("highest")`) is the truth: a training
loss and every served token are measured in its logits. bfloat16 is the
arithmetic the model is SERVED in, laid out plainly: bf16 weights and
activations, LayerNorm and softmax in float32, scores and logits
accumulated to float32. It is run beside the float32 one to show how far
an honest bf16 forward strays from float32 on the same rows, which is
the room a served stream is given (`paired_rows`; PERF.md section 6).

The block, as the repo's model class defines it (each departure from the
published OPT / GPT-J block is listed in the configuration's file):
pre-LayerNorm (eps 1e-5) -> q,k,v projections without bias -> rotary
embedding on the first `rotary_dim` dims of each head (GPT-J style:
even/odd pairs, base 10000) -> causal softmax attention scaled by
1/sqrt(head_dim) -> output projection -> residual; pre-LayerNorm ->
up-projection + bias -> GELU (tanh approximation) -> down-projection +
bias -> residual; final LayerNorm; an untied (or tied) head.

Parameters are the program's own pytree (stacked on a leading layers
axis): wte [V,D], ln_f_{scale,bias} [D], ln{1,2}_{scale,bias} [L,D],
wq/wk/wv [L,D,H,K], wo [L,H,K,D], w_up [L,D,F], b_up [L,F],
w_down [L,F,D], b_down [L,D], lm_head [D,V] when untied.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_F32 = jnp.float32


def _layer_norm(x, scale, bias):
    """In float32 whatever x is; the result goes back to x's type."""
    x32 = x.astype(_F32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean((x32 - mu) ** 2, axis=-1, keepdims=True)
    y = (x32 - mu) / jnp.sqrt(var + 1e-5) * scale.astype(_F32) \
        + bias.astype(_F32)
    return y.astype(x.dtype)


def _rotary(x, rotary_dim: int):
    """x: [S, H, K]; rotate the first rotary_dim dims of every head."""
    S = x.shape[0]
    inv_freq = 1.0 / (10000.0 ** (jnp.arange(0, rotary_dim, 2, dtype=_F32)
                                  / rotary_dim))
    ang = jnp.arange(S, dtype=_F32)[:, None] * inv_freq[None, :]   # [S, R/2]
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    even, odd = rot[..., 0::2], rot[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                    axis=-1).reshape(rot.shape)
    return jnp.concatenate([out, rest], axis=-1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, w, rotary_dim: int):
    """x: [S, D] in the arithmetic's type; w: one layer's weights."""
    S, dt = x.shape[0], x.dtype
    head_dim = w["wq"].shape[-1]
    h = _layer_norm(x, w["ln1_scale"], w["ln1_bias"])
    q = jnp.einsum("sd,dhk->shk", h, w["wq"].astype(dt))
    k = jnp.einsum("sd,dhk->shk", h, w["wk"].astype(dt))
    v = jnp.einsum("sd,dhk->shk", h, w["wv"].astype(dt))
    q, k = _rotary(q, rotary_dim), _rotary(k, rotary_dim)
    scores = jnp.einsum("shk,thk->hst", q, k,
                        preferred_element_type=_F32) / math.sqrt(head_dim)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(dt)
    attn = jnp.einsum("hst,thk->shk", probs, v)
    x = x + jnp.einsum("shk,hkd->sd", attn, w["wo"].astype(dt))
    h = _layer_norm(x, w["ln2_scale"], w["ln2_bias"])
    up = h @ w["w_up"].astype(dt) + w["b_up"].astype(dt)
    down = _gelu_tanh(up) @ w["w_down"].astype(dt) + w["b_down"].astype(dt)
    return x + down


_LAYER_KEYS = ("ln1_scale", "ln1_bias", "wq", "wk", "wv", "wo",
               "ln2_scale", "ln2_bias", "w_up", "b_up", "w_down", "b_down")


def hidden(params, tokens, rotary_dim: int, dtype=_F32):
    """tokens [S] int32 -> final-norm hidden states [S, D] in `dtype`. The
    layers are walked with `lax.scan` over the stacked weights, one layer
    cast at a time, so the reference fits beside bf16 weights."""
    x = params["wte"][tokens].astype(dtype)
    stacked = {k: params[k] for k in _LAYER_KEYS}

    def body(x, w):
        return _block(x, w, rotary_dim), None

    x, _ = jax.lax.scan(body, x, stacked)
    return _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"])


def _head(params, dtype=_F32):
    head = params["lm_head"] if "lm_head" in params else params["wte"].T
    return head.astype(dtype)


def logits(params, tokens, rotary_dim: int, dtype=_F32):
    """tokens [S] -> logits [S, V] float32 (accumulated to float32 from
    `dtype` operands)."""
    with jax.default_matmul_precision("highest"):
        return jnp.einsum("sd,dv->sv", hidden(params, tokens, rotary_dim, dtype),
                          _head(params, dtype), preferred_element_type=_F32)


def loss(params, tokens, targets, rotary_dim: int):
    """Mean next-token cross-entropy of a batch [B, S], float32."""
    with jax.default_matmul_precision("highest"):
        def one(toks, tgt):
            lg = hidden(params, toks, rotary_dim) @ _head(params)
            logz = jax.nn.logsumexp(lg, axis=-1)
            gold = jnp.take_along_axis(lg, tgt[:, None], axis=-1)[:, 0]
            return jnp.sum(logz - gold)

        total = sum(one(t, g) for t, g in zip(tokens, targets))
        return total / (tokens.shape[0] * tokens.shape[1])


def paired_rows(params, seq, rotary_dim: int):
    """For a padded stream `seq` [S], per position and all measured in the
    FLOAT32 reference's logits: the row's best logit and its argmax, the
    logit of the token that actually follows (what was served), and the
    logit of the token a plain bfloat16 forward of the same weights would
    have chosen there. The last is the yardstick's own noise: how far an
    honest bf16 server strays from float32 on this very row."""
    lg32 = logits(params, seq, rotary_dim, _F32)
    lg16 = logits(params, seq, rotary_dim, jnp.bfloat16)
    pick = lambda tok: jnp.take_along_axis(lg32, tok[:, None], axis=1)[:, 0]
    return (lg32.max(axis=1), lg32.argmax(axis=1), pick(jnp.roll(seq, -1)),
            pick(lg16.argmax(axis=1)))
