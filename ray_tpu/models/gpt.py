"""Functional GPT decoder, TPU-first.

Flagship model family for the Train/Serve stacks (reference capability:
GPT-2 124M pretrain and GPT-J 6B FSDP in Ray Train's release suites,
`/root/reference/release/train_tests`). Design choices for TPU/XLA:

- Pure-functional: params are a pytree; every entry is declared once in
  `PARAM_SPECS` with shape + logical sharding axes, so the same table drives
  init, sharding, and checkpointing.
- Per-layer weights are **stacked on a leading `layers` axis and scanned**
  (`jax.lax.scan`) — compile time is O(1) in depth and XLA still pipelines.
- bfloat16 activations / fp32 params + fp32 layernorm and softmax.
- Rotary position embeddings (GPT-J style, applied to the leading
  `rotary_dim` of each head) — no position table to shard.
- Attention heads shard over `tp`, mlp hidden over `tp`, params over `fsdp`
  along `embed`, batch over `dp`+`fsdp` (see parallel/mesh.py rules).
"""

from __future__ import annotations

import dataclasses
import math
import re
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.ops import scopes


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304          # GPT-2 BPE rounded up to a multiple of 128
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    d_ff: int = 3072
    max_seq: int = 1024
    rotary_dim: int = 64             # per-head dims that get rotary; <= head_dim
    dtype: Any = jnp.bfloat16        # activation/compute dtype
    param_dtype: Any = jnp.float32
    tie_embeddings: bool = True
    remat: bool = False              # jax.checkpoint each block (for big models)
    attn_impl: str = "xla"           # "xla" | "flash" (pallas) | "ring" (sp-sharded)
    # Pallas flash-attention tile sizes: at S<=1024 a 1024 tile clamps
    # the kernel to one tile per (batch, head), minimizing blocking
    # overhead.
    attn_block_q: int = 1024
    attn_block_kv: int = 1024
    # Cross-entropy head chunking: compute logits/loss over sequence chunks of
    # this many tokens (bounds the fp32 [B, chunk, V] materialization instead
    # of [B, S, V] — at B=32, S=1024, V=50k the unchunked fp32 logits alone
    # are 6.6 GB). None = single full-sequence head. Requires sp=1 (the chunk
    # scan slices the sequence axis).
    loss_chunk: int | None = None

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @classmethod
    def gpt2_124m(cls, **kw) -> "GPTConfig":
        return cls(d_model=768, n_layers=12, n_heads=12, d_ff=3072, **kw)

    @classmethod
    def gpt2_350m(cls, **kw) -> "GPTConfig":
        return cls(d_model=1024, n_layers=24, n_heads=16, d_ff=4096, **kw)

    @classmethod
    def gpt2_2_7b(cls, **kw) -> "GPTConfig":
        """GPT-Neo-2.7B-class decoder (2.77 B params). The largest tier a
        single 16 GB chip can train — with bf16 master weights +
        stochastic rounding + adafactor (train/low_precision.py); fp32
        masters at this size need fsdp≥2."""
        kw.setdefault("remat", True)
        # 512 attention tiles: the 1024-tile backward's scratch tips this
        # tier over a 16 GB chip (measured OOM; 512 runs at MFU 0.359).
        kw.setdefault("attn_block_q", 512)
        kw.setdefault("attn_block_kv", 512)
        return cls(
            d_model=2560, n_layers=32, n_heads=32, d_ff=10240,
            rotary_dim=64, tie_embeddings=False, **kw
        )

    @classmethod
    def gptj_6b(cls, **kw) -> "GPTConfig":
        kw.setdefault("remat", True)
        return cls(
            d_model=4096, n_layers=28, n_heads=16, d_ff=16384,
            rotary_dim=64, tie_embeddings=False, **kw
        )

    @classmethod
    def opt_1_3b(cls, **kw) -> "GPTConfig":
        """OPT-1.3B-class decoder (BASELINE config 5 serving target)."""
        kw.setdefault("remat", True)
        return cls(
            d_model=2048, n_layers=24, n_heads=32, d_ff=8192,
            rotary_dim=64, tie_embeddings=False, **kw
        )

    @classmethod
    def tiny(cls, **kw) -> "GPTConfig":
        """For tests / dryruns on CPU meshes."""
        kw.setdefault("vocab_size", 256)
        kw.setdefault("max_seq", 128)
        kw.setdefault("rotary_dim", 4)
        kw.setdefault("d_model", 64)
        kw.setdefault("n_layers", 2)
        kw.setdefault("n_heads", 8)
        kw.setdefault("d_ff", 128)
        return cls(**kw)

    @classmethod
    def tiny_untied(cls, **kw) -> "GPTConfig":
        """Tiny with the big-model head/embedding layout (gptj/opt style)."""
        kw.setdefault("tie_embeddings", False)
        return cls.tiny(**kw)

    _REGISTRY = ("gpt2_124m", "gpt2_350m", "gpt2_2_7b", "gptj_6b",
                 "opt_1_3b", "tiny", "tiny_untied")

    @classmethod
    def by_name(cls, name: str, **kw) -> "GPTConfig":
        if name not in cls._REGISTRY:
            raise KeyError(f"unknown model {name!r}; one of {cls._REGISTRY}")
        return getattr(cls, name)(**kw)


def param_specs(cfg: GPTConfig) -> dict[str, dict[str, Any]]:
    """name → {shape, axes (logical), init} — single source of truth.

    Block params carry a leading `layers` axis (scanned).
    """
    D, H, K, F, L, V = (
        cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff, cfg.n_layers,
        cfg.vocab_size,
    )
    norm = lambda *s: {"init": "normal", "scale": 0.02, "shape": s}
    resid = lambda *s: {"init": "normal", "scale": 0.02 / math.sqrt(2 * L), "shape": s}
    ones = lambda *s: {"init": "ones", "shape": s}
    zeros = lambda *s: {"init": "zeros", "shape": s}

    specs: dict[str, dict[str, Any]] = {
        "wte": {**norm(V, D), "axes": ("vocab", "embed")},
        "ln_f_scale": {**ones(D), "axes": ("embed",)},
        "ln_f_bias": {**zeros(D), "axes": ("embed",)},
        # Scanned block params:
        "ln1_scale": {**ones(L, D), "axes": ("layers", "embed")},
        "ln1_bias": {**zeros(L, D), "axes": ("layers", "embed")},
        "wq": {**norm(L, D, H, K), "axes": ("layers", "embed", "heads", "kv")},
        "wk": {**norm(L, D, H, K), "axes": ("layers", "embed", "heads", "kv")},
        "wv": {**norm(L, D, H, K), "axes": ("layers", "embed", "heads", "kv")},
        "wo": {**resid(L, H, K, D), "axes": ("layers", "heads", "kv", "embed")},
        "ln2_scale": {**ones(L, D), "axes": ("layers", "embed")},
        "ln2_bias": {**zeros(L, D), "axes": ("layers", "embed")},
        "w_up": {**norm(L, D, F), "axes": ("layers", "embed", "mlp")},
        "b_up": {**zeros(L, F), "axes": ("layers", "mlp")},
        "w_down": {**resid(L, F, D), "axes": ("layers", "mlp", "embed")},
        "b_down": {**zeros(L, D), "axes": ("layers", "embed")},
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = {**norm(D, V), "axes": ("embed", "vocab")}
    return specs


def logical_axes(cfg: GPTConfig) -> dict[str, tuple]:
    return {k: v["axes"] for k, v in param_specs(cfg).items()}


def partition_rules() -> tuple:
    """Regex → PartitionSpec rule table for the stacked-block layout
    (models/partition.py `match_partition_rules` — rules match the
    ``/``-joined pytree path, first match wins).

    Serving tensor parallelism shards along the axis decode already
    parallelizes over: attention QKV on heads, the out projection on
    its head input, the MLP on its hidden width — all "tp"; embeddings,
    norms, biases on the embed axis, and the LM head stay replicated
    (the per-position head matmul is one weight read per WINDOW, not
    per layer, and replicating it keeps logits — and therefore argmax /
    sampling — whole on every shard). Shapes per param_specs():
    wq/wk/wv [L, D, H, K], wo [L, H, K, D], w_up [L, D, F],
    b_up [L, F], w_down [L, F, D].
    """
    from jax.sharding import PartitionSpec

    from ray_tpu.models.partition import TP_AXIS as TP

    return (
        (r"^w[qkv]$", PartitionSpec(None, None, TP, None)),
        (r"^wo$", PartitionSpec(None, TP, None, None)),
        (r"^w_up$", PartitionSpec(None, None, TP)),
        (r"^b_up$", PartitionSpec(None, TP)),
        (r"^w_down$", PartitionSpec(None, TP, None)),
        # int8 scale companions (quantize_params): same rank as their
        # plane with the reduced axes kept at size 1, so a head-sharded
        # plane's scales shard along with it. wo/w_down scales reduce
        # over the tp'd axis itself — size 1 can't shard, replicate.
        (r"^w[qkv]_scale$", PartitionSpec(None, None, TP, None)),
        (r"^w_up_scale$", PartitionSpec(None, None, TP)),
        (r"^(wo|w_down)_scale$", PartitionSpec()),
        # Replicated tail: embeddings, layer norms, residual-side biases,
        # and the LM head (explicit entries — match_partition_rules
        # treats an unmatched leaf as an error, not as replication).
        (r"^(wte|lm_head|ln|b_down)", PartitionSpec()),
    )


def init_params(cfg: GPTConfig, rng: jax.Array) -> dict[str, jax.Array]:
    specs = param_specs(cfg)
    keys = jax.random.split(rng, len(specs))
    params = {}
    for key, (name, spec) in zip(keys, sorted(specs.items())):
        shape = spec["shape"]
        if spec["init"] == "normal":
            params[name] = (
                jax.random.normal(key, shape, cfg.param_dtype) * spec["scale"]
            )
        elif spec["init"] == "ones":
            params[name] = jnp.ones(shape, cfg.param_dtype)
        else:
            params[name] = jnp.zeros(shape, cfg.param_dtype)
    return params


# --------------------------------------------------------------------------
# int8 weight quantization (serving).
#
# Per-output-channel symmetric int8 for the matmul planes only — the
# leaves whose HBM stream dominates weight-bound decode. Rule table is
# keyed off the same `/`-joined pytree paths as partition_rules(), and
# each rule names the CONTRACTION axes (reduced with keepdims), so a
# quantized leaf `name` gains an fp32 `name_scale` companion of the same
# rank whose surviving axes line up with the plane's — tp head-sharding
# then shards the scales alongside their planes by construction.
# Norms, embeddings, biases, and the LM head stay in param_dtype.

QUANT_RULES: tuple = (
    (r"^w[qkv]$", (1,)),      # [L, D, H, K]: reduce D  → scale [L, 1, H, K]
    (r"^wo$", (1, 2)),        # [L, H, K, D]: reduce HK → scale [L, 1, 1, D]
    (r"^w_up$", (1,)),        # [L, D, F]:    reduce D  → scale [L, 1, F]
    (r"^w_down$", (1,)),      # [L, F, D]:    reduce F  → scale [L, 1, D]
)


def quant_axes(name: str):
    """Contraction axes for a quantizable leaf path, else None."""
    for pat, axes in QUANT_RULES:
        if re.search(pat, name):
            return axes
    return None


def quantize_params(params: dict[str, jax.Array]) -> dict[str, jax.Array]:
    """Symmetric per-output-channel int8 quantization of the matmul
    weights (QUANT_RULES). Idempotent: already-int8 leaves pass through
    untouched with their existing scales, so a pre-quantized checkpoint
    (or an engine-quantized draft handed back in) round-trips."""
    out = dict(params)
    for name, w in params.items():
        axes = quant_axes(name)
        if axes is None or name.endswith("_scale") or w.dtype == jnp.int8:
            continue
        w32 = w.astype(jnp.float32)
        absmax = jnp.max(jnp.abs(w32), axis=axes, keepdims=True)
        scale = jnp.maximum(absmax, 1e-8) / 127.0
        out[name] = jnp.clip(jnp.round(w32 / scale),
                             -127, 127).astype(jnp.int8)
        out[name + "_scale"] = scale
    return out


def dequant(plane: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    """THE sanctioned int8→float dequant (graftlint QUANT-UPCAST allows
    the upcast only here): elementwise and adjacent to the consuming
    einsum, so XLA fuses it into the matmul read instead of
    re-materializing a float plane in HBM."""
    return plane.astype(dtype) * scale.astype(dtype)


def weight_view(tree: dict[str, jax.Array], name: str, dtype) -> jax.Array:
    """Compute-dtype view of weight `name`: fused dequant when the
    stored plane is int8 (its `{name}_scale` companion must ride in the
    same tree), plain cast otherwise. Every traced matmul consumption
    site routes through here — never through a direct `.astype` on the
    stored leaf."""
    w = tree[name]
    if w.dtype == jnp.int8:
        return dequant(w, tree[name + "_scale"], dtype)
    return w.astype(dtype)


def stack_block_params(params: dict[str, jax.Array],
                       dtype=None) -> dict[str, jax.Array]:
    """Per-layer stacked leaf dict for scan bodies: `_BLOCK_KEYS` plus
    the `*_scale` companions of any int8 plane (scan slices layer l of
    a [L, 1, ...] scale to [1, ...], which broadcasts in dequant). With
    `dtype`, float leaves are pre-cast once outside the scan (the paged
    engine's convention); int8 planes always stay compressed."""
    stacked = {}
    for k in _BLOCK_KEYS:
        w = params[k]
        if w.dtype == jnp.int8:
            stacked[k] = w
            stacked[k + "_scale"] = params[k + "_scale"]
        else:
            stacked[k] = w if dtype is None else w.astype(dtype)
    return stacked


def _layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + 1e-5)
    return (y * scale + bias).astype(x.dtype)


def _rotary(x: jax.Array, rotary_dim: int, offset: int = 0) -> jax.Array:
    """Apply rotary embedding to x[..., S, H, K] over the first rotary_dim dims."""
    S = x.shape[-3]
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    inv_freq = 1.0 / (10000 ** (jnp.arange(0, rotary_dim, 2) / rotary_dim))
    pos = jnp.arange(offset, offset + S)[:, None] * inv_freq[None, :]  # [S, R/2]
    sin = jnp.sin(pos)[:, None, :].astype(x.dtype)  # [S, 1, R/2]
    cos = jnp.cos(pos)[:, None, :].astype(x.dtype)
    x1, x2 = rot[..., 0::2], rot[..., 1::2]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    rot = jnp.stack([out1, out2], axis=-1).reshape(rot.shape)
    return jnp.concatenate([rot, rest], axis=-1)


def _attention(q, k, v, cfg: GPTConfig, *, causal_offset: int = 0, mesh=None):
    """q,k,v: [B, S, H, K] (q) / [B, T, H, K] (k,v). fp32 logits+softmax."""
    if cfg.attn_impl in ("flash", "ring") and causal_offset != 0:
        raise NotImplementedError(
            f"causal_offset is only supported by attn_impl='xla', "
            f"not {cfg.attn_impl!r} (decode paths use the serve KV cache)"
        )
    if cfg.attn_impl == "flash":
        from ray_tpu.ops.attention import flash_attention

        attn = partial(flash_attention, causal=True,
                       block_q=cfg.attn_block_q, block_kv=cfg.attn_block_kv)
        if mesh is None or mesh.size == 1:
            return attn(q, k, v)
        # The SPMD partitioner cannot split a Mosaic kernel ("wrap the
        # call in a shard_map"), so on a multi-device mesh the kernel
        # runs per shard: attention is independent per batch row and per
        # head — batch over (dp, fsdp), heads over tp, sequence whole
        # (an sp-sharded sequence is attn_impl="ring").
        from jax.sharding import PartitionSpec

        from ray_tpu.utils.jax_compat import shard_map

        spec = PartitionSpec(("dp", "fsdp"), None, "tp", None)
        return shard_map(attn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)
    if cfg.attn_impl == "ring":
        from ray_tpu.parallel.ring import ring_attention_sharded

        if mesh is None:
            raise ValueError("attn_impl='ring' requires forward(..., mesh=)")
        impl = "flash" if jax.default_backend() == "tpu" else "xla"
        return ring_attention_sharded(q, k, v, mesh, causal=True, impl=impl)
    S, T = q.shape[-3], k.shape[-3]
    scale = 1.0 / math.sqrt(cfg.head_dim)
    logits = jnp.einsum(
        "bshk,bthk->bhst", q, k, preferred_element_type=jnp.float32
    ) * scale
    qpos = jnp.arange(S)[:, None] + causal_offset
    kpos = jnp.arange(T)[None, :]
    mask = qpos >= kpos
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthk->bshk", probs, v)


def _block(
    x: jax.Array, layer: dict[str, jax.Array], cfg: GPTConfig, mesh=None
) -> jax.Array:
    """One pre-norm transformer block. x: [B, S, D]."""
    with jax.named_scope(scopes.ATTN_IN):
        h = _layer_norm(x, layer["ln1_scale"], layer["ln1_bias"])
        q = jnp.einsum("bsd,dhk->bshk", h,
                       weight_view(layer, "wq", cfg.dtype))
        k = jnp.einsum("bsd,dhk->bshk", h,
                       weight_view(layer, "wk", cfg.dtype))
        v = jnp.einsum("bsd,dhk->bshk", h,
                       weight_view(layer, "wv", cfg.dtype))
        q = _rotary(q, cfg.rotary_dim)
        k = _rotary(k, cfg.rotary_dim)
    with jax.named_scope(scopes.ATTN_KERNEL):
        attn = _attention(q, k, v, cfg, mesh=mesh)
    with jax.named_scope(scopes.ATTN_OUT):
        attn_out = jnp.einsum("bshk,hkd->bsd", attn,
                              weight_view(layer, "wo", cfg.dtype))
        x = x + attn_out
    with jax.named_scope(scopes.MLP):
        h = _layer_norm(x, layer["ln2_scale"], layer["ln2_bias"])
        up = jnp.einsum("bsd,df->bsf", h,
                        weight_view(layer, "w_up", cfg.dtype))
        up = up + layer["b_up"].astype(cfg.dtype)
        up = jax.nn.gelu(up)
        down = jnp.einsum("bsf,fd->bsd", up,
                          weight_view(layer, "w_down", cfg.dtype))
        down = down + layer["b_down"].astype(cfg.dtype)
        return x + down


_BLOCK_KEYS = (
    "ln1_scale", "ln1_bias", "wq", "wk", "wv", "wo",
    "ln2_scale", "ln2_bias", "w_up", "b_up", "w_down", "b_down",
)


def forward_hidden(
    params: dict[str, jax.Array],
    tokens: jax.Array,
    cfg: GPTConfig,
    mesh=None,
) -> jax.Array:
    """tokens: [B, S] int32 → final-norm hidden states [B, S, D] (cfg.dtype).

    `mesh` is consulted by the kernel attention paths: "ring" (the
    sp-sharded ring attention) and, on more than one device, "flash" each
    run in an explicit shard_map over it.
    """
    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens]
    stacked = stack_block_params(params)
    block_fn = lambda x, layer: _block(x, layer, cfg, mesh)

    def body(x, layer):
        fn = jax.checkpoint(block_fn) if cfg.remat else block_fn
        return fn(x, layer), None

    x, _ = jax.lax.scan(body, x, stacked)
    with jax.named_scope(scopes.HEAD):
        return _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"])


def _head_matrix(params, cfg: GPTConfig):
    head = params["lm_head"] if not cfg.tie_embeddings else params["wte"].T
    return head.astype(cfg.dtype)


def forward(
    params: dict[str, jax.Array],
    tokens: jax.Array,
    cfg: GPTConfig,
    mesh=None,
) -> jax.Array:
    """tokens: [B, S] int32 → logits [B, S, V] (fp32)."""
    x = forward_hidden(params, tokens, cfg, mesh)
    with jax.named_scope(scopes.HEAD):
        return jnp.einsum(
            "bsd,dv->bsv", x, _head_matrix(params, cfg),
            preferred_element_type=jnp.float32,
        )


def forward_pipeline(
    params: dict[str, jax.Array],
    tokens: jax.Array,
    cfg: GPTConfig,
    mesh,
    n_micro: int,
) -> jax.Array:
    """Pipeline-parallel forward: the scanned block stack shards over the
    `pp` mesh axis and runs the GPipe microbatch schedule
    (parallel/pipeline.py); embedding, final norm, and head stay outside
    the pipeline (replicated over pp, sharded by the usual fsdp/tp rules).
    Requires cfg.n_layers % mesh.shape['pp'] == 0."""
    from ray_tpu.parallel.pipeline import pipeline_apply

    with jax.named_scope(scopes.EMBED):
        x = params["wte"].astype(cfg.dtype)[tokens]
    stacked = stack_block_params(params)

    def stage(local_stack, act):
        def body(a, layer):
            fn = (jax.checkpoint(lambda aa, ll: _block(aa, ll, cfg))
                  if cfg.remat else (lambda aa, ll: _block(aa, ll, cfg)))
            return fn(a, layer), None

        a, _ = jax.lax.scan(body, act, local_stack)
        return a

    x = pipeline_apply(stage, stacked, x, mesh=mesh, n_micro=n_micro)
    with jax.named_scope(scopes.HEAD):
        x = _layer_norm(x, params["ln_f_scale"], params["ln_f_bias"])
        return jnp.einsum(
            "bsd,dv->bsv", x, _head_matrix(params, cfg),
            preferred_element_type=jnp.float32,
        )


def pipeline_loss_fn(params, tokens, targets, cfg: GPTConfig, mesh,
                     n_micro: int) -> jax.Array:
    logits = forward_pipeline(params, tokens, cfg, mesh, n_micro)
    with jax.named_scope(scopes.LOSS):
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - gold)


def loss_fn(
    params: dict[str, jax.Array],
    tokens: jax.Array,
    targets: jax.Array,
    cfg: GPTConfig,
    mesh=None,
) -> jax.Array:
    """Mean next-token cross-entropy. tokens/targets: [B, S] int32.

    With cfg.loss_chunk set, the vocab projection + CE run under a scanned
    sequence-chunk loop with rematerialization: only one fp32 [B, chunk, V]
    logits block is live at a time (fwd AND bwd — the chunk recomputes its
    logits in the backward pass, and the head gradient accumulates across
    chunks inside the scan's own autodiff).
    """
    x = forward_hidden(params, tokens, cfg, mesh)
    with jax.named_scope(scopes.HEAD):
        head = _head_matrix(params, cfg)
    if cfg.loss_chunk is None or tokens.shape[1] <= cfg.loss_chunk:
        with jax.named_scope(scopes.HEAD):
            logits = jnp.einsum(
                "bsd,dv->bsv", x, head, preferred_element_type=jnp.float32)
        with jax.named_scope(scopes.LOSS):
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(
                logits, targets[..., None], axis=-1)[..., 0]
            return jnp.mean(logz - gold)
    S = tokens.shape[1]
    C = cfg.loss_chunk
    if S % C != 0:
        raise ValueError(f"seq len {S} not divisible by loss_chunk {C}")
    xs = x.reshape(x.shape[0], S // C, C, x.shape[-1])
    ts = targets.reshape(targets.shape[0], S // C, C)

    @jax.checkpoint
    def chunk_ce(x_c, t_c):
        with jax.named_scope(scopes.HEAD):
            logits = jnp.einsum(
                "bcd,dv->bcv", x_c, head, preferred_element_type=jnp.float32)
        with jax.named_scope(scopes.LOSS):
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(
                logits, t_c[..., None], axis=-1)[..., 0]
            return jnp.sum(logz - gold)

    def body(tot, chunk):
        x_c, t_c = chunk
        return tot + chunk_ce(x_c, t_c), None

    total, _ = jax.lax.scan(
        body, jnp.zeros((), jnp.float32),
        (jnp.swapaxes(xs, 0, 1), jnp.swapaxes(ts, 0, 1)))
    return total / (targets.shape[0] * S)


def num_params(cfg: GPTConfig) -> int:
    return sum(math.prod(s["shape"]) for s in param_specs(cfg).values())
