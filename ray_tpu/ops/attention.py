"""Flash attention as a Pallas TPU kernel (fwd + bwd), with LSE output.

This is the hot op of the Train/Serve stacks. The reference delegates all
tensor compute to torch/CUDA (e.g. its Train GPT workloads run torch models;
`/root/reference/python/ray/train/torch/`); the TPU-native equivalent is a
blockwise-softmax attention kernel that keeps the working set in VMEM, feeds
the MXU with [block_q, head_dim] x [block_kv, head_dim] tiles, and never
materialises the [S, T] score matrix in HBM.

Design notes:
- Grid is (batch, heads, q_blocks, kv_blocks) with the kv dimension innermost
  ("arbitrary" semantics) so the online-softmax state (m, l, acc) lives in
  VMEM scratch across kv iterations.
- Returns log-sum-exp per query row. ``lse`` makes the op composable: ring
  attention (parallel/ring.py) merges per-chunk partial results with the
  standard (o, lse) combine, and the custom VJP folds an incoming lse
  cotangent into the ``delta`` correction term, so the merge is differentiable.
- Backward is two more Pallas kernels (dq; dk+dv) using the stored lse —
  standard flash-attention-2 style recomputation, fp32 accumulators.
- Fully-masked causal blocks are skipped with ``pl.when`` (no MXU work).
- On non-TPU backends the same kernels run under ``interpret=True`` so every
  test exercises the identical code path on the 8-device CPU mesh.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _block_mask(iq, ik, *, causal, kv_len, block_q, block_kv):
    """[bq, bk] validity mask for one (q block, kv block) tile: in-range kv
    columns, and q >= kv when causal. Shared by fwd/dq/dkv kernels."""
    kpos = ik * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1
    )
    mask = kpos < kv_len
    if causal:
        qpos = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0
        )
        mask = jnp.logical_and(mask, qpos >= kpos)
    return mask


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
    *, sm_scale, causal, kv_len, block_q, block_kv, nk,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _compute():
        # MXU dots run in the input dtype (bf16) with fp32 accumulate —
        # upcasting the operands would silently drop the MXU into its ~4x
        # slower fp32 mode. Softmax statistics stay fp32.
        q = q_ref[0, 0]                      # [bq, K]
        k = k_ref[0, 0]                      # [bk, K]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # [bq, bk] fp32

        mask = _block_mask(iq, ik, causal=causal, kv_len=kv_len,
                           block_q=block_q, block_kv=block_kv)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                       # [bq, LANES] (uniform rows)
        row_max = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, row_max)      # [bq, LANES]
        p = jnp.exp(s - m_new[:, :1])             # [bq, bk] fp32
        corr = jnp.exp(m_prev[:, :1] - m_new[:, :1])  # [bq, 1]
        l_new = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new
        l_ref[...] = l_new

    # Skip kv blocks entirely above the causal diagonal.
    if causal:
        pl.when(ik * block_kv <= iq * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        # lse is stored lane-broadcast as [bq, LANES]: TPU pallas requires
        # the last two block dims to be (8k, 128m)-tiled, so a [bq]-shaped
        # row output cannot lower (same layout as the official kernel's
        # save_residuals l/m outputs).
        m = m_ref[...]
        lval = l_ref[...]
        lse = jnp.where(
            lval == 0.0, NEG_INF,
            m + jnp.log(jnp.where(lval == 0.0, 1.0, lval)))
        lse_ref[0, 0] = lse


def _fwd(q, k, v, causal, sm_scale, block_q, block_kv, interpret):
    """q: [B,H,S,K]; k,v: [B,H,T,K] → (o [B,H,S,K], lse [B,H,S] fp32)."""
    B, H, S, K = q.shape
    T = k.shape[2]
    bq = min(block_q, _round_up(S, 128))
    bk = min(block_kv, _round_up(T, 128))
    S_pad, T_pad = _round_up(S, bq), _round_up(T, bk)
    if S_pad != S:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, S_pad - S), (0, 0)))
    if T_pad != T:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, T_pad - T), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, T_pad - T), (0, 0)))
    nq, nk = S_pad // bq, T_pad // bk

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, kv_len=T,
        block_q=bq, block_kv=bk, nk=nk,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, K), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, K), lambda b, h, iq, ik: (b, h, ik, 0)),
            pl.BlockSpec((1, 1, bk, K), lambda b, h, iq, ik: (b, h, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, K), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bq, _LANES),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S_pad, K), q.dtype),
            jax.ShapeDtypeStruct((B, H, S_pad, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, K), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attn_fwd",
    )(q, k, v)
    return o[:, :, :S], lse[:, :, :S, 0]


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
    *, sm_scale, causal, kv_len, block_q, block_kv, nk,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]      # [bq, 1] (lane-broadcast input)
        delta = delta_ref[0, 0][:, :1]  # [bq, 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        mask = _block_mask(iq, ik, causal=causal, kv_len=kv_len,
                           block_q=block_q, block_kv=block_kv)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta) * sm_scale).astype(k.dtype)
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    if causal:
        pl.when(ik * block_kv <= iq * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(ik == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc,
    *, sm_scale, causal, kv_len, block_q, block_kv, nq,
):
    ik = pl.program_id(2)
    iq = pl.program_id(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, :1]
        delta = delta_ref[0, 0][:, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale
        mask = _block_mask(iq, ik, causal=causal, kv_len=kv_len,
                           block_q=block_q, block_kv=block_kv)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)  # [bq, bk] fp32
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)    # [bq, bk]
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    if causal:
        pl.when(ik * block_kv <= iq * block_q + block_q - 1)(_compute)
    else:
        _compute()

    @pl.when(iq == nq - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_impl(q, k, v, o, lse, do, dlse, causal, sm_scale, block_q, block_kv, interpret):
    B, H, S, K = q.shape
    T = k.shape[2]
    # delta folds both the standard rowsum(dO*O) correction and the incoming
    # lse cotangent: d s = p*(dp - delta) with delta = rowsum(dO*O) - dlse,
    # since d lse/d s = p.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)

    bq = min(block_q, _round_up(S, 128))
    bk = min(block_kv, _round_up(T, 128))
    S_pad, T_pad = _round_up(S, bq), _round_up(T, bk)
    pad4 = lambda x, n: jnp.pad(x, ((0, 0), (0, 0), (0, n - x.shape[2]), (0, 0)))
    # Padded q rows get a huge lse so p = exp(s - lse) underflows to 0 and
    # they contribute nothing to dk/dv (a NEG_INF pad would make p explode).
    pad3 = lambda x, n: jnp.pad(
        x, ((0, 0), (0, 0), (0, n - x.shape[2])), constant_values=-NEG_INF
    )
    if S_pad != S:
        q, do, o = pad4(q, S_pad), pad4(do, S_pad), pad4(o, S_pad)
        lse = pad3(lse, S_pad)
        delta = jnp.pad(delta, ((0, 0), (0, 0), (0, S_pad - S)))
    if T_pad != T:
        k, v = pad4(k, T_pad), pad4(v, T_pad)
    nq, nk = S_pad // bq, T_pad // bk

    # Row vectors enter the kernels lane-broadcast ([B,H,S,LANES]): TPU
    # pallas cannot lower a block whose last two dims aren't (8k, 128m).
    lse = jnp.broadcast_to(lse[..., None], (*lse.shape, _LANES))
    delta = jnp.broadcast_to(delta[..., None], (*delta.shape, _LANES))

    q_spec = pl.BlockSpec((1, 1, bq, K), lambda b, h, iq, ik: (b, h, iq, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, K), lambda b, h, iq, ik: (b, h, ik, 0))
    row_spec = pl.BlockSpec((1, 1, bq, _LANES),
                            lambda b, h, iq, ik: (b, h, iq, 0))

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, sm_scale=sm_scale, causal=causal, kv_len=T,
            block_q=bq, block_kv=bk, nk=nk,
        ),
        grid=(B, H, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, S_pad, K), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, K), jnp.float32)],
        interpret=interpret,
        name="flash_attn_bwd_dq",
    )(q, k, v, do, lse, delta)

    # kv-major grid: program_id(2)=ik, program_id(3)=iq.
    q_spec2 = pl.BlockSpec((1, 1, bq, K), lambda b, h, ik, iq: (b, h, iq, 0))
    kv_spec2 = pl.BlockSpec((1, 1, bk, K), lambda b, h, ik, iq: (b, h, ik, 0))
    row_spec2 = pl.BlockSpec((1, 1, bq, _LANES),
                             lambda b, h, ik, iq: (b, h, iq, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, sm_scale=sm_scale, causal=causal, kv_len=T,
            block_q=bq, block_kv=bk, nq=nq,
        ),
        grid=(B, H, nk, nq),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2],
        out_specs=[kv_spec2, kv_spec2],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T_pad, K), k.dtype),
            jax.ShapeDtypeStruct((B, H, T_pad, K), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, K), jnp.float32),
            pltpu.VMEM((bk, K), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attn_bwd_dkv",
    )(q, k, v, do, lse, delta)
    return dq[:, :, :S], dk[:, :, :T], dv[:, :, :T]


# ---------------------------------------------------------------------------
# custom_vjp wrapper (operates on [B,H,S,K])
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, sm_scale, block_q, block_kv, interpret):
    o, lse = _fwd(q, k, v, causal, sm_scale, block_q, block_kv, interpret)
    return o, lse


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_kv, interpret):
    o, lse = _fwd(q, k, v, causal, sm_scale, block_q, block_kv, interpret)
    return (o, lse), (q, k, v, o, lse)


def _flash_bwd(causal, sm_scale, block_q, block_kv, interpret, res, cot):
    q, k, v, o, lse = res
    do, dlse = cot
    dq, dk, dv = _bwd_impl(
        q, k, v, o, lse, do, dlse, causal, sm_scale, block_q, block_kv, interpret
    )
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int = 512,
    block_kv: int = 512,
    return_lse: bool = False,
    interpret: bool | None = None,
):
    """Blockwise flash attention.

    Args:
      q: [B, S, H, K] (model layout — seq-major per head).
      k, v: [B, T, H, K].
      causal: apply the causal mask (q position i attends to kv ≤ i).
      return_lse: also return per-row log-sum-exp [B, S, H] (fp32), for
        ring-attention combining.
    Returns o [B, S, H, K] (q.dtype), optionally (o, lse).
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = _interpret_default()
    qt = jnp.swapaxes(q, 1, 2)  # [B,H,S,K]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    o, lse = _flash(qt, kt, vt, causal, sm_scale, block_q, block_kv, interpret)
    o = jnp.swapaxes(o, 1, 2)
    if return_lse:
        return o, jnp.swapaxes(lse, 1, 2)  # [B,S,H]
    return o


def reference_attention(q, k, v, *, causal=True, sm_scale=None, return_lse=False):
    """Plain-XLA attention with identical semantics (test oracle + fallback)."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    S, T = q.shape[1], k.shape[1]
    logits = jnp.einsum(
        "bshk,bthk->bhst", q, k, preferred_element_type=jnp.float32
    ) * sm_scale
    if causal:
        mask = jnp.arange(S)[:, None] >= jnp.arange(T)[None, :]
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    o = jnp.einsum("bhst,bthk->bshk", probs, v)
    if return_lse:
        lse = jax.nn.logsumexp(logits, axis=-1)  # [B,H,S]
        return o, jnp.swapaxes(lse, 1, 2)
    return o
