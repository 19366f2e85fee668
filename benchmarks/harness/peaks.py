"""Device peaks and the arithmetic that turns shapes into operations and
bytes. The yardstick: later PRs read it and may not change it.

Peaks are per chip, from the Google Cloud TPU documentation's
per-generation pages ("TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
819 GB/s; "TPU v6e": 918 TFLOP/s, 32 GB at 1,640 GB/s; "TPU v5p":
459 TFLOP/s, 95 GB at 2,765 GB/s; "TPU v4": 275 TFLOP/s, 32 GB at
1,228 GB/s). Keys are matched as substrings of the lower-cased
`device_kind` with spaces removed, most specific first ("TPU v5 lite" ->
"tpuv5lite"). A device that is not in the table is an error, never a
default. The table and `gpt_train_flops_per_token` are copied from
`bench.py` (PR 21 state); HBM figures are added here.
"""

from __future__ import annotations

_PEAKS = (
    ("v5litepod", dict(flops_bf16=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9)),
    ("v5lite", dict(flops_bf16=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9)),
    ("v5e", dict(flops_bf16=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9)),
    ("v6lite", dict(flops_bf16=918e12, hbm_bytes_per_s=1640e9, hbm_bytes=32e9)),
    ("v6e", dict(flops_bf16=918e12, hbm_bytes_per_s=1640e9, hbm_bytes=32e9)),
    ("v5p", dict(flops_bf16=459e12, hbm_bytes_per_s=2765e9, hbm_bytes=95e9)),
    ("v4", dict(flops_bf16=275e12, hbm_bytes_per_s=1228e9, hbm_bytes=32e9)),
)


def peaks_for(device_kind: str) -> dict:
    kind = (device_kind or "").lower().replace(" ", "")
    for key, peaks in _PEAKS:
        if key in kind:
            return dict(peaks)
    raise ValueError(
        f"no peaks known for device_kind {device_kind!r}; add it to "
        "benchmarks/harness/peaks.py with its source")


def gpt_matmul_params(dims: dict) -> int:
    """Parameters that take part in matrix multiplications: one
    embedding-sized matrix (the head; the embedding itself is a gather),
    per layer qkv+proj (4*d^2) and the MLP in+out (2*d*d_ff)."""
    d, f = dims["d_model"], dims["d_ff"]
    return (dims["vocab_size"] * d
            + dims["n_layers"] * (4 * d * d + 2 * d * f))


def gpt_train_flops_per_token(dims: dict, seq: int) -> float:
    """Operations the forward and backward passes REQUIRE per token:
    6 per matmul parameter (forward 2, backward 4) plus the causal
    attention score/value term 12*L*d*S (bench.py's formula, which
    counts the full square; kept so numbers compare). Recomputation
    under remat does not count."""
    attn = 12 * dims["n_layers"] * dims["d_model"] * seq
    return 6.0 * gpt_matmul_params(dims) + attn


def gpt_decode_step_bytes(dims: dict, kv_tokens: float,
                          weight_itemsize: int = 2,
                          kv_itemsize: int = 2) -> float:
    """Bytes one decode step has to read from HBM, whole model (divide
    by the chips it is sharded over): every matmul weight once (the
    embedding table is gathered, a few rows, and is left out) plus K and
    V of every cached token of every decoding slot. Activations, the
    page tables and the written K/V row are left out (small), so the
    count errs low and a roofline share from it errs low too."""
    kv = kv_tokens * dims["n_layers"] * 2 * dims["d_model"] * kv_itemsize
    return gpt_matmul_params(dims) * weight_itemsize + kv
