"""decode_attn_roofline: the decode attention call that reads every
cached token (`paged_decode_attn`, and NOT its `_window` twin, which is
`window_attn_roofline`'s) against the HBM roofline, a decode step
(harness/kernel_roofline.py).

Bytes: K and V of every cached token of the decoding slots in the layers
that read them all (`decode_bytes_per_kv_token`, the family's
`serve_consts`, x `kv_tokens_decoding`, sampled inside the traced
interval). A slot's last page is fetched whole, so the share errs low.
"""

from harness.kernel_roofline import decode_kernel_share, traced_mean


def read(ctx):
    per = (ctx.get("consts") or {}).get("decode_bytes_per_kv_token")
    tokens = traced_mean(ctx, "kv_tokens_decoding")
    return decode_kernel_share(ctx, r"paged_decode_attn(?!_window)",
                               per and tokens and per * tokens)
