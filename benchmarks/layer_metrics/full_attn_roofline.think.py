"""full_attn_roofline.think: the full layers' decode attention call
(`paged_decode_attn`, and NOT its `_window` twin: K pages 4 x 192 lanes
beside V pages 4 x 128) against the HBM roofline, a decode step
(harness/kernel_roofline.py).

Bytes: K and V of every cached token of the decoding slots in the full
layers (`decode_bytes_per_kv_token`, families/mimo_v2.py: 192 + 128 a KV
head, the published sizes, x `kv_tokens_decoding`, sampled inside the
traced interval). A slot's last page is fetched whole, so the share errs
low.
"""

from harness.kernel_roofline import decode_kernel_share, traced_mean


def read(ctx):
    per = (ctx.get("consts") or {}).get("decode_bytes_per_kv_token")
    tokens = traced_mean(ctx, "kv_tokens_decoding")
    return decode_kernel_share(ctx, r"paged_decode_attn(?!_window)",
                               per and tokens and per * tokens)
