"""decode_attn_roofline.longform: the full layers' decode attention call
at head size 256 (`paged_decode_attn`) against the HBM roofline, a
decode step (harness/kernel_roofline.py).

Bytes: K and V of every cached token of the decoding slots in the two
full layers (`decode_bytes_per_kv_token`, families/qwen3_next.py, x
`kv_tokens_decoding`, sampled inside the traced interval). A slot's last
page is fetched whole, so the share errs low.
"""

from harness.kernel_roofline import decode_kernel_share, traced_mean


def read(ctx):
    per = (ctx.get("consts") or {}).get("decode_bytes_per_kv_token")
    tokens = traced_mean(ctx, "kv_tokens_decoding")
    return decode_kernel_share(ctx, r"paged_decode_attn",
                               per and tokens and per * tokens)
