"""window_attn_roofline: the window layers' decode attention call
(`paged_decode_attn_window`) against the HBM roofline, a decode step
(harness/kernel_roofline.py).

Bytes: K and V of one window a decoding slot and window layer
(`decode_bytes_per_window_slot`, the family's `serve_consts`) x the
decoding slots, sampled inside the traced interval. Every context of
the cells' traffic is longer than the window, so a decoding slot's
window is full. A window that starts inside a page fetches one page
more than its keys fill, so the share errs low.
"""

from harness.kernel_roofline import decode_kernel_share, traced_mean


def read(ctx):
    per = (ctx.get("consts") or {}).get("decode_bytes_per_window_slot")
    slots = traced_mean(ctx, "decoding_slots")
    return decode_kernel_share(ctx, r"paged_decode_attn_window",
                               per and slots and per * slots)
