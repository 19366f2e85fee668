"""window_attn_roofline.think: the window layers' decode attention call
(`paged_decode_attn_window`: K pages 8 x 192 lanes beside V pages
8 x 128, a sink a query head) against the HBM roofline, a decode step
(harness/kernel_roofline.py).

Bytes: K and V of one window (128 keys) a decoding slot and window layer
(`decode_bytes_per_window_slot`, families/mimo_v2.py) x the decoding
slots, sampled inside the traced interval. Every context of the cell's
traffic is over 128, so a decoding slot's window is full. A window that
starts inside a page fetches three pages (192 keys) for two pages' worth
of keys, so the share errs low.
"""

from harness.kernel_roofline import decode_kernel_share, traced_mean


def read(ctx):
    per = (ctx.get("consts") or {}).get("decode_bytes_per_window_slot")
    slots = traced_mean(ctx, "decoding_slots")
    return decode_kernel_share(ctx, r"paged_decode_attn_window",
                               per and slots and per * slots)
