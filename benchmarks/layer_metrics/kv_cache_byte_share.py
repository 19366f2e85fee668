"""kv_cache_byte_share: of the bytes a decode step must move, the share
that is the pages: K and V of every cached token of the decoding slots
in the layers that page (`decode_bytes_per_kv_token` x
`kv_tokens_decoding`), over that plus the weights a step reads whatever
the traffic (`decode_bytes_weights`), every decoding slot's state by the
slot, read and written (`decode_bytes_per_state_slot`), the held experts
that had a row (`decode_bytes_per_live_expert` x `experts_touched`) and
a window a decoding slot (`decode_bytes_per_window_slot`):
`decode_stream_mfu`'s own sum, the family's `serve_consts`, the slots
and tokens sampled inside the traced interval. A term a family states as
0.0 or does not state counts 0. `state_cache_byte_share` is the
recurrent state's share of the same sum; a latent cache's is
`latent_cache_byte_share`'s.
"""

from harness.kernel_roofline import traced_mean


def read(ctx):
    c, m = ctx.get("consts") or {}, ctx.get("metrics") or {}
    weights, per = (c.get("decode_bytes_weights"),
                    c.get("decode_bytes_per_kv_token"))
    tokens = traced_mean(ctx, "kv_tokens_decoding")
    slots = traced_mean(ctx, "decoding_slots")
    if not weights or not per or not tokens or not slots:
        return None
    term = lambda const, times: (c.get(const) or 0.0) * (times or 0.0)
    pages = per * tokens
    rest = (weights
            + term("decode_bytes_per_state_slot", slots)
            + term("decode_bytes_per_live_expert", m.get("experts_touched"))
            + term("decode_bytes_per_window_slot", slots))
    return pages / (pages + rest) * 100.0
