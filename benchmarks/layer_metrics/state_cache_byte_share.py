"""state_cache_byte_share: of the bytes a decode step must move, the
share that is the recurrent state by the slot, READ AND WRITTEN:
`decode_bytes_per_state_slot` x the decoding slots, over that plus the
weights a step reads whatever the traffic (`decode_bytes_weights`), K
and V of every cached token of the decoding slots in the layers that
page (`decode_bytes_per_kv_token` x `kv_tokens_decoding`), the held
experts that had a row (`decode_bytes_per_live_expert` x
`experts_touched`) and a window a decoding slot
(`decode_bytes_per_window_slot`): `decode_stream_mfu`'s own sum, the
family's `serve_consts`, the slots and tokens sampled inside the traced
interval. A term a family states as 0.0 or does not state counts 0; a
family that keeps no state by the slot reads nothing.
`kv_cache_byte_share` is the pages' share of the same sum.
"""

from harness.kernel_roofline import traced_mean


def read(ctx):
    c, m = ctx.get("consts") or {}, ctx.get("metrics") or {}
    weights, per = (c.get("decode_bytes_weights"),
                    c.get("decode_bytes_per_state_slot"))
    slots = traced_mean(ctx, "decoding_slots")
    if not weights or not per or not slots:
        return None
    term = lambda const, times: (c.get(const) or 0.0) * (times or 0.0)
    state = per * slots
    rest = (weights
            + term("decode_bytes_per_kv_token",
                   traced_mean(ctx, "kv_tokens_decoding"))
            + term("decode_bytes_per_live_expert", m.get("experts_touched"))
            + term("decode_bytes_per_window_slot", slots))
    return state / (state + rest) * 100.0
