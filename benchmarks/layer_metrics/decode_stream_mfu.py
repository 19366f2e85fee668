"""decode_stream_mfu: the WHOLE decode step against its roofline: the
larger of the bytes a step must move over the HBM peak and the matmul
operations it must do over the bf16 peak, over the step program's device
time (`decode_program_dev_ms`).

    max(bytes / HBM peak, `decode_flops_per_row` x decoding slots / bf16 peak)
    ---------------------------------------------------------------------------  x 100
    `decode_program_dev_ms`

Bytes: the weights a step reads whatever the traffic
(`decode_bytes_weights`), the held experts that had a row
(`decode_bytes_per_live_expert` x `experts_touched`), K and V of every
cached token of the decoding slots in the layers that page
(`decode_bytes_per_kv_token`), a window a decoding slot
(`decode_bytes_per_window_slot`) and every decoding slot's state by the
slot, read and written (`decode_bytes_per_state_slot`): the family's
`serve_consts`, the slots and tokens sampled inside the traced interval.
A term a family states as 0.0 or does not state counts 0 (a dense family
reports no `experts_touched` and states no expert bytes), so any served
family can be read by it; only the weights' term is required.
"""

from harness.kernel_roofline import traced_mean


def read(ctx):
    c, m = ctx.get("consts") or {}, ctx.get("metrics") or {}
    p = ctx.get("peaks") or {}
    weights, ms = c.get("decode_bytes_weights"), m.get("decode_program_dev_ms")
    slots = traced_mean(ctx, "decoding_slots")
    if not weights or not ms or not slots or not p.get("hbm_bytes_per_s"):
        return None
    term = lambda const, times: (c.get(const) or 0.0) * (times or 0.0)
    nbytes = (weights
              + term("decode_bytes_per_live_expert", m.get("experts_touched"))
              + term("decode_bytes_per_kv_token",
                     traced_mean(ctx, "kv_tokens_decoding"))
              + term("decode_bytes_per_window_slot", slots)
              + term("decode_bytes_per_state_slot", slots))
    chips = c.get("chips") or 1
    least_s = nbytes / chips / p["hbm_bytes_per_s"]
    if p.get("flops_bf16"):
        least_s = max(least_s, term("decode_flops_per_row", slots) / chips
                      / p["flops_bf16"])
    return least_s / (ms / 1e3) * 100.0
