"""ssm_step_roofline: the decode step's update of the state-space state
against the HBM roofline.

    decoding slots x `ssm_step_bytes_per_slot` / the HBM peak
    -----------------------------------------------------------  x 100
    `ssm.scan`'s milliseconds a decode step

Bytes: every decoding slot's state in the selective-scan layers, read
AND written (the family's `serve_consts`), the slots sampled inside the
traced interval: what `ssm.scan` moves. (The convolution tail, 8 % more
a slot, is read and written in `ssm.in` and is in
`decode_bytes_per_state_slot`, the whole step's count, not in this one:
counted here it would put bytes over a time that leaves their work out.)
Time: by SCOPE (harness/scope_times.py), so it reads the same work
whatever implements it. An idle slot moves nothing and counts nowhere.
"""

from harness import scope_times
from harness.kernel_roofline import traced_mean


def read(ctx):
    per = (ctx.get("consts") or {}).get("ssm_step_bytes_per_slot")
    peak = (ctx.get("peaks") or {}).get("hbm_bytes_per_s")
    slots = traced_mean(ctx, "decoding_slots")
    if (not per or not peak or not slots
            or "ssm.scan" not in scope_times.vocabulary()):
        return None
    ms = scope_times.ms_a_run(ctx, scope_times.DECODE, ("ssm.scan",))
    return slots * per / peak / (ms / 1e3) * 100.0 if ms else None
