"""moe_expert_roofline: the experts' grouped matmul (`ragged-dot`, not
its metadata kernel) against the HBM roofline, a decode step
(harness/kernel_roofline.py).

Bytes: the held experts that had a row (mean an expert layer, the
program's own counter `experts_touched`, read before this metric) x one
expert's three matrices x the expert layers
(`decode_bytes_per_live_expert`, the family's `serve_consts`). The layer
streams every expert it holds and the count takes only those a token
reached, so the share errs low; activations are left out.
"""

from harness.kernel_roofline import decode_kernel_share


def read(ctx):
    touched = (ctx.get("metrics") or {}).get("experts_touched")
    per = (ctx.get("consts") or {}).get("decode_bytes_per_live_expert")
    return decode_kernel_share(ctx, r"ragged-dot(?!-metadata)",
                               touched and per and touched * per)
