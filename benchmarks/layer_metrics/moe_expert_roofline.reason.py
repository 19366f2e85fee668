"""moe_expert_roofline.reason: the experts' grouped matmul against the
HBM roofline, inside the decode-step programs of the traced interval.

Bytes it must read in one step: the experts that had a row (mean a layer,
the program's own counter `experts_touched.reason`, read before this
metric) x one expert's three matrices x the layers
(`decode_bytes_per_live_expert`, families/zaya.py). Time: the self time
of the `ragged-dot` kernel instructions inside `decode_(sample|step)_paged`
over the number of those programs' executions (chip 0). The layer streams
every expert it holds and the count takes only those a token reached, so
the share errs low; activations are left out.
"""

import re

from harness import trace_reduce

PROGRAM = re.compile(r"decode_(sample|step)_paged")
KERNEL = re.compile(r"ragged-dot(?!-metadata)")


def read(ctx):
    red = ctx.get("trace") or {}
    touched = (ctx.get("metrics") or {}).get("experts_touched.reason")
    per_expert = (ctx.get("consts") or {}).get("decode_bytes_per_live_expert")
    peak = (ctx.get("peaks") or {}).get("hbm_bytes_per_s")
    if not red.get("ops") or not touched or not per_expert or not peak:
        return None
    kernel_s = sum(t for p, o, t in red["ops"] if PROGRAM.search(p)
                   and KERNEL.search(trace_reduce.op_key(o)))
    steps = sum(p["count"] for name, p in (red.get("programs") or {}).items()
                if PROGRAM.search(name))
    if not kernel_s or not steps:
        return None
    return touched * per_expert / peak / (kernel_s / steps) * 100.0
