"""A kernel's share of the HBM roofline inside the decode-step programs
of a traced interval: what a `<kernel>_roofline` reader file computes,
kept once.

    bytes the kernel must read in one step / the HBM peak
    ---------------------------------------------------------  x 100
    the kernel's self time a step

Time: the self time of the instructions whose `trace_reduce.op_key`
matches the kernel's regex inside `decode_(sample|step)_paged`, over the
number of those programs' executions (chip 0). Nothing to read (no
trace, no such instruction, no byte count) gives None: the metric is
left out of the line, as over a program that lacks the kernel.
"""

from __future__ import annotations

import re

from . import trace_reduce

DECODE_PROGRAM = re.compile(r"decode_(sample|step)_paged")


def traced_mean(ctx: dict, key: str):
    """Mean of the sampled `key` over the TRACED part of the window (what
    a quantity set against device time has to use), or None."""
    samples, t0 = ctx.get("samples") or {}, ctx.get("trace_t0")
    if t0 is None or not samples.get("t") or not samples.get(key):
        return None
    kept = [v for t, v in zip(samples["t"], samples[key]) if t >= t0]
    return sum(kept) / len(kept) if kept else None


def decode_kernel_share(ctx: dict, kernel_re: str, step_bytes):
    """`step_bytes`: the bytes the kernel must read in one decode step."""
    red = ctx.get("trace") or {}
    peak = (ctx.get("peaks") or {}).get("hbm_bytes_per_s")
    if not red.get("ops") or not step_bytes or not peak:
        return None
    kernel = re.compile(kernel_re)
    kernel_s = sum(t for p, o, t in red["ops"] if DECODE_PROGRAM.search(p)
                   and kernel.search(trace_reduce.op_key(o)))
    steps = sum(p["count"] for name, p in (red.get("programs") or {}).items()
                if DECODE_PROGRAM.search(name))
    if not kernel_s or not steps:
        return None
    return step_bytes / peak / (kernel_s / steps) * 100.0
