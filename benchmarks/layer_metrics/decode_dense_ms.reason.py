"""decode_dense_ms.reason: milliseconds of one decode step spent in `attn.in`
(norm, the q/k/v projections, both convolutions, the L2 norm, rotary) and
`attn.out` (the output projection and the scaled residual), chip 0
(harness/scope_times.py).
"""

from harness import scope_times


def read(ctx):
    return scope_times.ms_a_run(ctx, scope_times.DECODE,
                                ("attn.in", "attn.out"))
