"""decode_dense_ms: milliseconds of one decode step spent in `attn.in`
(norm, the q/k/v projections and what a family does to them before the
kernel) and `attn.out` (the output projection and the residual) of the
attention layers, chip 0 (harness/scope_times.py): what attention costs
a step outside its kernels.
"""

from harness import scope_times


def read(ctx):
    return scope_times.ms_a_run(ctx, scope_times.DECODE,
                                ("attn.in", "attn.out"))
