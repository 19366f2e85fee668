"""gdn_proj_ms: milliseconds of one decode step spent in `gdn.in` (norm,
both input projections, the convolution with its tail, SiLU, L2 norms,
beta and g) and `gdn.out` (gated norm, output projection, residual) of
the linear layers, chip 0 (harness/scope_times.py): what the recurrent
layers cost outside their state.
"""

from harness import scope_times


def read(ctx):
    if "gdn.in" not in scope_times.vocabulary():
        return None
    return scope_times.ms_a_run(ctx, scope_times.DECODE,
                                ("gdn.in", "gdn.out"))
