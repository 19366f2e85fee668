"""ssm_proj_ms: milliseconds of one decode step spent in `ssm.in` (norm,
the input projection, the convolution with its tail, SiLU, W_x, the
three inner norms, W_dt, softplus) and `ssm.out` (gate, output
projection, residual) of the selective-scan layers, chip 0
(harness/scope_times.py): what those layers cost outside their state.
"""

from harness import scope_times


def read(ctx):
    if "ssm.in" not in scope_times.vocabulary():
        return None
    return scope_times.ms_a_run(ctx, scope_times.DECODE,
                                ("ssm.in", "ssm.out"))
