"""head_ms.longform: milliseconds of one decode step spent in `head` (final
norm, the untied head's matmul over this chip's 37,984 rows) and
`sample` (the argmax over them), chip 0 (harness/scope_times.py).
"""

from harness import scope_times


def read(ctx):
    return scope_times.ms_a_run(ctx, scope_times.DECODE, ("head", "sample"))
