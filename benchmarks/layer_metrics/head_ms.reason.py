"""head_ms.reason: milliseconds of one decode step spent in `head` (final
norm, the tied head's matmul over 262k rows) and `sample` (the argmax
over them), chip 0 (harness/scope_times.py).
"""

from harness import scope_times


def read(ctx):
    return scope_times.ms_a_run(ctx, scope_times.DECODE, ("head", "sample"))
