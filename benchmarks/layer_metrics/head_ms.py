"""head_ms: milliseconds of one decode step spent in `head` (final norm,
the head's matmul over the vocabulary rows this chip holds) and `sample`
(the argmax over them), chip 0 (harness/scope_times.py).
"""

from harness import scope_times


def read(ctx):
    return scope_times.ms_a_run(ctx, scope_times.DECODE, ("head", "sample"))
