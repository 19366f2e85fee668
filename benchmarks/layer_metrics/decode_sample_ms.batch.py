"""decode_sample_ms.batch: milliseconds of one decode step spent in the
`sample` scope (`_sample_next`: the argmax and the categorical draw over
the step's logits), chip 0 (harness/scope_times.py).
"""

from harness import scope_times


def read(ctx):
    return scope_times.ms_a_run(ctx, scope_times.DECODE, ("sample",))
