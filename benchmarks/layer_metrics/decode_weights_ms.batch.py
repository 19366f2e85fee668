"""decode_weights_ms.batch: milliseconds of one decode step spent in the
scopes that stream the step's weights (`attn.in`: norm, q/k/v and rotary;
`attn.out`; `mlp`; `head`), chip 0, from the trace's op metadata
(harness/scope_times.py). PERF.md section 5's "the decode step's weight
stream": what batching more slots a step, or int8 weights, would move.
"""

from harness import scope_times


def read(ctx):
    return scope_times.ms_a_run(ctx, scope_times.DECODE,
                                ("attn.in", "attn.out", "mlp", "head"))
