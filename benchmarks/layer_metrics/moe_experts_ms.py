"""moe_experts_ms: milliseconds of one decode step spent in `moe.experts`
(the experts' grouped matmuls, every expert layer: the compiler's
`ragged-dot-none`, which `scope_times.COMPILER_MADE` hands to this scope,
or the repo's `moe_grouped_matmul` under the scope's own path), chip 0
(harness/scope_times.py). The layer's time whichever kernel runs it:
what a kernel of the repo's own (ROADMAP S12) has to bring down.
"""

from harness import scope_times


def read(ctx):
    return scope_times.ms_a_run(ctx, scope_times.DECODE, ("moe.experts",))
