"""moe_route_ms: milliseconds of one decode step spent in `moe.route`
(the router, top-k, the sort into expert groups, the grouped matmul's
group sizes, the unsort and the gated combine, every expert layer), chip
0 (harness/scope_times.py). What skipping experts no row reached
(ROADMAP S12 (c)) must not grow.
"""

from harness import scope_times


def read(ctx):
    return scope_times.ms_a_run(ctx, scope_times.DECODE, ("moe.route",))
