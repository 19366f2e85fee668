"""moe_route_ms.longform: milliseconds of one decode step spent in
`moe.route` (the softmax router over 512 experts, top-10, the sort into
expert groups, the grouped matmul's group sizes, the unsort and the
gated combine, 8 layers), chip 0 (harness/scope_times.py).
"""

from harness import scope_times


def read(ctx):
    return scope_times.ms_a_run(ctx, scope_times.DECODE, ("moe.route",))
