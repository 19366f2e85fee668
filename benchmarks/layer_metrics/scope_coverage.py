"""scope_coverage: the share of chip 0's busy time, in percent, whose
operations carry a scope of the programs' vocabulary
(ray_tpu/ops/scopes.py) in the trace's op metadata: how much of the
device's time the other scope metrics can see (harness/scope_times.py).
The rest is operations outside any scope (a scan's own slicing and
stacking, copies and prefetches the compiler adds) and programs that
name no part.
"""

from harness import scope_times


def read(ctx):
    return scope_times.coverage(ctx)
