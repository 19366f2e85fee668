"""slot_state_ms: milliseconds of one decode step spent in `slot_state`
(a per-slot state's read and write-back outside the scopes of the layer
that owns it), chip 0 (harness/scope_times.py).
"""

from harness import scope_times


def read(ctx):
    return scope_times.ms_a_run(ctx, scope_times.DECODE, ("slot_state",))
