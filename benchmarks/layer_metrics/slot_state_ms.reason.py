"""slot_state_ms.reason: milliseconds of one decode step spent in
`slot_state` (the per-slot conv/shift state's read and `_write_state`,
24 layers), chip 0 (harness/scope_times.py).
"""

from harness import scope_times


def read(ctx):
    return scope_times.ms_a_run(ctx, scope_times.DECODE, ("slot_state",))
