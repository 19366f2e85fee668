"""ssm_scan_share: percent of chip 0's busy time spent in `ssm.scan`,
the state-space state's only reader and writer (the decode step's update
of every live slot's state, and a chunk's scan with its write-back), in
the decode and the chunk programs together (harness/scope_times.py). A
program whose scopes lack the name reads nothing.
"""

from harness import scope_times


def read(ctx):
    if "ssm.scan" not in scope_times.vocabulary():
        return None
    return scope_times.share_of_busy(ctx, r"", ("ssm.scan",))
