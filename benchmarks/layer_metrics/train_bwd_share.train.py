"""train_bwd_share.train: device time of the train step's backward pass
(operations whose path a `transpose(` wraps, the recompute left out) over
chip 0's busy time, in percent (harness/scope_times.py).
"""

from harness import scope_times


def read(ctx):
    return scope_times.share_of_busy(ctx, r"", ("bwd",), by="by_pass")
