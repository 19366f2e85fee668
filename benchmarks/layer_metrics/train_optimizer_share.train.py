"""train_optimizer_share.train: device time of the `optimizer` scope
(`make_train_step`: the optimizer's update over fp32 masters and its
apply) over chip 0's busy time, in percent (harness/scope_times.py).
"""

from harness import scope_times


def read(ctx):
    return scope_times.share_of_busy(ctx, r"", ("optimizer",))
