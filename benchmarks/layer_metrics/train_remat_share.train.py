"""train_remat_share.train: device time of the forward pass recomputed
inside the backward pass (`jax.checkpoint`: operations under
`rematted_computation`) over chip 0's busy time, in percent
(harness/scope_times.py): what remat costs, and what a step that saved
its activations would not pay.
"""

from harness import scope_times


def read(ctx):
    return scope_times.share_of_busy(ctx, r"", ("remat",), by="by_pass")
