"""gdn_share: percent of chip 0's busy time spent in the Gated
DeltaNet layers' three scopes, `gdn.in` + `gdn.scan` + `gdn.out`, in the
decode and the chunk programs together (harness/scope_times.py). What
the recurrent layers cost beside the experts and the attention. A
program whose scopes lack the names reads nothing.
"""

from harness import scope_times

SCOPES = ("gdn.in", "gdn.scan", "gdn.out")


def read(ctx):
    if not set(SCOPES) <= set(scope_times.vocabulary()):
        return None
    return scope_times.share_of_busy(ctx, r"", SCOPES)
