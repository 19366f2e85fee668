"""ssm_share: percent of chip 0's busy time spent in the selective-scan
layers' three scopes, `ssm.in` + `ssm.scan` + `ssm.out`, in the decode
and the chunk programs together (harness/scope_times.py). What the
state-space layers cost beside the MLPs, the attention and the head. A
program whose scopes lack the names reads nothing.
"""

from harness import scope_times

SCOPES = ("ssm.in", "ssm.scan", "ssm.out")


def read(ctx):
    if not set(SCOPES) <= set(scope_times.vocabulary()):
        return None
    return scope_times.share_of_busy(ctx, r"", SCOPES)
