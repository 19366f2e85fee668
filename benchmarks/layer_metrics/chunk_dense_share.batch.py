"""chunk_dense_share.batch: device time of the chunk program's dense parts
(`attn.in`, `attn.out`, `mlp`, `head`: everything that is a pass over the
weights for the chunk's rows) over chip 0's busy time, in percent
(harness/scope_times.py). PERF.md section 5's "the chunk program outside
its kernel": what one chunk program a prompt (ROADMAP S13) would move.
"""

from harness import scope_times


def read(ctx):
    return scope_times.share_of_busy(ctx, scope_times.CHUNK,
                                     ("attn.in", "attn.out", "mlp", "head"))
