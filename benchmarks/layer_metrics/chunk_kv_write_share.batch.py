"""chunk_kv_write_share.batch: device time of the chunk program's
`attn.kv_write` scope (`_write_rows`: the chunk's K and V rows scattered
into their pages, and the write targets' table ops) over chip 0's busy
time, in percent (harness/scope_times.py). What `pool_move_share.batch`
was meant to read: that one matches op NAMES and counts the decode step's
weight slices too.
"""

from harness import scope_times


def read(ctx):
    return scope_times.share_of_busy(ctx, scope_times.CHUNK,
                                     ("attn.kv_write",))
