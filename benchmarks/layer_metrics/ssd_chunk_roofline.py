"""ssd_chunk_roofline: a chunk program's Mamba-2 scan against the chip's
roofline: the LARGER of its bytes over the HBM peak and its matmul
operations over the bf16 peak, over `ssm.scan`'s milliseconds a chunk
program (by scope: harness/scope_times.py), so it reads the same work
whatever implements it.

Both counts are a token's (`chunk_scan_bytes_per_token`,
`chunk_scan_flops_per_token`: the family's `serve_consts`) times the
tokens a dispatch carried, `prefill_tokens` over `prefill_dispatches` by
the engine's own counters. Unlike the selective scan
(`ssm_chunk_roofline`, which has no operations term), Mamba-2's
recurrence HAS a matmul form over a block of tokens; a family that
states no `chunk_scan_flops_per_token` reads nothing here. The
operations are counted once though the scan multiplies float32 operands
at the highest precision (several passes of the bf16 unit), so the share
errs low.
"""

from harness import scope_times


def read(ctx):
    engine, c = ctx.get("engine") or {}, ctx.get("consts") or {}
    p = ctx.get("peaks") or {}
    tokens, dispatches = (engine.get("prefill_tokens"),
                          engine.get("prefill_dispatches"))
    need = (c.get("chunk_scan_bytes_per_token"),
            c.get("chunk_scan_flops_per_token"), p.get("hbm_bytes_per_s"),
            p.get("flops_bf16"), tokens, dispatches)
    if not all(need) or "ssm.scan" not in scope_times.vocabulary():
        return None
    ms = scope_times.ms_a_run(ctx, scope_times.CHUNK, ("ssm.scan",))
    if not ms:
        return None
    a_dispatch = tokens / dispatches
    least_s = max(a_dispatch * need[0] / need[2], a_dispatch * need[1] / need[3])
    return least_s / (ms / 1e3) * 100.0
