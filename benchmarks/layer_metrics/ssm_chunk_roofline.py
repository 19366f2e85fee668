"""ssm_chunk_roofline: a chunk program's selective scan against the HBM
roofline: its bytes over the HBM peak, over `ssm.scan`'s milliseconds a
chunk program (by scope: harness/scope_times.py).

The bytes are a prompt token's (`chunk_scan_bytes_per_token`, the
family's `serve_consts`: xs and dt in and y out at float32 a channel, B
and C, and a row's state read and written, spread over its tokens) times
the tokens a dispatch carried, `prefill_tokens` over `prefill_dispatches`
by the engine's own counters. The recurrence has no matmul form, so
there is no operations term: its `exp`, multiplies and adds run on the
vector units, and a share well under 100 % says those, not the bytes,
bound it.
"""

from harness import scope_times


def read(ctx):
    engine, c = ctx.get("engine") or {}, ctx.get("consts") or {}
    per = c.get("chunk_scan_bytes_per_token")
    peak = (ctx.get("peaks") or {}).get("hbm_bytes_per_s")
    tokens, dispatches = (engine.get("prefill_tokens"),
                          engine.get("prefill_dispatches"))
    if (not all((per, peak, tokens, dispatches))
            or "ssm.scan" not in scope_times.vocabulary()):
        return None
    ms = scope_times.ms_a_run(ctx, scope_times.CHUNK, ("ssm.scan",))
    if not ms:
        return None
    return tokens / dispatches * per / peak / (ms / 1e3) * 100.0
