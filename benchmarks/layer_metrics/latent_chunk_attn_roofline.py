"""latent_chunk_attn_roofline: a chunk program's read of the latent cache
(its `attn.kernel` scope) against the larger of its two limits, as
`latent_attn_roofline` takes them, from the rows and keys it attends.

    max(keys read x bytes a row, (row, key) pairs x operations a pair)
    over the HBM and bf16 peaks, over `attn.kernel`'s ms a chunk program

The engine's counters give the mean dispatch: `prefill_tokens` over
`prefill_dispatches` prompt tokens a program. The cell's prompts start
at position 0 and one dispatch carries a whole prompt (512 tokens in
four chunk rows), so a dispatch of T tokens attends T (T + 1) / 2 (row,
key) pairs, each `latent_flops_per_kv_token` operations (every head's
score over the row and value sum, all layers), and must read each of its
T rows once (`decode_bytes_per_kv_token`). The kernel reads a row once a
group of heads and a chunk row, so the bytes err low; at these shapes
the operations are the larger limit by two orders.
"""

from harness import scope_times


def read(ctx):
    engine, c = ctx.get("engine") or {}, ctx.get("consts") or {}
    p = ctx.get("peaks") or {}
    tokens, dispatches = (engine.get("prefill_tokens"),
                          engine.get("prefill_dispatches"))
    nbytes, flops = (c.get("decode_bytes_per_kv_token"),
                     c.get("latent_flops_per_kv_token"))
    if (not all((tokens, dispatches, nbytes, flops,
                 p.get("hbm_bytes_per_s"), p.get("flops_bf16")))
            or "attn.absorb" not in scope_times.vocabulary()):
        return None
    ms = scope_times.ms_a_run(ctx, scope_times.CHUNK, ("attn.kernel",))
    if not ms:
        return None
    t = tokens / dispatches
    least = max(t * nbytes / p["hbm_bytes_per_s"],
                t * (t + 1) / 2 * flops / p["flops_bf16"])
    return least / (ms / 1e3) * 100.0
