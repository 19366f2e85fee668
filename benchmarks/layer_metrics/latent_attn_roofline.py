"""latent_attn_roofline: a decode step's read of the latent cache (the
`attn.kernel` scope of the decode programs: the paged call in its latent
form, whatever implements it) against the LARGER of its two limits.

    max(cached tokens x `decode_bytes_per_kv_token` / the HBM peak,
        cached tokens x `latent_flops_per_kv_token` / the bf16 peak)
    ----------------------------------------------------------------  x 100
    `attn.kernel`'s milliseconds a decode step

A cached token is ONE row of 576 values a layer (1,152 B) under 64 query
heads that each contract all of it and sum its first 512: 139,264
operations a layer, 121 a byte against the chip's 240, so the bytes bind
only while the MXU runs above half its peak at 64 query rows a slot. The
tokens are those of the decoding slots, sampled inside the traced
interval; a slot's last page is fetched whole and the chip stores a row
in 640 lanes, so the share errs low. Time by SCOPE
(harness/scope_times.py): the table ops before the call are in it.
"""

from harness import scope_times
from harness.kernel_roofline import traced_mean


def limit_s(ctx, tokens):
    """Seconds the read of `tokens` cached tokens must take, or None."""
    c, p = ctx.get("consts") or {}, ctx.get("peaks") or {}
    nbytes, flops = (c.get("decode_bytes_per_kv_token"),
                     c.get("latent_flops_per_kv_token"))
    if not all((tokens, nbytes, flops, p.get("hbm_bytes_per_s"),
                p.get("flops_bf16"))):
        return None
    return tokens * max(nbytes / p["hbm_bytes_per_s"],
                        flops / p["flops_bf16"])


def read(ctx):
    least = limit_s(ctx, traced_mean(ctx, "kv_tokens_decoding"))
    if not least or "attn.absorb" not in scope_times.vocabulary():
        return None
    ms = scope_times.ms_a_run(ctx, scope_times.DECODE, ("attn.kernel",))
    return least / (ms / 1e3) * 100.0 if ms else None
