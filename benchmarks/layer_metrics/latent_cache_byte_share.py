"""latent_cache_byte_share: of the bytes a decode step must read, the
share that is the latent cache: `decode_bytes_per_kv_token` x the cached
tokens of the decoding slots (`kv_tokens_decoding`, its mean over the
window) over that plus the weights a step reads whatever the routing
(`decode_bytes_weights`) and the held experts that had a row
(`decode_bytes_per_live_expert` x `experts_touched`, read before this
metric): the family's `serve_consts`. Read only where the family states
the latent read's operations (`latent_flops_per_kv_token`): elsewhere
the K/V share of a step's bytes is `decode_stream_roofline`'s to split.
"""


def read(ctx):
    c, m = ctx.get("consts") or {}, ctx.get("metrics") or {}
    tokens = (ctx.get("samples") or {}).get("kv_tokens_decoding")
    per, weights = (c.get("decode_bytes_per_kv_token"),
                    c.get("decode_bytes_weights"))
    if not (c.get("latent_flops_per_kv_token") and per and weights
            and tokens):
        return None
    cache = per * sum(tokens) / len(tokens)
    experts = ((c.get("decode_bytes_per_live_expert") or 0.0)
               * (m.get("experts_touched") or 0.0))
    return cache / (cache + weights + experts) * 100.0
