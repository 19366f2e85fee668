"""latent_absorb_ms: milliseconds of one decode step spent in
`attn.absorb`, chip 0 (harness/scope_times.py): what the absorbed form
costs beside the cache read it saves: every head's q_nope through W_UK
into the latent's space before the call, and its attended latent through
W_UV to the head's value after it (16.8 MB of weights a layer).
"""

from harness import scope_times


def read(ctx):
    if "attn.absorb" not in scope_times.vocabulary():
        return None
    return scope_times.ms_a_run(ctx, scope_times.DECODE, ("attn.absorb",))
