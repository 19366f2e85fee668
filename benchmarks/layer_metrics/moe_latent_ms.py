"""moe_latent_ms: milliseconds of one decode step spent in `moe.latent`
(the projection of every row into the routed experts' latent before the
grouped matmuls and of their gated sum out of it after, every expert
layer), chip 0 (harness/scope_times.py): what a latent narrower than the
model costs beside what it saves the experts' stream. A program without
the scope (no family with latent experts) reads nothing.
"""

from harness import scope_times


def read(ctx):
    if "moe.latent" not in scope_times.vocabulary():
        return None
    return scope_times.ms_a_run(ctx, scope_times.DECODE, ("moe.latent",))
