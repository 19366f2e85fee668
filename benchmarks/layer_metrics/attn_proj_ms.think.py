"""attn_proj_ms.think: milliseconds of one decode step spent in `attn.in`
(norm, W_q to 64 heads of 192, W_k and W_v to 4 or 8 KV heads of 192 and
128, rope on 64 dims, the value scale) and `attn.out` (W_o from 64 heads
of 128, residual) of the 7 layers, chip 0 (harness/scope_times.py): what
attention costs a step outside its two kernels.
"""

from harness import scope_times


def read(ctx):
    return scope_times.ms_a_run(ctx, scope_times.DECODE,
                                ("attn.in", "attn.out"))
