"""The names the device programs give their parts.

Every family's decode, chunk and full-sequence program, and the train
step, wrap each part of their work in `with jax.named_scope(NAME):` with
one of the names below. A named scope is location metadata only: it adds
no operation, and the compile-cache key strips it. The TPU trace carries
it on every op (`tf_op`), which is what
`benchmarks/harness/scope_times.py` splits device time by. The scopes
are flat: none opens inside another.
"""

EMBED = "embed"                  # token embedding lookup
ATTN_IN = "attn.in"              # pre-norm, q/k/v (and gate) projections,
                                 # rope; zaya's convolutions and L2 norm
ATTN_ABSORB = "attn.absorb"      # latent attention's absorbed form: the
                                 # query into the latent's space before
                                 # the call, the attended latent to the
                                 # heads' values after it
ATTN_KV_WRITE = "attn.kv_write"  # K/V rows into the pool's pages or ring
ATTN_KERNEL = "attn.kernel"      # the paged / flash call, or XLA's dense
                                 # attention; the table ops before it
ATTN_OUT = "attn.out"            # output (gate and) projection, residual
MLP = "mlp"                      # dense MLP with its norm; a shared expert
MOE_ROUTE = "moe.route"          # router, top-k, sort, group sizes,
                                 # unsort and combine
MOE_EXPERTS = "moe.experts"      # the grouped matmuls
MOE_LATENT = "moe.latent"        # latent experts: the shared projection
                                 # into the experts' latent before the
                                 # grouped matmuls and out of it after
SLOT_STATE = "slot_state"        # a per-slot state's read and write; the
                                 # chain of a dispatch's rows and the
                                 # write-back's targets
GDN_IN = "gdn.in"                # a gated delta layer: norm, both input
                                 # projections, the convolution with its
                                 # tail, SiLU, L2 norms, beta and g
GDN_SCAN = "gdn.scan"            # the recurrent state's only reader and
                                 # writer: the decode step's update, a
                                 # chunk's scan and its write-back
GDN_OUT = "gdn.out"              # gated norm, output projection, residual
SSM_IN = "ssm.in"                # a state-space layer: norm, input
                                 # projection, the convolution with its
                                 # tail, SiLU; Mamba-1's W_x, three inner
                                 # norms and W_dt; softplus
SSM_SCAN = "ssm.scan"            # the state-space state's only reader and
                                 # writer: the decode step's update, a
                                 # chunk's scan and its write-back
SSM_OUT = "ssm.out"              # gate (Mamba-2: skip and grouped norm),
                                 # output projection, residual
HEAD = "head"                    # final norm, head matmul, row selection
SAMPLE = "sample"                # argmax / categorical over the logits
COUNTERS = "counters"            # on-device counters the host pulls
LOSS = "loss"                    # cross-entropy over the logits
OPTIMIZER = "optimizer"          # the optimizer's update and its apply

ALL = (EMBED, ATTN_IN, ATTN_ABSORB, ATTN_KV_WRITE, ATTN_KERNEL, ATTN_OUT,
       MLP, MOE_ROUTE, MOE_EXPERTS, MOE_LATENT, SLOT_STATE, GDN_IN, GDN_SCAN, GDN_OUT,
       SSM_IN, SSM_SCAN, SSM_OUT, HEAD, SAMPLE, COUNTERS, LOSS, OPTIMIZER)
