"""Serve benchmark: continuous-batched LLM serving — req/s + TTFT.

BASELINE.json metric family 2 (Ray Serve req/s + p50 TTFT, OPT-1.3B-class
text generation). Run:

    python bench_serve.py [--model tiny|opt_1_3b] [--clients 16]
        [--requests 64] [--json-out FILE]

Drives the in-process LLMEngine directly (the Serve replica wraps exactly
this engine; the router adds ~ms). On the chip use --model opt_1_3b (any
model but tiny/tiny25m refuses to run without a TPU).
Prints one JSON line:
  {"metric": "serve_llm", "req_per_s": N, "ttft_p50_ms": N,
   "ttft_p95_ms": N, "decode_tok_per_s": N, ...}
"""

from __future__ import annotations

import argparse
import json
import threading
import time

import numpy as np

from ray_tpu.utils.platform import place_compile_cache

place_compile_cache()


def _resolve_draft_cfg(name, cfg):
    """Resolve --spec-draft into a GPTConfig tied to the target's
    tokenizer (vocab). "tiny1l" is the CPU-ablation draft: a 1-layer
    half-width shrink of the TARGET config — an order of magnitude less
    weight traffic per proposal, the cheap-proposer shape speculative
    decoding wants. Any registry name works too; the engine rejects
    vocab mismatches at construction."""
    from ray_tpu.models import gpt

    if name == "tiny1l":
        return gpt.GPTConfig.tiny(
            n_layers=1, d_model=cfg.d_model // 2,
            n_heads=max(1, cfg.n_heads // 2), d_ff=cfg.d_ff // 2,
            vocab_size=cfg.vocab_size, max_seq=cfg.max_seq,
            dtype=cfg.dtype, attn_impl=cfg.attn_impl)
    return gpt.GPTConfig.by_name(name)


def _weight_bytes_per_device(params, tp):
    """Weight bytes ONE device streams per decode step: params whose
    partition rule names the tp axis count size/tp, replicated params
    count in full. Decode is weight-bound (BENCH_SERVE.md roofline), so
    this is the per-shard HBM-bytes-per-step numerator the tp ablation
    pins — near-halving it at tp=2 is the whole point.

    Untied configs exclude `wte`: decode only GATHERS B embedding rows
    per step (the full table is never streamed), while the separate
    `lm_head` does stream for the logits pass. Tied configs keep `wte`
    — it IS the head matrix there."""
    from ray_tpu.models import gpt, partition

    specs = partition.match_partition_rules(gpt.partition_rules(), params)
    total = 0
    for name, leaf in params.items():
        if name == "wte" and "lm_head" in params:
            continue
        sharded = any(
            ax == "tp" or (isinstance(ax, tuple) and "tp" in ax)
            for ax in specs[name])
        total += (leaf.size * leaf.dtype.itemsize
                  // (tp if sharded else 1))
    return int(total)


def _fit_periodic(cfg, params, pattern, steps):
    """Adam-fit `params` to continue the repeated `pattern` (the
    --repeat-period workload): rotations of the period tiled to one
    sequence, next-token CE. Random weights measure nothing for
    speculation — acceptance needs a draft that PREDICTS the target, and
    both only predict the workload after seeing it. Deterministic
    (fixed rotations, no data randomness) so the spec/nospec ablation
    pair fits byte-identical target weights."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import gpt

    period = len(pattern)
    # One full period + 1 per row: every bigram of the cycle appears in
    # every row, which is all memorization needs — longer sequences just
    # multiply the per-step cost.
    seq = min(cfg.max_seq - 1, period + 1)
    batch = min(period, 8)
    reps = seq // period + 2
    tiled = pattern * reps
    rows = np.stack([
        np.asarray(tiled[(i * period) // batch:
                         (i * period) // batch + seq + 1], np.int32)
        for i in range(batch)])
    tokens = jnp.asarray(rows[:, :-1])
    targets = jnp.asarray(rows[:, 1:])
    # 3e-3: converges to ~1e-3 CE within ~100 steps on every config the
    # ablation uses; 1e-2 oscillates at d_model >= 512.
    opt = optax.adam(3e-3)

    @jax.jit
    def fit_update(params, opt_state):
        loss, grads = jax.value_and_grad(gpt.loss_fn)(
            params, tokens, targets, cfg)
        updates, opt_state = opt.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    opt_state = opt.init(params)
    loss = None
    for _ in range(steps):
        params, opt_state, loss = fit_update(params, opt_state)
    print(f"# fit {cfg.n_layers}L/{cfg.d_model}d to period {period}: "
          f"final loss {float(loss):.4f} after {steps} steps", flush=True)
    return params


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="tiny")
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--max-tokens", type=int, default=32)
    ap.add_argument("--max-tokens-spread", type=int, default=0,
                    help="± uniform per-request jitter on --max-tokens"
                         " (deterministic multiset). Constant output"
                         " lengths keep every admission wave synchronized"
                         " — the one-shot path's best case and unlike"
                         " real traffic; jitter staggers completions")
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--n-slots", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=1024,
                    help="KV capacity per slot; size to the workload —"
                         " paged-attention reads scale with the live page"
                         " width, and the chunked-prefill gather path's"
                         " prefix attention scales with it on CPU")
    ap.add_argument("--decode-block", type=int, default=16,
                    help="fused decode window: tokens per dispatch")
    ap.add_argument("--bf16", action="store_true",
                    help="serve bf16 weights (halves decode HBM traffic)")
    ap.add_argument("--weight-dtype", default="bf16",
                    choices=("bf16", "int8"),
                    help="llm_weight_dtype: int8 = per-output-channel"
                         " symmetric int8 matmul planes + fp32 scale"
                         " vectors, dequant fused at the consuming einsum"
                         " (gpt.weight_view); bf16 = storage as loaded"
                         " (fp32 masters unless --bf16). Requires"
                         " --kv-mode paged")
    ap.add_argument("--kv-dtype", default="bf16",
                    choices=("bf16", "int8"),
                    help="llm_kv_dtype: int8 = int8 KV page planes +"
                         " per-page scale planes riding the same page"
                         " tables (models/paged_kv.py). Requires"
                         " --kv-mode paged")
    ap.add_argument("--kv-mode", default="dense", choices=("dense", "paged"),
                    help="paged = block-paged KV pool (models/paged_kv.py);"
                         " slot count stops being bounded by max_len x B")
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--n-pages", type=int, default=None,
                    help="KV pool pages (default: half the dense footprint)")
    ap.add_argument("--attn-impl", default=None,
                    choices=("gather", "kernel"),
                    help="paged-decode attention: kernel = Pallas ragged"
                         " paged attention (ops/paged_attention.py),"
                         " gather = reference timeline reconstitution"
                         " (default: the llm_attn_impl config knob)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill (paged mode): tokens per prefill"
                         " chunk, co-scheduled against decode; 0 = one-shot"
                         " whole-prompt admission")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="max prefill tokens per engine tick while decode"
                         " is active (default: llm_prefill_token_budget)")
    ap.add_argument("--no-width-bucketing", dest="width_bucketing",
                    action="store_false", default=True,
                    help="control arm: dispatch every prefill chunk at the"
                         " full max_pages table width (the pre-bucketing"
                         " two-program grid) instead of grouping rows by"
                         " the pow-2 width their written prefix needs")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged-KV prefix cache (serve/prefix_cache.py):"
                         " completed requests donate chunk-aligned prefix"
                         " pages; warm admissions skip prefill up to the"
                         " first cold token (requires --prefill-chunk)")
    ap.add_argument("--prefix-cache-pages", type=int, default=None,
                    help="max pool pages cache entries may pin"
                         " (default: half the pool)")
    ap.add_argument("--spec-draft", default=None,
                    help="speculative decoding draft model: a GPTConfig"
                         " registry name, or 'tiny1l' (1-layer half-width"
                         " tiny — the CPU-ablation draft). Requires"
                         " --kv-mode paged and --prefill-chunk > 0; the"
                         " draft proposes --spec-k tokens per slot per"
                         " tick and the target scores all k+1 positions"
                         " in one chunked verify pass")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per slot per tick")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel shards (llm_tp): params +"
                         " KV pool shard along the head axis over a"
                         " ('tp',) mesh and every paged program runs"
                         " per-shard (models/partition.py). Requires"
                         " --kv-mode paged and --prefill-chunk > 0."
                         " Off-TPU the bench forces a host-device mesh"
                         " of this size (tiny models), so the CPU"
                         " ablation measures the per-device"
                         " weight/KV-bytes-per-step split, not wall"
                         " speedup — virtual devices share one core")
    ap.add_argument("--repeat-period", type=int, default=0,
                    help="repetitive workload: prompts are random-phase"
                         " rotations of one fixed token pattern of this"
                         " period (the shape speculative decoding is"
                         " built for — the greedy continuation repeats"
                         " the period, so a competent draft tracks the"
                         " target). 0 = fully random prompts")
    ap.add_argument("--spec-fit-steps", type=int, default=0,
                    help="fit the TARGET (and the draft, when"
                         " --spec-draft is set) to the --repeat-period"
                         " pattern for this many Adam steps before"
                         " serving. Random weights measure nothing for"
                         " speculation (acceptance needs a draft that"
                         " actually predicts the target); the fit makes"
                         " the CPU ablation reflect a competent"
                         " draft/target pair. Applied to BOTH the spec"
                         " and no-spec runs (same seed) so the ablation"
                         " is weight-identical")
    ap.add_argument("--shared-prefix-frac", type=float, default=0.0,
                    help="fraction of each prompt drawn from a small pool"
                         " of shared system prefixes (the millions-of-"
                         "users workload: same system prompt, different"
                         " user suffix). 0 = fully distinct prompts")
    ap.add_argument("--prefix-pool", type=int, default=4,
                    help="how many distinct shared prefixes the workload"
                         " rotates through")
    ap.add_argument("--turns", type=int, default=1,
                    help="multi-turn conversations: each request's context"
                         " = its previous turns' context + response + a"
                         " fresh user message (every turn after the first"
                         " re-submits a prefix the engine just decoded)")
    ap.add_argument("--ramp", default=None,
                    help="diurnal ramp: 'clients:seconds,...' phases"
                         " (e.g. '32:20,256:40,32:40'). Replaces the"
                         " fixed --clients/--requests run with timed"
                         " phases of closed-loop clients; emits one row"
                         " per phase (TTFT / burn-rate / recommended-"
                         "replica columns) plus the shadow autoscaler's"
                         " full decision trace — the ROADMAP"
                         " autoscaling acceptance harness")
    ap.add_argument("--ramp-sample-s", type=float, default=0.25,
                    help="load-snapshot sampling cadence into the local"
                         " series store during --ramp")
    ap.add_argument("--autoscale-interval-s", type=float, default=1.0,
                    help="shadow-autoscaler evaluation cadence (--ramp)")
    ap.add_argument("--autoscale-window-s", type=float, default=10.0,
                    help="policy window over the series store (--ramp)")
    ap.add_argument("--target-ongoing", type=float, default=None,
                    help="per-replica (inflight+queued) the policy sizes"
                         " for (default: n_slots)")
    ap.add_argument("--max-replicas", type=int, default=8,
                    help="recommendation clamp for the shadow policy")
    ap.add_argument("--slo-ttft-ms", type=float, default=1000.0,
                    help="TTFT p95 SLO target driving the burn-rate"
                         " signal during --ramp")
    ap.add_argument("--real-replicas", type=int, default=0,
                    help="closed-loop mode against a REAL deployed"
                         " cluster: deploy this many LLMDeployment"
                         " replicas, drive the ramp through the async"
                         " HTTP proxy as SSE streams (token-exact vs an"
                         " uninterrupted baseline), and let the"
                         " controller's autoscaler (--autoscale-mode)"
                         " drive the actual replica count. 0 = the"
                         " legacy in-process engine modes")
    ap.add_argument("--router", default="p2c_load",
                    choices=("p2c_local", "p2c_load", "affinity"),
                    help="serve_router_policy for the real-replica run:"
                         " legacy local p2c | blended load p2c |"
                         " prefix-affine with load spill")
    ap.add_argument("--autoscale-mode", default="enact",
                    choices=("off", "shadow", "enact"),
                    help="controller autoscaler mode (--real-replicas)")
    ap.add_argument("--chaos-kill-at", type=float, default=0.0,
                    help="seconds into the real-replica run at which a"
                         " routable replica gets a seeded decode-window"
                         " SIGKILL (0 = no chaos)")
    ap.add_argument("--overload-queue-depth", type=int, default=0,
                    help="serve_overload_queue_depth for the real run"
                         " (0 disables proxy overload shedding)")
    ap.add_argument("--spill-ongoing", type=float, default=None,
                    help="serve_router_spill_ongoing override for the"
                         " real run (affinity spill threshold)")
    ap.add_argument("--drain-timeout", type=float, default=20.0,
                    help="serve_drain_timeout_s for the real run")
    ap.add_argument("--prompt-pool-size", type=int, default=16,
                    help="distinct prompts the real-replica clients"
                         " rotate through (exactness baselines are"
                         " precomputed per pool member)")
    ap.add_argument("--pool-split", default="",
                    help="'P:D' — real-replica mode deploys a "
                         "DISAGGREGATED stack: P prefill-pool replicas "
                         "(own the /bench route, donate KV page sets "
                         "at the first token) + D decode-pool replicas "
                         "(adopt the pages by reference). Requires "
                         "--real-replicas (any value; the split counts "
                         "win), paged KV and chunked prefill. With "
                         "--chaos-kill-at the SIGKILL lands on a "
                         "PREFILL replica inside a donation (the "
                         "donor-death scenario) instead of a decode "
                         "window.")
    ap.add_argument("--fleet-warm", action="store_true",
                    help="Fleet-wide warm-hit model (round 16): two "
                         "in-process engines sharing one page-set "
                         "store. The donor serves a prompt set (every "
                         "completion donates its written prefix); its "
                         "exported kv_summary is handed to a "
                         "DeploymentHandle exactly as the routing push "
                         "would, and the ADOPTER — which never saw any "
                         "of those prompts — serves them again with "
                         "only the handle's discover hint. Emits cold "
                         "vs warm TTFT on the adopter plus the "
                         "request-path digest-lookup counters.")
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args()
    if args.fleet_warm:
        if args.kv_mode != "paged" or not args.prefill_chunk:
            ap.error("--fleet-warm requires --kv-mode paged and "
                     "--prefill-chunk > 0 (page-set donation is keyed "
                     "at chunk depth)")
        if (args.real_replicas or args.ramp or args.spec_draft
                or args.pool_split or args.repeat_period
                or args.prefix_cache):
            ap.error("--fleet-warm is the in-process two-engine model; "
                     "it cannot combine with --real-replicas/--ramp/"
                     "--spec-draft/--pool-split/--repeat-period/"
                     "--prefix-cache (cross-replica adoption is the "
                     "measured effect, local caching would mask it)")
    pool_split = None
    if args.pool_split:
        try:
            p, d = (int(x) for x in args.pool_split.split(":"))
        except ValueError:
            ap.error("--pool-split must be 'P:D' replica counts")
        if p < 1 or d < 1:
            ap.error("--pool-split needs P >= 1 and D >= 1")
        if not args.real_replicas:
            ap.error("--pool-split requires --real-replicas (the pools "
                     "are serve deployments)")
        if args.kv_mode != "paged" or not args.prefill_chunk:
            ap.error("--pool-split requires --kv-mode paged and "
                     "--prefill-chunk > 0 (page sets are keyed at the "
                     "prefill-chunk granularity)")
        if args.autoscale_mode != "off":
            ap.error("--pool-split deploys FIXED pool sizes (stable "
                     "denominators for the r13 comparison) — it cannot "
                     "combine with --autoscale-mode other than 'off'")
        pool_split = (p, d)
    args.pool_split_parsed = pool_split
    if not 0.0 <= args.shared_prefix_frac <= 1.0:
        ap.error("--shared-prefix-frac must be in [0, 1]")
    if args.turns < 1:
        ap.error("--turns must be >= 1")
    if args.prefix_pool < 1:
        ap.error("--prefix-pool must be >= 1")
    if args.max_tokens_spread < 0:
        ap.error("--max-tokens-spread must be >= 0")
    if args.max_tokens_spread >= args.max_tokens:
        ap.error("--max-tokens-spread must be < --max-tokens"
                 " (a request must generate at least one token)")
    if args.spec_draft and (args.kv_mode != "paged"
                            or not args.prefill_chunk):
        ap.error("--spec-draft requires --kv-mode paged and"
                 " --prefill-chunk > 0 (the verify pass is a"
                 " chunked-prefill row)")
    if args.spec_fit_steps and not args.repeat_period:
        ap.error("--spec-fit-steps needs --repeat-period (the fit"
                 " corpus IS the repeated pattern)")
    if args.repeat_period and (args.shared_prefix_frac or args.turns > 1):
        ap.error("--repeat-period replaces the whole prompt generator"
                 " (rotations of one pattern) — it cannot combine with"
                 " --shared-prefix-frac/--turns workload shaping")
    if args.real_replicas and (args.spec_draft or args.repeat_period
                               or args.spec_fit_steps):
        ap.error("--real-replicas does not drive the speculative flags"
                 " (--spec-draft/--repeat-period/--spec-fit-steps run"
                 " against the in-process engine only)")
    if args.real_replicas and args.model == "tiny25m":
        ap.error("--model tiny25m is the in-process ablation config;"
                 " replica deployments resolve models by registry name")
    if args.spec_k < 1:
        ap.error("--spec-k must be >= 1")
    if args.repeat_period and args.repeat_period < 1:
        ap.error("--repeat-period must be >= 1")
    if args.spec_fit_steps and args.spec_fit_steps < 1:
        ap.error("--spec-fit-steps must be >= 1")
    if args.tp < 1:
        ap.error("--tp must be >= 1")
    if args.tp > 1 and (args.kv_mode != "paged" or not args.prefill_chunk):
        ap.error("--tp > 1 requires --kv-mode paged and"
                 " --prefill-chunk > 0 (the sharded programs are the"
                 " paged chunked set)")
    if args.real_replicas and args.tp > 1:
        ap.error("--tp drives the in-process engine only (replica"
                 " processes size their own device mesh)")
    if ("int8" in (args.weight_dtype, args.kv_dtype)
            and args.kv_mode != "paged"):
        ap.error("--weight-dtype/--kv-dtype int8 require --kv-mode paged"
                 " (quantized serving targets the paged engine)")
    phases = None
    if args.ramp:
        try:
            phases = [(int(c), float(s)) for c, s in
                      (part.split(":") for part in args.ramp.split(","))]
        except ValueError:
            ap.error("--ramp must be 'clients:seconds,...' phases")
        if not phases or any(c < 1 or s <= 0 for c, s in phases):
            ap.error("--ramp phases need clients >= 1 and seconds > 0")

    if args.real_replicas:
        if phases is None:
            phases = [(args.clients, 30.0)]
        _run_real(args, phases)
        return

    if args.model in ("tiny", "tiny25m"):
        # CI path: force the CPU backend before jax initializes — with
        # enough virtual host devices to carry the --tp mesh (the
        # TESTING.md off-TPU repro: XLA_FLAGS=--xla_force_host_platform_
        # device_count=N before the first backend touch).
        from ray_tpu.utils.platform import force_cpu_devices

        force_cpu_devices(max(1, args.tp))
    else:
        # Chip arm: the numbers are device numbers, so the device must be
        # a TPU — never a quiet CPU/interpret fallback.
        import jax as _jax

        platform = _jax.devices()[0].platform
        if platform != "tpu":
            ap.error(f"--model {args.model} is the chip arm and needs a "
                     f"TPU; JAX platform is {platform!r} (tiny/tiny25m "
                     "are the CPU arms)")

    if args.fleet_warm:
        _run_fleet_warm(args)
        return

    from ray_tpu.models import gpt
    from ray_tpu.serve.llm import LLMEngine

    if args.model == "tiny25m":
        # CPU stand-in for the chip's weight-bound decode regime: ~25M
        # params (~100 MB fp32 weight traffic per pass) makes a decode
        # step memory-bandwidth-bound even on CPU, where the 64-dim
        # `tiny` is pure dispatch overhead. The speculative ablation
        # runs here: a k+1-token verify pass streams the same weights as
        # a 1-token decode step, which is the whole speculative bet.
        cfg = gpt.GPTConfig.tiny(d_model=512, n_layers=8, d_ff=2048)
    else:
        cfg = gpt.GPTConfig.by_name(args.model)
    params = None
    rng = np.random.default_rng(0)
    # Repetitive workload (speculative-decoding ablation): one fixed
    # pattern; every prompt is a random-phase rotation of it, so the
    # greedy continuation of a fitted model repeats the period. Sampled
    # WITHOUT replacement: distinct tokens make the continuation a
    # deterministic bigram map, learnable by a 1-layer draft — a
    # duplicated token would need 2-layer induction to disambiguate,
    # which quietly zeroes the draft's acceptance.
    pattern = None
    if args.repeat_period:
        if args.repeat_period > cfg.vocab_size:
            ap.error("--repeat-period must be <= the model vocab size")
        pattern = list(map(int, rng.choice(
            cfg.vocab_size, args.repeat_period, replace=False)))
    draft_cfg = draft_params = None
    if args.spec_draft:
        draft_cfg = _resolve_draft_cfg(args.spec_draft, cfg)
    if args.spec_fit_steps:
        import jax

        # Fit in fp32 ALWAYS (Adam updates into bf16 storage lose the
        # sub-ulp tail and the fit plateaus early); --bf16 casts the
        # fitted result below, the same master-weights-then-serve shape
        # real deployments use.
        if params is None:
            params = gpt.init_params(cfg, jax.random.key(0))
        params = _fit_periodic(cfg, params, pattern, args.spec_fit_steps)
        if draft_cfg is not None:
            draft_params = _fit_periodic(
                draft_cfg, gpt.init_params(draft_cfg, jax.random.key(1)),
                pattern, args.spec_fit_steps)
    if args.bf16:
        # Serving-standard bf16 weights: decode is HBM-bound, fp32 masters
        # would double the per-token weight traffic. Applied AFTER the
        # fit, to target and draft alike.
        import jax
        import jax.numpy as jnp

        def _to_bf16(tree):
            return jax.tree.map(
                lambda a: a.astype(jnp.bfloat16)
                if a.dtype == jnp.float32 else a, tree)

        params = _to_bf16(params if params is not None
                          else gpt.init_params(cfg, jax.random.key(0)))
        if draft_params is not None:
            draft_params = _to_bf16(draft_params)
    quant_fidelity = None
    if args.weight_dtype == "int8":
        # Quantization-fidelity preflight, committed with the row: the
        # int8 arm's logit MAE and eval-loss delta vs the SAME master
        # weights it serves, on a fixed batch — the JSON carries its own
        # accuracy evidence next to its byte counts.
        import jax
        import jax.numpy as jnp

        if params is None:
            params = gpt.init_params(cfg, jax.random.key(0))
        qp = gpt.quantize_params(params)
        ev = np.random.default_rng(123).integers(
            0, cfg.vocab_size, (4, 129))
        toks = jnp.asarray(ev[:, :-1], jnp.int32)
        tgts = jnp.asarray(ev[:, 1:], jnp.int32)
        lg0 = gpt.forward(params, toks, cfg)
        lg1 = gpt.forward(qp, toks, cfg)
        quant_fidelity = {
            "logit_mae": round(float(jnp.abs(lg0 - lg1).mean()), 6),
            "eval_loss_delta": round(
                float(gpt.loss_fn(qp, toks, tgts, cfg))
                - float(gpt.loss_fn(params, toks, tgts, cfg)), 6),
        }
    engine = LLMEngine(cfg, params, n_slots=args.n_slots,
                       max_len=args.max_len,
                       decode_block=args.decode_block,
                       kv_mode=args.kv_mode, page_size=args.page_size,
                       n_pages=args.n_pages, attn_impl=args.attn_impl,
                       prefill_chunk=args.prefill_chunk,
                       prefill_token_budget=args.prefill_budget,
                       prefix_cache=args.prefix_cache or None,
                       prefix_cache_pages=args.prefix_cache_pages,
                       spec_draft=draft_cfg, spec_k=args.spec_k,
                       spec_draft_params=draft_params,
                       # Always explicit: the tp=1 ablation arm must pin
                       # tp=1, not fall through to a stray RAY_TPU_LLM_TP.
                       tp=args.tp,
                       # Same discipline for the quantization ablation:
                       # every arm pins its dtypes, never a stray
                       # RAY_TPU_LLM_{WEIGHT,KV}_DTYPE.
                       weight_dtype=args.weight_dtype,
                       kv_dtype=args.kv_dtype,
                       # Explicit per arm: the full-width control arm
                       # must pin False, never fall through to a stray
                       # RAY_TPU_LLM_PREFILL_WIDTH_BUCKETING.
                       prefill_width_bucketing=args.width_bucketing)
    # Shared-prefix workload: a small pool of "system prompts" that a
    # fraction of every prompt is drawn from. Built up front so the
    # multiset is deterministic regardless of client scheduling.
    shared_len = int(round(args.shared_prefix_frac * args.prompt_len))
    prefix_pool = [
        list(map(int, rng.integers(0, cfg.vocab_size, shared_len)))
        for _ in range(args.prefix_pool)] if shared_len else []

    # Warm every admission-group size (8/4/2/1 batched prefill) and every
    # decode-window size the measured requests will hit. The engine thread
    # is not started yet, so step() is driven synchronously and the queued
    # burst sizes deterministically become the admission group sizes.
    def drive(reqs):
        while not all(r.done.is_set() for r in reqs):
            engine.step()

    if pattern is not None:
        reps = args.prompt_len // args.repeat_period + 2

        def prompt():
            phase = int(rng.integers(0, args.repeat_period))
            return (pattern * reps)[phase:phase + args.prompt_len]
    else:
        prompt = lambda: list(
            rng.integers(0, cfg.vocab_size, args.prompt_len))
    # Bucket-ladder warmup first: pre-compile every (table width, head)
    # chunk program — the traffic warmup below only visits the widths
    # its own prompts happen to cross, and a measured request crossing
    # into an unvisited width would book seconds of XLA compile against
    # one window (a non-zero jax_compiles_delta). Inert-row dispatches,
    # marked via compile_watch.warmup_scope(), before compiles0 below.
    engine.warmup_compile()
    for burst in (8, 4, 2):
        if burst <= args.n_slots:
            drive([engine.submit(prompt(), max_tokens=2)
                   for _ in range(burst)])
    # Drive one request to the LONGEST output the measured traffic can
    # reach: page-table width buckets double as slots grow, and a width
    # the warmup never visited would compile its decode programs
    # mid-measurement (seconds of XLA time booked against one window).
    drive([engine.submit(prompt(),
                         max_tokens=args.max_tokens + args.max_tokens_spread)])
    # ... then a full-occupancy burst at the same output length: chunked
    # admission staggers the slots' phases, so decode windows mix
    # remaining-budget sizes — (window k, table width) combos a lone
    # request never hits (e.g. small-k windows at the widest table)
    # would otherwise compile mid-measurement.
    drive([engine.submit(prompt(),
                         max_tokens=args.max_tokens + args.max_tokens_spread)
           for _ in range(args.n_slots)])
    # Engine-side counters restart here so the reported device-time split
    # covers ONLY the measured window (warmup compiles would skew it).
    engine.reset_stats()
    # Compile-watch baseline (flight recorder): the warmup above is
    # supposed to have visited every program shape the measured traffic
    # hits, so jax_compiles_delta should be 0 — a non-zero delta in a
    # committed BENCH JSON is a recompile regression caught from the
    # artifact alone, not from step-time noise.
    from ray_tpu import compile_watch

    compiles0 = compile_watch.compiles_total()
    engine.start()

    if phases is not None:
        _run_ramp(args, phases, engine, cfg, compiles0)
        return

    results = []
    lock = threading.Lock()
    todo = list(range(args.requests))
    # Per-request output budgets precomputed so the workload multiset is
    # deterministic regardless of client-thread scheduling.
    spread = args.max_tokens_spread
    budgets = [
        max(1, args.max_tokens - spread + int(rng.integers(0, 2 * spread + 1)))
        if spread else args.max_tokens
        for _ in range(args.requests)]

    def client():
        while True:
            with lock:
                if not todo:
                    return
                i = todo.pop()
            uniq = args.prompt_len - shared_len
            if pattern is not None:
                ids = prompt()
            else:
                ids = (list(prefix_pool[i % len(prefix_pool)])
                       if prefix_pool
                       else []) + list(rng.integers(0, cfg.vocab_size, uniq))
            # --turns > 1: one conversation per request slot — every turn
            # after the first re-submits context the engine just served
            # (prompt + response + fresh user message), the multi-turn
            # reuse pattern the prefix cache turns into warm admissions.
            for _turn in range(args.turns):
                try:
                    req = engine.submit(ids, max_tokens=budgets[i])
                except ValueError:
                    break       # conversation outgrew the engine's caps
                req.done.wait(600)
                if req.error:
                    break
                with lock:
                    results.append((req.first_token_at - req.submitted_at,
                                    req.finished_at - req.submitted_at,
                                    len(req.out_ids), req.cached_tokens))
                ids = (ids + [int(t) for t in req.out_ids]
                       + list(rng.integers(0, cfg.vocab_size,
                                           max(1, uniq))))

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client) for _ in range(args.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    engine.stop()

    ttfts = sorted(r[0] for r in results)
    toks = sum(r[2] for r in results)
    em = engine.metrics()
    row = {
        "metric": "serve_llm",
        "model": args.model,
        "kv_mode": args.kv_mode,
        "n_slots": args.n_slots,
        "req_per_s": round(len(results) / wall, 2),
        "ttft_p50_ms": round(ttfts[len(ttfts) // 2] * 1000, 1),
        "ttft_p95_ms": round(ttfts[int(len(ttfts) * 0.95)] * 1000, 1),
        "decode_tok_per_s": round(toks / wall, 1),
        "completed": len(results),
        "clients": args.clients,
        "wall_s": round(wall, 2),
        # Engine-side split (measured inside the engine loop, VERDICT r4
        # weak #2/next #3): what the CHIP sustains vs what clients see
        # through the dispatch path.
        "engine_decode_tok_per_s": round(
            em.get("engine_decode_tok_s", 0.0), 1),
        "engine_prefill_tok_per_s": round(
            em.get("engine_prefill_tok_s", 0.0), 1),
        # Engine-side TTFT percentiles (submit → first token measured in
        # the engine thread, no client/router path) — the number chunked
        # prefill moves.
        "engine_ttft_ms_p50": em.get("ttft_ms_p50", 0.0),
        "engine_ttft_ms_p95": em.get("ttft_ms_p95", 0.0),
        # Engine-side per-token step-time percentiles (window wall time /
        # window size, measured inside the engine loop) — the roofline-
        # facing number the paged-attention kernel moves.
        "decode_step_ms_p50": em.get("decode_step_ms_p50", 0.0),
        "decode_step_ms_p95": em.get("decode_step_ms_p95", 0.0),
        # Prefill interference: per-token decode latency window-END to
        # window-END across ticks that also ran prefill (admission stall
        # included) — the decode-stall bound the prefill token budget
        # enforces; the one-shot vs chunked ablation reads off here.
        "decode_step_burst_ms_p50": em.get("decode_step_burst_ms_p50", 0.0),
        "decode_step_burst_ms_p95": em.get("decode_step_burst_ms_p95", 0.0),
        "prefill_chunk": args.prefill_chunk,
        "prefill_budget": (args.prefill_budget if args.prefill_budget
                           is not None else engine.prefill_budget),
        "prefill_chunks_dispatched": em.get("prefill_chunks", 0),
        "shared_prefix_frac": args.shared_prefix_frac,
        "prefix_pool": args.prefix_pool if shared_len else 0,
        "turns": args.turns,
        "slot_occupancy": round(em.get("slot_occupancy", 0.0), 4),
        "decode_time_s": round(em.get("decode_time_s", 0.0), 2),
        "prefill_time_s": round(em.get("prefill_time_s", 0.0), 2),
        "preemptions": em.get("preemptions", 0),
        "decode_block": args.decode_block,
        # XLA compiles paid inside the measured window (0 after a correct
        # warmup; see the compile-watch baseline above).
        "jax_compiles_delta": int(
            compile_watch.compiles_total() - compiles0),
    }
    if args.kv_mode == "paged" and args.prefill_chunk:
        # Width-bucketed dispatch ablation surface: the per-bucket
        # dispatch counts prove interior chunks ran at bucketed (not
        # max_pages) width, and the p50/max pair is the bytes/chunk
        # model's parameter in BENCH_SERVE.md.
        row["prefill_width_bucketing"] = engine.prefill_width_bucketing
        row["prefill_dispatches"] = em.get("prefill_dispatches", 0)
        if "prefill_dispatch_width_p50" in em:
            row["prefill_dispatch_width_p50"] = (
                em["prefill_dispatch_width_p50"])
            row["prefill_dispatch_width_max"] = (
                em["prefill_dispatch_width_max"])
        row["prefill_dispatch_widths"] = em.get(
            "prefill_dispatch_widths", {})
        row["max_pages_per_slot"] = engine.max_pages_per_slot
    if args.kv_mode == "paged":
        row["kv_pages_total"] = em.get("kv_pages_total")
        row["kv_page_size"] = em.get("kv_page_size")
        # Peak pool occupancy over the measured window (pool low-water
        # mark): how close the run came to page exhaustion — pressure
        # regressions show up here before they show up as preemptions.
        free_min = em.get("kv_pages_free_min")
        row["kv_pages_free_min"] = free_min
        if free_min is not None and em.get("kv_pages_total"):
            row["kv_pool_peak_occupancy"] = round(
                1.0 - free_min / em["kv_pages_total"], 4)
        # Which attention implementation produced this row — kernel vs
        # gather ablations must be distinguishable from the JSON alone.
        row["llm_attn_impl"] = em.get("llm_attn_impl", engine.attn_impl)
        # Sharding topology + the per-device bytes-per-step split the
        # tp ablation pins (weights/TP + KV/TP; replicated weights —
        # embeddings/norms/head — pay full freight on every shard).
        import jax as _jax

        row["llm_tp"] = engine.tp
        row["platform"] = _jax.devices()[0].platform
        row["device_kind"] = _jax.devices()[0].device_kind
        row["n_devices"] = len(_jax.devices())
        row["weight_bytes_per_device"] = _weight_bytes_per_device(
            engine.params, engine.tp)
        row["kv_bytes_per_device"] = engine._pool_shard_bytes()
        # Quantization ablation: dtype-width-derived byte streams from
        # the same rule-table walk (int8 planes count 1 B + their fp32
        # scale vectors; scale PLANES of a quantized pool ride the
        # per-token quotient). weight_bytes_per_pass is the WHOLE
        # model's decode stream (tp=1 view — the quantization headline
        # independent of sharding); kv_bytes_per_token divides the full
        # pool footprint (scales included) by its token capacity.
        row["llm_weight_dtype"] = engine.weight_dtype
        row["llm_kv_dtype"] = engine.kv_dtype
        row["weight_bytes_per_pass"] = _weight_bytes_per_device(
            engine.params, 1)
        pool_tokens = engine.cache["k"].shape[1] * engine.page_size
        row["kv_bytes_per_token"] = round(sum(
            int(a.size) * a.dtype.itemsize
            for a in engine.cache.values()) / pool_tokens, 4)
        if quant_fidelity is not None:
            row.update(quant_fidelity)
    row["prefix_cache"] = bool(engine.prefix_cache is not None)
    if engine.prefix_cache is not None:
        # Warm-vs-cold TTFT split (client-observed AND engine-side): the
        # committed warm-prefix ablation's headline is the warm p50 —
        # prefill collapses to the cold suffix, so it must sit well
        # under the cache-off p50 at req/s parity.
        warm = sorted(r[0] for r in results if r[3] > 0)
        cold = sorted(r[0] for r in results if r[3] == 0)
        row["warm_requests"] = len(warm)
        row["cold_requests"] = len(cold)
        if warm:
            row["ttft_warm_p50_ms"] = round(warm[len(warm) // 2] * 1000, 1)
            row["ttft_warm_p95_ms"] = round(
                warm[int(len(warm) * 0.95)] * 1000, 1)
        if cold:
            row["ttft_cold_p50_ms"] = round(cold[len(cold) // 2] * 1000, 1)
        row["engine_ttft_warm_ms_p50"] = em.get("ttft_warm_ms_p50", 0.0)
        row["engine_ttft_warm_ms_p95"] = em.get("ttft_warm_ms_p95", 0.0)
        row["engine_ttft_cold_ms_p50"] = em.get("ttft_cold_ms_p50", 0.0)
        row["prefix_cache_hit_rate"] = em.get("prefix_cache_hit_rate", 0.0)
        row["prefix_cache_hits"] = em.get("prefix_hits", 0)
        row["prefix_cache_misses"] = em.get("prefix_misses", 0)
        row["prefix_cache_evictions"] = em.get("prefix_evictions", 0)
        row["prefix_cache_cow_copies"] = em.get("cow_copies", 0)
        row["prefix_cached_tokens"] = em.get("prefix_cached_tokens", 0)
        row["prefix_cache_pages"] = em.get("prefix_cache_pages", 0)
    # Workload + fit shape ride every row (spec or not) so the ablation
    # pair is self-describing: the nospec arm runs the same repetitive
    # workload against the same fitted target weights.
    row["repeat_period"] = args.repeat_period
    row["spec_fit_steps"] = args.spec_fit_steps
    row["spec_draft"] = args.spec_draft or ""
    row["spec_k"] = args.spec_k if args.spec_draft else 0
    if args.spec_draft:
        # accepted_per_step is the speculative headline: tokens emitted
        # per slot per verify pass — 1.0 = non-speculative rate, k+1 the
        # ceiling; engine tok/s should scale with it on a weight-bound
        # decode.
        row["accepted_per_step"] = em.get("spec_accepted_per_step", 0.0)
        row["spec_accept_rate"] = em.get("spec_accept_rate", 0.0)
        row["spec_proposed"] = em.get("spec_proposed", 0)
        row["spec_accepted"] = em.get("spec_accepted", 0)
        row["spec_verify_ticks"] = em.get("spec_ticks", 0)
    print(json.dumps(row), flush=True)
    if args.json_out:
        json.dump(row, open(args.json_out, "w"))


def _run_fleet_warm(args) -> None:
    """Fleet-wide warm-hit model (round 16): the cluster KV tier's
    headline, reproducible off-TPU with two in-process engines.

    The donor serves a prompt set; every completion donates its written
    prefix to the SHARED page-set store (insert-on-free). The donor's
    exported ``kv_summary`` is then handed to a real DeploymentHandle
    exactly as the routing push would ship it, and the ADOPTER — a
    replica that never saw any of those prompts — serves the same set
    with only the handle's ``kv={"discover": True}`` hint. Cold TTFT is
    the adopter on prompts nobody donated. The committed evidence:
    warm p50 under cold p50, ``kv_digest_lookups_cold == 0`` (unhinted
    admissions never poll the index — discovery rode the push, not the
    request path), ``kv_digest_lookups_warm == kv_adoptions`` (one
    authorized resolve per adopting admission), and
    ``jax_compiles_delta == 0``."""
    import jax

    from ray_tpu import compile_watch
    from ray_tpu.models import gpt
    from ray_tpu.serve.api import DeploymentHandle
    from ray_tpu.serve.kv_objects import LocalKVStore
    from ray_tpu.serve.llm import LLMEngine

    cfg = gpt.GPTConfig.by_name(args.model)
    params = gpt.init_params(cfg, jax.random.key(0))
    store = LocalKVStore(budget=4096)

    def mk_engine():
        return LLMEngine(cfg, params, n_slots=args.n_slots,
                         max_len=args.max_len,
                         decode_block=args.decode_block,
                         kv_mode="paged", page_size=args.page_size,
                         n_pages=args.n_pages, attn_impl=args.attn_impl,
                         prefill_chunk=args.prefill_chunk,
                         prefill_token_budget=args.prefill_budget,
                         tp=args.tp, weight_dtype=args.weight_dtype,
                         kv_dtype=args.kv_dtype,
                         kv_transfer=True, kv_store=store,
                         prefill_width_bucketing=args.width_bucketing)

    rng = np.random.default_rng(0)

    def mk_prompt():
        return list(map(int,
                        rng.integers(0, cfg.vocab_size, args.prompt_len)))

    warm_set = [mk_prompt() for _ in range(args.requests)]
    cold_set = [mk_prompt() for _ in range(args.requests)]
    prewarm = mk_prompt()

    donor, adopter = mk_engine(), mk_engine()

    def drive(eng, reqs):
        while not all(r.done.is_set() for r in reqs):
            eng.step()
        bad = [r.error for r in reqs if r.error]
        if bad:
            raise SystemExit(f"fleet-warm request failed: {bad[0]}")
        return reqs

    # Warmup: the bucket ladder on both engines, then one donation →
    # adoption round trip on a throwaway prompt so the gather/scatter
    # page-set programs (pow-2 widths) are compiled before the measured
    # window — exactly the discipline of the main bench path.
    for eng in (donor, adopter):
        eng.warmup_compile()
    drive(donor, [donor.submit(prewarm, max_tokens=args.max_tokens)])
    drive(adopter, [adopter.submit(prewarm, max_tokens=args.max_tokens,
                                   kv={"discover": True})])
    for burst in (8, 4, 2):
        if burst <= args.n_slots:
            drive(adopter, [adopter.submit(mk_prompt(), max_tokens=2)
                            for _ in range(burst)])
    for eng in (donor, adopter):
        eng.reset_stats()
    compiles0 = compile_watch.compiles_total()

    def serve_ttfts(eng, prompts, kvs=None):
        reqs = [eng.submit(p, max_tokens=args.max_tokens,
                           kv=(kvs[i] if kvs else None))
                for i, p in enumerate(prompts)]
        drive(eng, reqs)
        return sorted(r.first_token_at - r.submitted_at for r in reqs)

    # Cold phase: the adopter serves prompts NOBODY donated — and must
    # never poll the index for them (no hint, no lookup).
    cold = serve_ttfts(adopter, cold_set)
    lookups_cold = adopter.metrics()["kv_digest_lookups"]

    # Donor phase: completions donate insert-on-free; the summary this
    # engine exports via load_snapshot() is what the probe ships.
    serve_ttfts(donor, warm_set)
    summary = donor.load_snapshot()["kv_summary"]

    # The "routing push": a real handle, fed the pushed summary union,
    # attaches the discover hint — the same kv_hint every routed
    # request crosses. No cluster, no RPCs: the table is local.
    handle = DeploymentHandle("fleet-warm-bench")
    handle._kv_warm = frozenset(summary)
    handle._affinity_chunk = args.prefill_chunk
    hinted = [handle.kv_hint({"prompt_ids": p}) for p in warm_set]
    kvs = [h.get("kv") for h in hinted]

    # Warm phase: the adopter has NEVER seen these prompts — adoption
    # via the pushed summary + hint alone.
    warm = serve_ttfts(adopter, warm_set, kvs)
    am = adopter.metrics()
    lookups_warm = am["kv_digest_lookups"] - lookups_cold

    row = {
        "metric": "serve_llm_fleet_warm",
        "model": args.model,
        "kv_mode": "paged",
        "requests_per_phase": args.requests,
        "prompt_len": args.prompt_len,
        "max_tokens": args.max_tokens,
        "prefill_chunk": args.prefill_chunk,
        "page_size": args.page_size,
        "n_slots": args.n_slots,
        "llm_tp": args.tp,
        "llm_kv_dtype": adopter.kv_dtype,
        "ttft_cold_p50_ms": round(cold[len(cold) // 2] * 1000, 1),
        "ttft_cold_p95_ms": round(cold[int(len(cold) * 0.95)] * 1000, 1),
        "ttft_warm_p50_ms": round(warm[len(warm) // 2] * 1000, 1),
        "ttft_warm_p95_ms": round(warm[int(len(warm) * 0.95)] * 1000, 1),
        "warm_hinted": sum(1 for kv in kvs if kv),
        "kv_adoptions": am["kv_adoptions"],
        "kv_adopt_failures": am["kv_adopt_failures"],
        "kv_adopted_tokens": am["kv_adopted_tokens"],
        "kv_digest_lookups_cold": lookups_cold,
        "kv_digest_lookups_warm": lookups_warm,
        "kv_summary_entries": len(summary),
        # The per-replica push payload this summary costs (satellite:
        # serve_routes_push_bytes measures the live cluster's total).
        "kv_summary_bytes": len(json.dumps(summary)),
        "store_entries": store.stats()["entries"],
        "jax_compiles_delta": int(
            compile_watch.compiles_total() - compiles0),
    }
    print(json.dumps(row), flush=True)
    if args.json_out:
        json.dump(row, open(args.json_out, "w"))


def _run_real(args, phases) -> None:
    """Closed-loop ramp against REAL replicas: deploy LLMDeployment,
    drive timed phases of SSE clients through the async HTTP proxy, and
    let the controller's autoscaler (shadow or ENACT) move the actual
    replica count while the bench records the recommended-vs-actual
    trajectory, client TTFT, shed/failover/drain counters, per-replica
    prefix-cache hit rates, and token EXACTNESS of every stream against
    an uninterrupted in-process baseline (the PR 9 zero-drop bar — a
    seeded mid-ramp SIGKILL must cost zero dropped or duplicated
    tokens). The in-process --ramp mode is this loop's dry run; this is
    the closed loop itself."""
    import bench_chaos

    from ray_tpu.utils.platform import force_cpu_devices

    force_cpu_devices(1)

    import ray_tpu
    from ray_tpu import serve, state
    from ray_tpu.models import gpt
    from ray_tpu.serve.api import _get_controller
    from ray_tpu.serve.llm import LLMDeployment, LLMEngine

    cfg = gpt.GPTConfig.by_name(args.model)
    rng = np.random.default_rng(0)
    # Deterministic prompt pool: a fraction of each prompt comes from a
    # small shared-prefix pool (the affinity workload), the rest is a
    # fixed unique suffix — baselines are precomputed per pool member so
    # every completed stream is checked token-exact.
    shared_len = int(round(args.shared_prefix_frac * args.prompt_len))
    prefixes = [list(map(int, rng.integers(0, cfg.vocab_size, shared_len)))
                for _ in range(args.prefix_pool)] if shared_len else []
    pool = []
    for i in range(max(1, args.prompt_pool_size)):
        uniq = list(map(int, rng.integers(
            0, cfg.vocab_size, args.prompt_len - shared_len)))
        pool.append((prefixes[i % len(prefixes)] if prefixes else [])
                    + uniq)

    engine_kwargs: dict = {"decode_block": args.decode_block,
                           "kv_mode": args.kv_mode,
                           "page_size": args.page_size}
    if args.n_pages is not None:
        engine_kwargs["n_pages"] = args.n_pages
    if args.attn_impl is not None:
        engine_kwargs["attn_impl"] = args.attn_impl
    if args.prefill_chunk:
        engine_kwargs["prefill_chunk"] = args.prefill_chunk
        engine_kwargs["prefill_token_budget"] = (
            args.prefill_budget if args.prefill_budget is not None
            else args.n_slots * args.prefill_chunk)
    if args.prefix_cache:
        engine_kwargs["prefix_cache"] = True
        if args.prefix_cache_pages is not None:
            engine_kwargs["prefix_cache_pages"] = args.prefix_cache_pages

    # Uninterrupted greedy baseline (same params seed the replicas use).
    base = LLMEngine(cfg, None, n_slots=args.n_slots, max_len=args.max_len,
                     **engine_kwargs)
    expected = []
    for p in pool:
        req = base.submit(p, max_tokens=args.max_tokens)
        while not req.done.is_set():
            base.step()
        expected.append(list(req.out_ids))

    sys_cfg = {
        "serve_autoscale_mode": args.autoscale_mode,
        "serve_autoscale_interval_s": args.autoscale_interval_s,
        "serve_autoscale_window_s": args.autoscale_window_s,
        "serve_autoscale_up_sustain_s": 1.0,
        "serve_autoscale_down_sustain_s": 5.0,
        "serve_autoscale_up_cooldown_s": 2.0,
        "serve_autoscale_down_cooldown_s": 6.0,
        "serve_router_policy": args.router,
        "llm_prefill_chunk": args.prefill_chunk,
        "serve_drain_timeout_s": args.drain_timeout,
        "serve_overload_queue_depth": args.overload_queue_depth,
        "worker_profile_flush_interval_s": 0.5,
    }
    if args.spill_ongoing is not None:
        sys_cfg["serve_router_spill_ongoing"] = args.spill_ongoing
    split = getattr(args, "pool_split_parsed", None)
    n_cpus = (sum(split) if split else args.max_replicas) + 3
    ray_tpu.init(num_cpus=n_cpus, _system_config=sys_cfg)
    t_start = time.perf_counter()
    events: list = []
    try:
        target = (args.target_ongoing if args.target_ongoing
                  else float(args.n_slots))
        if split:
            # Disaggregated stack: the /bench route belongs to the
            # PREFILL pool; its replicas donate KV page sets at the
            # first token and hand off to the decode pool, whose
            # replicas adopt the pages by reference. Fixed counts —
            # the r13 comparison needs stable denominators.
            n_pre, n_dec = split
            decode_dep = serve.deployment(
                LLMDeployment, name="bench-decode",
                pool_role="decode").options(
                num_replicas=n_dec, route_prefix=None).bind(
                args.model, n_slots=args.n_slots, max_len=args.max_len,
                jax_platform="cpu", pool_role="decode",
                engine_kwargs=dict(engine_kwargs))
            prefill_dep = serve.deployment(
                LLMDeployment, name="bench",
                pool_role="prefill").options(
                num_replicas=n_pre, route_prefix="/bench").bind(
                args.model, n_slots=args.n_slots, max_len=args.max_len,
                jax_platform="cpu", pool_role="prefill",
                pool_peer="bench-decode",
                engine_kwargs=dict(engine_kwargs))
            serve.run(decode_dep, timeout=600.0)
            handle = serve.run(prefill_dep, timeout=600.0)
        else:
            dep = serve.deployment(LLMDeployment, name="bench").options(
                num_replicas=args.real_replicas, route_prefix="/bench",
                # mode=off pins the replica count (router/cache
                # ablations need a FIXED denominator — any
                # autoscaling_config would also arm the legacy
                # reactive policy).
                autoscaling_config=(
                    None if args.autoscale_mode == "off" else {
                        "min_replicas": 1,
                        "max_replicas": args.max_replicas,
                        "target_ongoing_requests": target,
                    })).bind(args.model, n_slots=args.n_slots,
                             max_len=args.max_len, jax_platform="cpu",
                             engine_kwargs=engine_kwargs)
            handle = serve.run(dep, timeout=600.0)
        _proxy, port = serve.start_proxy()
        # Warm EVERY initial replica's compile cache at the REAL output
        # length (a width the warmup never visited would compile
        # mid-measurement): dispatch directly per routable replica —
        # routing the warmups through the load-balanced handle can
        # leave a replica cold by chance. In the split stack the decode
        # replicas warm with a FULL generation (their engines compile
        # prefill + adoption + decode programs) and the prefill
        # replicas stop at their handoff envelope (first-token
        # programs only — all they ever run).
        ctrl = _get_controller()
        table = ray_tpu.get(ctrl.get_routing.remote(-1), timeout=60)
        warm_names = ["bench-decode", "bench"] if split else ["bench"]
        for wname in warm_names:
            for replica in table["routes"][wname]["replicas"]:
                ray_tpu.get(replica.handle_request.remote(
                    "generate", (pool[0],),
                    {"max_tokens": args.max_tokens}), timeout=600)
        bench_chaos._sse_stream(port, "/bench", {
            "prompt_ids": pool[0], "max_tokens": args.max_tokens},
            timeout_s=300)

        def counter_total(name: str) -> float:
            try:
                return sum(r.get("value", 0.0)
                           for r in state.metrics_rows()
                           if r.get("name") == name)
            except Exception:  # noqa: BLE001 — metrics hub unreachable
                return 0.0

        time.sleep(1.0)     # let warmup metrics flush before baselining
        c0 = {name: counter_total(name) for name in (
            "serve_requests_shed_total", "serve_failovers_total",
            "serve_drain_total", "serve_handoffs_total",
            "llm_kv_adoptions_total", "llm_kv_adopt_failures_total")}

        stop = threading.Event()
        traj: list = []

        def sampler():
            while not stop.is_set():
                try:
                    st = serve.status().get("bench")
                except Exception:  # noqa: BLE001 — controller mid-restart
                    st = None
                if st:
                    au = st.get("autoscale") or {}
                    traj.append({
                        "t": round(time.perf_counter() - t_start, 2),
                        "recommended": au.get("recommended_replicas"),
                        "num_replicas": st["num_replicas"],
                        "live": st["live_replicas"],
                        "starting": st["starting_replicas"],
                        "draining": st["draining_replicas"],
                    })
                stop.wait(0.5)

        sampler_t = threading.Thread(target=sampler, daemon=True)
        sampler_t.start()

        if args.chaos_kill_at > 0:
            def chaos_killer():
                time.sleep(args.chaos_kill_at)
                try:
                    ctrl = _get_controller()
                    table = ray_tpu.get(ctrl.get_routing.remote(-1),
                                        timeout=30)
                    reps = table["routes"]["bench"]["replicas"]
                    # Split stack: the SIGKILL lands on a PREFILL
                    # replica INSIDE a donation (serve.kv.donate) —
                    # the donor-death scenario the adoption ladder
                    # must absorb. Fused: the classic decode-window
                    # kill.
                    site = ("serve.kv.donate" if split
                            else "llm.decode_window")
                    if reps:
                        ray_tpu.get(reps[-1].install_chaos.remote(
                            [{"site": site,
                              "action": "kill", "after": 2}]), timeout=30)
                        events.append({
                            "t": round(time.perf_counter() - t_start, 2),
                            "event": f"chaos_sigkill_armed:{site}"})
                except Exception as e:  # noqa: BLE001
                    events.append({"event": f"chaos arm failed: {e!r}"})

            threading.Thread(target=chaos_killer, daemon=True).start()

        phase_rows = []
        totals = {"completed": 0, "dropped": 0, "mismatched": 0,
                  "shed": 0}
        for pi, (clients, dur) in enumerate(phases):
            deadline = time.perf_counter() + dur
            rec = {"completed": 0, "dropped": 0, "mismatched": 0,
                   "shed": 0, "ttfts": [], "tok_s": [], "gaps": [],
                   "errs": []}
            plock = threading.Lock()

            def client(tid: int, deadline=deadline, rec=rec, plock=plock):
                it = 0
                while time.perf_counter() < deadline:
                    idx = (tid + it * 13) % len(pool)
                    it += 1
                    t0 = time.perf_counter()
                    r = bench_chaos._sse_stream(port, "/bench", {
                        "prompt_ids": pool[idx],
                        "max_tokens": args.max_tokens}, timeout_s=300)
                    with plock:
                        if r["error"] and "overloaded" in str(r["error"]):
                            rec["shed"] += 1
                        elif r["error"] or not r["done"]:
                            rec["dropped"] += 1
                            if len(rec["errs"]) < 5:
                                rec["errs"].append(str(r["error"])[:160])
                        else:
                            rec["completed"] += 1
                            if r["tokens"] != expected[idx]:
                                rec["mismatched"] += 1
                            a = r["arrivals"]
                            if a:
                                rec["ttfts"].append(a[0] - t0)
                            if len(a) > 1 and a[-1] > a[0]:
                                rec["tok_s"].append(
                                    (len(a) - 1) / (a[-1] - a[0]))
                            if len(a) > 1:
                                # Worst inter-token stall per stream:
                                # a handoff or failover shows up HERE —
                                # the adopt-vs-re-prefill gap headline.
                                rec["gaps"].append(max(
                                    b - c for b, c in zip(a[1:], a)))
                    if r["error"] and "overloaded" in str(r["error"]):
                        time.sleep(0.5)     # honor the shed backoff

            threads = [threading.Thread(target=client, args=(t,))
                       for t in range(clients)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            ttfts = sorted(rec["ttfts"])
            toks = sorted(rec["tok_s"])
            gaps = sorted(rec["gaps"])
            tail = traj[-1] if traj else {}
            row = {
                "phase": pi, "clients": clients, "duration_s": dur,
                "wall_s": round(wall, 2),
                "completed": rec["completed"],
                "dropped": rec["dropped"],
                "mismatched": rec["mismatched"],
                "shed": rec["shed"],
                "req_per_s": round(rec["completed"] / wall, 2),
                "recommended_replicas": tail.get("recommended"),
                "live_replicas": tail.get("live"),
            }
            if rec["errs"]:
                row["errors_sample"] = rec["errs"]
            if ttfts:
                row["ttft_p50_ms"] = round(
                    ttfts[len(ttfts) // 2] * 1000, 1)
                row["ttft_p95_ms"] = round(
                    ttfts[int(len(ttfts) * 0.95)] * 1000, 1)
            if toks:
                # Per-stream decode rate (client-observed): the shed
                # acceptance pins its p95 within 15% of unloaded.
                row["stream_tok_s_p50"] = round(
                    toks[len(toks) // 2], 2)
                row["stream_tok_s_p05"] = round(
                    toks[int(len(toks) * 0.05)], 2)
            if gaps:
                row["gap_p50_ms"] = round(
                    gaps[len(gaps) // 2] * 1000, 1)
                row["gap_p95_ms"] = round(
                    gaps[int(len(gaps) * 0.95)] * 1000, 1)
            for k in totals:
                totals[k] += rec[k]
            phase_rows.append(row)
        stop.set()
        sampler_t.join(timeout=10)

        # Same settle as before the c0 baseline: counters reach the hub
        # on the flush cadence — a shed/failover/drain in the final
        # window must not be missed by an instant read.
        time.sleep(1.0)
        c1 = {name: counter_total(name) for name in c0}
        # Final per-replica cache view (affinity evidence) + the decode
        # pool's adoption ledger (split stacks).
        hit_rates: list = []
        per_hits: list = []
        per_misses: list = []
        agg_hits = agg_misses = 0
        kv_adoptions = kv_partial = kv_failures = kv_donations = 0
        try:
            ctrl = _get_controller()
            load = ray_tpu.get(ctrl.get_load.remote(), timeout=30)
            for dep_name in (("bench", "bench-decode") if split
                             else ("bench",)):
                for r in load.get(dep_name, {}).get("replicas", []):
                    eng = r.get("load") or {}
                    kv_adoptions += int(eng.get("kv_adoptions", 0))
                    kv_partial += int(eng.get("kv_partial_adoptions", 0))
                    kv_failures += int(eng.get("kv_adopt_failures", 0))
                    kv_donations += int(eng.get("kv_donations", 0))
            for r in load.get("bench", {}).get("replicas", []):
                eng = r.get("load") or {}
                if "prefix_cache_hit_rate" in eng:
                    hit_rates.append(eng["prefix_cache_hit_rate"])
                per_hits.append(int(eng.get("prefix_cache_hits", 0)))
                per_misses.append(int(eng.get("prefix_cache_misses", 0)))
                agg_hits += int(eng.get("prefix_cache_hits", 0))
                agg_misses += int(eng.get("prefix_cache_misses", 0))
        except Exception as e:  # noqa: BLE001
            events.append({"event": f"final load read failed: {e!r}"})

        # End-of-run engine view per replica (quiescent): decode-step
        # latency + burst-tick interference — the structural number the
        # split buys (decode-pool engines never co-schedule a full
        # prompt's prefill against live decodes; only 1-chunk cold
        # suffixes after an adoption) — and the page-accounting closure
        # the chaos acceptance demands.
        engine_metrics: dict = {}
        accounting_closed = True
        try:
            table = ray_tpu.get(ctrl.get_routing.remote(-1), timeout=30)
            for dep_name in (("bench", "bench-decode") if split
                             else ("bench",)):
                rows = []
                for replica in table["routes"][dep_name]["replicas"]:
                    m = ray_tpu.get(replica.handle_request.remote(
                        "metrics", (), {}), timeout=60)
                    rows.append({k: m[k] for k in (
                        "decode_step_ms_p50", "decode_step_ms_p95",
                        "decode_step_burst_ms_p50",
                        "decode_step_burst_ms_p95",
                        "engine_decode_tok_s", "prefill_tokens",
                        "kv_adoptions", "kv_donations", "preemptions")
                        if k in m})
                    acc = ray_tpu.get(replica.handle_request.remote(
                        "page_accounting", (), {}), timeout=60)
                    rows[-1]["page_accounting_closed"] = bool(
                        acc["closure"] and acc["refs_consistent"])
                    accounting_closed &= rows[-1][
                        "page_accounting_closed"]
                engine_metrics[dep_name] = rows
        except Exception as e:  # noqa: BLE001
            events.append({"event": f"engine metrics read failed: {e!r}"})

        recs = [s["recommended"] for s in traj
                if s["recommended"] is not None]
        lives = [s["live"] for s in traj]
        doc = {
            "metric": "serve_llm_real_ramp",
            "model": args.model, "kv_mode": args.kv_mode,
            "n_slots": args.n_slots,
            "prefill_chunk": args.prefill_chunk,
            "prefix_cache": bool(args.prefix_cache),
            "shared_prefix_frac": args.shared_prefix_frac,
            "prefix_pool": args.prefix_pool if shared_len else 0,
            "prompt_pool_size": len(pool),
            "router": args.router,
            "autoscale_mode": args.autoscale_mode,
            "real_replicas_initial": args.real_replicas,
            "max_replicas": args.max_replicas,
            "target_ongoing": target,
            "slo_ttft_ms": args.slo_ttft_ms,
            "chaos_kill_at_s": args.chaos_kill_at,
            "overload_queue_depth": args.overload_queue_depth,
            "pool_split": (f"{split[0]}:{split[1]}" if split else None),
            "phases": phase_rows,
            **totals,
            "kv_adoptions": kv_adoptions,
            "kv_partial_adoptions": kv_partial,
            "kv_adopt_failures": kv_failures,
            "kv_donations": kv_donations,
            "handoffs_delta": round(
                c1["serve_handoffs_total"]
                - c0["serve_handoffs_total"], 1),
            "kv_adoptions_counter_delta": round(
                c1["llm_kv_adoptions_total"]
                - c0["llm_kv_adoptions_total"], 1),
            "shed_counter_delta": round(
                c1["serve_requests_shed_total"]
                - c0["serve_requests_shed_total"], 1),
            "failovers_delta": round(
                c1["serve_failovers_total"]
                - c0["serve_failovers_total"], 1),
            "drains_delta": round(
                c1["serve_drain_total"] - c0["serve_drain_total"], 1),
            "per_replica_hit_rate": hit_rates,
            # Admission counts per replica: the spill/pileup evidence —
            # under affinity BOTH replicas must keep serving (spill),
            # and the hit/miss split shows whose cache was warm.
            "per_replica_hits": per_hits,
            "per_replica_misses": per_misses,
            "aggregate_hit_rate": (
                round(agg_hits / (agg_hits + agg_misses), 4)
                if agg_hits + agg_misses else None),
            "recommended_vs_actual": {
                "recommended_max": max(recs) if recs else None,
                "live_max": max(lives) if lives else None,
                "recommended_final": recs[-1] if recs else None,
                "live_final": lives[-1] if lives else None,
                "tracked_up": bool(recs and max(lives) >= max(recs)),
                "tracked_down": bool(recs and lives
                                     and lives[-1] == recs[-1]),
            },
            "engine_metrics": engine_metrics,
            "page_accounting_closed": accounting_closed,
            "trajectory": traj,
            "events": events,
            "wall_s": round(time.perf_counter() - t_start, 2),
        }
        print(json.dumps(doc), flush=True)
        if args.json_out:
            json.dump(doc, open(args.json_out, "w"))
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def _run_ramp(args, phases, engine, cfg, compiles0) -> None:
    """Diurnal ramp driver: timed phases of closed-loop clients against
    the in-process engine, a sampler thread recording load snapshots and
    the TTFT burn rate into a local SeriesStore (the same rings the GCS
    runs), and a ShadowAutoscaler consuming that store — the
    decision-plane dry run of the ROADMAP's SLO-driven autoscaling loop,
    minus only the cluster transport. Emits one JSON doc: per-phase rows
    (TTFT / burn-rate / recommended-replica columns), the full decision
    trace, and the store's bounded-memory accounting."""
    import dataclasses

    from ray_tpu import compile_watch, profiling
    from ray_tpu.core.config import Config
    from ray_tpu.obs_series import SeriesStore
    from ray_tpu.serve.autoscale import (AutoscalePolicy, ShadowAutoscaler,
                                         TTFT_SLO)
    # The serve replica wrapper observes this histogram per request; the
    # bench drives the engine directly, so it observes the same series
    # itself — the SloMonitor path stays the real one.
    from ray_tpu.serve.llm import _TTFT_HIST
    from ray_tpu.slo import Objective, SloMonitor

    knobs = Config.from_env()
    store = SeriesStore(
        max_points=knobs.obs_series_points,
        resolution_s=args.ramp_sample_s,
        max_series=knobs.obs_series_max_series,
        tombstone_ttl_s=knobs.obs_series_tombstone_ttl_s)
    monitor = SloMonitor(
        [Objective(TTFT_SLO, "serve_llm_ttft_s", 0.95,
                   args.slo_ttft_ms / 1000.0,
                   window_s=args.autoscale_window_s)],
        rows_fn=profiling.metrics_snapshot, export=False, seed=False)
    policy = AutoscalePolicy(
        min_replicas=1, max_replicas=args.max_replicas,
        window_s=args.autoscale_window_s,
        target_ongoing=(args.target_ongoing
                        if args.target_ongoing else float(args.n_slots)),
        target_ttft_p95_ms=args.slo_ttft_ms,
        up_sustain_s=2.0, down_sustain_s=8.0,
        up_cooldown_s=3.0, down_cooldown_s=10.0)
    autoscaler = ShadowAutoscaler(policy, series_fn=store.query,
                                  emit_events=False)

    stop = threading.Event()
    phase_box = {"i": 0}
    acc = [{"q_sum": 0.0, "q_n": 0, "q_max": 0.0, "burn_max": 0.0,
            "rec_min": None, "rec_max": None, "rec_last": None}
           for _ in phases]
    # The virtual replica count follows the recommendation: shadow
    # mode's trace IS the dry run of the closed loop, so the state
    # machine must see its own moves (a live controller reads the
    # actual replica count here).
    virtual = {"replicas": 1}
    tags = {"deployment": "bench", "replica": "r0"}

    def sampler():
        last_eval = 0.0
        while not stop.is_set():
            now = time.time()
            snap = engine.load_snapshot()
            qd = float(snap.get("queue_depth", 0))
            store.record("serve_replica_queue_depth", qd, tags,
                         source="bench", ts=now)
            store.record("serve_replica_ongoing",
                         qd + float(snap.get("active_slots", 0)), tags,
                         source="bench", ts=now)
            store.record("serve_replica_ttft_ewma_ms",
                         float(snap.get("ttft_ewma_ms", 0.0)), tags,
                         source="bench", ts=now)
            burn = monitor.evaluate()[0]["burn_rate"]
            store.record("slo_burn_rate", burn, {"slo": TTFT_SLO},
                         source="bench", ts=now)
            a = acc[phase_box["i"]]
            a["q_sum"] += qd
            a["q_n"] += 1
            a["q_max"] = max(a["q_max"], qd)
            a["burn_max"] = max(a["burn_max"], burn)
            if now - last_eval >= args.autoscale_interval_s:
                last_eval = now
                rec = autoscaler.evaluate(
                    "bench", virtual["replicas"])["recommended_replicas"]
                virtual["replicas"] = rec
                a["rec_last"] = rec
                a["rec_min"] = (rec if a["rec_min"] is None
                                else min(a["rec_min"], rec))
                a["rec_max"] = (rec if a["rec_max"] is None
                                else max(a["rec_max"], rec))
            stop.wait(args.ramp_sample_s)

    sampler_t = threading.Thread(target=sampler, daemon=True)
    sampler_t.start()

    phase_rows = []
    t_start = time.perf_counter()
    for pi, (clients, dur) in enumerate(phases):
        phase_box["i"] = pi
        deadline = time.perf_counter() + dur
        results: list = []
        plock = threading.Lock()

        def client(tid: int, pi=pi, deadline=deadline, results=results,
                   plock=plock):
            # Per-thread RNG (np.Generator is not thread-safe), seeded
            # by (phase, thread) so the prompt multiset is deterministic
            # given the phase schedule.
            crng = np.random.default_rng(100_000 + pi * 1024 + tid)
            while time.perf_counter() < deadline:
                ids = list(map(int, crng.integers(
                    0, cfg.vocab_size, args.prompt_len)))
                try:
                    req = engine.submit(ids, max_tokens=args.max_tokens)
                except ValueError:
                    break       # engine caps exceeded: stop this client
                if (not req.done.wait(600) or req.error
                        or req.first_token_at is None):
                    continue    # wedged/failed request: count nothing
                ttft = req.first_token_at - req.submitted_at
                _TTFT_HIST.observe(ttft, tags={"route": "bench",
                                               "replica": "r0"})
                with plock:
                    results.append((ttft, len(req.out_ids)))

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        a = acc[pi]
        ttfts = sorted(r[0] for r in results)
        row = {
            "phase": pi, "clients": clients, "duration_s": dur,
            "wall_s": round(wall, 2), "completed": len(results),
            "req_per_s": round(len(results) / wall, 2),
            "tok_per_s": round(sum(r[1] for r in results) / wall, 1),
            "queue_depth_mean": round(a["q_sum"] / max(a["q_n"], 1), 2),
            "queue_depth_max": a["q_max"],
            "burn_rate_max": round(a["burn_max"], 3),
            "recommended_replicas": a["rec_last"],
            "recommended_min": a["rec_min"],
            "recommended_max": a["rec_max"],
        }
        if ttfts:
            row["ttft_p50_ms"] = round(ttfts[len(ttfts) // 2] * 1000, 1)
            row["ttft_p95_ms"] = round(
                ttfts[int(len(ttfts) * 0.95)] * 1000, 1)
        phase_rows.append(row)
    total_wall = time.perf_counter() - t_start
    stop.set()
    sampler_t.join(timeout=10)
    engine.stop()

    decisions = autoscaler.decisions("bench")
    changes = [r for r in decisions if r["changed"]]
    stats = store.stats()
    doc = {
        "metric": "serve_llm_ramp",
        "model": args.model, "kv_mode": args.kv_mode,
        "n_slots": args.n_slots,
        "prefill_chunk": args.prefill_chunk,
        "llm_attn_impl": getattr(engine, "attn_impl", None),
        "slo_ttft_ms": args.slo_ttft_ms,
        "policy": dataclasses.asdict(policy),
        "autoscale_interval_s": args.autoscale_interval_s,
        "sample_s": args.ramp_sample_s,
        "phases": phase_rows,
        "wall_s": round(total_wall, 2),
        # Anti-flap acceptance: the recommendation may move at most
        # (phase transitions + 2) times across the whole ramp.
        "phase_count": len(phases),
        "recommendation_changes": len(changes),
        "no_flap": len(changes) <= (len(phases) - 1) + 2,
        # Every recommendation move with its full decision record
        # (inputs, window aggregates, rule fired, hysteresis state);
        # unchanged evaluations re-affirm the previous recommendation.
        "decisions": changes,
        "evaluations_total": len(decisions),
        # Bounded-memory accounting straight off the store: per-series
        # point count must never exceed the configured retention.
        "series_store": stats,
        "series_bounded":
            stats["points_max_per_series"] <= knobs.obs_series_points,
        "jax_compiles_delta": int(
            compile_watch.compiles_total() - compiles0),
    }
    print(json.dumps(doc), flush=True)
    if args.json_out:
        json.dump(doc, open(args.json_out, "w"))


if __name__ == "__main__":
    main()
