#!/bin/bash
# Test tiers (VERDICT r2 item 4 + r4 item 10).
#
#   ./ci.sh            fast tier: everything not marked slow, sharded 4-way
#   ./ci.sh full       fast tier + slow-marked convergence tests
#   ./ci.sh quick      <5-minute driver tier: core planes + one smoke per
#                      library (composition documented in TESTING.md)
#
# Sharding (-n 4 --dist loadfile) pays off even on a 1-core box: most suite
# wall time is event-loop waits (heartbeats, autoscale delays, failover
# windows), not CPU. loadfile keeps each module's cluster fixture on one
# worker. The persistent XLA compile cache (tests/conftest.py) makes warm
# runs much faster; cold-run times are reported in TESTING.md.
set -euo pipefail
cd "$(dirname "$0")"

TIER="${1:-fast}"
ARGS=(-q -p no:cacheprovider)
# Shard only when pytest-xdist is actually available (some driver
# containers ship bare pytest; the tiers must still run there).
if python -c "import xdist" 2>/dev/null; then
  ARGS+=(-n 4 --dist loadfile --max-worker-restart 0)
fi
TARGET=(tests/)
case "$TIER" in
  fast) ARGS+=(-m "not slow") ;;
  full) ;;
  quick)
    ARGS+=(-m "not slow")
    # Curated: control/data/worker planes, the native arena, and one
    # fast smoke module per library (no convergence runs, none of the
    # multi-minute cluster-churn modules).
    TARGET=(
      tests/test_core_units.py        # pure control-plane units
      tests/test_core_api.py          # live cluster: tasks/actors/objects
      tests/test_refcount.py          # distributed refcount/lineage seams
      tests/test_native_arena.py      # C++ allocator via ctypes
      tests/test_util.py              # ActorPool/Queue/collectives
      tests/test_data.py              # Data: blocks, ops, shuffles
      tests/test_serve.py             # Serve: deploy/route/batch/HTTP
      tests/test_serve_config.py      # Serve: YAML config + REST ops
      tests/test_tracing.py           # distributed tracing across hops
      tests/test_llm_serve.py         # LLM engine: paged KV, batching
      tests/test_paged_attention.py   # Pallas ragged paged-attn kernel
      tests/test_chip_compile.py      # kernels compile for a described v5e
      tests/test_chip_smoke.py        # chip_smoke.py phases, tiny, on CPU
      tests/test_chunked_prefill.py   # chunked prefill + token budget
      tests/test_width_bucketing.py   # pow-2 width-bucketed dispatch
      tests/test_prefix_cache.py      # prefix cache: COW page sharing
      tests/test_spec_decode.py       # speculative decode: verify/rollback
      tests/test_kv_objects.py        # KV page-set donate/adopt ladder
      tests/test_tp_decode.py         # tensor-parallel decode: tp=2 smoke
                                      # (self-skips if <2 XLA host devices)
      tests/test_quant.py             # int8 weights + KV scale planes
      tests/test_tune.py              # Tune: schedulers/searchers
      tests/test_workflow.py          # Workflows: DAG + resume
      tests/test_ops_layer.py         # model ops numerics
      tests/test_rllib_eval.py        # RLlib: eval workers + callbacks
      tests/test_sharding_audit.py    # SPMD audit arithmetic
      tests/test_graftlint.py         # static-analysis rules + baseline
      tests/test_graftlint_v2.py      # flow-aware families + compat shim
      tests/test_graftlint_v3.py      # concurrency/lifecycle families
      tests/test_flight_recorder.py   # compile watch / load / SLO
      tests/test_autoscale.py         # series store + shadow autoscaler
      tests/test_router.py            # load/affinity routing + shedding
      tests/test_chaos.py             # drain/failover + chaos harness
    ) ;;
  *) echo "usage: $0 [fast|full|quick]" >&2; exit 2 ;;
esac

# Collection guard: a silent import/collection error in these modules
# would just shrink the pass count — pytest's grep-style pass totals can't
# tell "all passed" from "never collected". Fail loudly instead. For
# test_paged_attention this doubles as the pallas-import guard on
# CPU-only boxes: a broken pallas install must fail the tier, not skip
# the kernel tests silently (the module asserts the interpret-mode
# fallback instead of importorskip'ing). test_chip_compile is guarded for
# collection only: whether its tests then PASS or SKIP (no topology can
# be described) shows in pytest's own summary.
for guarded in tests/test_tracing.py tests/test_paged_attention.py \
               tests/test_chip_compile.py \
               tests/test_chunked_prefill.py tests/test_width_bucketing.py \
               tests/test_prefix_cache.py \
               tests/test_spec_decode.py tests/test_kv_objects.py \
               tests/test_tp_decode.py tests/test_quant.py \
               tests/test_graftlint.py \
               tests/test_graftlint_v2.py tests/test_graftlint_v3.py \
               tests/test_flight_recorder.py \
               tests/test_autoscale.py tests/test_router.py \
               tests/test_chaos.py; do
  collected=$(python -m pytest "${guarded}" --collect-only -q \
    -p no:cacheprovider 2>/dev/null | grep -c "^${guarded}" || true)
  if [ "${collected}" -eq 0 ]; then
    echo "FATAL: ${guarded} collected zero tests" >&2
    exit 1
  fi
done

# Static analysis gate (fast/quick tiers, before pytest): graftlint over
# the runtime AND its own tooling against the committed baseline — a NEW
# jit-closure, recompile-hazard, shard-spec, jax-compat,
# blocked-event-loop, or swallowed-exception finding fails the tier
# before any test runs. The summary prints per-rule-family counts
# (total/baselined/new), so baseline drift between runs is visible
# straight from CI logs. Degrades gracefully on trees without a
# committed baseline (fresh forks): advisory-only, since every
# historical finding would read as "new" there.
if [ "$TIER" = "fast" ] || [ "$TIER" = "quick" ]; then
  # --jobs 0 = one worker per core: the v3 flow rules walk every class
  # model per file, and the scan is embarrassingly parallel.
  if [ -f tools/graftlint/baseline.json ]; then
    python -m tools.graftlint ray_tpu/ tools/ --jobs 0
  else
    echo "ci.sh: no graftlint baseline committed — advisory lint only" >&2
    python -m tools.graftlint ray_tpu/ tools/ --jobs 0 || true
  fi
fi

exec python -m pytest "${TARGET[@]}" "${ARGS[@]}"
