"""Continuous-batching LLM engine + Serve deployment.

The TPU-native answer to LLM serving (BASELINE config 5: continuous-batched
text generation). The reference batches requests per replica with
`@serve.batch` (`/root/reference/python/ray/serve/batching.py`) — static
batches that stall on the longest member. Here decode is *continuously*
batched: a fixed set of B slots over ONE cache, a paged KV pool, advances
a window of decode steps per iteration; requests join mid-flight, their
prompts entering a free slot's pages chunk by chunk, and retire
independently, so shapes are static (XLA-friendly) while occupancy
tracks load.

Design notes:
- A prompt is admitted ONE way: it enters its slot's page table in
  chunks of `llm_prefill_chunk` tokens, co-scheduled against decode
  under a token budget for each decode STEP
  (`llm_prefill_token_budget`) — Sarathi/Orca-style stall-free
  batching: a whole-prompt prefill per admission would stall every
  decoding slot for a prompt of compute. A tick runs one decode window
  of k steps and may place k budgets of prompt tokens before it, so the
  stall a decode step sees is bounded by one budget of chunk compute whatever
  the window's length; while slots decode, prefill also stops short of
  the pages they are about to need (a full pool stalls prompts, it
  does not preempt them). Admission back-pressure needs one CHUNK of
  pool headroom, not the whole prompt, and the prefill compile grid is
  two programs per page-table width (models/paged_kv.py
  `prefill_chunk_paged`), each
  [chunk_rows, chunk]: as many rows as full chunks fit ONE budget, not
  as the engine has slots nor as the tick's allowance has rows. An
  engine that dispatches at ONE table width (width bucketing off: a
  chunk program is a pass over the weights whatever it carries) has
  two programs in all, half a tick's allowance tall and a quarter of
  it, both with the head: a prompt is one program, not one a budget.
- The engine thread owns the cache; submit()/result flow through plain
  thread-safe queues, so the Serve replica's asyncio loop never blocks on
  device work.
- TTFT = submit → first token (prefill latency + queue wait); recorded
  per request for the Serve autoscaler and benchmarks, with a sampled
  queue-wait → first-chunk → last-chunk → first-token span breakdown in
  /api/traces (`llm.ttft*`).
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import logging
import math
import queue
import threading
import time
import uuid
from collections import OrderedDict
from typing import Any, Callable

import numpy as np

from ray_tpu import chaos as _chaos
from ray_tpu import profiling as _profiling
from ray_tpu import tracing

logger = logging.getLogger(__name__)

# Per-request serving histograms, tagged by the ingress route (from trace
# baggage) and the replica actor serving the request; flushed to the GCS
# with the hosting worker's metrics and exposed at the dashboard /metrics.
_TTFT_HIST = _profiling.Histogram(
    "serve_llm_ttft_s",
    description="LLM time-to-first-token (queue wait + prefill)",
    boundaries=_profiling.LATENCY_BUCKETS_S,
    tag_keys=("route", "replica"))
_DECODE_HIST = _profiling.Histogram(
    "serve_llm_decode_tok_s",
    description="LLM per-request decode throughput (tokens/s after TTFT)",
    boundaries=(1, 5, 10, 25, 50, 100, 250, 500, 1000, 2500),
    tag_keys=("route", "replica"))
# Engine-side decode step latency (window wall time / window size), tagged
# by kv/attention implementation so kernel-vs-gather runs are separable at
# /metrics. Buckets are finer than LATENCY_BUCKETS_S: the chip-side target
# is single-digit ms/step (HBM roofline), the client-path buckets start
# at 5 ms.
_DECODE_STEP_HIST = _profiling.Histogram(
    "serve_llm_decode_step_s",
    description="LLM engine per-token decode step latency (window / k)",
    boundaries=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                0.25, 0.5, 1.0, 2.5),
    tag_keys=("replica", "impl"))
# Per-chunk prefill dispatch latency (chunked-prefill scheduler): the
# decode-stall bound is ONE of these per budget token, so this histogram
# is the direct evidence that the token budget holds on a live replica.
_PREFILL_CHUNK_HIST = _profiling.Histogram(
    "serve_llm_prefill_chunk_s",
    description="LLM chunked-prefill per-chunk dispatch latency",
    boundaries=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                0.25, 0.5, 1.0, 2.5),
    tag_keys=("replica", "impl"))
# Width-bucketed chunk dispatch: one increment per prefill/graduation
# dispatch, tagged by the pow-2 page-table width the dispatch carried —
# the direct evidence (at /metrics and in the committed bench JSONs)
# that interior chunks run at bucketed width, not max_pages_per_slot.
_PREFILL_DISPATCH_COUNTER = _profiling.Counter(
    "llm_prefill_dispatch_total",
    description="LLM chunked-prefill dispatches by page-table width",
    tag_keys=("replica", "width"))

# Live engine-load gauges (flight recorder): set on every load_snapshot()
# call — the controller's stats-probe cadence — and flushed with the
# hosting worker's metrics, so /metrics, /api/serve/load, and the
# roadmap's least-loaded router all read the same numbers.
_LOAD_GAUGES = {
    key: _profiling.Gauge(f"llm_{key}", description=desc,
                          tag_keys=("replica",))
    for key, desc in (
        ("queue_depth", "LLM requests queued (pending + deferred)"),
        ("active_slots", "LLM slots bound to a request"),
        ("prefilling_slots", "LLM slots still streaming their prompt in"),
        ("pool_pages_free", "KV page-pool free pages"),
        ("pool_pages_total", "KV page-pool size"),
        ("prefill_budget_util",
         "EWMA of the share of a tick's prefill allowance it placed"),
        ("ttft_ewma_ms", "EWMA of time-to-first-token (ms)"),
        ("decode_tok_s_ewma", "EWMA of fused-window decode rate (tok/s)"),
        ("prefix_cache_pages",
         "KV pages currently pinned by prefix-cache entries"),
        ("prefix_cache_hit_rate",
         "Prefix-cache admission hit rate since last stats reset"),
        ("spec_accepted_per_step",
         "EWMA of tokens emitted per slot per speculative verify pass"),
        ("prefill_dispatch_width_p50",
         "Median page-table width of recent chunk dispatches"),
        ("prefill_dispatch_width_max",
         "Max page-table width of recent chunk dispatches"),
    )
}

# Speculative-decoding lifecycle counters: cumulative proposals vs
# acceptances, flushed with the hosting worker's metrics like every
# other serve counter — the acceptance RATE (the whole ballgame for the
# speculative speedup) is derivable at /metrics from the two series.
_SPEC_COUNTERS = {
    name: _profiling.Counter(
        f"llm_spec_{name}_total", description=desc, tag_keys=("replica",))
    for name, desc in (
        ("proposed", "Draft tokens proposed to speculative verification"),
        ("accepted", "Draft proposals the target model accepted"),
    )
}

# Prefix-cache lifecycle counters (serve/prefix_cache.py): cumulative,
# flushed with the hosting worker's metrics like every other serve
# counter, so hit/miss/eviction/COW rates are visible at /metrics and
# through the replica stats -> serve.status() -> /api/serve/load chain.
_PREFIX_COUNTERS = {
    name: _profiling.Counter(
        f"llm_prefix_cache_{name}_total", description=desc,
        tag_keys=("replica",))
    for name, desc in (
        ("hits", "Admissions that bound a cached prefix"),
        ("misses", "Admissions with no cached prefix"),
        ("evictions", "Prefix-cache entries evicted (LRU / pressure)"),
        ("cow_copies", "Copy-on-write page duplications at bind time"),
    )
}

# KV page-set lifecycle counters (serve/kv_objects.py): donations out
# of this engine, adoptions binding donated pages instead of
# re-prefilling, and adoption-ladder falls to the re-prefill rung —
# the failover-cost split the disaggregated-serving bench reads.
_KV_COUNTERS = {
    name: _profiling.Counter(
        f"llm_kv_{name}_total", description=desc, tag_keys=("replica",))
    for name, desc in (
        ("donations", "KV page-set objects donated to the object store"),
        ("adoptions", "Admissions that adopted donated KV pages"),
        ("adopt_failures",
         "Adoption attempts that fell to the re-prefill rung"),
    )
}


def _request_metric_tags() -> dict:
    """Route (ingress baggage) + replica (runtime context) tags for the
    per-request histograms. Safe anywhere: falls back to empty/local."""
    from ray_tpu import tracing

    ctx = tracing.get_current()
    route = (ctx.baggage.get("route", "") if ctx is not None else "") or ""
    replica = "local"
    try:
        from ray_tpu import api as _api

        aid = _api.get_runtime_context().get_actor_id()
        if aid:
            # ActorID hex = JobID(4B) + unique(8B): the head is the JOB
            # id, shared by every replica — the unique tail is the only
            # part that distinguishes replicas.
            replica = aid[-8:]
    except Exception:  # graftlint: disable=EXC-SWALLOW (metric tag enrichment only; "local" is the documented fallback)
        pass
    return {"route": route, "replica": replica}


def _observe_request_metrics(req: "GenRequest", tags: dict) -> None:
    if req.first_token_at is not None:
        _TTFT_HIST.observe(req.first_token_at - req.submitted_at, tags=tags)
    if (req.finished_at is not None and req.first_token_at is not None
            and len(req.out_ids) > 1):
        decode_s = req.finished_at - req.first_token_at
        if decode_s > 0:
            _DECODE_HIST.observe((len(req.out_ids) - 1) / decode_s,
                                 tags=tags)


def _pow2_width(n: int) -> int:
    """Smallest power of two >= max(1, n): THE width-bucketing rule for
    fused page dispatches — COW pair batches, donation gathers,
    adoption scatters, and the decode table view all share it, so their
    compiled-program width buckets cannot silently diverge."""
    width = 1
    while width < n:
        width *= 2
    return width


def _ring_pctls(ring, tail: float = 0.95) -> tuple[float, float]:
    """(p50, the `tail` quantile) of a bounded sample ring, rounded for
    JSON metrics."""
    s = sorted(ring)
    return (round(s[len(s) // 2], 3),
            round(s[max(0, math.ceil(len(s) * tail) - 1)], 3))


# The engine tick's phases, in `_step`'s order (LLMEngine._phase). Flat:
# at most one is open at a time. Host-only phases touch no device;
# `*.dispatch` hand a program to the runtime and return when it is
# queued; `*.pull` block until the device has produced what they fetch;
# `spec_verify` is the speculative tick's verify dispatch AND its pull.
_HOST_PHASES = ("admit", "prefill.build", "prefill.graduate", "plan", "emit")
_DISPATCH_PHASES = ("prefill.dispatch", "decode.dispatch")
_PULL_PHASES = ("prefill.pull", "decode.pull")
_PHASES = _HOST_PHASES + _DISPATCH_PHASES + _PULL_PHASES + ("spec_verify",)


# Why a decode window queued no step beyond its own (`_fit_window_pages`):
# the pool had no page for one more position; that position would reach
# max_len; the window is a single step (the host samples it); no slot
# has a window's worth of budget left after this one.
_STAND_DOWN = ("pages", "max_len", "k1", "budget")


@dataclasses.dataclass
class _InFlight:
    """One decode step queued BEHIND a window whose tokens the host has
    read: `tokens` [B] and `key` are its outputs, still on the device;
    `mask` [B] bool marks the slots whose next token it computes (the
    window's slots that did not finish in it). For those the host's
    `tokens[slot]` at `positions[slot]` is that step's INPUT: the device
    is one position ahead of the host until the next window's pull
    brings `tokens` back as its first row.

    `owed`: requests whose LAST token by `max_tokens` this step computes,
    by the slot they held. Whatever that token is, the request is over
    with it, so its slot and pages went back when the window before the
    step was read (a tick sooner than the token can be; `_release`), and
    the token is handed to the request when the step is."""
    tokens: Any
    key: Any
    mask: np.ndarray
    owed: dict = dataclasses.field(default_factory=dict)


class _Heartbeat:
    """The engine's second clock: a thread that sleeps `INTERVAL_S` at a
    time and notes how LATE each wake-up was. A phase turn that is long
    while this clock is on time is the engine waiting (for the device,
    the runtime, a transfer); a turn that is long while this clock is as
    late is the GIL held (a full collection, a C call that keeps it) or
    the process, or the machine, standing still. It writes no
    profiler annotation and no span: its stamps are `perf_counter`, the
    clock of `GenRequest`'s stamps and of the tick's account. `clock` and
    `sleep` are for tests. `run`/`beat` are the heartbeat thread's;
    `reset`/`late_within`/`snapshot` anyone's."""

    INTERVAL_S = 0.010
    _KEPT = 64          # late wake-ups remembered for `late_within`

    def __init__(self, clock=time.perf_counter, sleep=time.sleep):
        self._clock, self._sleep = clock, sleep
        self._lock = threading.Lock()
        self._due: float | None = None   # when the sleep under way should end
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.late_max_s = 0.0
            # Lateness beyond one interval, summed, and those wake-ups
            # as (woke at, seconds late): under that a wake-up is the
            # scheduler's own jitter or another thread's turn at the GIL.
            self.late_s = 0.0
            self._late: "collections.deque[tuple[float, float]]" = (
                collections.deque(maxlen=self._KEPT))

    def run(self, stop: threading.Event) -> None:
        while not stop.is_set():
            self.beat()

    def beat(self) -> None:
        due = self._clock() + self.INTERVAL_S
        with self._lock:
            self._due = due
        self._sleep(self.INTERVAL_S)
        now = self._clock()
        late = now - due
        with self._lock:
            self._due = None
            if late > self.late_max_s:
                self.late_max_s = late
            if late > self.INTERVAL_S:
                self.late_s += late
                self._late.append((now, late))

    def late_within(self, t0: float, t1: float) -> float:
        """The longest stretch of [t0, t1] over which this clock stood
        still: the part of a late wake-up's lateness that lies inside it
        (the sleep under way counts as far as it is overdue: the engine
        thread may be back before this one). 0.0 where every wake-up in
        the interval came within one interval of its time."""
        with self._lock:
            spans = [(woke - late, woke) for woke, late in self._late]
            due = self._due
        now = self._clock()
        if due is not None and now - due > self.INTERVAL_S:
            spans.append((due, now))
        return max([0.0] + [min(b, t1) - max(a, t0) for a, b in spans])

    def snapshot(self) -> dict:
        """The `metrics()` keys this clock owns."""
        with self._lock:
            return {"heartbeat_late_ms_max": self.late_max_s * 1000.0,
                    "heartbeat_late_s": self.late_s}


class _TickAccount:
    """Where the engine thread's time went, over WHOLE ticks.

    A tick runs from one `begin()` (the engine's `admit` phase opening)
    to the next. Phase durations collect in the open tick and are folded
    into the totals when it closes, so `phase_s` and `tick_s` always
    cover the same ticks and their difference is time the engine spent
    in no phase. Ticks that dispatched nothing (the idle loop) are
    dropped with their phases: the account describes a working engine.

    Beside the sums, the extremes, folded with them: every phase's
    longest single turn (`phase_max_s`), the longest tick (`tick_max_s`)
    and a ring of tick lengths for `tick_ms_p50` / `_p99`, and the STALL
    LOG: the `_STALLS` longest phase turns since the reset, longest
    first, each with where it began on `perf_counter`, its tick's index
    and how much of it `heartbeat` stood still (`_Heartbeat.late_within`,
    asked once, as the turn enters the log). No threshold decides what
    enters: a sound run's log is its eight longest pulls, a stalled
    run's first entry is the stall.
    `begin`/`add` are the engine thread's; `reset`/`snapshot` anyone's."""

    _STALLS = 8
    _TICK_RING = 4096

    def __init__(self, heartbeat: _Heartbeat):
        self._lock = threading.Lock()
        self._heartbeat = heartbeat
        self._t0 = 0.0
        self._open_tick()
        self.reset()        # no tick is open yet: the first is not counted

    def _open_tick(self) -> None:
        self._cur_s = dict.fromkeys(_PHASES, 0.0)
        self._cur_n = dict.fromkeys(_PHASES, 0)
        self._cur_max = dict.fromkeys(_PHASES, 0.0)
        # The open tick's turns longer than the log's shortest entry:
        # (seconds, began at, phase).
        self._cur_long: list[tuple[float, float, str]] = []

    def reset(self) -> None:
        with self._lock:
            self.phase_s = dict.fromkeys(_PHASES, 0.0)
            self.phase_n = dict.fromkeys(_PHASES, 0)
            self.phase_max_s = dict.fromkeys(_PHASES, 0.0)
            self.ticks = self.decode_ticks = 0
            self.tick_s = self.decode_tick_s = self.tick_max_s = 0.0
            self._tick_ms: "collections.deque[float]" = collections.deque(
                maxlen=self._TICK_RING)
            self._stalls: list[dict] = []
            self._stall_floor = 0.0     # what a turn must beat to enter
            # The open tick began before the reset: it is not counted.
            self._stale = True

    def begin(self, now: float) -> None:
        with self._lock:
            cur_s, cur_n = self._cur_s, self._cur_n
            decoded = cur_n["decode.dispatch"] + cur_n["spec_verify"]
            if not self._stale and (decoded or cur_n["prefill.dispatch"]):
                dt = now - self._t0
                self._log_stalls(self.ticks)
                self.ticks += 1
                self.tick_s += dt
                self.tick_max_s = max(self.tick_max_s, dt)
                self._tick_ms.append(dt * 1000.0)
                if decoded:
                    self.decode_ticks += 1
                    self.decode_tick_s += dt
                for name in _PHASES:
                    self.phase_s[name] += cur_s[name]
                    self.phase_n[name] += cur_n[name]
                    if self._cur_max[name] > self.phase_max_s[name]:
                        self.phase_max_s[name] = self._cur_max[name]
            self._stale = False
            self._t0 = now
            self._open_tick()

    def _log_stalls(self, tick: int) -> None:
        """Fold the closing tick's long turns into the stall log."""
        full = self._STALLS
        for seconds, t0, name in sorted(self._cur_long, reverse=True)[:full]:
            if len(self._stalls) >= full and seconds <= self._stall_floor:
                break
            late = self._heartbeat.late_within(t0, t0 + seconds)
            self._stalls.append({"phase": name, "t_start": t0,
                                 "ms": seconds * 1000.0, "tick": tick,
                                 "late_ms": late * 1000.0})
        self._stalls.sort(key=lambda e: -e["ms"])
        del self._stalls[full:]
        if len(self._stalls) == full:
            self._stall_floor = self._stalls[-1]["ms"] / 1000.0

    def add(self, name: str, t0: float, seconds: float) -> None:
        """One turn of phase `name`, begun at `t0`."""
        with self._lock:
            self._cur_s[name] += seconds
            self._cur_n[name] += 1
            if seconds > self._cur_max[name]:
                self._cur_max[name] = seconds
            if seconds > self._stall_floor:
                self._cur_long.append((seconds, t0, name))

    def snapshot(self) -> dict:
        """The `metrics()` keys this account owns."""
        with self._lock:
            phase_s, phase_n = dict(self.phase_s), dict(self.phase_n)
            tick_s, ticks = self.tick_s, self.ticks
            decode_tick_s, decode_ticks = self.decode_tick_s, self.decode_ticks
            tick_ms = list(self._tick_ms)
            out = {"phase_max_s": dict(self.phase_max_s),
                   "tick_ms_max": round(self.tick_max_s * 1000.0, 3),
                   "stalls": [dict(e) for e in self._stalls]}
        out.update(ticks=ticks, tick_s=tick_s, phase_s=phase_s,
                   phase_n=phase_n)
        out["tick_ms_p50"], out["tick_ms_p99"] = (
            _ring_pctls(tick_ms, 0.99) if tick_ms else (0.0, 0.0))
        if decode_ticks:
            out["tick_ms_mean"] = decode_tick_s / decode_ticks * 1000.0
        if tick_s > 0:
            out["tick_host_share"] = (
                sum(phase_s[p] for p in _HOST_PHASES) / tick_s)
            out["tick_blocked_share"] = (
                sum(phase_s[p] for p in _PULL_PHASES) / tick_s)
        if phase_n["decode.dispatch"]:
            out["decode_dispatch_ms_mean"] = (
                phase_s["decode.dispatch"] / phase_n["decode.dispatch"]
                * 1000.0)
        return out


def _softmax_f64(row: np.ndarray) -> np.ndarray:
    z = row.astype(np.float64)
    z -= z.max()
    e = np.exp(z)
    return e / e.sum()


def spec_accept_tokens(rng, temperature: float, proposals, draft_probs,
                       verify_logits, n_prop: int, *,
                       verify_argmax=None) -> tuple[list[int], int]:
    """Speculative rejection sampling for ONE slot (Leviathan-style):
    accept draft proposal x_i with probability min(1, p_i(x_i) /
    q_i(x_i)); on the first rejection emit one sample from the residual
    distribution norm(max(p_i − q_i, 0)); after n_prop straight
    acceptances emit a bonus token from the target's next-position
    distribution. The emitted marginal at every position is EXACTLY the
    target distribution p, for any proposal distribution q — the
    correctness argument the distributional test pins.

    Greedy (temperature 0) degenerates to argmax-chain matching: every
    emitted token is the argmax of the target's own logits at its
    position, so the stream is byte-identical to non-speculative greedy
    decode by construction, however bad the draft is.

    proposals: [>= n_prop] draft tokens; draft_probs: [>= n_prop, V] the
    temperature-scaled distributions they were actually sampled from
    (q); verify_logits: [>= n_prop+1, V] target logits, row i scoring
    the token after chunk position i; n_prop: proposals to consider;
    verify_argmax: optional [>= n_prop+1] precomputed per-row argmax —
    the greedy branch needs nothing else, so an all-greedy tick can
    skip the full-logits device->host copy and pass only this.
    → (emitted tokens, length 1..n_prop+1; accepted proposal count)."""
    emitted: list[int] = []
    if temperature == 0.0:
        if verify_argmax is None:
            verify_argmax = [int(np.argmax(verify_logits[i]))
                             for i in range(n_prop + 1)]
        for i in range(n_prop):
            tgt = int(verify_argmax[i])
            emitted.append(tgt)
            if int(proposals[i]) != tgt:
                return emitted, i
        emitted.append(int(verify_argmax[n_prop]))
        return emitted, n_prop
    for i in range(n_prop):
        x = int(proposals[i])
        p = _softmax_f64(verify_logits[i] / temperature)
        q = draft_probs[i].astype(np.float64)
        if rng.random() * max(float(q[x]), 1e-30) < float(p[x]):
            emitted.append(x)
            continue
        resid = np.maximum(p - q, 0.0)
        z = resid.sum()
        # A vanishing residual means p ≈ q, where acceptance is ~certain
        # anyway — falling back to p keeps the marginal exact.
        pr = resid / z if z > 1e-12 else p
        emitted.append(int(rng.choice(len(pr), p=pr)))
        return emitted, i
    p = _softmax_f64(verify_logits[n_prop] / temperature)
    emitted.append(int(rng.choice(len(p), p=p)))
    return emitted, n_prop


@dataclasses.dataclass
class GenRequest:
    request_id: str
    prompt_ids: list[int]
    max_tokens: int
    temperature: float
    eos_id: int | None
    submitted_at: float
    # Original prompt length: prompt_ids grows past it on preemption
    # (recompute context = prompt + generated), so continuation export
    # needs the split point to avoid double-counting generated tokens.
    n_prompt: int = 0
    first_token_at: float | None = None
    finished_at: float | None = None
    # TTFT breakdown (engine-side wall clock): first/last prefill dispatch
    # for this request. One-shot prefill sets both around its single
    # dispatch; chunked prefill spreads them across scheduler ticks.
    first_chunk_at: float | None = None
    last_chunk_at: float | None = None
    # What the caller felt between tokens (LLMEngine._hand_over, once a
    # request a decode window, never once a token): when tokens were last
    # handed to it, the longest interval between two hand-overs since its
    # first token (a stall, a window it sat out, an admission's chunk
    # programs, a preemption), and the decode windows it lived through.
    last_emit_at: float | None = None
    max_gap_s: float = 0.0
    windows: int = 0
    # Admission aging: how many _admit rounds bypassed this request while
    # it sat page-blocked at the queue head. Past _ADMIT_BYPASS_LIMIT the
    # head blocks all lookahead until it admits (starvation guard).
    admit_bypasses: int = 0
    # Prefix-cache hit at admission: tokens served from cached pages
    # (prefill started at this offset instead of 0). Benchmarks split
    # TTFT warm-vs-cold on it.
    cached_tokens: int = 0
    # Memoized chunk-hash chain over prompt_ids (prefix_cache.extend_
    # chain): contexts only grow (preempt appends generated tokens) and
    # the chain is parent-chained, so a page-blocked request re-scanned
    # every admission round hashes each chunk once, not once per tick.
    prefix_hashes: list = dataclasses.field(default_factory=list)
    # KV page-set adoption hint (serve/kv_objects.py): descriptor from a
    # donor's handoff/export ({"keys", "chunk", "page_size",
    # "fingerprint", "n_tokens"}) — admission tries the adoption ladder
    # against it before cold prefill. None = no hint (cold path).
    kv: dict | None = None
    # Memoized adoption plan (resolved ONCE per request): a page-blocked
    # request is re-scanned every admission round, and re-resolving the
    # digest chain against the cluster index each time would put one
    # blocking GCS RPC per chain depth inside the engine tick. A cached
    # plan can go stale (entries swept mid-wait) — the bind's fetch
    # failures walk the ladder down, so staleness costs a rung, never
    # correctness.
    kv_plan: dict | None = None
    kv_plan_tried: bool = False
    # Set when THIS request's pages were donated on handoff/export: the
    # descriptor the consumer forwards to the next replica.
    kv_handoff: dict | None = None
    out_ids: list[int] = dataclasses.field(default_factory=list)
    truncated: bool = False   # finished early (capacity/unresumable preempt)
    # Exported off a draining/dying engine as a resumable continuation:
    # done is set, error is None, and the consumer (proxy / handle
    # stream) resubmits (prompt, out_ids) to a surviving replica.
    migrated: bool = False
    # Last stream_read touch (perf_counter): drain's read-out wait only
    # holds for streams someone is actually consuming — an abandoned
    # record (client vanished mid-stream) must not cost a scale-down the
    # full drain window.
    last_read_at: float | None = None
    stream: "queue.Queue | None" = None
    done: "threading.Event" = dataclasses.field(
        default_factory=threading.Event)
    error: str | None = None


class LLMEngine:
    """Slot-based continuous batching: one engine thread drives a model
    family's paged programs (models/serving.py; models/paged_kv.py for a
    gpt) over a fixed set of slots and one paged KV pool. What its options
    resolve to is serve/llm_options.py's; which KV pages are free, shared
    or bound to a slot is serve/page_pool.py's (`self.pool`). The device
    pool (`self.cache`) and the scheduler are here."""

    def __init__(self, cfg, params=None, *, n_slots: int = 8,
                 max_len: int = 2048, seed: int = 0,
                 decode_block: int | None = None,
                 kv_mode: str | None = None, page_size: int | None = None,
                 n_pages: int | None = None, attn_impl: str | None = None,
                 prefill_chunk: int | None = None,
                 prefill_token_budget: int | None = None,
                 prefix_cache: bool | None = None,
                 prefix_cache_pages: int | None = None,
                 spec_draft=None, spec_k: int | None = None,
                 spec_draft_params=None, tp: int | None = None,
                 pool_role: str | None = None,
                 kv_transfer: bool | None = None, kv_store=None,
                 weight_dtype: str | None = None,
                 kv_dtype: str | None = None,
                 prefill_width_bucketing: bool | None = None,
                 warmup: bool | None = None):
        import jax

        from ray_tpu.models import gpt
        from ray_tpu.models import paged_kv as _paged
        from ray_tpu.models.serving import family_of
        from ray_tpu.serve.llm_options import resolve_options
        from ray_tpu.serve.page_pool import PagePool, pages_for

        if kv_mode not in (None, "paged"):
            # A keyword only because benchmarks/harness/serve_cell.py
            # `build_engine` passes "paged" and only a `benchmark` PR may
            # edit that file (ROADMAP Queue 2 B drops it from both sides).
            raise ValueError(
                f"kv_mode={kv_mode!r}: the dense KV cache was removed in "
                "PR 64, the engine serves from the paged pool only")
        self.cfg = cfg
        # The device side of this configuration's model family: pool,
        # programs, sharding rules (models/serving.py). The draft model
        # of speculative decoding is always a gpt.
        self._family = fam = family_of(cfg)
        self.n_slots = n_slots
        self.max_len = max_len
        self.params = (params if params is not None
                       else fam.model.init_params(cfg, jax.random.key(seed)))
        # Resolution and every refusal: serve/llm_options.py.
        o = resolve_options(
            cfg, max_len=max_len, spec_draft_params=spec_draft_params,
            pool_role=pool_role, page_size=page_size,
            attn_impl=attn_impl, prefill_chunk=prefill_chunk,
            prefill_token_budget=prefill_token_budget,
            prefix_cache=prefix_cache,
            prefix_cache_pages=prefix_cache_pages, spec_draft=spec_draft,
            spec_k=spec_k, tp=tp, kv_transfer=kv_transfer,
            weight_dtype=weight_dtype, kv_dtype=kv_dtype,
            prefill_width_bucketing=prefill_width_bucketing, warmup=warmup,
            decode_block=decode_block)
        page_size, prefill_chunk = o.page_size, o.prefill_chunk
        spec_draft, draft_cfg = o.spec_draft, o.draft_cfg
        self.weight_dtype = o.weight_dtype
        self.kv_dtype = o.kv_dtype
        self.mesh = o.mesh
        self.tp = o.tp
        self.pool_role = o.pool_role
        self.kv_transfer = bool(o.kv_transfer)
        self._kv_transfer_disabled_reason = o.kv_transfer_disabled_reason
        if o.kv_transfer_disabled_reason:
            logger.warning("llm_kv_transfer soft-disabled: %s",
                           o.kv_transfer_disabled_reason)
        # What each of these means: core/config.py's `llm_*` knobs.
        self.attn_impl = o.attn_impl
        self.prefill_width_bucketing = bool(o.prefill_width_bucketing)
        self._warmup_on_start = bool(o.warmup)
        self._warmed = False
        self.prefill_chunk = prefill_chunk
        self.prefill_budget = o.prefill_token_budget
        # Any prompt the cache and the pool can hold is admissible.
        self._prompt_cap = max_len - 1
        # Heights of the chunk programs, constants of the engine.
        # Where dispatches are bucketed by table width, ONE height:
        # the full chunks one budget holds (an idle tick's floor of
        # one chunk included), never more rows than slots; a tick
        # with more rows of a width runs the program again
        # (_dispatch_chunks), because a taller program would carry
        # the few rows of one width among inert ones and multiply
        # the width ladder. Where every dispatch runs at ONE table
        # width a chunk program is a pass over the weights whatever
        # it carries, so a prompt should be one program: TWO
        # heights, half a tick's allowance in rows (budget x the
        # window's steps / 2 chunks) and half of that, neither
        # lower than the one budget's rows above (what a tick
        # beside a window of one step places; where the window is
        # that short, or the slots that few, the two are one), and
        # the head in both (a program almost always holds a final
        # row: the headless twin would be two programs more to load
        # for ~1 ms of head a long prompt's interior program).
        full_chunks = -(-max(o.prefill_token_budget, prefill_chunk)
                        // prefill_chunk)
        rows = min(n_slots, full_chunks)
        if self.prefill_width_bucketing:
            self.chunk_heights: tuple[int, ...] = (rows,)
            self.chunk_heads: tuple[bool, ...] = (False, True)
        else:
            tall = min(n_slots, max(
                rows, o.prefill_token_budget * max(1, o.decode_block)
                // (2 * prefill_chunk)))
            self.chunk_heights = tuple(sorted(
                {max(rows, -(-tall // 2)), tall}))
            self.chunk_heads = (True,)
        # HBM holds `n_pages` pages TOTAL, not n_slots × max_len: slot
        # count stops being bounded by the worst-case sequence length
        # (models/paged_kv.py). Default pool = half the slots' worst
        # case, at least one slot's.
        self.page_size = page_size
        self.max_pages_per_slot = pages_for(max_len - 1, page_size)
        if n_pages is None:
            n_pages = max(self.max_pages_per_slot + 1,
                          (n_slots * self.max_pages_per_slot) // 2)
        self.n_pages = n_pages
        # A ring of pages a slot is sized by what one chunk dispatch
        # can write of one prompt.
        ring = ({"dispatch_tokens": self.chunk_rows * prefill_chunk}
                if fam.slot_ring else {})
        self.cache = fam.init_pool(cfg, n_pages, page_size, n_slots,
                                   self.kv_dtype, **ring)
        # Host-side page accounting (serve/page_pool.py).
        self.pool = PagePool(n_pages, page_size, n_slots,
                             self.max_pages_per_slot)
        # Speculative decoding: the draft model keeps its OWN page pool
        # (shaped to the draft config) but shares the target's page
        # TABLES and cursors — draft pool row p mirrors target pool row
        # p token-for-token (prefill chunks, decode writes, and COW
        # copies are all mirrored), so target-side page accounting,
        # prefix sharing, and rollback govern both pools and the draft
        # never holds a reference of its own.
        self.spec_k = int(o.spec_k) if spec_draft else 0
        self.spec_draft_name = (
            spec_draft if isinstance(spec_draft, str)
            else "custom" if spec_draft else "")
        self.draft_cfg = draft_cfg
        self.draft_params = None
        self.draft_cache = None
        if spec_draft:
            self.draft_params = (
                spec_draft_params if spec_draft_params is not None
                else gpt.init_params(draft_cfg, jax.random.key(seed + 1)))
            self.draft_cache = _paged.init_paged_kv(
                draft_cfg, self.n_pages, self.page_size,
                kv_dtype=self.kv_dtype)
            # Acceptance draws (temperature>0 rejection sampling) come
            # from a host-side generator: they gate host control flow
            # (emit / rollback), so deviceifying them buys nothing.
            self._spec_rng = np.random.default_rng(seed)
        if fam.lay_out is not None:
            # The tree as the family's programs want it laid out
            # (models/serving.py), before it is placed: a host array is
            # cut on the host.
            self.params = fam.lay_out(cfg, self.params)
        if self.weight_dtype == "int8":
            # One-time compression at load: matmul planes become int8 +
            # per-output-channel fp32 scale vectors (gpt.QUANT_RULES).
            # Idempotent, so pre-quantized checkpoints (or an int8
            # spec_draft_params next to a bf16 target) pass through.
            # BEFORE the tp shard below: the scale rules in
            # gpt.partition_rules shard the new leaves alongside their
            # planes, so quantize-then-shard is the only order.
            self.params = fam.model.quantize_params(self.params)
            if spec_draft:
                self.draft_params = gpt.quantize_params(self.draft_params)
        if self.tp > 1:
            # Shard ONCE at load onto the mesh the options built: params
            # (target + draft) per gpt.partition_rules, page pools along
            # the head axis. Every byte of host-side scheduler/allocator
            # state (page ids, tables, cursors) is shard-invariant.
            from ray_tpu.models import partition as _partition

            self.params = _partition.shard_by_rules(
                self.mesh, fam.model.partition_rules(), self.params)
            self.cache = _partition.shard_by_rules(
                self.mesh, fam.pool_partition_rules, self.cache)
            if spec_draft:
                self.draft_params = _partition.shard_by_rules(
                    self.mesh, gpt.partition_rules(), self.draft_params)
                self.draft_cache = _partition.shard_by_rules(
                    self.mesh, _paged.KV_POOL_PARTITION_RULES,
                    self.draft_cache)
        else:
            # Weights loaded from a checkpoint are host arrays; place
            # them once — left on the host, every dispatch would upload
            # the whole model again (a no-op for device arrays).
            self.params = jax.device_put(self.params)
            if spec_draft:
                self.draft_params = jax.device_put(self.draft_params)
        # After the weights and the pool are placed: the window's own two
        # small programs are loaded against them.
        self._bind_programs()
        self._spec_accept_ewma: float | None = None
        # Prefix cache (serve/prefix_cache.py): refcounted COW page
        # sharing across requests — admission binds the longest cached
        # chunk-aligned prefix and chunked prefill starts at the first
        # cold token. None = off (exact pre-cache engine behavior).
        self.prefix_cache = None
        if o.prefix_cache:
            from ray_tpu.serve.prefix_cache import PrefixCache

            budget = (min(o.prefix_cache_pages, self.n_pages)
                      if o.prefix_cache_pages
                      else max(1, self.n_pages // 2))
            self.prefix_cache = PrefixCache(
                chunk=prefill_chunk, page_size=page_size,
                max_pages=budget, ref_page=self.pool.ref_pages,
                unref_page=self.pool.unref_pages)
        # KV page-set store (serve/kv_objects.py): donation target +
        # adoption source. Backend selection gates on an ALREADY
        # attached client (never _ensure_client — constructing an
        # engine off-cluster must not boot a cluster); off-cluster
        # engines share the process-global LocalKVStore so in-process
        # donor/adopter pairs exercise the full ladder in unit tests.
        self._kv_store = None
        self._kv_fingerprint = ""
        self._kv_donor = ""
        # page -> refs held by an IN-FLIGHT donation (device gather +
        # store put): the "in-flight-donated" category of the page-
        # accounting closure (free + live + cached + exporting-only
        # == total). Empty between ticks; a chaos raise at
        # serve.kv.donate is exactly when the closure must still hold,
        # so it is rolled back in a finally.
        self._kv_exporting: dict[int, int] = {}
        self._kv_donated: "OrderedDict[str, int]" = OrderedDict()
        self._kv_summary_max = 0
        if self.kv_transfer:
            import os as _os

            from ray_tpu.serve import kv_objects as _kvo

            self._kvo = _kvo
            try:
                from ray_tpu import api as _api

                aid = _api.get_runtime_context().get_actor_id()
            except Exception:  # graftlint: disable=EXC-SWALLOW (outside an actor: the pid-based donor id below is the designed fallback)
                aid = None
            self._kv_donor = aid or f"local:{_os.getpid()}"
            self._kv_store = (kv_store if kv_store is not None
                              else _kvo.get_store(donor=self._kv_donor))
            self._kv_fingerprint = _kvo.engine_fingerprint(
                cfg, page_size, prefill_chunk, draft_cfg,
                kv_dtype=self.kv_dtype)
            from ray_tpu.core.config import runtime_config as _rc

            # Donated-chain summary (descriptor-less warm discovery):
            # chain head (16-hex prefix of the depth-1 digest — the
            # router's affinity-key space) → deepest depth donated.
            # Newest-last and budget-bounded (serve_kv_summary_max), it
            # is BOTH the kv_summary exported via load_snapshot() for
            # the controller's routing push AND the insert-on-free
            # donation memo (a chain already donated at >= depth skips
            # even the store resolve on repeat traffic).
            self._kv_summary_max = max(
                1, int(_rc().serve_kv_summary_max))
        # slot -> pinned CacheEntry while the slot is live (released on
        # free/preempt), and the tick's pending COW (src, dst) pairs,
        # flushed in one fused device copy per tick (_apply_cow).
        self._slot_entry: dict[int, Any] = {}
        self._cow_pairs: list[tuple[int, int]] = []
        self._evictions_synced = 0
        self.tokens = np.zeros(n_slots, np.int32)
        self.positions = np.zeros(n_slots, np.int32)
        self.temps = np.zeros(n_slots, np.float32)
        # The decode step in flight across the tick boundary (_InFlight),
        # None when the device's queue holds no step the host has not
        # read: the parent state every path outside the window works in.
        self._carry: _InFlight | None = None
        # Decode-window sizes (largest first): one window advances all
        # slots k tokens with on-device sampling and ONE host sync,
        # amortizing the host↔device round trip per token (a window is k
        # dispatches of one program, whatever k).
        self.decode_block = max(1, o.decode_block)
        self._k_ladder = tuple(
            k for k in (64, 32, 16, 8, 4, 2) if k <= self.decode_block)
        self.slot_req: list[GenRequest | None] = [None] * n_slots
        self.pending: "queue.Queue[GenRequest]" = queue.Queue()
        # Engine-thread-local FIFO drained BEFORE `pending`: requests that
        # failed page back-pressure or were preempted keep their place at
        # the head instead of rotating to the tail (starvation guard).
        self._deferred: "collections.deque[GenRequest]" = collections.deque()
        # Chunked-prefill scheduler state: slots whose prompt is still
        # entering the pool (admission order = service order, FCFS), and
        # each one's prefill progress in tokens.
        self._prefilling: list[int] = []
        self._chunk_pos: dict[int, int] = {}
        # Width-bucketed dispatch observability: per-dispatch width ring
        # (p50/max for metrics()/load_snapshot()) and cumulative
        # per-width dispatch counts — the host-side mirror of the
        # llm_prefill_dispatch_total{width} counter.
        self._dispatch_width_ring: "collections.deque[int]" = (
            collections.deque(maxlen=4096))
        self._dispatch_width_counts: dict[int, int] = {}
        # Table width -> pages a kv block of the prefill kernel holds,
        # and of the decode kernel.
        self._block_pages_at: dict[int, int] = {}
        self._decode_block_at: dict[int, int] = {}
        self._rng_key = jax.random.key(seed)
        # Per-token decode step times (window wall time / window size),
        # milliseconds — a bounded ring so metrics() can report p50/p95
        # step latency for the measured window.
        self._step_ms: "collections.deque[float]" = collections.deque(
            maxlen=4096)
        # Engine-side TTFT ring (submit → first token, ms), and the ring
        # of finished requests' longest waits between two hand-overs of
        # tokens (GenRequest.max_gap_s, ms).
        self._ttft_ms: "collections.deque[float]" = collections.deque(
            maxlen=4096)
        # Warm/cold TTFT split (prefix cache): warm = admission bound a
        # cached prefix (cached_tokens > 0).
        self._ttft_warm_ms: "collections.deque[float]" = collections.deque(
            maxlen=4096)
        self._ttft_cold_ms: "collections.deque[float]" = collections.deque(
            maxlen=4096)
        self._emit_gap_ms: "collections.deque[float]" = collections.deque(
            maxlen=4096)
        # Load EWMAs (flight recorder): smoothed TTFT / decode-rate /
        # prefill-budget-utilization signals for load_snapshot() — what
        # the least-loaded router and autoscaler consume. Updated under
        # the metrics lock at the points the raw samples already exist.
        self._ttft_ewma_ms: float | None = None
        self._decode_ewma_tok_s: float | None = None
        self._budget_util_ewma: float | None = None
        self._ttft_seq = 0                    # sampled TTFT-breakdown spans
        self._step_tags: dict | None = None   # lazy: replica id + impl
        # The tick's account of itself (see _phase), the second clock it
        # reads a stall's kind from (its thread runs from start() to
        # stop()), and the sequence numbers of the two phases that are
        # also sampled operator spans.
        self._heartbeat = _Heartbeat()
        self._ticks = _TickAccount(self._heartbeat)
        self._span_seq = {"decode_window": 0, "spec_verify": 0}
        self._annotate = jax.profiler.TraceAnnotation
        # High-water mark of requests still owed a first token, kept
        # like the pool's min_free (engine thread writes, reset re-bases).
        self._awaiting_max = 0
        self._shutdown = threading.Event()
        self._fatal: str | None = None
        # Drain protocol (replica scale-down / version roll): draining
        # engines reject new submits, finish in-flight work, and export
        # whatever the drain window didn't cover as resumable
        # continuations (see drain()).
        self._draining = False
        # Tick fence for drain(): a request popped from `pending` during
        # admission is invisible to slot/queue checks until it binds a
        # slot — the quiescence verdict is only stable between ticks.
        self._mid_tick = False
        self._thread: threading.Thread | None = None
        self._heartbeat_thread: threading.Thread | None = None
        self._lock = threading.Lock()
        # Serializes start()/stop(): two concurrent start() calls would
        # both see _thread is None and spawn two engine loops. Separate
        # from _lock — stop() joins the loop thread while holding it, and
        # the loop thread takes _lock on every tick.
        self._lifecycle_lock = threading.Lock()
        self.stats = {"requests": 0, "tokens_generated": 0,
                      "ttft_sum": 0.0, "completed": 0,
                      # Engine-side split (device dispatch + sync wall
                      # time, measured INSIDE the engine loop): engine
                      # capability apart from client-path RTT.
                      "prefill_time_s": 0.0, "prefill_tokens": 0,
                      "prefill_chunks": 0, "prefill_dispatches": 0,
                      # Row positions of those dispatches, inert ones
                      # included (`prefill_row_fill`'s denominator).
                      "prefill_rows_dispatched": 0,
                      # Prompt tokens the ticks that had prefill work
                      # waiting were allowed to place (the budget times
                      # the window's steps): prefill_tokens over it is
                      # `prefill_allowance_used`.
                      "prefill_allowance": 0,
                      # Pages the chunk rows attended, and pages the
                      # kernel's live kv blocks held for them
                      # (`prefill_block_fill`).
                      "prefill_pages_live": 0, "prefill_pages_fetched": 0,
                      # Pages the decoding slots attend at a window's
                      # first step, the pages the decode kernel's live
                      # kv blocks hold for them (`decode_block_fill`),
                      # and the columns of the window's table, every
                      # slot's (`decode_live_column_share`).
                      "decode_pages_live": 0, "decode_pages_fetched": 0,
                      "decode_columns": 0,
                      "decode_time_s": 0.0, "decode_windows": 0,
                      # Of those windows, the ones that left one more
                      # step in flight (_fit_window_pages), the others by
                      # what stood it down, and the rows of such steps
                      # thrown away because their slot had finished.
                      "lookahead_windows": 0,
                      **{"lookahead_stood_down_" + cause: 0
                         for cause in _STAND_DOWN},
                      "lookahead_rows_dropped": 0,
                      # Windows whose step programs were handed a
                      # temperature above 0, so that their sampling
                      # step drew (`_upload_temps`): `decode_draw_share`.
                      "decode_windows_drawn": 0,
                      "slot_step_sum": 0, "slot_cap_sum": 0,
                      "preemptions": 0,
                      # Prefix-cache lifecycle (zeros unless enabled).
                      "prefix_hits": 0, "prefix_misses": 0,
                      "prefix_evictions": 0, "cow_copies": 0,
                      "prefix_cached_tokens": 0,
                      # Speculative decoding (zeros unless enabled):
                      # proposed/accepted draft tokens, verify passes
                      # (ticks × nothing — one per tick), per-slot verify
                      # steps, and tokens actually emitted through the
                      # accept path (accepted + correction/bonus).
                      "spec_proposed": 0, "spec_accepted": 0,
                      "spec_ticks": 0, "spec_slot_steps": 0,
                      "spec_emitted": 0,
                      # KV page-set transfer (zeros unless enabled):
                      # donations/pages leaving this engine, adoptions
                      # (full + partial) binding donated pages instead
                      # of re-prefilling, tokens served from adopted
                      # pages, and ladder falls to the re-prefill rung.
                      "kv_donations": 0, "kv_donated_pages": 0,
                      "kv_adoptions": 0, "kv_partial_adoptions": 0,
                      "kv_adopted_tokens": 0, "kv_adopt_failures": 0,
                      # Request-path digest index lookups (adopt-plan
                      # resolve rounds): the descriptor-less discovery
                      # bench pins this at 0 for un-hinted traffic —
                      # warm discovery must ride the routing push, not
                      # per-request GCS RPCs.
                      "kv_digest_lookups": 0,
                      # Families with experts (zeros otherwise), from
                      # the decode programs' on-device counters, pulled
                      # with a window's tokens: (layer, step) pairs run,
                      # experts that had a row, the fullest expert's
                      # rows, rows routed — summed over those pairs; of
                      # the rows routed, the choices that landed on a
                      # held expert, and of those the ones the expert
                      # layer's first block did not take (one more turn
                      # over the experts; 0 near an even router); and
                      # the choices a router's bias moved out of the
                      # unbiased top-k (0: no bias).
                      "moe_layer_steps": 0,
                      "moe_experts_touched_sum": 0, "moe_rows_max_sum": 0,
                      "moe_rows_routed": 0, "moe_rows_held": 0,
                      "moe_rows_over": 0, "moe_rows_bias_moved": 0}
        # The decode programs' counters run on, wrapping uint32; the
        # window's share is the difference from the last pull.
        self._moe_seen: dict | None = None

    def _bind_programs(self) -> None:
        """`self._rt`: the jax / model-fn surface the hot loop touches,
        resolved once (a tick must not re-execute import machinery).
        Every jitted callable goes through compile_watch.wrap, so XLA
        compiles are attributed to the owning program at /metrics
        (jax_compiles_total{fn}) and recompile churn trips the
        recompile-storm alarm instead of hiding in step-time noise.

        The set is the model family's (models/serving.py): for a gpt,
        models/paged_kv.py's; at tp > 1 the programs are their shard_map
        twins (`*_tp`) with the mesh bound as a static kwarg, under the
        SAME compile-watch names: every call site is unchanged."""
        import types

        import jax
        import jax.numpy as jnp

        from ray_tpu import compile_watch as _cw

        _cw.install()
        programs = self._family.programs(self.tp, self.mesh)
        # The decode window's extra keyword for a family whose decode
        # programs count experts' rows in the pool: where the counters
        # go, fetched with the window's tokens.
        self._window_counters = (
            {"counters": self._note_device_counters}
            if self._family.expert_counters else {})
        from ray_tpu.models import paged_kv as _paged

        # `sample_token` is every family's: the host's draw from one row
        # of logits (a prompt's first token, the one-step tick).
        self._rt = types.SimpleNamespace(
            jax=jax, jnp=jnp,
            sample_token=_cw.wrap(_paged.sample_token, "sample_token"),
            **{name: _cw.wrap(fn, name) for name, fn in programs.items()})
        # The two programs a window that leaves a step in flight adds to
        # the step program (models/paged_kv.py `join_window`, `snapshot`),
        # [B]-sized, loaded here so that no request meets them cold. Each
        # is called as the tick will call it: `carried` is a step's
        # output, committed to its devices exactly when some weight or
        # pool leaf is (a committed operand is another program to jit).
        self._rt.join_window = _cw.wrap(_paged.join_window, "join_window")
        placed = next((a for a in jax.tree.leaves((self.params, self.cache))
                       if a.committed), None)
        zeros = np.zeros(self.n_slots, np.int32)
        carried = zeros
        if placed is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            carried = jax.device_put(
                carried, NamedSharding(self.mesh, PartitionSpec())
                if self.tp > 1 else placed.sharding)
        with _cw.warmup_scope():
            self._rt.join_window(
                jnp.asarray(np.zeros(self.n_slots, bool)),
                jnp.asarray(carried), jnp.asarray(zeros), jnp.asarray(zeros))
            if self._family.expert_counters:
                _paged.snapshot(self.cache["moe_counters"])

    def _row_slots(self, slots) -> dict:
        """The chunk program's extra keyword for a family whose pool
        carries a per-slot state or a ring of pages a slot: the slot of
        each row."""
        if not (self._family.slot_state or self._family.slot_ring):
            return {}
        return {"slots": self._rt.jnp.asarray(slots)}

    def _note_device_counters(self, totals: dict) -> None:
        """`totals`: the decode programs' running uint32 counters after a
        window. The stats take what was added since the last pull (a
        window of one step pulls nothing: its share arrives with the
        next window's)."""
        seen = self._moe_seen or dict.fromkeys(totals, 0)
        self._moe_seen = totals
        delta = {k: (v - seen[k]) % (1 << 32) for k, v in totals.items()}
        with self._lock:
            self.stats["moe_layer_steps"] += delta["layer_steps"]
            self.stats["moe_experts_touched_sum"] += delta["experts_touched"]
            self.stats["moe_rows_max_sum"] += delta["rows_max"]
            self.stats["moe_rows_routed"] += delta["rows_routed"]
            # A family that holds every expert counts no share of its
            # own, and its expert layer's block is every row.
            self.stats["moe_rows_held"] += delta.get(
                "rows_held", delta["rows_routed"])
            self.stats["moe_rows_over"] += delta.get("rows_over", 0)
            self.stats["moe_rows_bias_moved"] += delta.get(
                "rows_bias_moved", 0)

    # ------------------------------------------------------------- API

    def submit(self, prompt_ids: list[int], *, max_tokens: int = 64,
               temperature: float = 0.0, eos_id: int | None = None,
               stream: bool = False,
               generated_ids: list[int] | None = None,
               request_id: str | None = None,
               kv: dict | None = None,
               prefix_hashes: list | None = None,
               prefix_chunk: int = 0) -> GenRequest:
        """Queue one generation request.

        `generated_ids` resumes a continuation migrated off another
        replica (drain export / death failover): the already-emitted
        tokens are teacher-forced — they join the prefill context, seed
        out_ids (so max_tokens stays a TOTAL output budget and the
        stream cursor splices exactly), and are never re-emitted. Same
        math as the in-replica preempt-by-recompute path, so a greedy
        continuation is byte-identical to the uninterrupted run.

        `kv` is a donor's page-set descriptor (handoff / drain export):
        admission walks the adoption ladder against it — adopt the
        donated pages if the refs resolve, partial-adopt a surviving
        prefix, else fall through to the teacher-forced re-prefill
        above. `prefix_hashes` (+ `prefix_chunk`, the granularity they
        were computed at) seeds the request's memoized chunk-hash chain
        from the source replica's export, so a resumed continuation
        never re-hashes its full context; a memo at a different chunk
        granularity is silently dropped (wrong key space).
        """
        # An empty prompt has no last-token logits to sample from: it
        # would never build a chunk row and wedge its slot forever.
        if not prompt_ids:
            raise ValueError("prompt_ids must be non-empty")
        if temperature < 0.0:
            # Every sampling path branches on "0 = greedy, >0 = sample";
            # a negative value would invert the softmax on some paths
            # and be treated as greedy on others (the on-device draft
            # loop clamps at <= 0) — reject it at the boundary.
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        generated = [int(t) for t in (generated_ids or [])]
        context = list(prompt_ids) + generated
        too_big = (len(context) > self._prompt_cap
                   or self.pool.pages_for(len(context)) > self.n_pages)
        req = GenRequest(
            request_id=request_id or uuid.uuid4().hex[:12],
            prompt_ids=context,
            n_prompt=len(prompt_ids),
            max_tokens=max_tokens,
            temperature=temperature,
            eos_id=eos_id,
            submitted_at=time.perf_counter(),
            out_ids=generated,
            stream=queue.Queue() if stream else None,
        )
        if prefix_hashes and prefix_chunk == self.prefill_chunk:
            try:
                req.prefix_hashes = [
                    bytes.fromhex(h) if isinstance(h, str) else bytes(h)
                    for h in prefix_hashes]
            except (ValueError, TypeError):
                # A malformed memo is only a lost optimization — the
                # chain rebuilds from the tokens.
                req.prefix_hashes = []
        if kv and self._kv_store is not None:
            req.kv = dict(kv)
        if generated and (
                len(generated) >= max_tokens
                or (eos_id is not None and generated[-1] == eos_id)):
            # The continuation is already complete — the source replica
            # died/drained between emitting the final token and the
            # reader observing done. Finish it here instead of rejecting
            # (the consumer needs [DONE], not an error) or decoding past
            # eos (extra tokens the uninterrupted run never produced).
            self._finish_presubmit(req, truncated=False)
            return req
        if too_big:
            if generated:
                # Mid-stream resume that no longer fits this engine's
                # caps: finish with what the client already has, flagged
                # truncated — the same contract as an in-replica preempt
                # whose regrown context stopped fitting (_preempt). An
                # error here would drop a live stream over a capacity
                # detail the client can't act on.
                self._finish_presubmit(req, truncated=True)
                return req
            if len(context) > self._prompt_cap:
                raise ValueError(
                    f"prompt too long: {len(context)} (cap "
                    f"{self._prompt_cap}: cache bound, chunked prefill)")
            # A prompt the pool can never cover would requeue forever.
            raise ValueError(
                f"prompt needs {self.pool.pages_for(len(context))} KV pages "
                f"but the pool only has {self.n_pages}")
        # The fatal/draining check and the enqueue must be atomic with the
        # death handler's / drain export's single pending drain, or a
        # submit racing them could enqueue after the drain and hang.
        with self._lock:
            if self._fatal is not None:
                raise RuntimeError(self._fatal)
            if self._draining:
                raise RuntimeError(
                    "replica draining: not accepting new requests")
            self.stats["requests"] += 1
            self.pending.put(req)
        return req

    def _finish_presubmit(self, req: GenRequest, *, truncated: bool) -> None:
        """Complete a request at submit time without queueing it — a
        resumed continuation that is already done (budget/eos reached on
        the source replica) or can no longer fit this engine's caps."""
        req.truncated = truncated
        req.finished_at = time.perf_counter()
        with self._lock:
            self.stats["requests"] += 1
            self.stats["completed"] += 1
        if req.stream is not None:
            req.stream.put(None)
        req.done.set()

    def generate(self, prompt_ids: list[int], **kw) -> list[int]:
        """Blocking convenience wrapper."""
        req = self.submit(prompt_ids, **kw)
        req.done.wait()
        if req.error:
            raise RuntimeError(req.error)
        return req.out_ids

    def _width_ladder(self) -> list[int]:
        """The pow-2 table widths chunk dispatches can occur at: {1, 2,
        4, …} up to and including `max_pages_per_slot` (which caps the
        bucket rule, so it appears even when it isn't itself a power of
        two). With width bucketing off there is exactly one width — the
        PR 4 full-width grid."""
        if not self.prefill_width_bucketing:
            return [self.max_pages_per_slot]
        widths, w = [], 1
        while w < self.max_pages_per_slot:
            widths.append(w)
            w *= 2
        widths.append(self.max_pages_per_slot)
        return widths

    @property
    def chunk_rows(self) -> int:
        """Height of the tallest chunk program: the most rows, so the
        most chunks of one prompt, one dispatch carries (a family's ring
        of pages is sized by it)."""
        return self.chunk_heights[-1]

    def chunk_programs(self) -> list[tuple[int, int, bool]]:
        """The (height, table width, head) of every `prefill_chunk_paged`
        program this engine dispatches: what `_dispatch_chunks` cuts a
        tick's rows into and what `warmup_compile` walks. Bucketed by
        width: one height at every width of the ladder, with and
        without the head. At one width: the two heights, head always."""
        return [(rows, width, head) for width in self._width_ladder()
                for rows in self.chunk_heights for head in self.chunk_heads]

    def _cut_rows(self, n: int) -> list[int]:
        """Heights of the programs that carry `n` chunk rows of one
        table width: the tallest while it fills, then ONE program for
        the remainder, the lowest that holds it (its other rows inert).
        At heights (4, 8): 4 → [4]; 5-8 → [8]; 9-12 → [8, 4]; 13-16 →
        [8, 8]. At one height, that height ceil(n / height) times."""
        tall = self.chunk_heights[-1]
        cut = [tall] * (n // tall)
        if n % tall:
            cut.append(next(h for h in self.chunk_heights if h >= n % tall))
        return cut

    def warmup_compile(self) -> int:
        """Pre-compile the chunk programs so no measured window (or live
        request) pays a first-touch compile: one inert dispatch (all
        rows n_valid 0 — every write lands on the reserved null page,
        pool bytes untouched) per program of `chunk_programs()`, plus
        the draft-prefill mirror (same rows) and `verify_chunk_paged`
        ([n_slots, k+1]: one row per decoding slot) when speculative
        decoding is on. Runs
        under `compile_watch.warmup_scope()` so the back-to-back ladder
        (well past the storm threshold, well inside the storm window)
        never files a false `recompile.storm` event; the compiles still
        count at /metrics, so benches snapshot `compiles_total()` AFTER
        calling this. Idempotent per engine; opt-in at `start()` via
        `llm_warmup_compile` (default off — short-lived engines are
        better served by lazy compilation). Returns the number of
        warmup dispatches issued (0 when already warmed)."""
        if self._warmed:
            return 0
        from ray_tpu import compile_watch as _cw

        rt = self._rt
        jnp = rt.jnp
        zeros = lambda *shape: jnp.asarray(np.zeros(shape, np.int32))
        n = 0
        with _cw.warmup_scope():
            for rows, width, head in self.chunk_programs():
                # graftlint: disable=GUARDED-BY (warmup runs before the engine thread exists: start() calls it pre-spawn under _lifecycle_lock, and direct callers own the engine single-threaded)
                _x, self.cache = rt.prefill_chunk_paged(
                    self.cfg, self.params, zeros(rows, self.prefill_chunk),
                    self.cache, zeros(rows, width), zeros(rows), zeros(rows),
                    return_logits=head, attn_impl=self.attn_impl,
                    **self._row_slots(zeros(rows)))
                n += 1
            for width in self._width_ladder() if self.spec_k else ():
                for rows in self.chunk_heights:
                    # graftlint: disable=GUARDED-BY (pre-spawn, see above)
                    _x, self.draft_cache = rt.prefill_chunk_paged(
                        self.draft_cfg, self.draft_params,
                        zeros(rows, self.prefill_chunk), self.draft_cache,
                        zeros(rows, width), zeros(rows), zeros(rows),
                        return_logits=False, attn_impl=self.attn_impl)
                    n += 1
                # graftlint: disable=GUARDED-BY (pre-spawn, see above)
                _x, self.cache = rt.verify_chunk_paged(
                    self.cfg, self.params,
                    zeros(self.n_slots, self.spec_k + 1), self.cache,
                    zeros(self.n_slots, width), zeros(self.n_slots),
                    zeros(self.n_slots), attn_impl=self.attn_impl)
                n += 1
        # graftlint: disable=GUARDED-BY (pre-spawn, see above)
        self._warmed = True
        return n

    def start(self) -> None:
        with self._lifecycle_lock:
            if self._thread is None:
                if self._warmup_on_start:
                    self.warmup_compile()
                self._thread = threading.Thread(
                    target=self._loop, daemon=True, name="llm-engine")
                self._heartbeat_thread = threading.Thread(
                    target=self._heartbeat.run, args=(self._shutdown,),
                    daemon=True, name="llm-heartbeat")
                self._thread.start()
                self._heartbeat_thread.start()

    def stop(self) -> None:
        """Stop the engine thread. A step it left in flight is absorbed
        (`_absorb_carry`), so a stopped engine's host state is the
        device's, position for position."""
        self._shutdown.set()
        with self._lifecycle_lock:
            if self._thread is not None:
                self._thread.join(timeout=30)
                joined = not self._thread.is_alive()
                self._thread = None
                self._heartbeat_thread.join(timeout=1)
                self._heartbeat_thread = None
                if joined:
                    self._absorb_carry()

    def drain(self, timeout_s: float) -> dict:
        """Drain protocol: stop admission, let in-flight decodes finish,
        export whatever the window didn't cover as resumable
        continuations `(request_id, prompt_ids, generated_ids,
        max_tokens, sampling params)`.

        After drain() returns, the engine accepts no new work and every
        request has either completed normally or carries migrated=True —
        the actor can be killed without losing a client-visible token:
        stream readers see the migrated flag and resubmit the
        continuation to a surviving replica (cursor-exact splice via the
        teacher-forced re-prefill in submit())."""
        with self._lock:
            self._draining = True
        deadline = time.monotonic() + max(0.0, timeout_s)
        while time.monotonic() < deadline:
            with self._lock:
                # _mid_tick fences the admission window: a request popped
                # from `pending` but not yet slot-bound would otherwise
                # read as idle and be truncated by the kill that follows.
                busy = (self._mid_tick
                        or any(r is not None for r in self.slot_req)
                        or self.pending.qsize() > 0
                        or len(self._deferred) > 0)
            if not busy:
                break
            time.sleep(0.02)
        continuations = self._export_unfinished()
        return {"drained": not continuations,
                "exported": len(continuations),
                "continuations": continuations}

    def _export_unfinished(self) -> list[dict]:
        """Evict every unfinished request as a resumable continuation.
        The engine thread is stopped FIRST so no tick races the export
        (a request must never emit a token after its continuation left)."""
        if self._thread is not None:
            self.stop()
        # An engine driven by step() has no thread to stop: a step in
        # flight is absorbed here, before any length or page is read.
        self._absorb_carry()
        doomed: list[GenRequest] = []
        slot_of: dict[int, GenRequest] = {}
        with self._lock:
            for slot, req in enumerate(self.slot_req):
                if req is not None:
                    doomed.append(req)
                    slot_of[slot] = req
                    self.slot_req[slot] = None
            chunk_pos = dict(self._chunk_pos)
            self._prefilling.clear()
            self._chunk_pos.clear()
            doomed.extend(self._deferred)
            self._deferred.clear()
            while True:
                try:
                    doomed.append(self.pending.get_nowait())
                except queue.Empty:
                    break
        # The engine thread is stopped: return every evicted slot's
        # pages (decrement-only — prefix-cache entries keep theirs,
        # so a drained-but-not-killed engine still closes the page
        # accounting: free + cached == total). With KV transfer on,
        # each slot's WRITTEN prefix is donated to the page-set
        # store FIRST — the destination replica adopts those pages
        # instead of re-prefilling the teacher-forced context (the
        # drain rung of the adoption ladder).
        for slot in range(self.n_slots):
            req = slot_of.get(slot)
            if (req is not None and self._kv_store is not None
                    and self.pool.slot_n_pages[slot]):
                n_written = int(self.positions[slot])
                if n_written <= 0:
                    n_written = int(chunk_pos.get(slot, 0))
                # True written sequence (see the matching comment
                # in _release): anchored at n_prompt so a preempt-
                # regrown context can't duplicate generated tokens
                # into the donation keys.
                seq = (req.prompt_ids[:req.n_prompt]
                       + req.out_ids)[:n_written]
                req.kv_handoff = self._donate_kv(
                    seq, self.pool.row(slot), memo=req.prefix_hashes)
            entry = self._slot_entry.pop(slot, None)
            if entry is not None:
                self.prefix_cache.release(entry)
            self.pool.free_slot(slot)
            # graftlint: disable=GUARDED-BY (single-threaded by protocol: _export_unfinished runs after stop() joined the engine thread — see its docstring — so nothing races these resets)
            self.positions[slot] = 0
            self.tokens[slot] = 0
        out = []
        for req in doomed:
            cont = {
                "request_id": req.request_id,
                # prompt_ids may have regrown past n_prompt on preempt
                # (context = prompt + generated); split so the consumer
                # never double-forces generated tokens.
                "prompt_ids": [int(t) for t in req.prompt_ids[:req.n_prompt]],
                "generated_ids": [int(t) for t in req.out_ids],
                "max_tokens": req.max_tokens,
                "temperature": req.temperature,
                "eos_id": req.eos_id,
            }
            if req.prefix_hashes:
                # The memoized chunk-hash chain rides the continuation
                # (hex — JSON-safe), so the destination replica never
                # re-hashes the full context on resume; prefix_chunk
                # lets a differently-configured destination drop an
                # incompatible memo instead of poisoning its key space.
                cont["prefix_hashes"] = [h.hex()
                                         for h in req.prefix_hashes]
                cont["prefix_chunk"] = self.prefill_chunk
            if req.kv_handoff is not None:
                cont["kv"] = req.kv_handoff
            out.append(cont)
            req.migrated = True
            if req.stream is not None:
                req.stream.put(None)
            req.done.set()
        return out

    def reset_stats(self) -> None:
        """Zero the counters (benchmarks call this after warmup so the
        engine-side split covers only the measured window)."""
        with self._lock:
            for k, v in self.stats.items():
                self.stats[k] = 0 if isinstance(v, int) else 0.0
            self._step_ms.clear()
            self._dispatch_width_ring.clear()
            self._dispatch_width_counts.clear()
            self._ttft_ms.clear()
            self._ttft_warm_ms.clear()
            self._ttft_cold_ms.clear()
            self._emit_gap_ms.clear()
            self._ttft_ewma_ms = None
            self._decode_ewma_tok_s = None
            self._budget_util_ewma = None
            self._spec_accept_ewma = None
            self.pool.rebase_low_water()
            self._awaiting_max = self._awaiting_first_token()
            # A live request's longest wait starts over too: what it waited
            # before the reset is not the window's (copies: `_deferred` and
            # the carry are the engine thread's).
            now = time.perf_counter()
            carry = self._carry
            for req in (*self.slot_req, *list(self._deferred),
                        *(list(carry.owed.values()) if carry is not None
                          else ())):
                if req is not None and req.last_emit_at is not None:
                    req.max_gap_s, req.last_emit_at = 0.0, now
        self._heartbeat.reset()
        self._ticks.reset()

    _SPAN_SAMPLE = 64

    @contextlib.contextmanager
    def _phase(self, name: str):
        """One phase of the engine tick, on the engine thread. For the
        block it wraps: a `jax.profiler.TraceAnnotation("llm.<name>")` —
        a level check while no profiler session is open, a host event on
        the device trace's own clock while one is — and the block's
        `perf_counter` time and one entry in the tick's account
        (`metrics()["phase_s"]`, zeroed by reset_stats()). Phases are
        flat: none opens inside another, and no annotation encloses the
        tick, so the host event covering an idle gap of the device IS
        the phase the engine was in.

        `decode_window` and `spec_verify` are also operator spans in
        /api/traces, over the same interval, for 1 entry in _SPAN_SAMPLE
        (the first always): enough to see engine step time there without
        the decode loop minting a fresh root trace per window — at
        decode rates that floods the GCS per-trace index and would
        eventually exhaust the bounded profile table, starving every
        other trace producer. `decode_window` is only that span: it
        groups the `decode.*` phases it encloses and has neither time of
        its own nor an annotation."""
        sampled = contextlib.nullcontext()
        seq = self._span_seq.get(name)
        if seq is not None:
            self._span_seq[name] = seq + 1
            if seq % self._SPAN_SAMPLE == 0:
                sampled = tracing.start_span("llm." + name, cat="serve_llm")
        with sampled:
            if name == "decode_window":
                yield
                return
            t0 = time.perf_counter()
            try:
                with self._annotate("llm." + name):
                    yield
            finally:
                self._ticks.add(name, t0, time.perf_counter() - t0)

    def _awaiting_first_token(self) -> int:
        """Requests owed a first token: queued, deferred, and bound to a
        slot while their prompt still waits for the prefill budget (which
        `queued` does not show: the engine admits into free slots at
        once)."""
        return (self.pending.qsize() + len(self._deferred)
                + sum(1 for r in self.slot_req
                      if r is not None and r.first_token_at is None))

    def _impl_tags(self) -> dict:
        """replica/impl tags for the engine-side histograms (built once,
        first use — the replica id needs the runtime context)."""
        if self._step_tags is None:
            self._step_tags = {
                "replica": _request_metric_tags()["replica"],
                "impl": f"paged-{self.attn_impl}"}
        return self._step_tags

    def _observe_decode(self, t0: float, end: float, per_slot: float,
                        emitted: int, cap: int) -> None:
        """Shared decode-tick accounting (non-speculative window AND
        speculative propose/verify tick — one implementation so the
        bookkeeping can't diverge across the spec knob): engine stats,
        the bounded per-slot-token step-time ring behind metrics()'s
        p50/p95 (tick wall / tokens each slot advanced — the
        roofline-facing ms-per-weight-pass-per-token number) and the
        step-latency histogram that makes kernel-vs-gather runs
        distinguishable at /metrics. `cap` is the tick's max emittable
        tokens (slot_occupancy's denominator)."""
        dt = end - t0
        tags = self._impl_tags()
        with self._lock:
            self.stats["decode_time_s"] += dt
            self.stats["decode_windows"] += 1
            self.stats["slot_step_sum"] += emitted
            self.stats["slot_cap_sum"] += cap
            self._step_ms.append(dt / max(1.0, per_slot) * 1000.0)
            if dt > 0:
                self._decode_ewma_tok_s = self._ewma(
                    self._decode_ewma_tok_s, emitted / dt)
        _DECODE_STEP_HIST.observe(dt / max(1.0, per_slot), tags=tags)

    def metrics(self) -> dict:
        with self._lock:
            active = sum(r is not None for r in self.slot_req)
            awaiting = self._awaiting_first_token()
            m = dict(self.stats, active_slots=active,
                     queued=self.pending.qsize() + len(self._deferred),
                     awaiting_first_token=awaiting,
                     awaiting_first_token_max=max(self._awaiting_max,
                                                  awaiting),
                     n_slots=self.n_slots)
            m["kv_pages_total"] = self.n_pages
            m["kv_pages_free"] = self.pool.n_free
            m["kv_pages_free_min"] = self.pool.min_free
            m["kv_page_size"] = self.page_size
            m["llm_attn_impl"] = self.attn_impl
            # Live pages the decoding slots attended over the pages
            # of the decode kernel's live kv blocks: under 1.0 a
            # block's tail lay past its slot's last page.
            m["decode_block_fill"] = m["decode_pages_live"] / max(
                1, m["decode_pages_fetched"])
            # The same live pages over every column of the windows'
            # tables: the share of a (slot, column) grid that the
            # decode kernel fetches; the rest costs it nothing.
            m["decode_live_column_share"] = m["decode_pages_live"] / max(
                1, m["decode_columns"])
            # How often a decode window left one more step in flight
            # for the host to work beside, and what stood the others
            # down (_STAND_DOWN; the flat counters stay beside it).
            m["lookahead_share"] = m["lookahead_windows"] / max(
                1, m["decode_windows"])
            m["lookahead_stood_down"] = {
                cause: m["lookahead_stood_down_" + cause]
                for cause in _STAND_DOWN}
            # The share of decode windows whose steps ran the
            # categorical draw (paged_kv._sample_next skips it for
            # a batch with no temperature above 0): 0.0 under
            # greedy traffic, 1.0 where some slot always samples.
            m["decode_draw_share"] = m["decode_windows_drawn"] / max(
                1, m["decode_windows"])
            # Quantized-serving observability (rides the PR 6 chain:
            # replica stats → serve.status() → /api/serve/load →
            # `ray_tpu status --serve`): the dtype knobs as resolved
            # (soft-off shows "bf16") + the pool's actual device
            # bytes, scale planes included.
            m["llm_weight_dtype"] = self.weight_dtype
            m["llm_kv_dtype"] = self.kv_dtype
            nbytes = lambda a: int(math.prod(a.shape) * a.dtype.itemsize)
            # A family's window layers keep rings a slot beside the
            # pages (0: none); the pool's bytes count both kinds.
            rings = [a for name, a in self.cache.items()
                     if name in ("k_win", "v_win")]
            m["window_kv_bytes"] = sum(map(nbytes, rings))
            m["kv_pool_bytes"] = m["window_kv_bytes"] + sum(
                nbytes(a) for name, a in self.cache.items()
                if name in ("k", "v", "kv", "k_scale", "v_scale"))
            # The pool's bytes by kind, and of the window kind what
            # every slot's live window needs: the rest of a ring is
            # room for a dispatch's writes (models/laguna.py
            # `ring_pages`).
            m["kv_bytes_window"] = m["window_kv_bytes"]
            m["kv_bytes_full"] = m["kv_pool_bytes"] - m["kv_bytes_window"]
            m["kv_bytes_window_live"] = sum(
                self.n_slots * a.shape[0] * self.cfg.window
                * a.shape[3] * a.dtype.itemsize for a in rings)
            # A family's per-slot state beside the pages (0: none).
            m["slot_state_bytes"] = sum(
                nbytes(self.cache[name]) for name in self._family.slot_state)
            m["weight_bytes"] = sum(
                int(a.nbytes) for a in self._rt.jax.tree.leaves(self.params))
            m["llm_tp"] = self.tp
            if self.tp > 1:
                m.update(self._tp_topology())
            m["prefill_chunk"] = self.prefill_chunk
            m["prefill_token_budget"] = self.prefill_budget
            m["chunk_rows"] = self.chunk_rows
            m["chunk_heights"] = list(self.chunk_heights)
            # Prompt tokens placed over token positions the chunk
            # dispatches carried: 1.0 = every row a full chunk.
            m["prefill_row_fill"] = m["prefill_tokens"] / max(
                1, m["prefill_rows_dispatched"] * self.prefill_chunk)
            # Live rows a chunk program: how often one program
            # carries what would have been several.
            m["prefill_rows_per_program"] = m["prefill_chunks"] / max(
                1, m["prefill_dispatches"])
            # Tokens placed over tokens allowed: under 1.0 the pool
            # (or the work), not the budget, bounds prefill.
            m["prefill_allowance_used"] = m["prefill_tokens"] / max(
                1, m["prefill_allowance"])
            # Live pages attended over the pages the prefill
            # kernel's live kv blocks fetched: under 1.0 a block's
            # tail was null or not yet written.
            m["prefill_block_fill"] = m["prefill_pages_live"] / max(
                1, m["prefill_pages_fetched"])
            m["prefilling_slots"] = len(self._prefilling)
            m["prefill_width_bucketing"] = self.prefill_width_bucketing
            if self._dispatch_width_ring:
                widths = sorted(self._dispatch_width_ring)
                m["prefill_dispatch_width_p50"] = widths[len(widths) // 2]
                m["prefill_dispatch_width_max"] = widths[-1]
            if self._dispatch_width_counts:
                # Cumulative-since-reset per-width dispatch counts:
                # host mirror of llm_prefill_dispatch_total{width}
                # (str keys — this dict rides JSON to /api/serve).
                m["prefill_dispatch_widths"] = {
                    str(w): c for w, c in
                    sorted(self._dispatch_width_counts.items())}
            if self.spec_k:
                m["spec_k"] = self.spec_k
                m["spec_draft"] = self.spec_draft_name
                if m["spec_slot_steps"]:
                    # Tokens emitted per slot per verify pass (accepted
                    # proposals + the always-emitted correction/bonus):
                    # the speculative speedup headline — 1.0 is the
                    # non-speculative rate, k+1 the ceiling.
                    m["spec_accepted_per_step"] = round(
                        m["spec_emitted"] / m["spec_slot_steps"], 4)
                if m["spec_proposed"]:
                    m["spec_accept_rate"] = round(
                        m["spec_accepted"] / m["spec_proposed"], 4)
            if self.kv_transfer:
                m["kv_transfer"] = True
                m["pool_role"] = self.pool_role or "fused"
                m["kv_summary_entries"] = len(self._kv_donated)
                m["kv_summary_max"] = self._kv_summary_max
            elif self._kv_transfer_disabled_reason:
                # Satellite of the soft-disable contract: the misfit
                # that flipped the global knob off is inspectable, not
                # just a boot-time log line.
                m["kv_transfer"] = False
                m["kv_transfer_disabled_reason"] = (
                    self._kv_transfer_disabled_reason)
            if self.prefix_cache is not None:
                m["prefix_cache"] = True
                m["prefix_cache_entries"] = len(self.prefix_cache.entries)
                m["prefix_cache_pages"] = self.prefix_cache.n_pages_cached()
                m["prefix_cache_pages_budget"] = self.prefix_cache.max_pages
                looked = m["prefix_hits"] + m["prefix_misses"]
                if looked:
                    m["prefix_cache_hit_rate"] = round(
                        m["prefix_hits"] / looked, 4)
                if self._ttft_warm_ms:
                    (m["ttft_warm_ms_p50"],
                     m["ttft_warm_ms_p95"]) = _ring_pctls(self._ttft_warm_ms)
                if self._ttft_cold_ms:
                    (m["ttft_cold_ms_p50"],
                     m["ttft_cold_ms_p95"]) = _ring_pctls(self._ttft_cold_ms)
            if self._step_ms:
                m["decode_step_ms_p50"], m["decode_step_ms_p95"] = (
                    _ring_pctls(self._step_ms))
            if self._ttft_ms:
                m["ttft_ms_p50"], m["ttft_ms_p95"] = _ring_pctls(
                    self._ttft_ms)
            # The longest wait between two hand-overs of tokens, over
            # the requests finished since the reset (0.0: none has).
            gaps = list(self._emit_gap_ms)
        m["emit_gap_ms_p50"], m["emit_gap_ms_p99"] = (
            _ring_pctls(gaps, 0.99) if gaps else (0.0, 0.0))
        m["emit_gap_ms_max"] = round(max(gaps, default=0.0), 3)
        m.update(self._ticks.snapshot())
        m.update(self._heartbeat.snapshot())
        if m["completed"]:
            m["ttft_mean_s"] = m["ttft_sum"] / m["completed"]
        # Engine-side rates: what the chip sustains, independent of the
        # client path.
        if m["decode_time_s"] > 0:
            m["engine_decode_tok_s"] = (
                m["slot_step_sum"] / m["decode_time_s"])
        if m["prefill_time_s"] > 0:
            m["engine_prefill_tok_s"] = (
                m["prefill_tokens"] / m["prefill_time_s"])
        if m["slot_cap_sum"] > 0:
            m["slot_occupancy"] = m["slot_step_sum"] / m["slot_cap_sum"]
        # Per layer and decode step (0 where no expert layer ran): held
        # experts that had a row, and the fullest one's rows over the
        # mean of those that had any.
        pairs = max(1, m["moe_layer_steps"])
        m["moe_experts_touched"] = m["moe_experts_touched_sum"] / pairs
        m["moe_rows_max"] = (
            m["moe_rows_max_sum"] * m["moe_experts_touched_sum"]
            / max(1, m["moe_rows_held"]) / pairs)
        return m

    _EWMA_ALPHA = 0.2

    @classmethod
    def _ewma(cls, prev: float | None, sample: float) -> float:
        if prev is None:
            return sample
        return cls._EWMA_ALPHA * sample + (1 - cls._EWMA_ALPHA) * prev

    def load_snapshot(self) -> dict:
        """Live load for the router/autoscaler (flight recorder): queue
        depth, slot-occupancy split, page-pool fill, prefill-budget
        utilization, and TTFT/decode-rate EWMAs — all from the engine's
        own bookkeeping, no device sync. Also sets the `llm_*` gauges so
        the same numbers reach /metrics via the worker's flush loop.
        Propagation path: Replica.stats() → controller reconcile probe →
        serve.status() / controller.get_load() / GET /api/serve/load."""
        with self._lock:
            active = sum(r is not None for r in self.slot_req)
            prefilling = len(self._prefilling)
            snap: dict = {
                "queue_depth": self.pending.qsize() + len(self._deferred),
                "awaiting_first_token": self._awaiting_first_token(),
                "n_slots": self.n_slots,
                "active_slots": active,
                "prefilling_slots": prefilling,
                "decoding_slots": active - prefilling,
                "slot_utilization": round(active / self.n_slots, 4),
            }
            if self._ttft_ewma_ms is not None:
                snap["ttft_ewma_ms"] = round(self._ttft_ewma_ms, 3)
            if self._decode_ewma_tok_s is not None:
                snap["decode_tok_s_ewma"] = round(
                    self._decode_ewma_tok_s, 3)
            snap["pool_pages_total"] = self.n_pages
            snap["pool_pages_free"] = self.pool.n_free
            snap["pool_pages_free_min"] = self.pool.min_free
            snap["pool_utilization"] = round(
                1.0 - self.pool.n_free / self.n_pages, 4)
            # Quantized-serving load surface (PR 6 chain: replica
            # stats → serve.status() → /api/serve/load → CLI).
            snap["llm_weight_dtype"] = self.weight_dtype
            snap["llm_kv_dtype"] = self.kv_dtype
            snap["kv_pool_bytes"] = sum(
                int(math.prod(a.shape) * a.dtype.itemsize)
                for a in self.cache.values())
            if self.tp > 1:
                # Riding the PR 6 chain as-is: Replica.stats() →
                # controller probe → serve.status() / /api/serve/load /
                # `ray_tpu status --serve`.
                snap["llm_tp"] = self.tp
                snap.update(self._tp_topology())
            snap["prefill_chunk"] = self.prefill_chunk
            snap["prefill_token_budget"] = self.prefill_budget
            snap["chunk_rows"] = self.chunk_rows
            if self._budget_util_ewma is not None:
                snap["prefill_budget_util"] = round(self._budget_util_ewma, 4)
            # Width-bucketed dispatch load (rides the PR 6 chain:
            # Replica.stats() → controller probe → serve.status() /
            # /api/serve/load / `ray_tpu status --serve`, plus the
            # matching llm_* gauges set below): the median/max page-
            # table width of recent chunk dispatches — full-width
            # medians on short-prompt traffic are the interior-chunk
            # waste width bucketing exists to remove.
            if self._dispatch_width_ring:
                widths = sorted(self._dispatch_width_ring)
                snap["prefill_dispatch_width_p50"] = widths[len(widths) // 2]
                snap["prefill_dispatch_width_max"] = widths[-1]
            if self.spec_k:
                # Rides the PR 6 chain as-is: Replica.stats() →
                # controller reconcile probe → serve.status() /
                # /api/serve/load / `ray_tpu status --serve`, plus the
                # llm_spec_accepted_per_step gauge set below.
                snap["spec_k"] = self.spec_k
                if self._spec_accept_ewma is not None:
                    snap["spec_accepted_per_step"] = round(
                        self._spec_accept_ewma, 4)
            if self.kv_transfer:
                # Pool role + adoption/donation counts ride the PR 6
                # chain as-is: Replica.stats() → controller probe →
                # serve.status() / /api/serve/load / the CLI render —
                # the disaggregation observability surface.
                snap["pool_role"] = self.pool_role or "fused"
                snap["kv_donations"] = self.stats["kv_donations"]
                snap["kv_adoptions"] = self.stats["kv_adoptions"]
                snap["kv_partial_adoptions"] = (
                    self.stats["kv_partial_adoptions"])
                snap["kv_adopted_tokens"] = (
                    self.stats["kv_adopted_tokens"])
                snap["kv_adopt_failures"] = (
                    self.stats["kv_adopt_failures"])
                snap["kv_digest_lookups"] = (
                    self.stats["kv_digest_lookups"])
                # Donated-chain-head summary (descriptor-less warm
                # discovery): rides the SAME zero-extra-RPC chain as
                # the load row — Replica.stats() → controller reconcile
                # probe → get_routing's per-replica loads → the
                # handle's push-refreshed cache. Oldest→newest;
                # the controller truncates keeping the newest when a
                # replica exceeds the push cap.
                snap["kv_summary"] = list(self._kv_donated)
            elif self._kv_transfer_disabled_reason:
                snap["kv_transfer_disabled_reason"] = (
                    self._kv_transfer_disabled_reason)
            if self.prefix_cache is not None:
                # Cached-pages + hit-rate ride the same probe chain as
                # the rest of the load snapshot: Replica.stats() →
                # controller reconcile → serve.status() /
                # /api/serve/load / `ray_tpu status --serve`.
                snap["prefix_cache_entries"] = len(self.prefix_cache.entries)
                snap["prefix_cache_pages"] = (
                    self.prefix_cache.n_pages_cached())
                # Raw counts ride along so cross-replica consumers (the
                # affinity-vs-load bench) can aggregate hit rates with
                # real weights instead of averaging per-replica rates.
                snap["prefix_cache_hits"] = self.stats["prefix_hits"]
                snap["prefix_cache_misses"] = self.stats["prefix_misses"]
                looked = (self.stats["prefix_hits"]
                          + self.stats["prefix_misses"])
                if looked:
                    snap["prefix_cache_hit_rate"] = round(
                        self.stats["prefix_hits"] / looked, 4)
        tags = {"replica": self._impl_tags()["replica"]}
        for key, gauge in _LOAD_GAUGES.items():
            # Absent fields (EWMAs cleared by reset_stats) export 0, not
            # their last stale value — the router must never act on a
            # pre-reset TTFT.
            gauge.set(float(snap.get(key, 0.0)), tags=tags)
        return snap

    # --------------------------------------------------- page accounting

    def _pool_shard_bytes(self) -> int:
        """Per-device bytes of the KV pool (K + V planes plus, when
        quantized, the per-page scale planes; null page included). Page
        ids are shard-invariant — every shard holds every page — so at
        tp > 1 each K/V shard's cut is the head slice (total / tp)
        while scale planes are replicated in full on every shard. The
        topology number `serve.status()` / `/api/serve/load` / the CLI
        render."""
        total = 0
        for key, a in self.cache.items():
            nbytes = int(math.prod(a.shape) * a.dtype.itemsize)
            total += nbytes if key.endswith("_scale") else nbytes // self.tp
        return total

    def _tp_topology(self) -> dict:
        """Page ids (and thus the occupancy FRACTION) are shard-invariant;
        the per-shard number is the bytes each device pins."""
        shard = self._pool_shard_bytes()
        return {"mesh_shape": {"tp": self.tp},
                "kv_heads_per_shard": self.cfg.n_heads // self.tp,
                "pool_shard_bytes": shard,
                "pool_shard_bytes_used": round(
                    shard * (1.0 - self.pool.n_free / self.n_pages))}

    def _cache_reclaim(self, need: int) -> None:
        """Pressure valve: evict zero-active prefix-cache entries (LRU)
        until `need` pages are free or nothing evictable remains — the
        cache gives its pages back BEFORE the scheduler shrinks a
        window or preempts a live decode."""
        if self.prefix_cache is None:
            return
        while self.pool.n_free < need:
            if self.prefix_cache.evict_one() is None:
                break
        self._sync_cache_evictions()

    def _sync_cache_evictions(self) -> None:
        """Fold the cache's cumulative eviction count into the windowed
        stats + Prometheus counter (evictions also happen inside
        donate()'s budget enforcement, not just _cache_reclaim)."""
        delta = self.prefix_cache.evictions - self._evictions_synced
        if delta > 0:
            self._evictions_synced = self.prefix_cache.evictions
            self.stats["prefix_evictions"] += delta
            _PREFIX_COUNTERS["evictions"].inc(
                float(delta),
                tags={"replica": self._impl_tags()["replica"]})

    # ------------------------------------------- KV page-set transfer

    def _kv_note_donation(self, head: str, depth: int) -> None:
        """Fold a donated chain into the summary memo: head (16-hex
        depth-1 digest prefix — the router's affinity-key space) →
        deepest donated depth, newest-last, truncated to
        serve_kv_summary_max so the routing push stays bounded
        whatever this engine's donation history."""
        m = self._kv_donated
        m[head] = max(depth, m.get(head, 0))
        m.move_to_end(head)
        while len(m) > self._kv_summary_max:
            m.popitem(last=False)

    def _kv_chain_head(self, seq) -> str | None:
        """Summary key for ``seq``'s chain: 16-hex prefix of the
        depth-1 chunk digest (prefix_cache.affinity_key byte-identical
        space, so pushed summaries match the handle's routing keys)."""
        c = self.prefill_chunk
        if not c or len(seq) < c:
            return None
        from ray_tpu.serve.prefix_cache import affinity_key

        return affinity_key(seq, c).hex()[:16]

    def _donate_kv(self, seq, table_row, memo: list) -> dict | None:
        """Donate the chunk-aligned written prefix of ``seq`` (its K/V
        already sits in ``table_row``'s pages) to the page-set store as
        one entry per chain depth, keyed by the SAME parent-chained
        digests the prefix cache uses. Pages are reffed for the
        duration of the device gather + store put (the in-flight-
        donated accounting category) and released in a finally, so a
        chaos raise at serve.kv.donate can't leak a reference. Best-
        effort by contract: any failure returns what was resolvable and
        never fails the completing request. → adoption descriptor for
        the continuation consumer, or None."""
        if self._kv_store is None:
            return None
        from ray_tpu.serve.prefix_cache import extend_chunk_chain

        c = self.prefill_chunk
        n_full = len(seq) // c
        if n_full <= 0:
            return None
        chain = extend_chunk_chain(seq, c, memo if memo is not None else [])
        keys = [h.hex() for h in chain[:n_full]]
        total_pages = self._kvo.pages_for_tokens(n_full * c, self.page_size)
        pages = [int(table_row[i]) for i in range(total_pages)]
        if any(p <= 0 for p in pages):
            # Defensive (mirrors PrefixCache.donate): a donor must own
            # real pages for every token it claims to have written.
            return None
        desc = {"keys": keys, "chunk": c, "page_size": self.page_size,
                "fingerprint": self._kv_fingerprint,
                "n_tokens": n_full * c}
        try:
            # Chaos fault point: EVERY donation attempt (not just novel
            # digests — the store dedups those) — a "kill" rule here is
            # the donor-SIGKILL-mid-donation scenario, a "raise" skips
            # this donation while the engine keeps serving.
            _chaos.hit("serve.kv.donate")
            existing = self._kv_store.resolve(keys)
        except Exception as e:  # noqa: BLE001 — index blip / chaos:
            # skip donation, the descriptor still names the keys.
            logger.debug("kv donation skipped: %s", e)
            return desc
        new_depths = [d for d in range(1, n_full + 1)
                      if keys[d - 1] not in existing]
        if not new_depths:
            # Fully deduped against prior donations — the chain is
            # live in the store, so it still belongs in this replica's
            # summary (and the memo spares repeat traffic the resolve).
            self._kv_note_donation(keys[0][:16], n_full)
            return desc
        self.pool.ref_pages(pages)
        self._kv_exporting = dict.fromkeys(pages, 1)
        tags = {"replica": self._impl_tags()["replica"]}
        try:
            rt = self._rt
            width = _pow2_width(total_pages)
            ids = np.zeros(width, np.int32)
            ids[:total_pages] = pages
            gathered = rt.gather_pages(self.cache, rt.jnp.asarray(ids))
            # Dict-generic host pull: a quantized pool's k_scale/v_scale
            # planes ride the SAME gather (every pool key is paged on
            # axis 1), so payloads carry them with no extra bookkeeping.
            # At tp>1 the host asarray reassembles FULL-head planes from
            # the sharded gather output; split_head_planes then cuts
            # them back into per-shard wire planes ("k@0".."k@{tp-1}",
            # replicated _scale planes unsuffixed) so adopters at ANY tp
            # degree reassemble exactly the shards they need. tp=1
            # donors keep the original unsharded payload schema.
            host = {key: np.asarray(a) for key, a in gathered.items()}
            dhost = None
            if self.spec_k:
                # Draft pool mirror: draft page p ≡ target page p, so
                # donations carry both and an adopting spec engine keeps
                # the mirror exact (a spec adopter REQUIRES the draft
                # planes — see _kv_adopt_plan).
                dg = rt.gather_pages(self.draft_cache, rt.jnp.asarray(ids))
                dhost = {key: np.asarray(a) for key, a in dg.items()}
            if self.tp > 1:
                from ray_tpu.models import partition as _partition

                host = _partition.split_head_planes(host, self.tp)
                if dhost is not None:
                    dhost = _partition.split_head_planes(dhost, self.tp)
            for d in new_depths:
                s, e = self._kvo.page_span(d, c, self.page_size)
                payload = {key: a[:, s:e] for key, a in host.items()}
                if dhost is not None:
                    for key, a in dhost.items():
                        payload["d" + key] = a[:, s:e]
                meta = self._kvo.make_meta(
                    keys[d - 1], d, c, self.page_size,
                    self._kv_fingerprint, self._kv_donor, e - s,
                    bool(self.spec_k), tp=self.tp)
                self._kv_store.donate(meta, payload)
                self.stats["kv_donations"] += 1
                self.stats["kv_donated_pages"] += e - s
                _KV_COUNTERS["donations"].inc(tags=tags)
            self._kv_note_donation(keys[0][:16], n_full)
        except Exception as e:  # noqa: BLE001 — incl. ChaosError: the
            # donor keeps serving; already-published depths stay usable.
            logger.debug("kv donation aborted mid-chain: %s", e)
        finally:
            self._kv_exporting = {}
            self.pool.unref_pages(pages)
        return desc

    def _kv_adopt_plan(self, req: GenRequest,
                       n_local: int) -> dict | None:
        """Resolve the deepest contiguous donated chain prefix for
        ``req``'s context, deeper than the local prefix-cache match
        ``n_local`` (local sharing is zero-copy — adoption only wins
        when it covers MORE tokens). Walks depth 1 upward: a missing or
        incompatible entry stops the walk, so a dead donor's partially
        swept chain degrades to partial adoption, never a wrong bind."""
        if self._kv_store is None or not req.kv:
            return None
        kv = req.kv
        if not kv.get("discover") and (
                kv.get("fingerprint") != self._kv_fingerprint
                or kv.get("chunk") != self.prefill_chunk
                or kv.get("page_size") != self.page_size):
            # A full descriptor (handoff / drain export) pre-screens on
            # its embedded geometry. A {"discover": True} hint — the
            # handle's push-refreshed summary saying "this chain is
            # donated SOMEWHERE" — carries none, so it goes straight to
            # the resolve; the per-meta checks below still validate
            # fingerprint/chunk/page_size before anything binds (a
            # summary false positive falls through the ladder).
            return None
        from ray_tpu.serve.prefix_cache import extend_chunk_chain

        cap = (len(req.prompt_ids) - 1) // self.prefill_chunk
        if cap <= 0:
            return None
        chain = extend_chunk_chain(req.prompt_ids, self.prefill_chunk,
                                   req.prefix_hashes)
        keys = [h.hex() for h in chain[:cap]]
        try:
            self.stats["kv_digest_lookups"] += 1
            found = self._kv_store.resolve(keys)
        except Exception as e:  # noqa: BLE001 — index blip = cold path
            logger.debug("kv adoption resolve failed: %s", e)
            return None
        metas = []
        for d in range(1, cap + 1):
            meta = found.get(keys[d - 1])
            if (meta is None
                    or meta.get("fingerprint") != self._kv_fingerprint
                    or meta.get("chunk") != self.prefill_chunk
                    or meta.get("page_size") != self.page_size
                    or (self.spec_k and not meta.get("draft"))):
                break
            metas.append(meta)
        if not metas or len(metas) * self.prefill_chunk <= n_local:
            return None
        return {"n_tokens": len(metas) * self.prefill_chunk,
                "metas": metas}

    def _bind_kv_adopt(self, slot: int, req: GenRequest,
                       plan: dict) -> int:
        """Adoption bind: fetch the planned page-set payloads (deepest
        contiguous run that transfers — serve.kv.adopt chaos drops a
        rung here), allocate fresh exclusive pages, scatter the
        payloads into the pool in one fused dispatch (+ the draft-pool
        mirror when speculative decoding is on), and bind them into
        ``slot``'s table like a local warm hit. The chunk cursor starts
        at the first cold token. → adopted tokens (0 = ladder fell
        through to re-prefill)."""
        tags = {"replica": self._impl_tags()["replica"]}
        payloads: list[dict] = []
        for meta in plan["metas"]:
            try:
                p = self._kv_store.fetch(meta)
                donor_tp = int(meta.get("tp", 1) or 1)
                if donor_tp > 1:
                    # Resharding adoption: reassemble the donor's
                    # per-shard head planes into full-head planes
                    # (raises on a torn donation → partial rung); the
                    # scatter below — shard_map-rebound at tp>1 —
                    # re-slices per THIS engine's mesh, so tp=2→tp=4
                    # and the reverse are the same two steps.
                    from ray_tpu.models import partition as _partition

                    p = _partition.concat_head_planes(p, donor_tp)
                if (p["k"].shape[1:] != (meta["n_pages"],)
                        + tuple(self.cache["k"].shape[2:])
                        or (self.spec_k and "dk" not in p)):
                    raise ValueError("kv payload shape mismatch")
                payloads.append(p)
            except Exception as e:  # noqa: BLE001 — transfer failed:
                # adopt the depths that DID arrive (partial rung).
                logger.debug("kv fetch of depth %s failed: %s",
                             meta.get("depth"), e)
                break
        n_adopt = len(payloads) * self.prefill_chunk
        n_pages = self.pool.pages_for(n_adopt - 1)
        if not payloads or not self.pool.grow(slot, n_adopt - 1,
                                              self._cache_reclaim):
            # Nothing arrived, or the pool is dry at bind (reservation
            # shortfall): a partial page run can't serve the prefix.
            self.stats["kv_adopt_failures"] += 1
            _KV_COUNTERS["adopt_failures"].inc(tags=tags)
            return 0
        rt = self._rt
        width = _pow2_width(n_pages)
        ids = np.zeros(width, np.int32)
        ids[:n_pages] = self.pool.row(slot, n_pages)

        def _stitch(pool, prefix=""):
            # Dict-generic payload stitch: every pool key (K/V planes
            # AND a quantized pool's scale planes) concatenates along
            # the page axis and pads rank-generically, so the scatter
            # is one fused dispatch per pool regardless of dtype.
            data = {}
            for key in pool:
                a = np.concatenate([p[prefix + key] for p in payloads],
                                   axis=1)
                if width > n_pages:
                    a = np.pad(a, ((0, 0), (0, width - n_pages))
                               + ((0, 0),) * (a.ndim - 2))
                data[key] = rt.jnp.asarray(a)
            return data

        self.cache = rt.scatter_pages(
            self.cache, rt.jnp.asarray(ids), _stitch(self.cache))
        if self.spec_k:
            self.draft_cache = rt.scatter_pages(
                self.draft_cache, rt.jnp.asarray(ids),
                _stitch(self.draft_cache, prefix="d"))
        req.cached_tokens = n_adopt
        self.stats["kv_adoptions"] += 1
        self.stats["kv_adopted_tokens"] += n_adopt
        if len(payloads) < len(plan["metas"]):
            self.stats["kv_partial_adoptions"] += 1
        _KV_COUNTERS["adoptions"].inc(tags=tags)
        return n_adopt

    def _handoff_prefill(self, slot: int, req: GenRequest) -> None:
        """Prefill-pool handoff (pool_role='prefill'): the prompt's KV
        pages are donated and the request leaves this replica as a
        migrated continuation the moment its first token is out — the
        consumer (proxy / handle stream) resubmits
        ``(prompt, [first token], kv descriptor)`` to a decode-pool
        replica, which adopts the pages instead of re-prefilling. Same
        migration contract as drain export, so greedy streams stay
        byte-identical across the handoff."""
        req.kv_handoff = self._donate_kv(
            req.prompt_ids, self.pool.row(slot), memo=req.prefix_hashes)
        req.migrated = True
        if req.stream is not None:
            req.stream.put(None)
        req.done.set()
        self._release(slot)

    def page_accounting(self) -> dict:
        """Closure check (tests + chaos triage): every pool page is
        exactly one of free / referenced, and every reference is owned
        by a slot table or a cache entry. Engine-thread-safe only when
        the engine is stopped or driven synchronously."""
        cache = self.prefix_cache
        return self.pool.accounting(
            cached=cache.cached_pages() if cache is not None else set(),
            cached_refs=(cache.page_refs_held if cache is not None
                         else lambda pg: 0),
            exporting=self._kv_exporting)

    # ------------------------------------------------------------- engine

    _TTFT_SPAN_SAMPLE = 16

    def _emit_ttft_spans(self, req: GenRequest) -> None:
        """TTFT breakdown spans for 1-in-N first tokens (the first
        always): queue-wait → prefill (first chunk → last chunk) →
        first-token, three children under one llm.ttft root, recorded
        retroactively from the request's engine-side timestamps. Sampled
        so a request flood doesn't mint a root trace per request and
        starve the bounded profile table (same reasoning as
        _phase's sampling)."""
        seq, self._ttft_seq = self._ttft_seq, self._ttft_seq + 1
        if seq % self._TTFT_SPAN_SAMPLE or req.first_chunk_at is None:
            return
        # GenRequest timestamps are perf_counter; anchor to the wall
        # clock the profiling buffer speaks.
        anchor = time.time() - time.perf_counter()
        root = tracing.TraceContext(
            tracing.new_trace_id(), tracing.new_span_id(), None, {})
        first = req.first_chunk_at
        last = req.last_chunk_at if req.last_chunk_at is not None else first
        _profiling.record_event(
            "llm.ttft", "serve_llm", anchor + req.submitted_at,
            req.first_token_at - req.submitted_at,
            tid="llm-engine",
            args=tracing.span_event_args(root, request_id=req.request_id))
        for name, a, b in (("llm.ttft.queue_wait", req.submitted_at, first),
                           ("llm.ttft.prefill", first, last),
                           ("llm.ttft.first_token", last,
                            req.first_token_at)):
            _profiling.record_event(
                name, "serve_llm", anchor + a, max(0.0, b - a),
                tid="llm-engine",
                args=tracing.span_event_args(root.child()))

    def _emit(self, req: GenRequest, token: int) -> bool:
        """Append a token; → True if the request just finished. The clock
        is read for the request's two stamps only (its first token, its
        end): two reads a request, not one a token."""
        if req.first_token_at is None:
            now = req.first_token_at = time.perf_counter()
            self.stats["ttft_sum"] += now - req.submitted_at
            # Under the lock: metrics() sorts this ring concurrently.
            with self._lock:
                ms = (now - req.submitted_at) * 1000.0
                self._ttft_ms.append(ms)
                if self.prefix_cache is not None:
                    (self._ttft_warm_ms if req.cached_tokens
                     else self._ttft_cold_ms).append(ms)
                self._ttft_ewma_ms = self._ewma(self._ttft_ewma_ms, ms)
            self._emit_ttft_spans(req)
        req.out_ids.append(token)
        if req.stream is not None:
            req.stream.put(token)
        self.stats["tokens_generated"] += 1
        finished = (len(req.out_ids) >= req.max_tokens
                    or (req.eos_id is not None and token == req.eos_id))
        if finished:
            req.finished_at = time.perf_counter()
            self.stats["completed"] += 1
            if req.windows:
                # Under the lock: metrics() copies this ring concurrently.
                with self._lock:
                    self._emit_gap_ms.append(req.max_gap_s * 1000.0)
            if req.stream is not None:
                req.stream.put(None)  # stream sentinel
            req.done.set()
        return finished

    def _hand_over(self, req: GenRequest, now: float, window: int = 1) -> None:
        """A decode window's tokens (`window=0`: a prefill's one) are being
        handed to `req`, at `now`: ONCE a request a window, ahead of its
        `_emit`s, however many tokens those are. Keeps the request's
        longest wait between two hand-overs; `_emit` puts it into the
        ring behind metrics()'s `emit_gap_ms_*` when the request ends."""
        last = req.last_emit_at
        req.last_emit_at = now
        req.windows += window
        if last is not None:
            gap = now - last
            if gap > req.max_gap_s:
                req.max_gap_s = gap

    def _sample(self, logits_row, temperature: float) -> int:
        rt = self._rt
        if temperature == 0.0:
            return int(np.argmax(logits_row))
        self._rng_key, sub = rt.jax.random.split(self._rng_key)
        return int(rt.sample_token(
            logits_row, temperature=temperature, key=sub))

    # Admission lookahead bound: how many page-blocked requests one round
    # scans past (keeps the tick O(1) under a deep blocked queue) — and
    # the aging limit after which a repeatedly-bypassed head goes
    # strict-FIFO so it cannot starve behind a stream of small prompts.
    _ADMIT_LOOKAHEAD = 8
    _ADMIT_BYPASS_LIMIT = 16

    def _admit(self) -> None:
        """Move queued requests into free slots: a request is admitted
        once ONE CHUNK of pool headroom exists; its prompt then enters
        chunk-by-chunk under step()'s token budget
        (`_run_prefill_chunks`).

        Head-of-line fix: a page-blocked request no longer stops the scan.
        Up to _ADMIT_LOOKAHEAD blocked requests are set aside — returning
        to the deferred head IN ORDER, so queue position is preserved —
        while requests behind them that DO fit admit now. A round that
        admits someone past a blocked head ages the head; past
        _ADMIT_BYPASS_LIMIT it blocks all lookahead until it admits."""
        free = [s for s in range(self.n_slots) if self.slot_req[s] is None]
        reqs: list[GenRequest] = []
        blocked: list[GenRequest] = []
        hits: dict[str, Any] = {}
        plans: dict[str, dict] = {}
        head_mark = 0
        planned_pages = 0
        while len(reqs) < len(free):
            if self._deferred:
                req = self._deferred.popleft()
            else:
                try:
                    req = self.pending.get_nowait()
                except queue.Empty:
                    break
            hit = None
            # Admission back-pressure: only the FIRST CHUNK has to be
            # covered — the rest is budgeted lazy growth. A warm
            # prefix shrinks the reservation further: shared full
            # pages come from the cache, so only the COW tail (if
            # the prefix ends mid-page) plus the first COLD chunk's
            # pages need the free list.
            n_cached = 0
            if self.prefix_cache is not None:
                # Acquire (pin) at RESERVATION time: the reclaim
                # below evicts zero-active entries, and it must
                # not evict the entry this reservation is sized
                # for — an unpinned match could silently turn a
                # warm admission cold with an undersized page
                # reservation.
                hit = self.prefix_cache.acquire(
                    req.prompt_ids, memo=req.prefix_hashes)
                if hit is not None:
                    n_cached = hit.n_tokens
            if not req.kv_plan_tried:
                req.kv_plan = self._kv_adopt_plan(req, n_cached)
                req.kv_plan_tried = True
            plan = req.kv_plan
            if plan is not None:
                # Adoption ladder rung 1: donated pages resolve
                # DEEPER than any local warm hit. Adopted pages
                # are fresh exclusive allocations (nothing is
                # shared across replicas), so the reservation
                # covers the whole adopted run + first cold
                # chunk — the bind may still degrade (partial /
                # re-prefill) without exceeding it.
                plans[req.request_id] = plan
                end = min(plan["n_tokens"] + self.prefill_chunk,
                          len(req.prompt_ids))
                need = self.pool.pages_for(end - 1)
            else:
                end = min(n_cached + self.prefill_chunk,
                          len(req.prompt_ids))
                need = (self.pool.pages_for(end - 1)
                        - n_cached // self.page_size)
            if planned_pages + need > self.pool.n_free:
                self._cache_reclaim(planned_pages + need)
            if planned_pages + need > self.pool.n_free:
                plans.pop(req.request_id, None)
                if hit is not None:
                    # Not admitted this round: unpin (the entry is
                    # re-acquired when the request is re-scanned).
                    self.prefix_cache.release(hit)
                if not blocked:
                    head_mark = len(reqs)
                    if req.admit_bypasses >= self._ADMIT_BYPASS_LIMIT:
                        blocked.append(req)
                        break   # aged head: strict FIFO until it fits
                blocked.append(req)
                if len(blocked) >= self._ADMIT_LOOKAHEAD:
                    break
                continue
            planned_pages += need
            if hit is not None:
                hits[req.request_id] = hit
            reqs.append(req)
        for req in reversed(blocked):
            self._deferred.appendleft(req)   # original order, at the head
        if blocked and len(reqs) > head_mark:
            blocked[0].admit_bypasses += 1
        # Bind request → slot now; the prompt enters the pool
        # chunk-by-chunk via _run_prefill_chunks. A prefix-cache hit
        # pre-binds the cached page run into the slot's table and starts
        # the chunk cursor at the first COLD token — the cached prefix is
        # never re-prefilled.
        for req, slot in zip(reqs, free):
            n_cached = 0
            hit = hits.pop(req.request_id, None)
            plan = plans.pop(req.request_id, None)
            if plan is not None:
                n_cached = self._bind_kv_adopt(slot, req, plan)
            if n_cached:
                # Adopted: the pinned local entry (if any) goes
                # unused — release it; adoption only planned when
                # it covers MORE tokens than the local hit.
                if hit is not None:
                    self.prefix_cache.release(hit)
            elif self.prefix_cache is not None:
                # Ladder falls through: local warm hit, else cold.
                n_cached = self._bind_cached_prefix(slot, req, hit)
            with self._lock:
                self.slot_req[slot] = req
            self.tokens[slot] = 0
            self.positions[slot] = 0
            self.temps[slot] = req.temperature
            self._chunk_pos[slot] = n_cached
            self._prefilling.append(slot)

    def _bind_cached_prefix(self, slot: int, req: GenRequest,
                            entry) -> int:
        """Warm admission: bind `entry` — the cached chunk-aligned
        prefix of `req.prompt_ids` that _admit acquired (pinned) while
        sizing the page reservation — into `slot`'s page table.

        Full pages of the prefix are shared READ-ONLY (refcount bumped;
        the binder's writes all land past them). If the prefix ends
        mid-page, that tail page will be written by the cold suffix, so
        a fresh page is allocated and a (src, dst) copy is queued —
        flushed as ONE fused device copy per tick (_apply_cow). When no
        page is free for the COW, the bind degrades to the full-page
        part of the prefix (chunk prefill handles arbitrary offsets).
        → tokens served from cache (the chunk cursor's start)."""
        tags = {"replica": self._impl_tags()["replica"]}
        # Reset before the verdict: a preempted warm request can
        # re-admit COLD (its entry was evicted) and must not keep the
        # stale warm classification.
        req.cached_tokens = 0
        if entry is None:
            self.stats["prefix_misses"] += 1
            _PREFIX_COUNTERS["misses"].inc(tags=tags)
            return 0
        ps = self.page_size
        n_cached = entry.n_tokens
        p_full = n_cached // ps
        self.pool.share(slot, entry.pages[:p_full])
        if n_cached % ps:
            if not self.pool.grow(slot, n_cached - 1):
                # Pool dry for the divergence copy: fall back to the
                # full-page part (re-prefill the partial tail's tokens).
                n_cached = p_full * ps
            else:
                dst = int(self.pool.row(slot)[p_full])
                self._cow_pairs.append((int(entry.pages[p_full]), dst))
                self.stats["cow_copies"] += 1
                _PREFIX_COUNTERS["cow_copies"].inc(tags=tags)
        if n_cached <= 0:
            # Degraded all the way to cold (prefix shorter than a page
            # and no COW page free).
            self.prefix_cache.release(entry)
            self.stats["prefix_misses"] += 1
            _PREFIX_COUNTERS["misses"].inc(tags=tags)
            return 0
        self._slot_entry[slot] = entry
        req.cached_tokens = n_cached
        self.stats["prefix_hits"] += 1
        self.stats["prefix_cached_tokens"] += n_cached
        _PREFIX_COUNTERS["hits"].inc(tags=tags)
        return n_cached

    def _apply_cow(self) -> None:
        """Flush the tick's queued copy-on-write pairs as one fused
        `copy_pages` dispatch. Pair counts are padded to a power of two
        (capped at n_slots — at most one COW per admitted slot per
        tick), so the copy lowers O(log n_slots) programs total;
        padding pairs are (0, 0) null-page no-ops."""
        if not self._cow_pairs:
            return
        rt = self._rt
        pairs, self._cow_pairs = self._cow_pairs, []
        width = _pow2_width(len(pairs))
        src = np.zeros(width, np.int32)
        dst = np.zeros(width, np.int32)
        for i, (s, d) in enumerate(pairs):
            src[i] = s
            dst[i] = d
        self.cache = rt.copy_pages(
            self.cache, rt.jnp.asarray(src), rt.jnp.asarray(dst))
        if self.spec_k:
            # Mirror the COW into the draft pool: the shared-table
            # invariant (draft page p ≡ target page p, token-for-token)
            # must survive divergence copies, or a warm bind's partial
            # tail page would feed the draft stale K/V.
            self.draft_cache = rt.copy_pages(
                self.draft_cache, rt.jnp.asarray(src), rt.jnp.asarray(dst))

    # ----------------------------------------------- chunked prefill

    def _run_prefill_chunks(self, decoding: list[int]) -> int:
        """Spend the tick's prefill allowance: advance mid-prefill
        slots chunk-by-chunk, FCFS (the head slot finishes before the
        next starts — earliest-admitted reaches its first token first).
        `prefill_token_budget` is the prefill a DECODE STEP may be made
        to wait for, and a tick runs one decode window of k steps for
        the `decoding` slots, so the tick may place budget × k prompt
        tokens: the per-token stall stays one budget of chunk compute
        whatever the window's length (a window of one step, and a
        speculative tick, which is one target pass, carry one budget;
        budget 0 = pure decode ticks). With nothing decoding there is
        nobody to stall: an idle tick may place a whole tick's
        allowance (budget × the engine's decode window, what a tick
        beside a full window places; so a prompt alone in the engine
        is cut into the programs the same prompt is cut into under
        load) and always advances at least one chunk. → tokens spent.

        The allowance is strict, and so is the pool: while slots decode,
        chunks grow only into the pages those slots are not about to
        need (`_decode_page_reserve`), so a full pool stalls prefill
        instead of preempting it.

        The tick's rows are collected first and cut into programs by
        `_dispatch_chunks`, whose heights are constants of the engine
        (`chunk_heights`): bucketed by table width, as tall as one
        budget fills it, run several times by a tick with more rows (a
        longer window, many short prompts); at one table width, as
        tall as half the tick's allowance or a quarter of it — the
        same algorithm with its parameters read off the engine's own
        budget, chunk and window, not a second path.
        """
        if not self._prefilling:
            return 0
        if decoding:
            steps = 1 if self.spec_k else self._pick_window(decoding)
            allowance = self.prefill_budget * steps
            spare = self._decode_page_reserve(decoding)
        else:
            allowance = max(self.prefill_budget * self.decode_block,
                            self.prefill_chunk)
            spare = 0
        self.stats["prefill_allowance"] += allowance
        spent = 0
        while self._prefilling:
            # Pack the tick's chunk ROWS, FCFS, until the work, the
            # allowance or the pool runs out. Rows from the same prompt
            # (consecutive chunks) are as legal as rows from different
            # slots: within a layer every row's K/V is written to its
            # pages BEFORE any row attends, and causal masking bounds
            # each row to its own prefix — the same argument that makes
            # chunked prefill exact across dispatches makes it exact
            # across rows of one dispatch.
            batch: list[tuple[int, GenRequest, int, int]] = []
            planned = 0
            stop = False
            with self._phase("prefill.build"):
                for slot in self._prefilling:
                    if stop:
                        break
                    req = self.slot_req[slot]
                    done = self._chunk_pos[slot]
                    total = len(req.prompt_ids)
                    while done < total:
                        n = min(self.prefill_chunk, total - done)
                        if spent + planned + n > allowance:
                            stop = True
                            break
                        # A prompt's last chunk makes its slot a decoding
                        # one: the page its own decode is about to need
                        # is set aside with the others'.
                        own = (self._next_page_needed(
                            slot, total, self.pool.pages_for(total - 1))
                            if decoding and done + n == total else 0)
                        if not self.pool.grow(slot, done + n - 1,
                                              self._cache_reclaim,
                                              spare=spare + own):
                            # Pool dry: stop at the blocked chunk (FCFS —
                            # later work must not consume pages the head
                            # could use).
                            stop = True
                            break
                        spare += own
                        batch.append((slot, req, done, n))
                        planned += n
                        done += n
            if not batch:
                # Head page-blocked or allowance exhausted. With decode
                # in flight, retiring requests will free pages — stall
                # this tick and retry. With nothing decoding and several
                # mid-prefill slots wedged against each other, preempt
                # the YOUNGEST (least sunk prefill work) to unwedge the
                # head. A lone prefilling slot can always grow (submit()
                # caps prompts at the pool size), so this terminates.
                if (not decoding and spent == 0
                        and len(self._prefilling) > 1):
                    reclaim = [s for s in self._prefilling
                               if self.pool.slot_n_pages[s]]
                    if reclaim:
                        # Youngest PAGE-HOLDING slot, as in
                        # _fit_window_pages: a slot admitted but not yet
                        # chunked frees nothing and requeueing it only
                        # inverts FCFS.
                        self._preempt(reclaim[-1])
                        continue
                break
            self._dispatch_chunks(batch)
            spent += planned
        if allowance:
            # Allowance utilization: how much of what ticks WITH waiting
            # prefill work may place they do place — sustained ~1.0
            # under queue depth means prefill throughput (not admission,
            # not the pool) is the TTFT bottleneck.
            with self._lock:
                self._budget_util_ewma = self._ewma(
                    self._budget_util_ewma, spent / allowance)
        return spent

    def _next_page_needed(self, slot: int, position: int, held: int) -> int:
        """1 if a slot decoding from `position` with `held` pages must
        take another page before its request's `max_tokens` are out,
        else 0. A window writes all its steps' positions for every slot
        it carries, so what is left counts in whole windows."""
        req = self.slot_req[slot]
        left = req.max_tokens - len(req.out_ids)
        left = -(-left // self.decode_block) * self.decode_block
        last = min(position + left, self.max_len) - 1
        return int(self.pool.pages_for(last) > held)

    def _decode_page_reserve(self, decoding: list[int]) -> int:
        """Pages a tick's prefill leaves free for the slots that already
        decode: for each, the NEXT page it will need within what is left
        of its `max_tokens` (at most one a slot). The window's own need
        alone would be too little (full slots swing around the pool's
        size from tick to tick, and the fitter then preempts a
        mid-prefill slot whose rows were just paid for); everything a
        slot could ever need would hold slots empty for a `max_tokens`
        that is never reached. No clock: positions, page counts and the
        requests' own limits."""
        held = self.pool.slot_n_pages
        return sum(self._next_page_needed(s, int(self.positions[s]),
                                          int(held[s])) for s in decoding)

    def _kv_planes(self) -> tuple:
        """(the plane whose pages the kernels' block rules are asked
        about, the value's lanes, whether the pool is LATENT: one plane
        `kv` whose row is key and, in its first `kv_lora_rank` lanes,
        value) of the full kind's cache."""
        if "kv" in self.cache:
            return self.cache["kv"], self.cfg.kv_lora_rank, True
        return self.cache["k"], self.cache["v"].shape[3], False

    def _count_decode_pages(self, active: list[int], width: int) -> None:
        """`decode_block_fill`'s two sums for one decode window at table
        width `width`: the pages the decoding slots' keys lie on (the
        pages the decode kernel fetches), and those pages rounded up to
        whole kv blocks of the kernel (its own rule,
        ops/paged_attention.decode_block_pages, asked once a width with
        the shapes the kernel sees: a live block's dead columns are
        masked compute). `decode_live_column_share` puts the first sum
        over the table the call is handed: every slot's `width` columns."""
        if width not in self._decode_block_at:
            from ray_tpu.ops.paged_attention import decode_block_pages

            pool, v_lanes, latent = self._kv_planes()
            self._decode_block_at[width] = decode_block_pages(
                width, self.page_size, pool.shape[3] // self.tp,
                pool.dtype.itemsize, self.cfg.n_heads // self.tp,
                v_lanes // self.tp, latent=latent)
        block = self._decode_block_at[width]
        live = self.pool.pages_for(self.positions[active])
        self.stats["decode_pages_live"] += int(live.sum())
        self.stats["decode_pages_fetched"] += int(
            (-(-live // block) * block).sum())
        self.stats["decode_columns"] += self.n_slots * width

    def _chunk_width(self, done: int, n: int) -> int:
        """Pow-2 page-table width a chunk row [done, done+n) actually
        needs to attend over: the pages covering its slot's written
        tokens PLUS this chunk, bucketed by the shared `_pow2_width`
        rule (the prefill twin of _decode_table_view's width)."""
        return min(_pow2_width(self.pool.pages_for(done + n - 1)),
                   self.max_pages_per_slot)

    def _prefill_block_pages(self, width: int) -> int:
        """Table columns a grid step of the prefill kernel attends in a
        dispatch of this width: the kernel's own rule
        (ops/paged_attention.prefill_block_pages), asked once a width
        with the shapes the kernel sees (a tp shard's heads)."""
        if width not in self._block_pages_at:
            from ray_tpu.ops.paged_attention import prefill_block_pages

            pool, v_lanes, latent = self._kv_planes()
            heads = self.cfg.n_heads // self.tp
            self._block_pages_at[width] = prefill_block_pages(
                width, self.page_size, pool.shape[3] // self.tp,
                pool.dtype.itemsize, self.prefill_chunk,
                heads * self.cfg.head_dim,
                np.dtype(self.cfg.dtype).itemsize, heads,
                v_lanes // self.tp, latent=latent)
        return self._block_pages_at[width]

    def _dispatch_chunks(self, batch) -> None:
        """Width-bucketed chunk dispatch: group the TICK's chunk rows by
        the pow-2 page width each row actually attends over
        (`_chunk_width`) and cut each bucket, in batch (FCFS) order,
        into fixed-shape [rows, C] dispatches (`_cut_rows`: the heights
        are the engine's own, `chunk_programs`), each carrying a
        table view sliced to its bucket's width (a bucket's last
        dispatch pads with inert rows) — interior chunks of a
        long-max-len engine stop paying attention compute/bytes ∝
        max_pages_per_slot, and rows of one width from different prompts
        fill a program together. Buckets run in ASCENDING width order:
        consecutive chunks of one prompt have monotonically
        non-decreasing widths (written tokens only grow), so ascending
        order preserves the write-before-attend chain across buckets
        exactly as batch order does within one. A bucketed program's
        height does not follow the tick's allowance: a tall program
        would carry the few rows of one width among inert ones, and an
        inert row costs what a live one does outside the kernel. With
        prefill_width_bucketing off every row dispatches at full width,
        the tick's rows are one bucket, and the program is as tall as
        half a tick's allowance or a quarter of it, whichever holds
        what is left: a prompt is one pass over the weights, not one a
        budget."""
        if self.prefill_width_bucketing:
            buckets: dict[int, list] = {}
            for row in batch:
                _slot, _req, done, n = row
                buckets.setdefault(self._chunk_width(done, n), []).append(row)
        else:
            buckets = {self.max_pages_per_slot: batch}
        failed: set[int] = set()
        for width in sorted(buckets):
            rows, i = buckets[width], 0
            for height in self._cut_rows(len(rows)):
                # A dispatch failure releases its slots; later dispatches
                # may still carry those slots' follow-on chunks — drop
                # them (the request already errored, the slot may be
                # rebound).
                group = [r for r in rows[i:i + height]
                         if r[0] not in failed]
                i += height
                if group:
                    failed |= self._dispatch_chunk_bucket(group, width,
                                                          height)

    def _dispatch_chunk_bucket(self, batch, width: int, rows: int) -> set[int]:
        """One fixed-shape [rows, C] prefill_chunk_paged dispatch
        at one page-table width: each (slot, req, done, n) ROW writes
        prompt tokens [done, done+n) into its slot's pages (several rows
        may carry consecutive chunks of the same prompt); rows without
        work are inert (n_valid 0). `batch` holds at most `rows`
        rows (`_dispatch_chunks` cuts them so). The table view is sliced to `width`
        columns — every row's written prefix + chunk fits by bucket
        construction, and a slot's allocation BEYOND the row's own width
        (a later same-tick chunk already grew it) is simply invisible to
        this row, which never reads or writes past its own kv length.
        The shapes are part of the jit cache key (tables is a traced
        argument), so programs lower per (height, width, head) triple of
        `chunk_programs()` — bucketed, the 2·log₂(max_pages)+2 budget
        the compile-count test pins; at one width, two. Final
        chunks alone return logits (at one width the program computes
        them regardless and they are pulled only then) and graduate
        their slot to decode
        (the first token emits here — TTFT does not wait for the next
        decode window). Returns the set of slots released by a dispatch
        failure (empty on success) so the bucketed caller can drop their
        follow-on chunks from later buckets in the same tick."""
        rt = self._rt
        with self._phase("prefill.build"):
            toks = np.zeros((rows, self.prefill_chunk), np.int32)
            offsets = np.zeros(rows, np.int32)
            valid = np.zeros(rows, np.int32)
            tables = np.zeros((rows, width), np.int32)
            slots = np.zeros(rows, np.int32)
            any_final = False
            t0 = time.perf_counter()
            for i, (slot, req, done, n) in enumerate(batch):
                toks[i, :n] = req.prompt_ids[done:done + n]
                offsets[i] = done
                valid[i] = n
                slots[i] = slot
                tables[i] = self.pool.row(slot, width)
                any_final |= done + n >= len(req.prompt_ids)
                if req.first_chunk_at is None:
                    req.first_chunk_at = t0
        try:
            with self._phase("prefill.dispatch"):
                last, self.cache = rt.prefill_chunk_paged(
                    self.cfg, self.params, rt.jnp.asarray(toks), self.cache,
                    rt.jnp.asarray(tables), rt.jnp.asarray(offsets),
                    rt.jnp.asarray(valid),
                    return_logits=any_final or False not in self.chunk_heads,
                    attn_impl=self.attn_impl, **self._row_slots(slots))
                if self.spec_k:
                    # Draft prefill mirror: the same [rows, C] rows
                    # through the draft model into the draft pool (same
                    # tables/offsets), so a slot graduates with draft
                    # cursor == target cursor and the propose loop never
                    # needs a catch-up pass. The draft's graduation
                    # logits are unused (propose feeds the pending token
                    # itself), so this is always the cheaper no-head
                    # program.
                    _none, self.draft_cache = rt.prefill_chunk_paged(
                        self.draft_cfg, self.draft_params,
                        rt.jnp.asarray(toks), self.draft_cache,
                        rt.jnp.asarray(tables), rt.jnp.asarray(offsets),
                        rt.jnp.asarray(valid),
                        return_logits=False, attn_impl=self.attn_impl)
            if any_final:
                with self._phase("prefill.pull"):
                    last = np.asarray(last)
        except Exception as e:
            failed = set()
            for slot, req, _done, _n in batch:
                if slot in failed:
                    continue
                failed.add(slot)
                req.error = f"prefill failed: {e!r}"
                req.done.set()
                self._release(slot)
            return failed
        now = time.perf_counter()
        with self._phase("prefill.graduate"):
            self.stats["prefill_time_s"] += now - t0
            self.stats["prefill_tokens"] += sum(n for *_x, n in batch)
            self.stats["prefill_chunks"] += len(batch)
            self.stats["prefill_dispatches"] += 1
            self.stats["prefill_rows_dispatched"] += rows
            block = self._prefill_block_pages(width)
            for _s, _r, done, n in batch:
                live = self.pool.pages_for(done + n - 1)
                self.stats["prefill_pages_live"] += live
                self.stats["prefill_pages_fetched"] += -(-live // block) * block
            self._dispatch_width_ring.append(width)
            self._dispatch_width_counts[width] = (
                self._dispatch_width_counts.get(width, 0) + 1)
            _PREFILL_CHUNK_HIST.observe(now - t0, tags=self._impl_tags())
            _PREFILL_DISPATCH_COUNTER.inc(
                1.0, tags={"replica": self._impl_tags()["replica"],
                           "width": str(width)})
            for i, (slot, req, done, n) in enumerate(batch):
                self._chunk_pos[slot] = done + n
                if done + n < len(req.prompt_ids):
                    continue
                req.last_chunk_at = now
                self._prefilling.remove(slot)
                self._chunk_pos.pop(slot, None)
                tok = self._sample(last[i], req.temperature)
                self.tokens[slot] = tok
                self.positions[slot] = len(req.prompt_ids)
                self.temps[slot] = req.temperature
                self._hand_over(req, now, 0)
                if self._emit(req, tok):
                    self._release(slot)
                elif self.pool_role == "prefill":
                    # Disaggregated serving: the prefill pool's job ends
                    # at the first token — donate the prompt's pages and
                    # hand the stream off to the decode pool.
                    self._handoff_prefill(slot, req)
        return set()

    def _release(self, slot: int, *, owed: bool = False) -> None:
        """Free a slot. Positions reset so multi-step windows never walk an
        idle slot's write cursor toward the cache boundary.

        `owed`: the request is over but for its last token, which the
        step in flight computes (`_InFlight.owed`): it leaves as a request
        that completed cleanly does, and the token follows it.

        Insert-on-free: a request that completed cleanly donates its
        chunk-aligned written prefix (prompt AND generated tokens — the
        next turn of a chat re-prefills exactly this sequence) to the
        prefix cache BEFORE its pages are unreffed, so the cache's own
        refs keep the donated pages alive. Preempted/errored slots never
        donate: a preempt exists to RECLAIM pages (donation would pin
        them right back), and an error path's pages may be garbage."""
        req = self.slot_req[slot]
        with self._lock:
            self.slot_req[slot] = None
        if (self.prefix_cache is not None and req is not None
                and (owed or req.done.is_set()) and req.error is None
                and (not req.migrated or req.kv_handoff is not None)):
            # Migrated requests normally never donate (drain export
            # wants the pages BACK) — except a prefill-pool handoff,
            # whose pages were just object-donated and are equally
            # valid local warm state for the next same-prefix prompt.
            # positions[slot] counts the slot's correctly-written leading
            # positions in EVERY path (prefill graduation sets it to the
            # prompt length; each decode write advances it; a mid-window
            # finish just leaves this conservative). The written
            # sequence is the TRUE context prompt_ids[:n_prompt] +
            # out_ids — NOT prompt_ids + out_ids, which double-counts
            # the pre-preempt generated tokens a regrow already folded
            # into prompt_ids and would key pages under digests of a
            # sequence that was never written (wrong-KV serving if a
            # later prompt matched the stale key).
            n_written = int(self.positions[slot])
            seq = (req.prompt_ids[:req.n_prompt]
                   + req.out_ids)[:n_written]
            self.prefix_cache.donate(seq, self.pool.row(slot),
                                     memo=req.prefix_hashes)
            self._sync_cache_evictions()
        if (self.kv_transfer and self._kv_store is not None
                and self.pool_role is None and req is not None
                and (owed or req.done.is_set()) and req.error is None
                and not req.migrated):
            # Insert-on-free OBJECT donation (the fused-engine half of
            # the init contract: "completed requests donate"): the
            # written chunk-aligned prefix leaves as page-set objects
            # BEFORE the slot's refs drop, so any other replica — via a
            # pushed summary hint or an explicit descriptor — can adopt
            # it. The summary memo gates repeat traffic: a chain this
            # engine already donated at >= this depth skips even the
            # store resolve (pool replicas donate on handoff/drain
            # instead — prefill donates per-request already, decode
            # frees adopted pages it did not produce).
            n_written = int(self.positions[slot])
            seq = (req.prompt_ids[:req.n_prompt]
                   + req.out_ids)[:n_written]
            head = self._kv_chain_head(seq)
            if (head is not None
                    and self._kv_donated.get(head, 0)
                    < len(seq) // self.prefill_chunk):
                self._donate_kv(seq, self.pool.row(slot),
                                memo=req.prefix_hashes)
        carry = self._carry
        if carry is not None and carry.mask[slot]:
            # The step in flight computed a token for a request that is
            # over: the row is thrown away (or, `owed`, kept for the
            # request alone). Its write and its advance of the slot's
            # state stand, harmlessly: whatever takes these pages or this
            # slot next is dispatched after that step.
            carry.mask[slot] = False
            if owed:
                carry.owed[slot] = req
            else:
                self.stats["lookahead_rows_dropped"] += 1
            if not carry.mask.any() and not carry.owed:
                self._carry = None
        self.tokens[slot] = 0
        self.positions[slot] = 0
        self.temps[slot] = 0.0
        if slot in self._chunk_pos:      # mid-prefill slot going away
            self._chunk_pos.pop(slot, None)
            self._prefilling.remove(slot)
        entry = self._slot_entry.pop(slot, None)
        if entry is not None:
            self.prefix_cache.release(entry)
        self.pool.free_slot(slot)

    def _preempt(self, slot: int) -> None:
        """Evict a slot by RECOMPUTE (vLLM-style): its pages return to the
        pool and the request re-enters the queue with context = prompt +
        everything generated so far, so a later prefill rebuilds the KV
        and generation continues exactly where it stopped (out_ids is
        preserved; _emit's budget check keeps counting against it).

        The regrow is anchored at n_prompt — NOT appended to the
        already-regrown prompt_ids — so the invariant `context ==
        prompt_ids[:n_prompt] + out_ids` holds across ANY number of
        preempts. Appending (the old form) duplicated the pre-preempt
        generated tokens on the SECOND preempt, corrupting both the
        recompute context and every digest keyed off it (pinned by
        test_kv_objects.TestPreemptRegrow)."""
        if self._carry is not None and self._carry.mask[slot]:
            # Absorb, never discard, for a live slot: the context below
            # must hold every token the device has computed for it. (The
            # page fitter never gets here with a step in flight: its
            # one-step rung then needs no page. This is for callers
            # outside the tick.)
            self._absorb_carry()
            if self.slot_req[slot] is None:
                return              # that token finished the request
        req = self.slot_req[slot]
        req.prompt_ids = (list(req.prompt_ids[:req.n_prompt])
                          + [int(t) for t in req.out_ids])
        self._release(slot)
        self.stats["preemptions"] += 1
        if (len(req.prompt_ids) > self._prompt_cap
                or self.pool.pages_for(len(req.prompt_ids)) > self.n_pages):
            # Regrown context no longer fits the cache or the pool — finish
            # with what we have rather than wedging the queue, flagged so
            # clients can tell this from natural completion.
            req.truncated = True
            self._finish(req)
            return
        # Head of the deferred FIFO: it is the oldest in-flight work.
        self._deferred.appendleft(req)

    def _finish(self, req: GenRequest) -> None:
        """Slot-independent completion bookkeeping (shared by capacity
        finishes and unresumable preemptions)."""
        req.finished_at = time.perf_counter()
        self.stats["completed"] += 1
        if req.stream is not None:
            req.stream.put(None)
        req.done.set()

    def _fit_window_pages(self, active: list[int],
                          k: int) -> tuple[list[int], int, str | None]:
        """Shrink the window and/or preempt until the pool can
        cover every active slot's writes for the window, then allocate.
        → (surviving active slots, window size; 0 = nothing to run, why
        no step follows the window in flight; None = one does).

        A window of `kk` rows dispatches `kk` steps, or `kk - 1` behind a
        step in flight (`self._carry`: its row is the window's first), and
        a slot that step covers writes from `positions + 1`. The window
        leaves one more step in flight when there is a window to follow
        it and room for its write: `kk` > 1 (the one-step tick samples on
        the host), some slot with two tokens or more of budget after
        this window (so the next tick is a window too, not the one-step
        tick), the position after the window short of max_len for every
        slot, and a page for it from the pool, asked after the prefix
        cache's reclaim hook and before the window shrinks or anything
        is shed. Otherwise the tick is the parent's."""
        carry = self._carry
        behind = int(carry is not None)
        while active:
            first = self.positions[active] + (
                carry.mask[active] if behind else 0)
            for kk in [k] + [x for x in self._k_ladder if x < k] + [1]:
                # Cached pages are speculative value; a live decode
                # window is not. Zero-active prefix-cache entries are
                # evicted (the reclaim hook) before the window shrinks —
                # and long before anything is preempted.
                last = first + (kk - behind) - 1
                cause = (self._lookahead_stand_down(active, kk, last + 1)
                         if kk == k else "pages")
                if cause is None:
                    if self.pool.grow(active, last + 1, self._cache_reclaim):
                        return active, kk, None
                    cause = "pages"
                # A slot AT max_len (its last window ended there, where
                # max_len is whole pages) runs the one-step tick only to
                # be finished by it: there is no page past the table.
                last = np.minimum(last, self.max_len - 1)
                # Behind a step in flight the one-step rung asks for no
                # page (that step's write is held): nothing is shed
                # while the device is a position ahead of the host.
                if self.pool.grow(active, last, self._cache_reclaim):
                    return active, kk, cause
            active = self._shed_for_pages(active)
        return [], 0, "pages"

    def _lookahead_stand_down(self, active: list[int], k: int,
                              ahead) -> str | None:
        """What, pages apart, keeps a window of `k` rows from leaving one
        more step in flight (one of _STAND_DOWN), or None. `ahead`: the
        position that step would write, per active slot."""
        if k == 1:
            return "k1"
        if int(ahead.max()) >= self.max_len:
            return "max_len"
        carry = self._carry
        for slot in active:
            req = self.slot_req[slot]
            rows = k - int(carry is not None and not carry.mask[slot])
            if req.max_tokens - len(req.out_ids) - rows >= 2:
                return None
        return "budget"

    def _shed_for_pages(self, active: list[int]) -> list[int]:
        """Pressure-relief tail shared by the decode-window and
        speculative page fitters (one implementation so the two engines
        can't diverge under pool pressure), in fixed order: reclaim the
        YOUNGEST page-holding mid-prefill slot first (chunked
        over-admission can drain the pool into slots `active` can't
        see; zero sunk decode work, pure recompute — a slot admitted
        but not yet chunked holds nothing worth requeueing for); then,
        if a sole survivor still can't fit, the request plus pool are
        simply too big — finish it; else preempt the decode victim with
        the most remaining budget. → surviving active slots."""
        reclaim = [s for s in self._prefilling
                   if self.pool.slot_n_pages[s]]
        if reclaim:
            self._preempt(reclaim[-1])
            return active
        if len(active) == 1:
            self._finish_capacity(active[0])
            return []
        victim = max(active, key=lambda s: self.slot_req[s].max_tokens
                     - len(self.slot_req[s].out_ids))
        self._preempt(victim)
        return [s for s in active if s != victim]

    def _finish_capacity(self, slot: int) -> None:
        """Slot exhausted the cache: finish early rather than overflow."""
        req = self.slot_req[slot]
        req.error = None
        req.truncated = True
        self._finish(req)
        self._release(slot)

    def _pick_window(self, active: list[int]) -> int:
        """Fused-decode window size. Bounded by the LONGEST remaining
        budget (a nearly-done slot trims its tail host-side rather than
        forcing k=1 on everyone — its wasted window tokens cost ~ms of
        compute vs a full RTT per token saved) and, strictly, by the
        KV-cache capacity of the furthest-along slot (scatter writes past
        max_len would be dropped and the slot's attention mask poisoned)."""
        remaining = max(self.slot_req[s].max_tokens
                        - len(self.slot_req[s].out_ids) for s in active)
        # Mid-window eos trimming wastes the tail of the window; requests
        # with an eos_id cap the window to keep waste bounded.
        if any(self.slot_req[s].eos_id is not None for s in active):
            remaining = min(remaining, 8)
        cap = self.max_len - int(max(self.positions[s] for s in active))
        bound = min(remaining, cap)
        for k in self._k_ladder:
            if k <= bound:
                return k
        return 1

    # --------------------------------------------- speculative decoding

    def _decode_table_view(self, active: list[int]) -> np.ndarray:
        """Page-table view for a decode/propose/verify dispatch.

        Ragged-attention win: slice the table to the widest ACTIVE slot
        (next power of two bounds compile count), so attention
        gathers/reads scale with the pages actually in use, not max_len.
        Mid-prefill slots don't count: their rows are zeroed in a COPY so
        their window writes land on the null page instead of corrupting
        the pages their chunks already filled (and a long prompt
        mid-prefill never widens — and re-compiles — every window while
        it streams in)."""
        width = min(_pow2_width(int(self.pool.slot_n_pages[active].max())),
                    self.max_pages_per_slot)
        return self.pool.table_view(width, blank=self._prefilling)

    def _fit_spec_pages(self, active: list[int], k_map: dict) -> list[int]:
        """Paged fit for the speculative window: grow every active slot
        to cover its verify writes (cursor .. cursor + k_i). Pressure
        order mirrors _fit_window_pages (cached pages are speculative
        value, a live window is not): zero-active prefix-cache entries
        are reclaimed at each rung FIRST, then the proposal budget
        degrades (k_i → 1 → 0; a 0-proposal tick is a plain one-token
        verify, i.e. ordinary decode), then mid-prefill slots are
        reclaimed, then a decode victim preempted (the shared
        _shed_for_pages tail)."""
        while active:
            for cap in (self.spec_k, 1, 0):
                ext = {s: min(k_map[s], cap) for s in active}
                if self.pool.grow(
                        active,
                        self.positions[active] + [ext[s] for s in active],
                        self._cache_reclaim):
                    k_map.update(ext)
                    return active
            active = self._shed_for_pages(active)
        return []

    def _spec_decode_window(self, active: list[int]) -> int:
        """One speculative tick for every decode-ready slot: the draft
        proposes up to spec_k tokens per slot in ONE fused on-device
        loop (models/paged_kv.spec_draft_propose — k+1 draft steps, no
        host round trips inside), the target scores all k+1 positions in
        ONE batched chunked-prefill verify pass (verify_chunk_paged),
        rejection sampling accepts a prefix of the proposals plus the
        correction/bonus token, and the rejected tail's pages are rolled
        back in one batched cursor update. → slots that did decode work.
        """
        with self._phase("plan"):
            planned = self._plan_spec_window(active)
        if planned is None:
            return 0
        active, k_map, table_view, n_prop = planned
        rt = self._rt
        jnp = rt.jnp
        k = self.spec_k
        t0 = time.perf_counter()
        self._rng_key, sub = rt.jax.random.split(self._rng_key)
        # Full distributions are only read by the temperature>0
        # rejection-sampling branch: the draft's q, and the target's
        # verify logits (greedy acceptance is argmax-chain matching).
        # When every active slot is greedy — the common serving case —
        # the draft never materializes its [k, B, V] probs on device
        # (need_probs=False program variant), and both [.., V]
        # device->host copies (~14 MB/tick combined at OPT-1.3B vocab,
        # k=4, B=8) are skipped in favor of the [B, k+1] argmax.
        sampling = any(self.slot_req[s].temperature > 0.0 for s in active)
        with self._phase("decode.dispatch"):
            proposals, draft_probs, self.draft_cache = rt.spec_draft_propose(
                self.draft_cfg, self.draft_params, jnp.asarray(self.tokens),
                self.draft_cache, jnp.asarray(self.positions),
                jnp.asarray(table_view), jnp.asarray(n_prop),
                self._upload_temps(), sub, k=k, attn_impl=self.attn_impl,
                need_probs=sampling)
        with self._phase("decode.pull"):
            proposals = np.asarray(proposals)                  # [k, B]
            draft_probs = np.asarray(draft_probs) if sampling else None
        with self._phase("plan"):
            # Verify rows: [pending, d_1 .. d_k] per slot, written at the
            # slot's decode cursor; inert rows (mid-prefill / free slots)
            # carry n_valid 0.
            vtoks = np.zeros((self.n_slots, k + 1), np.int32)
            vtoks[:, 0] = self.tokens
            vtoks[:, 1:] = proposals.T
            n_valid = np.where(n_prop >= 0, n_prop + 1, 0).astype(np.int32)
        with self._phase("spec_verify"):
            logits, self.cache = rt.verify_chunk_paged(
                self.cfg, self.params, jnp.asarray(vtoks), self.cache,
                jnp.asarray(table_view), jnp.asarray(self.positions),
                jnp.asarray(n_valid), attn_impl=self.attn_impl)
            if sampling:
                logits = np.asarray(logits)                # [B, k+1, V]
                argmax = None
            else:
                argmax = np.asarray(jnp.argmax(logits, axis=-1))
                logits = None                              # [B, k+1]
        with self._phase("emit"):
            return self._accept_spec_window(
                active, k_map, proposals, draft_probs, logits, argmax, t0)

    def _plan_spec_window(self, active: list[int]):
        """Host-side plan of a speculative tick. → (surviving active
        slots, per-slot proposal budgets, table view, n_prop [B]) or
        None when nothing is left to run."""
        k = self.spec_k
        survivors = []
        for slot in active:
            if self.positions[slot] + 1 >= self.max_len:
                self._finish_capacity(slot)
            else:
                survivors.append(slot)
        active = survivors
        if not active:
            return None
        # Per-slot proposal budget: never past the request's remaining
        # output budget (− 1: the verify pass itself always emits one
        # token beyond the accepted proposals) or the KV capacity. 0 is
        # legal — the tick degenerates to a one-token verify (= decode)
        # but still dispatches the full fixed-shape propose/verify pair:
        # a per-k_eff program variant would trade the bounded compile
        # count (ONE program per (k, width)) for savings that are
        # negligible where spec belongs — a (k+1)-wide verify costs
        # ≈ a 1-wide pass on a weight-bound decode, and the masked
        # draft steps are ~k/(draft weight ratio) of a target pass.
        k_map = {
            s: max(0, min(k,
                          self.slot_req[s].max_tokens
                          - len(self.slot_req[s].out_ids) - 1,
                          self.max_len - 1 - int(self.positions[s])))
            for s in active}
        active = self._fit_spec_pages(active, k_map)
        if not active:
            return None
        n_prop = np.full(self.n_slots, -1, np.int32)
        for slot in active:
            n_prop[slot] = k_map[slot]
        return active, k_map, self._decode_table_view(active), n_prop

    def _accept_spec_window(self, active: list[int], k_map: dict, proposals,
                            draft_probs, logits, argmax, t0: float) -> int:
        """Rejection-sample each slot's proposals against the verify
        pass, emit, roll the rejected tails' pages back, and book the
        tick. → slots that did decode work."""
        k = self.spec_k
        proposed = accepted = emitted_total = 0
        survivors = []
        handed_at = time.perf_counter()
        for slot in active:
            req = self.slot_req[slot]
            ki = k_map[slot]
            proposed += ki
            emitted, j = spec_accept_tokens(
                self._spec_rng, req.temperature, proposals[:, slot],
                draft_probs[:, slot] if draft_probs is not None else None,
                logits[slot] if logits is not None else None, ki,
                verify_argmax=argmax[slot] if argmax is not None else None)
            t = int(self.positions[slot])
            e = 0
            finished = False
            self._hand_over(req, handed_at)
            for tok in emitted:
                e += 1
                if self._emit(req, tok):
                    finished = True
                    break
            # Cursor after acceptance: every emitted token except the
            # LAST has its KV written by the verify pass ([pending,
            # d_1..d_ki] landed at t..t+ki); the last emitted token is
            # the new pending token — exactly the non-speculative
            # cursor/pending contract.
            self.positions[slot] = t + e
            accepted += min(j, e)
            emitted_total += e
            if finished:
                # Insert-on-free donation reads positions[slot], which
                # now covers exactly the emitted tokens — exported
                # continuations and cache entries carry ONLY accepted
                # tokens.
                self._release(slot)
            else:
                self.tokens[slot] = emitted[e - 1]
                survivors.append(slot)
        if survivors:
            # Rejected proposals' pages: past a rolled-back cursor they
            # were grown for this window alone (shared prefix-cache pages
            # sit below it), so no partially-verified KV stays held.
            self.pool.truncate(survivors, self.positions[survivors])
        end = time.perf_counter()
        per_slot = emitted_total / len(active)
        # Cap = what this tick could have emitted: the FITTED per-slot
        # budgets (k_map shrinks under pool/output pressure — the same
        # way the non-spec path books its post-fit shrunk k), idle
        # slots at the full k+1 like the non-spec window counts them.
        cap = (sum(k_map[s] + 1 for s in active)
               + (self.n_slots - len(active)) * (k + 1))
        self._observe_decode(t0, end, per_slot, emitted_total, cap)
        tags = self._impl_tags()
        with self._lock:
            self.stats["spec_ticks"] += 1
            self.stats["spec_slot_steps"] += len(active)
            self.stats["spec_proposed"] += proposed
            self.stats["spec_accepted"] += accepted
            self.stats["spec_emitted"] += emitted_total
            self._spec_accept_ewma = self._ewma(
                self._spec_accept_ewma, per_slot)
        if proposed:
            _SPEC_COUNTERS["proposed"].inc(
                float(proposed), tags={"replica": tags["replica"]})
        if accepted:
            _SPEC_COUNTERS["accepted"].inc(
                float(accepted), tags={"replica": tags["replica"]})
        return len(active)

    def step(self) -> int:
        """One engine tick: admit queued requests, spend the chunked-
        prefill token budget, then one decode window for every
        decode-ready slot. → slots that did work (decoding + prefilling).

        A window of k rows ends with k + 1 steps queued and the
        first k read: the last runs on the device while the NEXT tick
        admits, dispatches chunk programs and plans, and is that tick's
        first row (`_fit_window_pages` says when; `_InFlight`,
        `_absorb_carry`). Between ticks the device may therefore be one
        position ahead of `tokens` / `positions` for the slots that
        step covers; whatever reads them from outside a tick absorbs it
        first. A request whose last token by `max_tokens` is the one in
        flight gives its slot back at once (`_InFlight.owed`): it is in
        no slot and not yet done until that step is read."""
        with self._lock:
            self._mid_tick = True
        try:
            return self._step()
        finally:
            with self._lock:
                self._mid_tick = False

    def _step(self) -> int:
        rt = self._rt
        jnp = rt.jnp
        # A tick is the interval from one `admit` to the next.
        self._ticks.begin(time.perf_counter())
        with self._phase("admit"):
            self._admit()
            # COW flush MUST precede any dispatch that could write this
            # tick: admission queued the pairs, and the first cold chunk
            # of a warm slot writes into its COW'd tail page.
            self._apply_cow()
            with self._lock:
                self._awaiting_max = max(self._awaiting_max,
                                         self._awaiting_first_token())
        self._run_prefill_chunks(self._decode_ready_slots())
        n_prefilling = len(self._prefilling)
        if self.spec_k:
            # Speculative decoding replaces the fused decode window
            # entirely: one draft propose dispatch + one batched verify
            # per tick, emitting 1..k+1 tokens per slot.
            active = self._decode_ready_slots()
            if not active:
                return n_prefilling
            _chaos.hit("llm.decode_window")
            return self._spec_decode_window(active) + n_prefilling
        if self._carry is not None and not self._decode_ready_slots():
            # Nothing decodes, yet a step is in flight: for requests that
            # left their slots ahead of their last token (`_InFlight.owed`).
            self._absorb_carry(self._phase)
        carry = self._carry
        with self._phase("plan"):
            # Mid-prefill slots are not decode-active (their page tables
            # are masked off below); chunks completed this tick already
            # graduated.
            active = self._decode_ready_slots()
            k = 0
            if active:
                # Chaos fault point: a "kill" rule here exits the replica
                # process abruptly with decodes in flight — the scenario
                # the cross-replica failover path must make invisible to
                # clients.
                _chaos.hit("llm.decode_window")
                k = self._pick_window(active)
                active, k, stood_down = self._fit_window_pages(active, k)
                if active:
                    table_view = self._decode_table_view(active)
                    self._count_decode_pages(active, table_view.shape[1])
            if not active:
                return n_prefilling
            # The steps this window dispatches: all its rows, or all but
            # the first where a step of the last window is in flight.
            n_new = k - (carry is not None)
            # decode_step_ms is taken from here (the window's inputs go
            # to the device) to the end of the pull. Behind a step in
            # flight that is the rest of that step and the n_new new
            # ones, over k rows: it reads under the device's step by the
            # part of one step the host's work ran beside.
            t0 = time.perf_counter()
            if carry is None:
                if k > 1:
                    self._rng_key, sub = rt.jax.random.split(self._rng_key)
                    temps = self._upload_temps()
                tokens = jnp.asarray(self.tokens)
                positions = jnp.asarray(self.positions)
            elif n_new:
                # A slot the step in flight covers feeds that step's
                # token, on the device, a position on; a slot that
                # graduated this tick feeds the host's, as ever.
                sub = carry.key
                temps = self._upload_temps()
                tokens, positions = rt.join_window(
                    jnp.asarray(carry.mask), carry.tokens,
                    jnp.asarray(self.tokens), jnp.asarray(self.positions))
            if n_new:
                table_view = jnp.asarray(table_view)
        if not n_new:
            # A one-row window behind a step in flight IS that step: it
            # is read, nothing is dispatched, and the next tick starts
            # from the parent's state.
            self._absorb_carry(self._phase)
            return len(active) + n_prefilling
        if k > 1:
            with self._phase("decode_window"):
                # Device order is the safety argument for the step
                # `ahead` leaves in flight: it is queued here, behind
                # this window's steps and ahead of every chunk program,
                # page copy or step a later tick dispatches, the pool
                # donated from each to the next, and its table view is
                # this tick's immutable upload. A slot the emit below
                # releases keeps its pages, ring rows and state untouched
                # by anyone else until that step has run.
                self._carry = None
                # graftlint: disable=GUARDED-BY (engine-thread state: only _step writes the KV cache while the loop runs; drain/export mutate it after stop() joins the thread)
                toks_out, self.cache = rt.decode_multi_paged(
                    self.cfg, self.params, tokens, self.cache,
                    positions, table_view, n_new, temps, sub,
                    attn_impl=self.attn_impl, phase=self._phase,
                    carried=None if carry is None else carry.tokens,
                    ahead=None if stood_down else (
                        lambda toks, key: self._hold_ahead(
                            active, toks, key)),
                    **self._window_counters)
            with self._phase("emit"):
                # Slot-steps the device was handed this tick (the step
                # left in flight is this tick's; the one absorbed was
                # the last's), over every slot's: slot_occupancy.
                steps = n_new + (self._carry is not None)
                handed_at = time.perf_counter()
                self._observe_decode(
                    t0, handed_at, float(k), steps * len(active),
                    steps * self.n_slots)
                self.stats["lookahead_windows" if stood_down is None else
                           "lookahead_stood_down_" + stood_down] += 1
                if carry is not None:
                    self._pay_owed(carry, toks_out[0], handed_at)
                for slot in active:
                    req = self.slot_req[slot]
                    # A slot the absorbed step did not cover (it joined
                    # this tick) has no first row.
                    rows = toks_out[int(carry is not None
                                        and not carry.mask[slot]):, slot]
                    finished = False
                    self._hand_over(req, handed_at)
                    for tok in rows:
                        if self._emit(req, int(tok)):
                            finished = True
                            break
                    if finished:
                        self._release(slot)
                    else:
                        # graftlint: disable=GUARDED-BY (engine-thread state, see cache note above)
                        self.tokens[slot] = rows[-1]
                        # graftlint: disable=GUARDED-BY (engine-thread state, see cache note above)
                        self.positions[slot] += len(rows)
                        if (self._carry is not None
                                and req.max_tokens - len(req.out_ids) == 1):
                            # The step in flight computes this request's
                            # last token: the slot and its pages are the
                            # next admission's NOW, as they would be had
                            # the token been read with this window.
                            self._release(slot, owed=True)
            return len(active) + n_prefilling
        with self._phase("decode_window"):
            with self._phase("decode.dispatch"):
                logits, self.cache = rt.decode_step_paged(
                    self.cfg, self.params, tokens, self.cache,
                    positions, table_view, attn_impl=self.attn_impl)
            with self._phase("decode.pull"):
                logits = np.asarray(logits)
        with self._phase("emit"):
            handed_at = time.perf_counter()
            self._observe_decode(t0, handed_at, 1.0, len(active),
                                 self.n_slots)
            self.stats["lookahead_stood_down_" + stood_down] += 1
            for slot in active:
                req = self.slot_req[slot]
                if self.positions[slot] + 1 >= self.max_len:
                    self._finish_capacity(slot)
                    continue
                tok = self._sample(logits[slot], req.temperature)
                self.tokens[slot] = tok
                self.positions[slot] += 1
                self._hand_over(req, handed_at)
                if self._emit(req, tok):
                    self._release(slot)
        return len(active) + n_prefilling

    def _upload_temps(self):
        """Every slot's temperature, for a window's step programs. The
        sampling step draws where any of them is above 0
        (`paged_kv._sample_next`): the same test, on the host's copy,
        counts the window as one that drew."""
        if (self.temps > 0.0).any():
            self.stats["decode_windows_drawn"] += 1
        return self._rt.jnp.asarray(self.temps)

    def _decode_ready_slots(self) -> list[int]:
        return [i for i in range(self.n_slots)
                if self.slot_req[i] is not None
                and i not in self._chunk_pos]

    def _hold_ahead(self, active: list[int], tokens, key) -> None:
        """`_decode_window`'s `ahead`: the step it queued behind the
        window of the slots `active`, unread."""
        mask = np.zeros(self.n_slots, bool)
        mask[active] = True
        self._carry = _InFlight(tokens, key, mask)

    def _pay_owed(self, carry: _InFlight, row, now: float) -> None:
        """Hand the requests that left their slots ahead of their last
        token (`_InFlight.owed`) that token, from the step's `row` [B]."""
        for slot, req in carry.owed.items():
            self._hand_over(req, now)
            self._emit(req, int(row[slot]))

    def _absorb_carry(self, phase=lambda _name: contextlib.nullcontext()) -> None:
        """Read the step in flight, if any, and emit its tokens: absorb,
        never discard, for a live slot. A step advances a family's
        per-slot state in place beside writing K/V, so a continuing slot
        whose token were dropped would stay one token ahead of the host
        for good. Afterwards the host's `tokens` / `positions` are the
        device's again, which is what everything that reads or moves a
        live slot's pages or state outside the window needs (`stop`,
        `drain` / `_export_unfinished`, `_preempt`). Inside a tick
        `phase` is the engine's recorder; outside, the engine thread is
        not running."""
        carry, self._carry = self._carry, None
        if carry is None:
            return
        with phase("decode.pull"):
            toks = np.asarray(carry.tokens)
        with phase("emit"):
            now = time.perf_counter()
            self._pay_owed(carry, toks, now)
            for slot in np.flatnonzero(carry.mask):
                req = self.slot_req[slot]
                self._hand_over(req, now)
                if self._emit(req, int(toks[slot])):
                    self._release(slot)
                else:
                    self.tokens[slot] = toks[slot]
                    self.positions[slot] += 1

    def _loop(self) -> None:
        try:
            while not self._shutdown.is_set():
                # step() IS the host-side scheduler tick: it syncs once
                # per multi-token decode window by design, amortized over
                # llm_decode_block tokens, and the sync
                # waits for the window's rows only: one more step is
                # queued behind them, so the device's queue is not empty
                # while this thread emits, admits and plans.
                # graftlint: disable=HOST-SYNC-IN-HOT-LOOP (designed once-per-window sync point)
                n = self.step()
                if n == 0 and self.pending.empty() and not self._deferred:
                    # Idle: block briefly instead of spinning.
                    time.sleep(0.002)
        except Exception as exc:  # noqa: BLE001
            # The engine thread is the only consumer: if it dies (e.g. an
            # XLA OOM at compile time), every queued/active request would
            # otherwise hang until client timeout. Fail them all loudly
            # and poison future submits instead. Setting _fatal and
            # draining happen under the submit lock (see submit()).
            with self._lock:
                self._fatal = f"engine died: {exc!r}"
                doomed = []
                for slot, req in enumerate(self.slot_req):
                    if req is not None:
                        doomed.append(req)
                        self.slot_req[slot] = None
                self._prefilling.clear()
                self._chunk_pos.clear()
                if self._carry is not None:
                    doomed.extend(self._carry.owed.values())
                    self._carry = None
                doomed.extend(self._deferred)
                self._deferred.clear()
                while True:
                    try:
                        doomed.append(self.pending.get_nowait())
                    except queue.Empty:
                        break
            for req in doomed:
                req.error = self._fatal
                if req.stream is not None:
                    req.stream.put(None)
                req.done.set()


class LLMDeployment:
    """Serve deployment class wrapping one engine per replica.

    serve.run(serve.deployment(LLMDeployment).options(...).bind(cfg_name))
    Each replica owns its model + cache; the Serve router load-balances
    requests across replicas, and the engine continuously batches within
    the replica.
    """

    def __init__(self, model: str = "tiny", *, n_slots: int = 8,
                 max_len: int = 1024, params_checkpoint: str | None = None,
                 spec_draft_checkpoint: str | None = None,
                 engine_kwargs: dict | None = None,
                 jax_platform: str | None = None,
                 pool_role: str | None = None,
                 pool_peer: str | None = None):
        if jax_platform is not None:
            # Must run before this replica process's JAX backend initializes
            # (tests pin replicas to host CPU; production leaves the TPU).
            import jax

            jax.config.update("jax_platforms", jax_platform)
        from ray_tpu.models import gpt

        cfg = gpt.GPTConfig.by_name(model)
        params = None
        engine_kwargs = dict(engine_kwargs or {})
        if params_checkpoint:
            from ray_tpu.train.checkpoint import Checkpoint

            ck = Checkpoint.from_directory(params_checkpoint).to_dict()
            params = ck["params"]
        if spec_draft_checkpoint:
            # Trained draft weights for speculative decoding (the
            # llm_spec_draft knob names the draft ARCHITECTURE; without
            # a checkpoint the engine falls back to random draft init,
            # whose ~zero acceptance makes every tick strictly slower
            # than non-speculative decode).
            if "spec_draft_params" in engine_kwargs:
                raise ValueError(
                    "spec_draft_checkpoint and"
                    " engine_kwargs['spec_draft_params'] both name draft"
                    " weights — pass exactly one")
            from ray_tpu.train.checkpoint import Checkpoint

            dck = Checkpoint.from_directory(spec_draft_checkpoint).to_dict()
            engine_kwargs["spec_draft_params"] = dck["params"]
        # Disaggregated pools (serve_pool_role): "prefill" replicas run
        # prompt prefill + first token, donate the KV pages, and hand
        # the stream off to `pool_peer` — the decode deployment whose
        # replicas adopt the pages by reference. The consumer (proxy /
        # handle.stream) reads the peer name off the handoff record, so
        # the engine itself stays deployment-agnostic.
        if pool_role == "prefill" and not pool_peer:
            raise ValueError(
                "pool_role='prefill' requires pool_peer (the decode "
                "deployment name the handoff resubmits to)")
        self._pool_role = pool_role or None
        self._pool_peer = pool_peer
        if pool_role:
            if engine_kwargs.get("pool_role", pool_role) != pool_role:
                raise ValueError(
                    "pool_role and engine_kwargs['pool_role'] disagree "
                    f"({pool_role!r} vs {engine_kwargs['pool_role']!r})")
            engine_kwargs["pool_role"] = pool_role
        self.engine = LLMEngine(cfg, params, n_slots=n_slots,
                                max_len=max_len, **engine_kwargs)
        self.engine.start()

    def generate(self, prompt_ids: list[int], max_tokens: int = 64,
                 temperature: float = 0.0, eos_id: int | None = None,
                 generated_ids: list[int] | None = None,
                 kv: dict | None = None,
                 request_id: str | None = None,
                 prefix_hashes: list | None = None,
                 prefix_chunk: int = 0) -> dict:
        tags = _request_metric_tags()
        req = self.engine.submit(
            prompt_ids, max_tokens=max_tokens, temperature=temperature,
            eos_id=eos_id, generated_ids=generated_ids, kv=kv,
            request_id=request_id, prefix_hashes=prefix_hashes,
            prefix_chunk=prefix_chunk)
        req.done.wait()
        _observe_request_metrics(req, tags)
        if req.migrated:
            if self._pool_role == "prefill":
                # Pool handoff, not an error: the caller (proxy /
                # handle) resubmits this envelope — prompt, the tokens
                # already produced, and the page-set descriptor — to
                # the decode pool, which adopts instead of
                # re-prefilling.
                return {"handoff": self._handoff_record(req),
                        "request_id": req.request_id,
                        "generated_ids": [int(t) for t in req.out_ids],
                        "max_tokens": max_tokens,
                        "temperature": temperature,
                        "eos_id": eos_id}
            # Drain export raced this in-flight call: the proxy/handle
            # treats "migrated"/"draining" errors as retriable-elsewhere
            # (the unary path is side-effect-free to re-run in full).
            raise RuntimeError(
                "request migrated off draining replica: resubmit")
        if req.error:
            raise RuntimeError(req.error)
        return {
            "request_id": req.request_id,
            "output_ids": req.out_ids,
            "truncated": req.truncated,
            "ttft_s": req.first_token_at - req.submitted_at,
            "total_s": req.finished_at - req.submitted_at,
        }

    def _handoff_record(self, req) -> dict:
        """What a migrated request's consumer needs to resume it
        elsewhere: the decode-pool deployment (prefill role only — a
        drain migration resumes within the same deployment), the
        page-set descriptor for adoption, and the memoized chunk-hash
        chain so the destination never re-hashes the context."""
        hand: dict = {}
        if self._pool_role == "prefill":
            hand["deployment"] = self._pool_peer
        if req.kv_handoff is not None:
            hand["kv"] = req.kv_handoff
        if req.prefix_hashes:
            hand["prefix_hashes"] = [h.hex() for h in req.prefix_hashes]
            hand["prefix_chunk"] = self.engine.prefill_chunk
        return hand

    # --------------------------------------------------------- streaming
    # Cursor protocol (consumed by DeploymentHandle.stream and the HTTP
    # proxy's SSE path): submit_stream() → request_id; stream_read(id, cur)
    # long-polls for tokens past the cursor. Tokens come straight from the
    # engine's per-request out_ids, so TTFT is visible to clients the
    # moment prefill lands (ref: the reference proxy's ASGI streaming,
    # http_proxy.py:217).

    def submit_stream(self, request: dict) -> str:
        if not hasattr(self, "_streams"):
            from ray_tpu.core.config import runtime_config

            self._streams: dict[str, Any] = {}
            self._STREAM_TTL_S = runtime_config().llm_stream_ttl_s
        self._gc_streams()
        req = self.engine.submit(
            request["prompt_ids"],
            max_tokens=request.get("max_tokens", 64),
            temperature=request.get("temperature", 0.0),
            eos_id=request.get("eos_id"),
            # Failover resume: tokens the client already received from a
            # dead/drained replica, teacher-forced so the stream cursor
            # splices exactly (see LLMEngine.submit).
            generated_ids=request.get("generated_ids"),
            request_id=request.get("request_id"),
            # Adoption hint + memoized hash chain from a donor's
            # handoff/export (see LLMEngine.submit).
            kv=request.get("kv"),
            prefix_hashes=request.get("prefix_hashes"),
            prefix_chunk=request.get("prefix_chunk", 0),
        )
        self._streams[req.request_id] = req
        return req.request_id

    def stream_read(self, request_id: str, cursor: int = 0,
                    timeout_s: float = 0.25) -> dict:
        """Tokens past `cursor` (long-poll up to timeout_s if none yet)."""
        req = (getattr(self, "_streams", {}) or {}).get(request_id)
        if req is None:
            return {"tokens": [], "done": True,
                    "error": f"unknown stream {request_id!r}"}
        req.last_read_at = time.perf_counter()
        deadline = time.perf_counter() + timeout_s
        while (len(req.out_ids) <= cursor and not req.done.is_set()
               and time.perf_counter() < deadline):
            time.sleep(0.005)
        toks = [int(t) for t in req.out_ids[cursor:]]
        done = req.done.is_set() and cursor + len(toks) >= len(req.out_ids)
        out = {"tokens": toks, "done": done}
        if req.migrated:
            # Drain export / pool handoff: the reader drains the local
            # tail, then resubmits `(prompt, tokens so far)` — done=True
            # here ends only THIS replica's leg of the stream. The
            # handoff record routes the resubmit (decode-pool peer) and
            # carries the page-set descriptor for adoption.
            out["migrated"] = True
            hand = self._handoff_record(req)
            if hand:
                out["handoff"] = hand
        if req.error:
            out["error"] = req.error
        if done:
            self._streams.pop(request_id, None)
            _observe_request_metrics(req, _request_metric_tags())
            out["truncated"] = req.truncated
            if req.first_token_at is not None:
                out["ttft_s"] = req.first_token_at - req.submitted_at
            if req.finished_at is not None:
                out["total_s"] = req.finished_at - req.submitted_at
        return out

    def _gc_streams(self) -> None:
        """Drop finished streams nobody read to completion."""
        now = time.perf_counter()
        for rid, req in list(self._streams.items()):
            if req.done.is_set() and now - req.submitted_at > self._STREAM_TTL_S:
                self._streams.pop(rid, None)

    def metrics(self) -> dict:
        return self.engine.metrics()

    def page_accounting(self) -> dict:
        """Engine page-accounting closure (chaos tests / triage).
        Meaningful only when the engine is quiescent — the check walks
        host-side tables the engine thread mutates."""
        return self.engine.page_accounting()

    def drain(self, timeout_s: float) -> dict:
        """Replica drain (called by Replica.drain on controller
        scale-down / version roll): stop admission, let in-flight
        decodes finish, export the rest as continuations — then hold the
        remaining window for stream readers to drain their cursors, so
        in the common case the tail tokens leave over THIS replica's
        stream instead of being re-decoded elsewhere."""
        deadline = time.monotonic() + max(0.0, timeout_s)
        out = self.engine.drain(timeout_s)
        # Hold only for streams a reader is ACTIVELY consuming (touched
        # within the grace window): an abandoned record — client gone
        # mid-stream, nobody will ever read it out — must not cost every
        # scale-down the full drain window. Its tail tokens are not lost
        # either way; a resumed reader re-decodes them elsewhere.
        grace = 1.0
        while time.monotonic() < deadline:
            now = time.perf_counter()
            streams = getattr(self, "_streams", {}) or {}
            if not any(
                    now - (r.last_read_at if r.last_read_at is not None
                           else r.submitted_at) < grace
                    for r in list(streams.values())):
                break
            time.sleep(0.05)
        out["unread_streams"] = len(getattr(self, "_streams", {}) or {})
        return out

    def load_snapshot(self) -> dict:
        """Live engine load — picked up by Replica.stats() on every
        controller probe, so serve.status() / /api/serve/load carry it."""
        return self.engine.load_snapshot()

    def __call__(self, request: dict) -> dict:
        return self.generate(
            request["prompt_ids"],
            max_tokens=request.get("max_tokens", 64),
            temperature=request.get("temperature", 0.0),
            eos_id=request.get("eos_id"),
            # Continuation / handoff context (see generate): resumes a
            # stream migrated off another replica, with the page-set
            # descriptor driving adoption on this one.
            generated_ids=request.get("generated_ids"),
            kv=request.get("kv"),
            request_id=request.get("request_id"),
            prefix_hashes=request.get("prefix_hashes"),
            prefix_chunk=request.get("prefix_chunk", 0),
        )
