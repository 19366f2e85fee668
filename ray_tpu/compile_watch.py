"""Compile watch: JAX compile/recompile observability (flight recorder).

The serve engine's throughput story depends on a *bounded* compile grid
(two chunked-prefill programs, a power-of-two decode-width ladder). A bug
that widens that grid — e.g. PR 4's decode table-view width recomputed
over mid-prefill slots, re-lowering every decode window — shows up only
as step-time noise unless compilation itself is observable. This module
makes it a first-class signal:

- `install()` registers a `jax.monitoring` duration listener for XLA
  backend compiles: every compile increments `jax_compiles_total{fn}`,
  observes `jax_compile_seconds{fn}`, and records a `jax.compile` tracing
  span (child of the ambient trace when one exists), so compiles are
  visible at /metrics, /api/traces, and in `ray_tpu.timeline()`.
- `wrap(fn, name)` is the attribution half: jitted callables we own
  (serve/llm.py's engine dispatch table over a family's programs,
  models/serving.py) run under a thread-local label, so listener-observed
  compiles carry the owning program's name instead of "jax". On JAX builds
  without `jax.monitoring`, the wrapper itself detects compiles via the
  jitted callable's `_cache_size()` delta (counted, wall-time-bounded
  duration) — coverage degrades, attribution doesn't.
- A storm detector counts per-label compiles over a rolling window and
  raises a structured `recompile.storm` cluster event (the existing GCS
  events channel, `state.list_cluster_events`) past the threshold —
  turning the silent-recompile class of bug into a production alarm.
  Knobs: `jax_recompile_storm_threshold` / `jax_recompile_storm_window_s`.

Persistent-compilation-cache hits skip XLA backend compilation and are
deliberately NOT counted: the watch measures compile cost actually paid.
"""

from __future__ import annotations

import collections
import contextlib
import logging
import os
import threading
import time

from ray_tpu import profiling as _profiling

logger = logging.getLogger(__name__)

# The jax.monitoring event one XLA backend compile records
# (jax/_src/dispatch.py BACKEND_COMPILE_EVENT).
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_COMPILES_TOTAL = _profiling.Counter(
    "jax_compiles_total",
    description="XLA program compilations observed in this process",
    tag_keys=("fn",))
_COMPILE_SECONDS = _profiling.Histogram(
    "jax_compile_seconds",
    description="XLA backend-compile wall time",
    boundaries=(0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
                60.0, 120.0),
    tag_keys=("fn",))
_STORMS_TOTAL = _profiling.Counter(
    "jax_recompile_storms_total",
    description="Recompile storms detected (threshold crossings)",
    tag_keys=("fn",))

_tls = threading.local()
_lock = threading.Lock()
_installed = False
_fallback_only = False      # jax.monitoring unavailable → wrapper counting
_storm: "_StormDetector | None" = None


class _StormDetector:
    """Rolling-window recompile counter per program label. Crossing the
    threshold fires once, then re-arms only after a full window — a storm
    is one alarm, not one alarm per compile."""

    def __init__(self, threshold: int, window_s: float):
        self.threshold = max(1, int(threshold))
        self.window_s = float(window_s)
        self._times: dict[str, collections.deque] = {}
        self._alarmed_at: dict[str, float] = {}
        self._lock = threading.Lock()
        # Local record of fired storms (tests / clusterless processes read
        # this; the cluster event below is the production surface).
        self.storms: list[dict] = []

    def observe(self, fn_name: str) -> None:
        now = time.monotonic()
        fire = None
        with self._lock:
            ring = self._times.setdefault(fn_name, collections.deque())
            ring.append(now)
            while ring and now - ring[0] > self.window_s:
                ring.popleft()
            if len(ring) >= self.threshold:
                last = self._alarmed_at.get(fn_name)
                if last is None or now - last >= self.window_s:
                    self._alarmed_at[fn_name] = now
                    fire = {"fn": fn_name, "count": len(ring),
                            "threshold": self.threshold,
                            "window_s": self.window_s}
        if fire is None:
            return
        self.storms.append(fire)
        _STORMS_TOTAL.inc(1.0, tags={"fn": fn_name})
        # Off-thread: observe() runs inside the jax.monitoring compile
        # listener — i.e. on the thread (the engine loop) that just paid
        # the compile. emit_cluster_event is a GCS RPC that can block for
        # the full rpc timeout when the GCS is degraded; an alarm must
        # never freeze token generation at the exact moment the system is
        # already misbehaving. Storms fire at most once per window per
        # label, so a short-lived thread is cheap.
        threading.Thread(
            target=self._emit_event, args=(fn_name, fire),
            name="recompile-storm-event", daemon=True).start()

    def _emit_event(self, fn_name: str, fire: dict) -> None:
        from ray_tpu import state as _state

        _state.emit_cluster_event(
            "recompile.storm",
            f"program {fn_name!r} compiled {fire['count']}x within "
            f"{self.window_s:g}s (threshold {self.threshold}) — the same "
            "program is re-lowering per call; check for shape churn",
            severity="WARNING", source="compile_watch", **fire)


def install(*, storm_threshold: int | None = None,
            storm_window_s: float | None = None) -> bool:
    """Arm the compile watch (idempotent). Registers the jax.monitoring
    listener once per process; threshold/window default to the
    `jax_recompile_storm_*` config knobs, and passing either re-arms the
    detector (tests lower the threshold this way). Returns True when the
    monitoring listener is active, False when only wrapper-fallback
    counting is available."""
    global _installed, _fallback_only, _storm
    with _lock:
        if _storm is None or storm_threshold is not None \
                or storm_window_s is not None:
            from ray_tpu.core.config import runtime_config

            cfg = runtime_config()
            thr = (storm_threshold if storm_threshold is not None
                   else getattr(cfg, "jax_recompile_storm_threshold", 10))
            win = (storm_window_s if storm_window_s is not None
                   else getattr(cfg, "jax_recompile_storm_window_s", 120.0))
            _storm = _StormDetector(thr, win)
        if _installed:
            return not _fallback_only
        _installed = True
        try:
            from jax import monitoring as _monitoring

            _monitoring.register_event_duration_secs_listener(_on_duration)
            _fallback_only = False
        except Exception as e:
            logger.warning(
                "jax.monitoring unavailable (%s): compile watch falls back "
                "to wrapper cache-size deltas (wrapped callables only)", e)
            _fallback_only = True
    return not _fallback_only


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    if event != _BACKEND_COMPILE_EVENT:
        return
    try:
        record_compile(current_label(), duration_secs)
    except Exception:  # graftlint: disable=EXC-SWALLOW (observability listener must never fail a jax compile)
        pass


def current_label() -> str:
    """The program label of the innermost wrapped call on this thread
    ("jax" outside any wrapped callable)."""
    return getattr(_tls, "label", None) or "jax"


@contextlib.contextmanager
def label(fn_name: str):
    """Attribute compiles inside the block to `fn_name` (thread-local)."""
    prev = getattr(_tls, "label", None)
    _tls.label = fn_name
    try:
        yield
    finally:
        _tls.label = prev


def in_warmup() -> bool:
    """True while the current thread is inside a `warmup_scope()` block."""
    return bool(getattr(_tls, "warmup", False))


@contextlib.contextmanager
def warmup_scope():
    """Mark compiles on this thread as INTENTIONAL warmup (thread-local).

    The serve engine's bucket-ladder warmup deliberately compiles every
    width variant of the chunked prefill/verify programs back-to-back at
    boot — log₂(max_pages)+1 widths × two head variants, well past the
    storm threshold in well under the storm window. Those compiles are
    the opposite of the storm detector's target (shape churn re-lowering
    the SAME shape per call), so inside this scope they still count at
    /metrics (`jax_compiles_total{fn}` — the bench's compile-delta
    baseline is taken AFTER warmup) and still emit tracing spans, but
    they do not feed the storm detector: a clean engine boot must never
    file a `recompile.storm` cluster event."""
    prev = getattr(_tls, "warmup", False)
    _tls.warmup = True
    try:
        yield
    finally:
        _tls.warmup = prev


def wrap(fn, name: str | None = None):
    """Attribution wrapper for a jitted callable we own: calls run under
    `name`, so compiles the listener observes during the call are labeled.
    When jax.monitoring is unavailable, falls back to detecting compiles
    via the callable's `_cache_size()` delta (the call's wall time bounds
    the compile duration from above)."""
    fn_name = name or getattr(fn, "__name__", "jitted")
    cache_size = getattr(fn, "_cache_size", None)

    def watched(*args, **kwargs):
        prev = getattr(_tls, "label", None)
        _tls.label = fn_name
        before = (cache_size() if (_fallback_only and cache_size is not None)
                  else None)
        t0 = time.perf_counter()
        try:
            return fn(*args, **kwargs)
        finally:
            _tls.label = prev
            if before is not None and cache_size() > before:
                record_compile(fn_name, time.perf_counter() - t0)

    watched.__name__ = fn_name
    watched.__wrapped__ = fn
    return watched


def record_compile(fn_name: str, duration_s: float) -> None:
    """Account one compile: counter + duration histogram + `jax.compile`
    tracing span + storm-detector feed (skipped inside `warmup_scope()`
    — marked warmup compiles are intentional, not shape churn)."""
    _COMPILES_TOTAL.inc(1.0, tags={"fn": fn_name})
    _COMPILE_SECONDS.observe(duration_s, tags={"fn": fn_name})
    _emit_span(fn_name, duration_s)
    if in_warmup():
        return
    det = _storm
    if det is not None:
        det.observe(fn_name)


def _emit_span(fn_name: str, duration_s: float) -> None:
    """Record the compile as a tracing span, retroactively (the listener
    fires at compile end): a child of the ambient trace when one exists —
    so a Serve request that paid a compile shows it on its critical path
    in /api/traces — else its own root."""
    from ray_tpu import tracing

    cur = tracing.get_current()
    ctx = (cur.child() if cur is not None
           else tracing.TraceContext(tracing.new_trace_id(),
                                     tracing.new_span_id(), None, {}))
    _profiling.record_event(
        "jax.compile", "jax", time.time() - duration_s, duration_s,
        pid=f"pid:{os.getpid()}", tid=threading.current_thread().name,
        args=tracing.span_event_args(ctx, fn=fn_name))


def compiles_total(fn: str | None = None) -> float:
    """Compiles observed in this process (optionally for one label) —
    benches record the delta across their measured window."""
    total = 0.0
    for key, value in _COMPILES_TOTAL.snapshot():
        if fn is None or (key and key[0] == fn):
            total += value
    return total


def storm_log() -> list[dict]:
    """Storms fired in this process (local mirror of the cluster events)."""
    det = _storm
    return list(det.storms) if det is not None else []


__all__ = [
    "install", "wrap", "label", "current_label", "record_compile",
    "compiles_total", "storm_log", "warmup_scope", "in_warmup",
]
