"""The device's idle time, split by the engine phase the host was in.

`LLMEngine._phase` writes every phase of its tick into the profiler's
trace as a host event `llm.<phase>`, flat (no phase inside another, and
nothing around the tick). This module opens the run's `.xplane.pb`,
takes chip 0's idle intervals inside `bench.window` the way
`trace_reduce.reduce_trace` takes them (its `merged`, `_events` and line
names, imported), takes the `llm.*` host events by name, and intersects
the two:

  host_work_s   idle while the engine was in a host-only phase (admit,
                prefill.build, prefill.graduate, plan, emit): the device
                waited for Python
  dispatch_s    idle while the engine was handing the device a program
                or fetching its result (`*.dispatch`, `*.pull`,
                `spec_verify`): the runtime's and the transfer's time
  by_phase      the same per phase name

What neither covers, `idle_s - host_work_s - dispatch_s`, is idle while
the engine was in no phase at all: its loop asleep for want of requests.
A trace with no `llm.*` event (a program older than the phases) gives
None: nothing to read.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os

from . import trace_reduce

TRACE_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".trace")
PREFIX = "llm."
HOST_ONLY = frozenset(PREFIX + p for p in (
    "admit", "prefill.build", "prefill.graduate", "plan", "emit"))


def newest_xplane(trace_root: str = TRACE_ROOT) -> str | None:
    """A run deletes and rewrites its own cell's directory under
    benchmarks/.trace/, and `ctx` carries no path: the newest file is
    the running process's."""
    paths = glob.glob(os.path.join(trace_root, "*", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def _overlap_ns(gaps: list, starts: list, s: float, e: float) -> float:
    """Length of [s, e) inside the sorted, disjoint `gaps`."""
    total = 0.0
    for gs, ge in gaps[max(0, bisect.bisect_right(starts, s) - 1):]:
        if gs >= e:
            break
        total += max(0.0, min(e, ge) - max(s, gs))
    return total


def idle_split(path: str) -> dict | None:
    """-> window_s, idle_s, host_work_s, dispatch_s, by_phase {name:
    idle seconds}, events {name: count inside the window}; None when the
    trace has no device plane, no `bench.window` or no `llm.*` event."""
    return _idle_split(path, os.path.getmtime(path))


@functools.lru_cache(maxsize=2)
def _idle_split(path: str, _mtime: float) -> dict | None:
    from jax.profiler import ProfileData

    ops, window, phases = {}, None, []
    for plane in ProfileData.from_file(path).planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    ops[int(m.group(1))] = trace_reduce._events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for n, s, d in trace_reduce._events(line):
                    if n == trace_reduce.WINDOW_ANNOTATION and (
                            window is None or d > window[1] - window[0]):
                        window = (s, s + d)
                    elif n.startswith(PREFIX) and d > 0:
                        phases.append((n, s, s + d))
    if not ops or window is None or not phases:
        return None
    lo, hi = window
    busy = trace_reduce.merged(
        (max(s, lo), min(s + d, hi)) for _n, s, d in ops[min(ops)]
        if s < hi and s + d > lo)
    gaps, cur = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    starts = [g[0] for g in gaps]
    by_phase, events = {}, {}
    for n, s, e in phases:
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        events[n] = events.get(n, 0) + 1
        by_phase[n] = by_phase.get(n, 0.0) + _overlap_ns(gaps, starts, s, e)
    host = sum(t for n, t in by_phase.items() if n in HOST_ONLY)
    return {"window_s": (hi - lo) * 1e-9,
            "idle_s": sum(e - s for s, e in gaps) * 1e-9,
            "host_work_s": host * 1e-9,
            "dispatch_s": (sum(by_phase.values()) - host) * 1e-9,
            "by_phase": {n: t * 1e-9 for n, t in sorted(by_phase.items())},
            "events": dict(sorted(events.items()))}


def idle_share(ctx: dict, part: str) -> float | None:
    """For a layer_metrics/<name>.py: `part` (`host_work_s` or
    `dispatch_s`) of the traced window, in percent. None unless the run
    was traced and its trace holds the engine's phases."""
    if not (ctx.get("trace") or {}).get("window_s"):
        return None
    path = newest_xplane()
    split = idle_split(path) if path else None
    if not split or not split["window_s"]:
        return None
    return split[part] / split["window_s"] * 100.0
