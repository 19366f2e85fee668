"""From a profiler trace (.xplane.pb) to numbers: device busy and idle
time, device time per program and per operation, and the longest idle
gaps with what the host was doing in them. Read with nothing but
`jax.profiler.ProfileData`. The reduction is part of the yardstick.

What a TPU trace holds (looked at by hand, PR 23): one plane per chip,
`/device:TPU:<n>`, whose line "XLA Modules" has one event per execution
of a compiled program (named like `jit__decode_sample_paged(123...)`)
and whose line "XLA Ops" has one event per HLO instruction executed,
nested where an instruction (a `while`, a fusion's parent) contains
others; and `/host:CPU`, one line per host thread, where
`jax.profiler.TraceAnnotation` spans and the runtime's own host events
land. All on one clock, in nanoseconds, to within about a millisecond:
in the recorded test trace the device's events read ~1.2 ms ahead of the
host's, which a traced window of seconds does not feel.

Busy time is the UNION of the op intervals on a chip, so nesting and
overlap count once. An operation's own time is its duration minus its
direct children's (its "self" time), so a `while` that only wraps its
body does not head the list.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_ANNOTATION = "bench.window"


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def _events(line):
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def merged(intervals) -> list:
    """Overlapping [start, end) intervals joined, in order."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_length(intervals) -> float:
    """Total length covered by [start, end) intervals."""
    return sum(e - s for s, e in merged(intervals))


def self_times(events) -> list:
    """[(name, start, self_ns)] for nested events on one line."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    self_ns = [ev[2] for ev in events]
    stack = []                                  # indices of open events
    for i in order:
        _n, s, d = events[i]
        while stack and s >= events[stack[-1]][1] + events[stack[-1]][2]:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= d
        stack.append(i)
    return [(events[i][0], events[i][1], max(0.0, self_ns[i]))
            for i in range(len(events))]


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


def reduce_trace(path: str, chips: int | None = None) -> dict:
    """The whole reduction. Times in seconds. Keys:
    window_s, busy_s (mean over chips), per_chip_busy_s, programs
    {name: {count, total_s, mean_s}} (chip 0), ops [(program, op,
    self_s)] (chip 0, inside the window), idle_gaps [(label, seconds)]
    (chip 0, longest first), lines (what the file held, for a reader
    that finds nothing)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host_lines, seen = {}, [], {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        lines = {ln.name: ln for ln in plane.lines}
        seen[plane.name] = sorted(lines)
        if m:
            devices[int(m.group(1))] = lines
        elif plane.name.startswith("/host:"):
            host_lines.extend(plane.lines)
    host = [(ln.name, _events(ln)) for ln in host_lines]
    window = None
    for _name, evs in host:
        for n, s, d in evs:
            if n == WINDOW_ANNOTATION and (window is None or d > window[1] - window[0]):
                window = (s, s + d)
    ids = sorted(devices)[:chips] if chips else sorted(devices)
    if not ids:
        return {"lines": seen, "window_s": None}
    all_ops = {i: _events(devices[i][OPS_LINE])
               for i in ids if OPS_LINE in devices[i]}
    if window is None:          # no annotation: the span of device events
        flat = [(s, s + d) for evs in all_ops.values() for _n, s, d in evs]
        if not flat:
            return {"lines": seen, "window_s": None}
        window = (min(s for s, _e in flat), max(e for _s, e in flat))
    lo, hi = window
    per_chip = []
    for i in ids:
        ivs = [_clip(s, s + d, lo, hi) for _n, s, d in all_ops.get(i, [])
               if s < hi and s + d > lo]
        per_chip.append(union_length(ivs) * 1e-9)
    first = ids[0]
    mods = sorted(((s, s + d, n) for n, s, d in
                   _events(devices[first][MODULES_LINE])), key=lambda m: m[0]) \
        if MODULES_LINE in devices[first] else []
    programs = {}
    for s, e, n in mods:
        if s >= hi or e <= lo:
            continue
        p = programs.setdefault(re.sub(r"\(\d+\)$", "", n),
                                {"count": 0, "total_s": 0.0})
        p["count"] += 1
        p["total_s"] += (e - s) * 1e-9
    for p in programs.values():
        p["mean_s"] = p["total_s"] / p["count"]
    starts = [m[0] for m in mods]

    def program_of(t):
        j = bisect.bisect_right(starts, t) - 1
        if j >= 0 and t < mods[j][1]:
            return re.sub(r"\(\d+\)$", "", mods[j][2])
        return ""

    ops = {}
    first_ops = all_ops.get(first, [])
    for n, s, self_ns in self_times(first_ops):
        if lo <= s < hi and self_ns > 0:
            key = (program_of(s), n)
            ops[key] = ops.get(key, 0.0) + self_ns * 1e-9
    busy = merged(_clip(s, s + d, lo, hi) for _n, s, d in first_ops
                  if s < hi and s + d > lo)
    gaps, cur = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {"lines": seen, "window_s": (hi - lo) * 1e-9,
            "busy_s": sum(per_chip) / len(per_chip),
            "per_chip_busy_s": per_chip, "programs": programs,
            "ops": sorted(((p, o, t) for (p, o), t in ops.items()),
                          key=lambda x: -x[2]),
            "idle_gaps": [(_gap_label(g, host), (g[1] - g[0]) * 1e-9)
                          for g in gaps[:10]]}


def _gap_label(gap, host) -> str:
    """`window|<host event that covers most of the gap>`: only the window
    is traced, and the engine's own phases are not annotated yet, so the
    runtime's host events say what the host was doing."""
    s, e = gap
    best, best_cover = "", 0.0
    for _line, evs in host:
        for n, es, d in evs:
            if d <= 0 or n == WINDOW_ANNOTATION:
                continue
            cover = min(e, es + d) - max(s, es)
            if cover > best_cover:
                best, best_cover = n, cover
    return f"window|{best or 'no host event'}"


def short_op(name: str, limit: int = 120) -> str:
    """An op's trace name is its whole HLO line; keep its name, opcode and
    result shape: `%copy.78 bf16[1,513,64,32,64]{4,3,2,1,0:...} copy(...`."""
    lhs, sep, rhs = name.partition(" = ")
    return (lhs + " " + rhs if sep else name)[:limit]


_OPCODE = re.compile(r"(?:^|\s)([a-z][\w\-.]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')


def op_key(name: str) -> str:
    """What an op regex is matched against: the instruction's own name,
    its opcode and, for a custom call, its target:
    `%all-reduce.3 all-reduce`, `%closed_call.8 custom-call
    tpu_custom_call`. NOT the whole HLO line: that names the operands
    too, and a fusion fed by `%all-reduce.3` is not a collective."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name
    opcode, target = _OPCODE.search(rhs), _TARGET.search(rhs)
    return " ".join(x for x in (lhs.strip(), opcode and opcode.group(1),
                                target and target.group(1)) if x)


def share(red: dict, program_re: str, op_re: str) -> float | None:
    """Self time of ops whose `op_key` matches op_re inside programs
    matching program_re, over chip 0's busy time in the window, as a
    fraction."""
    if not red.get("ops") or not red.get("per_chip_busy_s"):
        return None
    busy = red["per_chip_busy_s"][0]
    if busy <= 0:
        return None
    p_re, o_re = re.compile(program_re), re.compile(op_re)
    return sum(t for p, o, t in red["ops"]
               if p_re.search(p) and o_re.search(op_key(o))) / busy


def program_mean_s(red: dict, program_re: str) -> float | None:
    p_re = re.compile(program_re)
    hit = [p for n, p in (red.get("programs") or {}).items() if p_re.search(n)]
    count = sum(p["count"] for p in hit)
    return sum(p["total_s"] for p in hit) / count if count else None
