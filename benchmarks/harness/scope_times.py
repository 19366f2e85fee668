"""Device time split by the scopes the programs name their parts with.

`ray_tpu/ops/scopes.py` is a flat vocabulary of `jax.named_scope`s
that every family's decode, chunk and train program wraps its parts in.
A named scope extends the `op_name` of every operation traced inside it,
and the TPU trace carries that path on each "XLA Ops" event's METADATA
as the stat `tf_op` (`jit(f)/jit(main)/while/body/closed_call/mlp/
dot_general:`), beside `program_id`. `jax.profiler.ProfileData` shows an
event's own stats only, so this module reads the metadata off the file
itself: `_op_paths` walks the five protobuf messages XSpace / XPlane /
XEventMetadata / XStatMetadata / XStat on the wire (~60 lines, no
package but the standard library; every "XLA Ops" and "XLA Modules" line
is skipped by its length, so an 8 s trace costs tenths of a second).
The events themselves come from `trace_reduce.reduce_trace`, imported and
not edited: chip 0's op SELF times inside `bench.window`, by the program
each ran in and its HLO text, which is also the metadata entry's name.
A traced run has that reduction already (`ctx["trace"]`), so the file's
events are not read a second time.

Each (program, operation)'s self time goes to

  program   the "XLA Modules" interval it starts in (`reduce_trace`'s)
  scope     the first component of its path that is a vocabulary name,
            JAX's own wrappers stepped over (`jit(..)`, `jvp(..)`,
            `transpose(..)`, `while`, `body`, `closed_call`,
            `checkpoint`, `rematted_computation`, `shard_map`, ...)
  pass      `remat` under `rematted_computation`; else `bwd` under a
            `transpose(` wrapper; else `fwd`

A fusion carries ONE op's metadata, so a fusion that spans two parts
goes to one of them whole. And where XLA:TPU expands an operation into
ops it names itself (`lax.ragged_dot` becomes `ragged-dot-none` and
`ragged-dot-metadata`, each under a path that is just that name), the
program's path is lost: `COMPILER_MADE` gives those to the scope the
program wrote the call in. Nothing to read gives None: a trace
with no `tf_op` (recorded before the scopes), a program without the
vocabulary (the parent), or an executable served from a compile cache
that an older tree warmed (same key, the older metadata).
"""

from __future__ import annotations

import functools
import os
import re

from . import host_phases, trace_reduce

DECODE = r"decode_(sample|step)_paged"
CHUNK = r"prefill_chunk_paged"

# Paths XLA:TPU writes itself, and the scope their call was written in
# (ray_tpu/ops/moe.py: the grouped matmuls, and the group bookkeeping
# the expansion adds before them).
COMPILER_MADE = {"ragged-dot-none": "moe.experts",
                 "ragged-dot-metadata": "moe.route"}
OPS_KEPT = 40       # heaviest (scope, operation) pairs kept a program

_WRAPPER = re.compile(r"[\w.\-]+\((.*)\)")
_PROGRAM_ID = re.compile(r"\((\d+)\)$")


def vocabulary() -> tuple:
    """The program's scope names, or () where it has none (a tree from
    before the scopes)."""
    try:
        from ray_tpu.ops import scopes
    except ImportError:
        return ()
    return tuple(scopes.ALL)


def scope_of(path: str, names=None) -> tuple:
    """-> (scope or None, pass) of one `tf_op` / `op_name` path."""
    names = vocabulary() if names is None else names
    path = path.rpartition(":")[0] if ":" in path else path
    scope, bwd, remat = None, False, False
    for part in path.split("/"):
        while True:
            m = _WRAPPER.fullmatch(part)
            if not m:
                break
            bwd |= part.startswith("transpose(")
            part = m.group(1)
        remat |= part == "rematted_computation"
        if part in names:
            scope = part
            break
    if scope is None and COMPILER_MADE.get(path) in names:
        scope = COMPILER_MADE[path]
    return scope, "remat" if remat else "bwd" if bwd else "fwd"


# --- the trace's op metadata, off the wire

def _varint(buf, i):
    result = shift = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: a varint as an int, a
    length-delimited field as a memoryview, fixed 32/64 as raw bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {kind} in an xplane file")
        yield key >> 3, value


def _map_value(entry):
    """The value message of a `map<int64, Message>` entry."""
    for number, value in _fields(entry):
        if number == 2:
            return value
    return memoryview(b"")


@functools.lru_cache(maxsize=2)
def _op_paths(path: str, _mtime: float) -> dict:
    """{(program, HLO text of the op): tf_op} over chip 0's plane, the
    program named as `trace_reduce` names it (its "XLA Modules" event's
    name less the id): XSpace.planes=1; XPlane.name=2, event_metadata=4,
    stat_metadata=5; XEventMetadata.name=2, stats=5; XStatMetadata.id=1,
    name=2; XStat.metadata_id=1, uint64=3, int64=4, str=5, ref=7."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, events, stats = "", [], []
        for number, value in _fields(plane):
            if number == 2:
                name = bytes(value).decode()
            elif number == 4:
                events.append(value)
            elif number == 5:
                stats.append(value)
        m = trace_reduce.DEVICE_PLANE.match(name)
        if m:
            planes[int(m.group(1))] = (events, stats)
    if not planes:
        return {}
    events, stats = planes[min(planes)]
    stat_names = {}
    for entry in stats:
        fields = dict(_fields(_map_value(entry)))
        stat_names[fields.get(1, 0)] = bytes(fields.get(2, b"")).decode()
    ops, program_names = [], {}
    for entry in events:
        name, tf_op, program = "", None, 0
        for number, value in _fields(_map_value(entry)):
            if number == 2:
                name = bytes(value).decode()
            elif number == 5:
                stat = dict(_fields(value))
                kind = stat_names.get(stat.get(1))
                if kind == "tf_op":
                    tf_op = (bytes(stat[5]).decode() if 5 in stat
                             else stat_names.get(stat.get(7), ""))
                elif kind == "program_id":
                    program = stat.get(3, stat.get(4, 0))
        m = _PROGRAM_ID.search(name)
        if m:                               # a module event's own entry
            program_names[int(m.group(1))] = name[:m.start()]
        elif tf_op:
            ops.append((program, name, tf_op))
    return {(program_names.get(program, ""), name): tf_op
            for program, name, tf_op in ops}


# --- the reduction

def scope_times(path: str, red: dict | None = None) -> dict | None:
    """-> {"busy_s": chip 0's busy seconds in the window, "programs":
    {program: {"runs", "total_s" (its module events), "by_scope": {scope:
    s}, "by_pass": {pass: s}, "unscoped_s", "unscoped": {path: s}, "ops":
    [[scope or "", operation, s]] (the heaviest)}}}, seconds of op SELF
    time; None when there is nothing to read. `red`: what
    `trace_reduce.reduce_trace` made of the same file, where the caller
    has it already."""
    names = vocabulary()
    paths = _op_paths(path, os.path.getmtime(path)) if names else {}
    if not paths:
        return None
    red = red or trace_reduce.reduce_trace(path, 1)
    if not red.get("ops") or not red.get("per_chip_busy_s"):
        return None
    programs = {
        name: {"runs": p["count"], "total_s": p["total_s"], "by_scope": {},
               "by_pass": {}, "unscoped_s": 0.0, "unscoped": {}, "ops": []}
        for name, p in red["programs"].items()}
    scoped = 0.0
    for program, name, t in red["ops"]:         # heaviest first
        p = programs.get(program)
        if p is None:                           # outside every module event
            continue
        tf_op = paths.get((program, name), "")
        scope, which = scope_of(tf_op, names)
        if len(p["ops"]) < OPS_KEPT:
            p["ops"].append([scope or "", trace_reduce.short_op(name, 72), t])
        if tf_op:
            p["by_pass"][which] = p["by_pass"].get(which, 0.0) + t
        if scope is None:
            p["unscoped_s"] += t
            key = tf_op or trace_reduce.op_key(name)
            p["unscoped"][key] = p["unscoped"].get(key, 0.0) + t
        else:
            p["by_scope"][scope] = p["by_scope"].get(scope, 0.0) + t
            scoped += t
    if not scoped:
        return None
    return {"busy_s": red["per_chip_busy_s"][0], "programs": programs}


# --- what the layer_metrics/<name>.py files call

def for_run(ctx: dict) -> dict | None:
    """The table of the running process's own traced window, or None
    unless the run was traced and its trace names the scopes."""
    red = ctx.get("trace") or {}
    if not red.get("window_s"):
        return None
    path = host_phases.newest_xplane()
    return scope_times(path, red) if path else None


def _seconds(table: dict, program_re: str, by: str, keys) -> tuple:
    """(seconds under `keys` of `by_scope` / `by_pass`, runs) summed over
    the programs whose name matches."""
    hit = [p for name, p in table["programs"].items()
           if re.search(program_re, name)]
    return (sum(p[by].get(k, 0.0) for p in hit for k in keys),
            sum(p["runs"] for p in hit))


def ms_a_run(ctx: dict, program_re: str, scopes) -> float | None:
    """Milliseconds of one run of the matching programs spent in
    `scopes`."""
    table = for_run(ctx)
    if not table:
        return None
    seconds, runs = _seconds(table, program_re, "by_scope", scopes)
    return seconds / runs * 1e3 if runs else None


def share_of_busy(ctx: dict, program_re: str, keys,
                  by: str = "by_scope") -> float | None:
    """Percent of chip 0's busy time spent in `keys` (scopes, or passes
    with by="by_pass") inside the matching programs."""
    table = for_run(ctx)
    if not table or not table["busy_s"]:
        return None
    seconds, runs = _seconds(table, program_re, by, keys)
    return seconds / table["busy_s"] * 100.0 if runs else None


def coverage(ctx: dict) -> float | None:
    """Percent of chip 0's busy time that lies in a vocabulary scope."""
    return share_of_busy(ctx, r"", vocabulary())
