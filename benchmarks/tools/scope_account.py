"""A traced window's device time by program and by the scope the program
gave each part (harness/scope_times.py): what to read instead of
`fusion.238`.

    python benchmarks/tools/scope_account.py [<file.xplane.pb> | <trace dir>] [--ops]

Without a path: the newest trace under benchmarks/.trace/ (a `--trace 1`
run of benchmarks/run.py leaves one there). For each program with device
time in `bench.window`: its runs, the mean device time of a run, then
milliseconds a run by scope (share of the program's op time beside it),
by pass, and what lay in no scope with the heaviest of those operations'
paths. With `--ops`, under each scope its heaviest operations as the
compiler named them: where a fusion that spans two parts went.
"""

from __future__ import annotations

import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.dirname(os.path.dirname(HERE))]

from harness import host_phases, scope_times, trace_reduce  # noqa: E402


def account(table: dict, top: int = 6, ops: bool = False) -> str:
    lines = [f"chip 0 busy {table['busy_s']:.4f} s in the window"]
    programs = sorted(table["programs"].items(),
                      key=lambda kv: -kv[1]["total_s"])
    for name, p in programs:
        ops_s = sum(p["by_scope"].values()) + p["unscoped_s"]
        if not p["runs"] or not ops_s:
            continue
        ms = lambda s: s / p["runs"] * 1e3
        lines.append(f"\n{name}: {p['runs']} runs, {ms(p['total_s']):.3f} ms "
                     f"a run on the device, {ms(ops_s):.3f} ms of op time "
                     f"({p['total_s'] / table['busy_s'] * 100:.1f} % of busy)")
        rows = sorted(p["by_scope"].items(), key=lambda kv: -kv[1])
        rows.append(("(no scope)", p["unscoped_s"]))
        for scope, s in rows:
            lines.append(f"  {scope:<14} {ms(s):9.4f} ms  "
                         f"{s / ops_s * 100:5.1f} %")
            if ops and scope != "(no scope)":
                lines += [f"      {ms(t):9.4f} ms  {op}"
                          for sc, op, t in p["ops"] if sc == scope][:3]
        lines.append("  by pass: " + ", ".join(
            f"{k} {ms(s):.4f} ms" for k, s in sorted(p["by_pass"].items())))
        for path, s in sorted(p["unscoped"].items(),
                              key=lambda kv: -kv[1])[:top]:
            lines.append(f"    no scope {ms(s):9.4f} ms  {path[:110]}")
    return "\n".join(lines)


def main(argv) -> int:
    paths = [a for a in argv[1:] if a != "--ops"]
    path = paths[0] if paths else host_phases.newest_xplane()
    if path and os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    if not path or not os.path.exists(path):
        print("no .xplane.pb found", file=sys.stderr)
        return 1
    t0 = time.perf_counter()
    table = scope_times.scope_times(path)
    took = time.perf_counter() - t0
    if table is None:
        print(f"{path}: no operation carries a scope of the vocabulary "
              "(a trace from before the scopes, or an executable out of a "
              "compile cache an older tree warmed)", file=sys.stderr)
        return 1
    print(f"{path} ({os.path.getsize(path) / 1e6:.1f} MB, reduced in "
          f"{took:.2f} s)")
    print(account(table, ops="--ops" in argv))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
