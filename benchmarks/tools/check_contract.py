"""Check BENCHMARK.json against the parts of the driver's contract that a
file can be held to, before the driver does: key sets, names, units,
lengths, files that exist under `paths`, `moves` targets reported in
every cell of the metric, the four-chip quota, the time budget.

    python benchmarks/tools/check_contract.py
"""

from __future__ import annotations

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def main() -> int:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    b = json.loads(raw)
    errs = []
    chk = lambda ok, msg: errs.append(msg) if not ok else None
    chk(len(raw) <= 64 * 1024, "file over 64 KiB")
    chk(set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                   "end_to_end", "per_layer"}, f"top-level keys {sorted(b)}")
    chk(1 <= len(b["paths"]) <= 16 and all(PATH.match(p) for p in b["paths"]),
        "paths")
    chk(len(b["command"]) <= 32, "command too long")
    line = lambda s: 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s
    chk(all(line(w) for w in b["command"]), "command words")
    chk(isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51,
        "run_seconds")
    cfgs = {c["name"] for c in b["configs"]}
    chk(1 <= len(b["configs"]) <= 24, "configs count")
    files = set()
    for c in b["configs"]:
        chk(set(c) == {"name", "source", "file", "reduced", "why"},
            f"config keys {c.get('name')}")
        chk(NAME.match(c["name"]) and line(c["source"]) and line(c["why"]),
            f"config {c['name']}")
        chk(any(c["file"].startswith(p + "/") for p in b["paths"])
            and os.path.exists(os.path.join(ROOT, c["file"]))
            and c["file"] not in files, f"config file {c['file']}")
        files.add(c["file"])
        chk(len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"]),
            f"reduced of {c['name']}")
        held = json.load(open(os.path.join(ROOT, c["file"])))
        chk(sorted(held.get("reduced", [])) == sorted(c["reduced"]),
            f"{c['file']} and BENCHMARK.json disagree on `reduced`")
    cells = [w["name"] for w in b["workloads"]]
    chk(1 <= len(cells) <= 24 and len(set(cells)) == len(cells), "cells")
    pairs = set()
    for w in b["workloads"]:
        chk(set(w) == {"name", "config", "traffic", "chips", "why"},
            f"cell keys {w.get('name')}")
        chk(NAME.match(w["name"]) and NAME.match(w["traffic"])
            and w["config"] in cfgs and w["chips"] in (1, 4) and line(w["why"]),
            f"cell {w['name']}")
        chk((w["config"], w["traffic"]) not in pairs, f"pair twice {w['name']}")
        pairs.add((w["config"], w["traffic"]))
    chk(cfgs == {w["config"] for w in b["workloads"]}, "unused config")
    four = sum(w["chips"] == 4 for w in b["workloads"])
    chk(four <= max(1, len(cells) // 4), f"{four} four-chip cells")
    e2e = {m["name"]: m for m in b["end_to_end"]}
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    chk(len(set(names)) == len(names), "metric name twice")
    chk(1 <= len(b["end_to_end"]) <= 16 and "setup_s" in e2e, "end_to_end")
    chk(1 <= len(b["per_layer"]) <= 128, "per_layer count")
    cells_of = lambda m: set(m.get("workloads", cells))
    for m in b["end_to_end"]:
        chk(set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                       "source"}, f"keys of {m['name']}")
        chk(NAME.match(m["name"]) and UNIT.match(m["unit"])
            and m["better"] in ("lower", "higher")
            and m["source"] in ("host_clock", "device_trace")
            and 0.01 <= m["bound"] <= 0.1, f"metric {m['name']}")
        chk(cells_of(m) <= set(cells), f"cells of {m['name']}")
    for m in b["per_layer"]:
        chk(set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                       "layer", "moves"}, f"keys of {m['name']}")
        chk(NAME.match(m["name"]) and UNIT.match(m["unit"])
            and m["better"] in ("lower", "higher") and m["source"] in SOURCES
            and line(m["layer"]), f"metric {m['name']}")
        chk(m["moves"] in e2e and cells_of(m) <= cells_of(e2e[m["moves"]]),
            f"{m['name']} moves {m['moves']}, not reported in all its cells")
        chk(any(os.path.exists(os.path.join(ROOT, p, "layer_metrics",
                                            m["name"] + ext))
                for p in b["paths"] for ext in (".json", ".py")),
            f"no reader file for {m['name']}")
    for c in cells:
        chk(sum(c in cells_of(m) for m in b["end_to_end"]) >= 2
            and any(c in cells_of(m) for m in b["per_layer"]),
            f"cell {c} lacks metrics")
    # the check's budget reckons a run at run_seconds + 60; the driver
    # STOPS a whole run (set-up, window, reference check) at 360 s, which
    # no file can show: tools/measure.py times it on the chip
    rs = b["run_seconds"]
    chk((2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200, "time budget")
    for p in b["paths"]:
        for d, _s, fs in os.walk(os.path.join(ROOT, p)):
            if "/." in d or "__pycache__" in d:
                continue
            for f in fs:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                chk(re.match(r"^[A-Za-z0-9_.\-/]+$", rel), f"file name {rel}")
    print("\n".join(errs) if errs else "BENCHMARK.json: ok")
    return 1 if errs else 0


if __name__ == "__main__":
    sys.exit(main())
