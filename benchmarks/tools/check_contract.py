"""Check BENCHMARK.json against the parts of the driver's contract that a
file can be held to, before the driver does: key sets, names, units,
lengths, files that exist under `paths`, `moves` targets reported in
every cell of the metric, the four-chip quota, the time budget; and to
the benchmark's own rule for `per_layer` (PR 52): a metric's name says
WHAT is read and its entry's `workloads` says WHERE, so one entry a
metric and one reader file an entry.

    python benchmarks/tools/check_contract.py
"""

from __future__ import annotations

import ast
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
PER_LAYER_MAX = 128
_DERIVED_NAME = re.compile(r"""\bm\[\s*['"]([^'"]+)['"]\s*\]""")


def stem(name: str) -> str:
    return name.split(".", 1)[0]


def _body(path: str):
    """What the reader file at `path` DOES, apart from what it is called
    and what it says of itself: a JSON reader's string and scale, a
    Python reader's code without its docstrings."""
    with open(path) as f:
        text = f.read()
    if path.endswith(".json"):
        held = json.loads(text)
        return held.get("reader", ""), held.get("scale")
    tree = ast.parse(text)
    for node in ast.walk(tree):
        if (isinstance(node, (ast.Module, ast.FunctionDef)) and node.body
                and isinstance(node.body[0], ast.Expr)
                and isinstance(node.body[0].value, ast.Constant)):
            node.body = node.body[1:]
    return ast.unparse(tree)


def metrics_read(path: str) -> set:
    """The names of other metrics the reader file at `path` reads:
    `m['<name>']` in a `derived:` expression; in a Python reader a
    `.get("<name>")` or a `["<name>"]` on anything that holds the key
    `"metrics"` (`ctx["metrics"]["x"]`, `(ctx.get("metrics") or {}).get("x")`)
    or on a name that was assigned from such a thing (`m = ctx["metrics"]`)."""
    with open(path) as f:
        text = f.read()
    if path.endswith(".json"):
        reader = json.loads(text).get("reader", "")
        return (set(_DERIVED_NAME.findall(reader))
                if reader.startswith("derived:") else set())
    tree = ast.parse(text)
    held = set()

    def of_metrics(node) -> bool:
        return any(isinstance(n, ast.Constant) and n.value == "metrics"
                   or isinstance(n, ast.Name) and n.id in held
                   for n in ast.walk(node))

    held.update(t.id for node in ast.walk(tree)
                if isinstance(node, ast.Assign) and of_metrics(node.value)
                for t in node.targets if isinstance(t, ast.Name))

    read = set()
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                and node.func.attr == "get" and node.args):
            key, of = node.args[0], node.func.value
        elif isinstance(node, ast.Subscript):
            key, of = node.slice, node.value
        else:
            continue
        if (isinstance(key, ast.Constant) and isinstance(key.value, str)
                and of_metrics(of)):
            read.add(key.value)
    return read


def per_layer_rule(b: dict, root: str, chk) -> None:
    """One entry a metric, one file a reader (PR 52). An end-to-end
    metric that more than one cell reports is SHARED: an entry that moves
    it is named by what it reads alone and lists its cells. A cell that
    reports a quantity of its own (`out_tokens_per_s.batch`,
    `train_tokens_per_s`) cannot join such a list (an entry has one
    `moves`), so its entries may carry a suffix, the cell's traffic, that
    tells their names from the shared ones."""
    entries = b["per_layer"]
    cells = [w["name"] for w in b["workloads"]]
    traffic = {w["name"]: w["traffic"] for w in b["workloads"]}
    cells_of = lambda m: set(m.get("workloads", cells))
    reported_in = {m["name"]: cells_of(m) for m in b["end_to_end"]}
    seen = {}
    for m in entries:
        key = (stem(m["name"]), m["moves"])
        chk(key not in seen, f"{m['name']} and {seen.get(key)} are one metric "
            f"moving {m['moves']}: one entry that lists both cells")
        seen[key] = m["name"]
        if "." not in m["name"]:
            continue
        own = reported_in.get(m["moves"], set())
        chk(len(own) == 1, f"{m['name']} moves {m['moves']}, which "
            f"{len(own)} cells report: its name is its stem, its cells are "
            "its `workloads`")
        chk(len(own) != 1
            or m["name"].split(".", 1)[1] == traffic[next(iter(own))],
            f"{m['name']}: a suffix is the traffic of the one cell that "
            f"reports {m['moves']}")
    index = {m["name"]: i for i, m in enumerate(entries)}
    by_name = {m["name"]: m for m in entries}
    e2e = {m["name"] for m in b["end_to_end"]}
    on_disk = {}
    for p in b["paths"]:
        base = os.path.join(root, p, "layer_metrics")
        for f in sorted(os.listdir(base)) if os.path.isdir(base) else ():
            name, ext = os.path.splitext(f)
            if ext in (".json", ".py"):
                on_disk.setdefault(name, []).append(os.path.join(base, f))
    for name, paths in on_disk.items():
        chk(name in by_name, f"{paths[0]}: a reader file with no entry")
        chk(len(paths) == 1, f"{name} has {len(paths)} reader files: "
            + ", ".join(os.path.relpath(p, root) for p in paths))
    does = {}
    for m in entries:
        if m["name"] not in on_disk:
            continue                    # "no reader file", said by the caller
        key = (_body(on_disk[m["name"]][0]), m["moves"])
        chk(key not in does, f"{m['name']} and {does.get(key)} read the same "
            f"thing the same way and move {m['moves']}: one entry that "
            "lists both cells")
        does.setdefault(key, m["name"])
        for read in sorted(metrics_read(on_disk[m["name"]][0]) - e2e):
            if read not in by_name:
                chk(False, f"{m['name']} reads {read!r}, which is no entry")
                continue
            chk(index[read] < index[m["name"]],
                f"{m['name']} reads {read!r}, which stands after it")
            lacking = cells_of(m) - cells_of(by_name[read])
            chk(not lacking, f"{m['name']} reads {read!r} in a cell that "
                f"does not list it: {sorted(lacking)}")


def errors(root: str = ROOT) -> list:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
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
            and os.path.exists(os.path.join(root, c["file"]))
            and c["file"] not in files, f"config file {c['file']}")
        files.add(c["file"])
        chk(len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"]),
            f"reduced of {c['name']}")
        held = json.load(open(os.path.join(root, c["file"])))
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
    chk(1 <= len(b["per_layer"]) <= PER_LAYER_MAX, "per_layer count")
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
        chk(any(os.path.exists(os.path.join(root, p, "layer_metrics",
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
        for d, _s, fs in os.walk(os.path.join(root, p)):
            if "/." in d or "__pycache__" in d:
                continue
            for f in fs:
                rel = os.path.relpath(os.path.join(d, f), root)
                chk(re.match(r"^[A-Za-z0-9_.\-/]+$", rel), f"file name {rel}")
    per_layer_rule(b, root, chk)
    return errs


def main() -> int:
    errs = errors()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        taken = len(json.load(f)["per_layer"])
    print(f"per_layer count: {taken} of {PER_LAYER_MAX} entries taken")
    print("\n".join(errs) if errs else "BENCHMARK.json: ok")
    return 1 if errs else 0


if __name__ == "__main__":
    sys.exit(main())
