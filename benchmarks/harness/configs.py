"""Configuration and traffic files, found by the names BENCHMARK.json gives.

A configuration file holds the model's sizes under the keys of its
public source (`config.json`), as they are run, and says which program
field each feeds (`program_keys`: field of the program's configuration
object -> key of this file), which family builds that object
(`family`, see families.py) and which plain reference it is held to
(`reference`). So a new configuration with other key names is a new
file, not new code.
"""

from __future__ import annotations

import importlib.util
import json
import os


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, prefix: str):
    """The Python file at `path`, imported under a name of its own
    (`prefix` + its stem): a reader, a family or a reference that a
    later PR dropped in."""
    stem = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(
        prefix + stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_benchmark(root: str) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                     f"{[c['name'] for c in bench['workloads']]}")


def load_config(root: str, bench: dict, name: str) -> dict:
    for entry in bench["configs"]:
        if entry["name"] == name:
            return load_json(os.path.join(root, entry["file"]))
    raise SystemExit(f"no config {name!r} in BENCHMARK.json")


def load_traffic(root: str, bench: dict, name: str) -> dict:
    """`<paths[0]>/traffic/<name>.json`, or the same file name under any
    other directory of `paths` (a later PR's own directory)."""
    for base in bench["paths"]:
        path = os.path.join(root, base, "traffic", name + ".json")
        if os.path.exists(path):
            return load_json(path)
    raise SystemExit(f"no traffic file {name}.json under {bench['paths']}")


def metrics_dirs(root: str, bench: dict) -> list[str]:
    return [os.path.join(root, base, "layer_metrics")
            for base in bench["paths"]]


def metrics_for_cell(bench: dict, group: str, cell: str) -> list[dict]:
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def quantity_of(end_to_end_name: str) -> str:
    """The harness's quantity that an end-to-end metric reports: the name
    up to its first dot. `out_tokens_per_s.batch` is `out_tokens_per_s`
    under a bound of its own cells' spread (PR 47), so an entry gives a
    cell a bound of its own and no code knows the cell."""
    return end_to_end_name.split(".", 1)[0]


def dims(config: dict) -> dict:
    """Program-side names of the model's sizes: d_model, n_layers, ..."""
    return {field: config[key]
            for field, key in config["program_keys"].items()}


def program_kwargs(config: dict, **overrides) -> dict:
    """What a family hands its program's configuration class: the file's
    sizes under the program's names, `program_fixed`, then the runner's
    overrides."""
    return {**dims(config), **config.get("program_fixed", {}), **overrides}
