"""Configuration and traffic files, found by the names BENCHMARK.json gives.

A configuration file holds the model's sizes under the keys of its
public source (`config.json`), as they are run, and says which program
field each feeds (`program_keys`: GPTConfig field -> key of this file).
So a new configuration with other key names is a new file, not new code.
"""

from __future__ import annotations

import json
import os


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


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


def dims(config: dict) -> dict:
    """Program-side names of the model's sizes: d_model, n_layers, ..."""
    return {field: config[key]
            for field, key in config["program_keys"].items()}


def gpt_config(config: dict, **overrides):
    from ray_tpu.models import gpt

    kw = dict(dims(config))
    kw.update(config.get("program_fixed", {}))
    kw.update(overrides)
    return gpt.GPTConfig(**kw)
