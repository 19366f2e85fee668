"""Per-layer metric readers, found by name.

A per-layer metric is an entry of BENCHMARK.json's `per_layer` plus one
file of its own under benchmarks/layer_metrics/: `<name>.json` with a
`reader` out of the small generic set below (and an optional `scale`),
or `<name>.py` with one function `read(ctx) -> float | None`. The
harness holds no list of metrics: it takes the cell's entries from
BENCHMARK.json and looks each file up by the entry's name. A reader that
finds nothing to read returns None and the metric is left out of the
line.

A metric's name says WHAT is read, its entry's `workloads` WHERE (PR 52):
`decode_program_dev_ms` is one entry and one file, read in every cell
the entry lists, and a new cell joins it by its name in that list. No
reader knows a cell: what differs by family comes through `consts`, and
a sibling is named by its stem (`m['experts_touched']`). Only an entry
whose cell reports an end-to-end quantity of its own keeps a suffix
(`decode_program_dev_ms.batch` moves `out_tokens_per_s.batch`,
`attn_kernel_share.train` moves `train_tokens_per_s`): an entry has one
`moves`. A file says what holds in every cell that lists it; what is
true of one family stays in families/<family>.py beside its constants.
tools/check_contract.py holds the rule.

`ctx` is what a run hands its readers:
  engine    dict, `LLMEngine.metrics()` at the window's end (counters
            reset at its start), plus `compiles_in_window`
  requests  list of dicts, one per measured request: due, sent,
            submitted_at, first_chunk_at, first_token_at, finished_at,
            n_prompt, n_out (seconds on one clock)
  train     dict: step_s (list), tokens_per_step, flops_per_token
  samples   dict of lists sampled through the window (4x a second),
            with the sampling times under `t`
  trace_t0  when the trace began, on the clock of `samples['t']`
  trace     `trace_reduce.reduce_trace` result, or None
  memory    `memory_stats()` of the fullest chip
  peaks     this device's row of harness/peaks.py
  consts    chips, window_s, model dims, bytes per step functions' inputs
  metrics   values of the metrics read so far (for `derived`)

Generic readers (`"reader": "<kind>:<args>"`):
  engine_metric:<key>
  request_quantile:<expr over a request's fields>:<q>
  trace_share:<program regex>:<op regex>        (fraction of busy time)
  trace_program_s:<program regex>               (mean device s per run)
  trace_idle                                    (fraction of the window)
  memory_stat:<key>
  train_step_quantile:<q>
  derived:<expr over m['<metric>'], c['<const>'], p['<peak>'],
          s['<sample key>'] (its mean over the window),
          st['<sample key>'] (its mean over the TRACED part of the window:
          what a quantity set against device time has to use)>
"""

from __future__ import annotations

import ast
import json
import operator
import os

from . import configs, stats, trace_reduce

_BIN = {ast.Add: operator.add, ast.Sub: operator.sub, ast.Mult: operator.mul,
        ast.Div: operator.truediv, ast.Pow: operator.pow}


def evaluate(expr: str, names: dict):
    """Arithmetic over names, name['key'] lookups, numbers, + - * / **.
    Nothing else, and no function: a `min(x, 100)` would hide a roofline
    share counted too high. A missing name or key, or a None operand,
    makes the whole value None (nothing to read)."""

    class Missing(Exception):
        pass

    def ev(node):
        if isinstance(node, ast.Expression):
            return ev(node.body)
        if isinstance(node, ast.Constant) and isinstance(
                node.value, (int, float, str)):
            return node.value
        if isinstance(node, ast.Name):
            if node.id not in names or names[node.id] is None:
                raise Missing(node.id)
            return names[node.id]
        if isinstance(node, ast.Subscript):
            base, key = ev(node.value), ev(node.slice)
            if not isinstance(base, dict) or base.get(key) is None:
                raise Missing(key)
            return base[key]
        if isinstance(node, ast.BinOp) and type(node.op) in _BIN:
            return _BIN[type(node.op)](ev(node.left), ev(node.right))
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
            return -ev(node.operand)
        raise ValueError(f"not allowed in a metric expression: "
                         f"{ast.dump(node)}")

    try:
        return ev(ast.parse(expr, mode="eval"))
    except (Missing, ZeroDivisionError):
        return None


def _engine_metric(ctx, key):
    return (ctx.get("engine") or {}).get(key)


def _request_quantile(ctx, expr, q):
    vals = [v for v in (evaluate(expr, r) for r in ctx.get("requests") or [])
            if v is not None]
    return stats.quantile(vals, float(q)) if vals else None


def _trace_share(ctx, program_re, op_re):
    return trace_reduce.share(ctx["trace"], program_re, op_re) \
        if ctx.get("trace") else None


def _trace_program_s(ctx, program_re):
    return trace_reduce.program_mean_s(ctx["trace"], program_re) \
        if ctx.get("trace") else None


def _trace_idle(ctx):
    red = ctx.get("trace") or {}
    if not red.get("window_s") or red.get("busy_s") is None:
        return None
    return 1.0 - red["busy_s"] / red["window_s"]


def _memory_stat(ctx, key):
    return (ctx.get("memory") or {}).get(key)


def _train_step_quantile(ctx, q):
    xs = (ctx.get("train") or {}).get("step_s")
    return stats.quantile(xs, float(q)) if xs else None


def _derived(ctx, expr):
    samples = ctx.get("samples") or {}
    mean = lambda v: sum(v) / len(v)
    means = {k: mean(v) for k, v in samples.items() if v and k != "t"}
    traced = {}
    if ctx.get("trace_t0") is not None and samples.get("t"):
        keep = [i for i, t in enumerate(samples["t"]) if t >= ctx["trace_t0"]]
        traced = {k: mean([v[i] for i in keep])
                  for k, v in samples.items() if k != "t" and keep}
    return evaluate(expr, {"m": ctx.get("metrics", {}),
                           "c": ctx.get("consts", {}),
                           "p": ctx.get("peaks", {}), "s": means,
                           "st": traced})


GENERIC = {"engine_metric": _engine_metric,
           "request_quantile": _request_quantile,
           "trace_share": _trace_share, "trace_program_s": _trace_program_s,
           "trace_idle": _trace_idle, "memory_stat": _memory_stat,
           "train_step_quantile": _train_step_quantile, "derived": _derived}


def _split_reader(spec: str) -> tuple[str, list[str]]:
    """`kind:a:b` -> (kind, [a, b]); the LAST argument may hold colons
    only for `derived` (one argument, the rest of the string)."""
    kind, _, rest = spec.partition(":")
    if kind == "derived":
        return kind, [rest]
    return kind, rest.split(":") if rest else []


def load_reader(metrics_dirs: list[str], name: str):
    """-> read(ctx) for the metric called `name`, or None if it has no
    file under any of `metrics_dirs`."""
    py = js = ""
    for base in metrics_dirs:
        py = os.path.join(base, name + ".py")
        js = os.path.join(base, name + ".json")
        if os.path.exists(py) or os.path.exists(js):
            break
    if os.path.exists(py):
        return configs.load_module(py, "layer_metric_").read
    if not os.path.exists(js):
        return None
    with open(js) as f:
        meta = json.load(f)
    kind, args = _split_reader(meta["reader"])
    if kind not in GENERIC:
        raise ValueError(f"{js}: unknown reader kind {kind!r}")
    scale = float(meta.get("scale", 1.0))

    def read(ctx):
        value = GENERIC[kind](ctx, *args)
        return None if value is None else float(value) * scale

    return read


def read_all(metrics_dirs: list[str], entries: list[dict], ctx: dict,
             end_to_end: dict) -> dict:
    """Read every entry (BENCHMARK.json `per_layer` rows of this cell), in
    order, so a `derived` metric can use the end-to-end values and the
    metrics before it. -> {name: {"value", "unit"}} without the metrics
    that had nothing to read."""
    out = {}
    ctx = dict(ctx, metrics=dict(end_to_end))
    for entry in entries:
        reader = load_reader(metrics_dirs, entry["name"])
        if reader is None:
            raise FileNotFoundError(
                f"per-layer metric {entry['name']!r} has no reader under "
                f"{metrics_dirs}")
        value = reader(ctx)
        if value is None or value != value:          # None or NaN
            continue
        ctx["metrics"][entry["name"]] = value
        out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out
