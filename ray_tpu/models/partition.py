"""Regex→PartitionSpec rules + the serving tensor-parallel mesh.

THE one spec-derivation implementation in the repo (the logical-axis
helpers that used to live in ``parallel/sharding.py`` are folded in
below and re-exported from there): models declare WHERE each parameter
shards once — either as a regex rule table over ``/``-joined pytree
paths (`match_partition_rules`, the fmtrainer/EasyLM pattern; see
``models/gpt.py::partition_rules`` and
``models/paged_kv.py::KV_POOL_PARTITION_RULES``) or as logical axis
names resolved against a rule table (`logical_to_spec`, the train-side
path) — and everything downstream (engine load-time sharding, pjit
in/out specs, shard_map in_specs, the SPMD memory audit) derives from
that single source.

Serving tensor parallelism (``llm_tp``): the engine builds a 1-axis
``("tp",)`` mesh over local devices at load, shards params/KV pool once
with `shard_by_rules`, and every compiled program runs per-shard through
``utils/jax_compat.shard_map`` (models/paged_kv.py ``*_tp`` twins). The
head axis is the partition axis because decode attention is already
embarrassingly parallel over heads: QKV projections, rotary, per-head
softmax, and the paged-KV page reads/writes (pool sharded on its head
dim) are all shard-local; only the attention-out and MLP-down partial
sums cross shards (one ``psum`` each per layer).
"""

from __future__ import annotations

import re
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ray_tpu.parallel.mesh import DEFAULT_LOGICAL_RULES

__all__ = [
    "PartitionRuleError", "match_partition_rules", "make_tp_mesh",
    "shard_by_rules", "tree_path_names", "logical_to_spec",
    "tree_to_shardings", "shard_tree", "TP_AXIS",
    "split_head_planes", "concat_head_planes",
]

# KV page planes [L, n_pages, page_size, H*K] shard on their minor axis —
# the axis the ("tp",) mesh partitions (paged_kv.KV_POOL_PARTITION_RULES).
# Heads are the major part of it, so an even split into tp pieces hands
# each shard (H/tp)*K contiguous lanes: its own whole heads.
# split_head_planes/concat_head_planes below speak the same axis.
KV_HEAD_AXIS = 3


def split_head_planes(payload: dict, tp: int) -> dict:
    """Full-head host page planes → per-shard planes keyed ``name@s``.

    The KV page-set donation path at ``llm_tp > 1``: a gathered payload
    ``{"k": [L, n, ps, H*K], ...}`` splits along the head axis into
    ``tp`` planes (``k@0`` … ``k@{tp-1}``), so each entry in the object
    store is one shard's bytes and an adopter reassembles exactly the
    shards it needs. ``_scale``-suffixed planes ([L, n] per-page
    scalars) are replicated across head shards by construction
    (`paged_kv._quant_write` pmax's them), so ONE copy rides unsuffixed.
    ``tp == 1`` is the identity (the unsharded wire schema of tp=1
    donors is unchanged)."""
    if tp <= 1:
        return dict(payload)
    out: dict = {}
    for name, arr in payload.items():
        if name.endswith("_scale") or getattr(arr, "ndim", 0) <= KV_HEAD_AXIS:
            out[name] = arr
            continue
        h = arr.shape[KV_HEAD_AXIS]
        if h % tp:
            raise ValueError(
                f"cannot split plane {name!r}: head dim {h} not divisible "
                f"by tp={tp}")
        for s, piece in enumerate(np.split(arr, tp, axis=KV_HEAD_AXIS)):
            out[f"{name}@{s}"] = piece
    return out


def concat_head_planes(payload: dict, tp: int) -> dict:
    """Inverse of `split_head_planes`: ``name@s`` shard planes →
    full-head planes (head-axis concatenation in shard order).

    The adoption path: heads are shard-invariant math, so an adopter at
    a DIFFERENT tp degree first reassembles the donor's full-head plane
    here, then its own (possibly shard_map-rebound) scatter re-slices
    per its mesh — tp=2 donor → tp=4 adopter and the reverse both fall
    out of the same two steps. Raises if a shard plane is missing (a
    torn donation must fail the adopt rung, not bind garbage heads)."""
    if tp <= 1:
        return dict(payload)
    out: dict = {}
    shards: dict[str, dict[int, Any]] = {}
    for name, arr in payload.items():
        base, sep, idx = name.rpartition("@")
        if sep and idx.isdigit():
            shards.setdefault(base, {})[int(idx)] = arr
        else:
            out[name] = arr
    for base, pieces in shards.items():
        if sorted(pieces) != list(range(tp)):
            raise ValueError(
                f"sharded payload plane {base!r} is torn: have shards "
                f"{sorted(pieces)}, want 0..{tp - 1}")
        out[base] = np.concatenate(
            [pieces[s] for s in range(tp)], axis=KV_HEAD_AXIS)
    return out

# The serving tensor-parallel mesh axis. Rule tables that shard over it
# (gpt.partition_rules, paged_kv.KV_POOL_PARTITION_RULES) name it via
# this constant so the axis vocabulary has one spelling.
TP_AXIS = "tp"


class PartitionRuleError(ValueError):
    """A pytree leaf matched no partition rule (typed so callers can
    distinguish an incomplete rule table from other config errors)."""


def _key_str(entry: Any) -> str:
    """One pytree path entry → its path-segment string."""
    for attr in ("key", "idx", "name"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def _path_name(path: tuple) -> str:
    return "/".join(_key_str(p) for p in path)


def tree_path_names(tree: Any) -> list[str]:
    """``/``-joined path of every leaf, in flatten order (debugging /
    tests: what `match_partition_rules` matches its regexes against)."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [_path_name(path) for path, _leaf in leaves]


def match_partition_rules(rules, params):
    """Pytree of PartitionSpec for ``params`` from a regex rule table.

    ``rules`` is an ordered sequence of ``(regex, PartitionSpec)``; each
    leaf's ``/``-joined path is matched with ``re.search`` and the FIRST
    matching rule wins (rule precedence is list order). Scalar leaves —
    ndim 0 or a single element — are never partitioned and resolve to
    ``PartitionSpec()`` without consulting the table, so optimizer
    step-counts and the like need no rules. A leaf no rule covers raises
    `PartitionRuleError` naming the path: an unmatched leaf silently
    replicated would hide exactly the weight the table forgot.

    Works on shape-carrying leaves only (arrays, ShapeDtypeStructs, or
    jit tracers — the shapes are all it reads).
    """
    compiled = [(re.compile(pat), spec) for pat, spec in rules]

    def get_spec(path, leaf):
        name = _path_name(path)
        shape = getattr(leaf, "shape", ())
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            return PartitionSpec()
        for pat, spec in compiled:
            if pat.search(name) is not None:
                return spec
        raise PartitionRuleError(
            f"no partition rule matches param {name!r} (shape "
            f"{tuple(shape)}); add a rule or an explicit replicated "
            "entry — silent replication would hide the miss")

    return jax.tree_util.tree_map_with_path(get_spec, params)


def make_tp_mesh(tp: int, *, devices=None) -> Mesh:
    """1-axis ``("tp",)`` mesh over the first ``tp`` local devices — the
    serving engine's whole mesh story (single host; pod-wide pjit is the
    ROADMAP follow-up). Off TPU, ``XLA_FLAGS=--xla_force_host_platform_
    device_count=N`` (utils/platform.force_cpu_devices) forks the
    virtual devices this slices."""
    if devices is None:
        devices = jax.devices()
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if tp > len(devices):
        raise ValueError(
            f"tp={tp} exceeds the {len(devices)} visible device(s); "
            "off-TPU, force a host-device mesh with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={tp}")
    return Mesh(np.asarray(devices[:tp]), (TP_AXIS,))


def shard_by_rules(mesh: Mesh, rules, tree: Any) -> Any:
    """Device-put ``tree`` onto ``mesh`` per its rule table — the
    engine's one-time load-side sharding (params, KV pools)."""
    specs = match_partition_rules(rules, tree)
    return jax.tree.map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)),
        tree, specs,
        is_leaf=lambda x: isinstance(x, PartitionSpec))


# --------------------------------------------------------------------------
# Logical-axis → PartitionSpec resolution (folded in from
# parallel/sharding.py, which re-exports these for its existing callers):
# models annotate parameters with logical axis names (("embed", "mlp"))
# and the active rule table + mesh resolve them to NamedShardings at jit
# time. Train-side twin of the regex tables above.
# --------------------------------------------------------------------------


def logical_to_spec(
    logical_axes: tuple[Any, ...],
    rules: tuple[tuple[str, Any], ...] = DEFAULT_LOGICAL_RULES,
    *,
    mesh: Mesh | None = None,
) -> PartitionSpec:
    """Map a tuple of logical axis names to a PartitionSpec.

    If `mesh` is given, any mesh axis of size 1 (or absent) resolves to None so
    the same rules work on a single chip and a pod. A mesh axis may be consumed
    by at most one dimension of a given array.
    """
    table = dict(rules)
    used: set[str] = set()
    out: list[Any] = []
    for ax in logical_axes:
        mapped = table.get(ax) if ax is not None else None
        if mapped is None:
            out.append(None)
            continue
        axes = (mapped,) if isinstance(mapped, str) else tuple(mapped)
        kept = []
        for m in axes:
            if m in used:
                continue
            if mesh is not None and mesh.shape.get(m, 1) == 1:
                continue
            kept.append(m)
            used.add(m)
        if not kept:
            out.append(None)
        elif len(kept) == 1:
            out.append(kept[0])
        else:
            out.append(tuple(kept))
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def tree_to_shardings(
    logical_tree: Any,
    mesh: Mesh,
    rules: tuple[tuple[str, Any], ...] = DEFAULT_LOGICAL_RULES,
) -> Any:
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings."""
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, logical_to_spec(axes, rules, mesh=mesh)),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x
        ),
    )


def shard_tree(tree: Any, shardings: Any) -> Any:
    """Device-put a pytree according to a matching pytree of shardings."""
    return jax.tree.map(lambda x, s: jax.device_put(x, s), tree, shardings)
