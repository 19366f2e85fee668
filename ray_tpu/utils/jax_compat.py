"""The two JAX API names every sharding call site goes through.

Call sites import ``shard_map`` and ``tree_map`` from here instead of
spelling the JAX API inline; graftlint's JAX-COMPAT rule
(tools/graftlint/jax_compat.py) statically flags any direct use of a
symbol the installed version does not ship, and this module is the
canonical rewrite target its findings suggest.
"""

from __future__ import annotations

from typing import Any, Callable

import jax

__all__ = ["shard_map", "tree_map"]


def shard_map(
    f: Callable,
    *,
    mesh: Any,
    in_specs: Any,
    out_specs: Any,
    check_vma: bool = True,
    axis_names: Any = None,
) -> Callable:
    """``jax.shard_map``.

    - ``check_vma``: replication (varying-manual-axes) checking.
    - ``axis_names``: the set of mesh axes the body is *manual* over
      (partial-manual mode); ``None`` means fully manual (every mesh
      axis).
    """
    kwargs: dict[str, Any] = dict(
        mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=check_vma)
    if axis_names is not None:
        kwargs["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kwargs)


def tree_map(f: Callable, tree: Any, *rest: Any, **kwargs: Any) -> Any:
    """``jax.tree.map``."""
    return jax.tree.map(f, tree, *rest, **kwargs)
