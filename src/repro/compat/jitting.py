"""Sharded ``jax.jit`` over PartitionSpec pytrees.

The step bundles (repro.steps) carry *PartitionSpec pytrees* as their
in/out shardings.  :func:`resolve_shardings` binds every spec leaf to a
concrete ``NamedSharding`` on the given mesh, so the step does not depend
on which mesh is current, and :func:`jit_sharded` is the ``jax.jit``
wrapper the launchers use.  ``None`` subtrees (= let XLA decide) pass through
untouched, as do leaves that are already ``Sharding`` objects.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def resolve_shardings(mesh: Mesh, tree: Any) -> Any:
    """Bind PartitionSpec leaves in ``tree`` to ``NamedSharding(mesh, .)``."""
    def fix(leaf):
        if isinstance(leaf, PartitionSpec):
            return NamedSharding(mesh, leaf)
        return leaf

    return jax.tree_util.tree_map(
        fix, tree, is_leaf=lambda x: isinstance(x, PartitionSpec))


def jit_sharded(fn: Callable[..., Any], mesh: Mesh, *,
                in_shardings: Any = None, out_shardings: Any = None,
                donate_argnames: Optional[Sequence[str]] = None,
                **jit_kwargs: Any) -> Any:
    """``jax.jit`` that accepts PartitionSpec pytrees on every JAX.

    ``donate_argnames`` may be empty/None and is then omitted entirely.
    """
    kwargs = dict(jit_kwargs)
    if donate_argnames:
        kwargs["donate_argnames"] = tuple(donate_argnames)
    return jax.jit(fn,
                   in_shardings=resolve_shardings(mesh, in_shardings),
                   out_shardings=resolve_shardings(mesh, out_shardings),
                   **kwargs)


def cost_analysis_dict(compiled: Any) -> dict:
    """``Compiled.cost_analysis()``, or ``{}`` where the backend gives none."""
    return compiled.cost_analysis() or {}
