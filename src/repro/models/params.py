"""Parameter definitions: one source of truth for shape, logical axes, init.

A model is described as a pytree of ``ParamDef``s.  From that single tree we
derive:
  * concrete initialized parameters (``init_params``),
  * abstract ``ShapeDtypeStruct`` stand-ins for the dry-run (``abstract_params``),
  * ``PartitionSpec``s via logical-axis rules (``repro.sharding``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]  # logical axis name per dim (None = replicated)
    init: str = "normal"  # normal | zeros | ones | scaled | embed
    scale: float = 1.0  # stddev multiplier
    dtype: Any = jnp.bfloat16
    # inputs summed per output element; 0 = the second-to-last dim (right
    # for (in, out) matrices, wrong for head-split projections)
    fan_in: int = 0

    def __post_init__(self) -> None:
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} vs axes {self.axes} rank mismatch")


def is_paramdef(x: Any) -> bool:
    return isinstance(x, ParamDef)


def _init_leaf(rng: jax.Array, d: ParamDef) -> jax.Array:
    if d.init == "zeros":
        return jnp.zeros(d.shape, d.dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, d.dtype)
    if d.init == "normal":
        # truncated-normal, stddev = scale / sqrt(fan_in); fan_in defaults to
        # the second-to-last dim (stacked-layer leading dims excluded by
        # convention: the last two dims are the matmul dims).
        fan_in = d.fan_in or (d.shape[-2] if len(d.shape) >= 2 else d.shape[-1])
        std = d.scale / np.sqrt(max(fan_in, 1))
        x = jax.random.truncated_normal(rng, -2.0, 2.0, d.shape, jnp.float32) * std
        return x.astype(d.dtype)
    if d.init == "embed":
        x = jax.random.truncated_normal(rng, -2.0, 2.0, d.shape, jnp.float32) * d.scale
        return x.astype(d.dtype)
    if d.init == "scaled":  # uniform in +-scale (conv/ssm misc params)
        x = jax.random.uniform(rng, d.shape, jnp.float32, -d.scale, d.scale)
        return x.astype(d.dtype)
    raise ValueError(f"unknown init {d.init!r}")


# one program per leaf: the f32 draw, the scaling and the cast fuse, so the
# device holds one leaf in its final dtype at a time instead of several f32
# temporaries (a full-width layer stack is GBs)
_init_leaf_jit = jax.jit(_init_leaf, static_argnums=1)


def init_params(rng: jax.Array, defs: Any) -> Any:
    leaves, treedef = jax.tree_util.tree_flatten(defs, is_leaf=is_paramdef)
    rngs = jax.random.split(rng, len(leaves))
    vals = [_init_leaf_jit(r, d) for r, d in zip(rngs, leaves)]
    return jax.tree_util.tree_unflatten(treedef, vals)


def abstract_params(defs: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda d: jax.ShapeDtypeStruct(d.shape, d.dtype), defs, is_leaf=is_paramdef
    )


def param_axes(defs: Any) -> Any:
    """Tree of logical-axes tuples, mirroring the param tree."""
    return jax.tree_util.tree_map(lambda d: d.axes, defs, is_leaf=is_paramdef)


def count_params(defs: Any) -> int:
    leaves = jax.tree_util.tree_leaves(defs, is_leaf=is_paramdef)
    return int(sum(int(np.prod(d.shape)) for d in leaves))


def param_bytes(defs: Any) -> int:
    leaves = jax.tree_util.tree_leaves(defs, is_leaf=is_paramdef)
    return int(sum(int(np.prod(d.shape)) * jnp.dtype(d.dtype).itemsize for d in leaves))


def cast_tree(tree: Any, dtype: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x, tree
    )
