"""The "make this mesh current" context manager.

Call sites write ``with use_mesh(mesh): ...``; it establishes the mesh for
subsequent ``jax.jit`` calls whose shardings name its axes, through
``jax.sharding.set_mesh``.
"""
from __future__ import annotations

import contextlib
from typing import Iterator

import jax
from jax.sharding import Mesh


@contextlib.contextmanager
def use_mesh(mesh: Mesh) -> Iterator[Mesh]:
    with jax.sharding.set_mesh(mesh):
        yield mesh
