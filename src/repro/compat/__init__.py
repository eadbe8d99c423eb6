"""repro.compat — the one place this tree touches JAX's less stable APIs.

The tree runs on one installed JAX (see ROADMAP.md "Supported JAX
versions").  Every use of an API that has moved or been renamed across
JAX releases goes through this package, so that the next move is absorbed
here and call sites stay as they are:

  * :func:`shard_map`        — ``jax.shard_map``;
  * :func:`use_mesh`         — ``jax.sharding.set_mesh``;
  * :func:`mosaic_params`    — ``pltpu.CompilerParams`` for ``pallas_call``;
  * :func:`jit_sharded`      — ``jax.jit`` over PartitionSpec pytrees,
    bound to ``NamedSharding``s on the given mesh;
  * :func:`resolve_interpret` — Mosaic on an accelerator, the Pallas
    interpreter on the CPU backend (:mod:`repro.compat.capability`).

Rule of the seam (enforced by tests/test_compat.py's source scan): no
module under ``src/repro/`` outside this package may reference
``jax.shard_map``, ``set_mesh`` or ``*CompilerParams`` directly.
"""
from __future__ import annotations

from typing import Any, Dict

import jax

from repro.compat.capability import (kernel_path, pallas_interpret_default,
                                     resolve_interpret)
from repro.compat.jitting import (cost_analysis_dict, jit_sharded,
                                  resolve_shardings)
from repro.compat.meshctx import use_mesh
from repro.compat.pallas import mosaic_params
from repro.compat.shard import shard_map

__all__ = [
    "cost_analysis_dict", "describe", "jit_sharded", "kernel_path",
    "mosaic_params", "pallas_interpret_default", "resolve_interpret",
    "resolve_shardings", "shard_map", "use_mesh",
]


def describe() -> Dict[str, Any]:
    """The installed JAX, its default backend and the kernel path — for
    logs and bug reports."""
    return {
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "kernel_path": kernel_path(),
    }
