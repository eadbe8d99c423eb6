"""``shard_map``.

Every ``shard_map`` call site in the tree goes through :func:`shard_map`
below; a regression test scans for direct uses.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax


def shard_map(f: Callable[..., Any], mesh, in_specs, out_specs, *,
              check_vma: Optional[bool] = None, **kwargs: Any
              ) -> Callable[..., Any]:
    """``jax.shard_map(f, mesh=..., in_specs=..., out_specs=..., ...)``."""
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)
