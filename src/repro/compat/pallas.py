"""Pallas-TPU compiler parameters.

Kernels splat the result into ``pl.pallas_call``::

    pl.pallas_call(kernel, ..., **mosaic_params(
        dimension_semantics=("parallel", "arbitrary")))
"""
from __future__ import annotations

from typing import Any, Dict

from jax.experimental.pallas import tpu as pltpu


def mosaic_params(**fields: Any) -> Dict[str, Any]:
    """The ``compiler_params=`` kwarg dict for ``pl.pallas_call``."""
    return {"compiler_params": pltpu.CompilerParams(**fields)}
