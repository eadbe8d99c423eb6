"""Which Pallas path this process runs: Mosaic, or the interpreter.

``interpret=None`` in the kernel wrappers means "pick for me":
:func:`resolve_interpret` chooses interpret mode exactly when JAX's default
backend is the CPU, so the same call site compiles through Mosaic on a TPU
and interprets in a CPU container.  The backend comes from JAX itself: a
device query that fails raises, and is never read as "no accelerator".

``REPRO_PALLAS_INTERPRET=0/1`` forces either mode.  The compile tests set it
to 0 to lower the model's kernels for a described TPU from a CPU process.
"""
from __future__ import annotations

import os
from typing import Optional

import jax


def pallas_interpret_default() -> bool:
    """True when Pallas kernels should run in interpret mode by default."""
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env not in ("0", "false", "False", "")
    return jax.default_backend() == "cpu"


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Map the tri-state kernel arg (None = auto) to a concrete bool."""
    if interpret is None:
        return pallas_interpret_default()
    return bool(interpret)


def kernel_path() -> str:
    """'pallas_interpret' | 'mosaic': what ``interpret=None`` runs here."""
    return "pallas_interpret" if pallas_interpret_default() else "mosaic"
