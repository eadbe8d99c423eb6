"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; everything else sees the real device count.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Mesh over the actually-available devices (tests / local training)."""
    return jax.make_mesh((data, model), ("data", "model"))


# Per-chip peaks, keyed by ``jax.Device.device_kind``.  Source: Google
# Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM bandwidth,
# 16 GB HBM, 1,600 Gbit/s interconnect per chip (taken as 4 links of 50 GB/s).
PEAKS = {
    "TPU v5 lite": {
        "peak_flops_bf16": 197e12,  # FLOP/s
        "hbm_bw": 819e9,            # B/s
        "ici_bw": 50e9,             # B/s per link
        "hbm_bytes": 16 * 1024**3,  # capacity
    },
}

# the chip the dry-run matrix and its roofline model target
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """Peak table of one chip; a device that is not in the table is an error,
    never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak table for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
