"""The yardstick's arithmetic that belongs to no model family: peaks, the
roofline, percentiles, causal pairs and the sums of useful work.

Nothing here is imported from the program.  What one request of a family
costs is its module's ``request_work`` (``bench/models/<model_type>.py``).
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, Sequence, Tuple

# Per-chip peaks keyed by ``jax.Device.device_kind``.  Source: Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM bandwidth, 16 GB
# of HBM.  A device that is not in the table is an error, not a default.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}

BF16 = 2  # bytes per element of the served activations and KV cache


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak table for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def causal_pairs(first: int, last: int) -> int:
    """Sum of ``i + 1`` over positions ``first .. last`` (query-key pairs
    of causal attention at those positions); 0 when the range is empty."""
    if last < first:
        return 0
    return (last + 1) * (last + 2) // 2 - first * (first + 1) // 2


def least_time(flops: float, nbytes: float, peak: Dict[str, float]) -> float:
    """The roofline: the least seconds the chip needs for ``flops`` and
    ``nbytes``, whichever of the two bounds it."""
    return max(flops / peak["flops"], nbytes / peak["hbm_bytes_per_s"])


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100), linear between closest ranks (the
    "inclusive" definition: rank ``q/100 * (n-1)``)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    rank = q / 100.0 * (len(xs) - 1)
    lo = math.floor(rank)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (rank - lo)


def totals(family, cfg: dict, lengths: Iterable[Tuple[int, int]]
           ) -> Dict[str, float]:
    """Sums, key by key, of the family's useful work
    (``family.request_work``) over requests given as (prompt, returned)."""
    out: Dict[str, float] = {}
    for p, o in lengths:
        for key, v in family.request_work(cfg, p, o).items():
            out[key] = out.get(key, 0.0) + v
    return out
