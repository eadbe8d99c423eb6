"""The yardstick's arithmetic: peaks, model shapes, useful work, percentiles.

Nothing here is imported from the program.  Useful work is counted from a
configuration file and the requests' own lengths only: never padded
positions, never the cache's ``max_len``, never empty slots.

A served request with ``P`` prompt tokens that returned ``O`` tokens is, as
model work, one causal forward over ``P + O - 1`` tokens (its prompt, then
every generated token but the last fed back) with the output head at the
``O`` positions whose token was served.  Positions ``0 .. P-1`` are the
prefill's (the flash kernel's); positions ``P .. P+O-2`` are decode steps,
and the one at position ``i`` attends over ``i + 1`` cached keys.
"""
from __future__ import annotations

import dataclasses
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


@dataclasses.dataclass(frozen=True)
class Shape:
    """A dense decoder's sizes, read from a configuration file's
    Hugging Face-style keys."""
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int

    @classmethod
    def of(cls, cfg: dict) -> "Shape":
        heads = cfg["num_attention_heads"]
        return cls(layers=cfg["num_hidden_layers"], d=cfg["hidden_size"],
                   heads=heads, kv_heads=cfg["num_key_value_heads"],
                   head_dim=cfg.get("head_dim") or cfg["hidden_size"] // heads,
                   d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"])

    @property
    def layer_params(self) -> int:
        """Matmul weights of one block: q, k, v, o and the gated MLP."""
        hd = self.head_dim
        return (self.d * self.heads * hd + 2 * self.d * self.kv_heads * hd
                + self.heads * hd * self.d + 3 * self.d * self.d_ff)

    @property
    def head_params(self) -> int:
        return self.d * self.vocab


def causal_pairs(first: int, last: int) -> int:
    """Sum of ``i + 1`` over positions ``first .. last`` (query-key pairs
    of causal attention at those positions); 0 when the range is empty."""
    if last < first:
        return 0
    return (last + 1) * (last + 2) // 2 - first * (first + 1) // 2


def request_flops(shape: Shape, prompt: int, out: int) -> float:
    """Useful model FLOPs of one served request: every weight matmul at
    ``P + O - 1`` positions, the head at ``O`` and causal attention (QK and
    PV, 4 FLOPs per query-key pair per head dim) at each position's own
    context."""
    tokens = prompt + out - 1
    attn = 4 * shape.heads * shape.head_dim * causal_pairs(0, tokens - 1)
    return float(2 * shape.layers * shape.layer_params * tokens
                 + 2 * shape.head_params * out + shape.layers * attn)


def flash_work(shape: Shape, prompt: int) -> Tuple[float, float]:
    """(FLOPs, bytes) the prefill attention kernel needs for one prompt of
    ``prompt`` real tokens, over all layers: causal QK and PV, and reading
    Q, K, V and writing O once."""
    flops = 4 * shape.heads * shape.head_dim * causal_pairs(0, prompt - 1)
    io = (2 * shape.heads + 2 * shape.kv_heads) * shape.head_dim * prompt
    return float(shape.layers * flops), float(shape.layers * io * BF16)


def decode_attn_work(shape: Shape, prompt: int, out: int
                     ) -> Tuple[float, float]:
    """(FLOPs, bytes) the decode attention kernel needs for one request's
    useful decode steps, over all layers: at positions ``P .. P+O-2`` it
    reads the K and V of the valid context (``i + 1`` positions) and does
    QK and PV against it."""
    pairs = causal_pairs(prompt, prompt + out - 2)
    flops = 4 * shape.heads * shape.head_dim * pairs
    kv = 2 * shape.kv_heads * shape.head_dim * pairs * BF16
    return float(shape.layers * flops), float(shape.layers * kv)


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


def totals(shape: Shape, lengths: Iterable[Tuple[int, int]]
           ) -> Dict[str, float]:
    """Sums of the useful work over requests given as (prompt, returned)."""
    out = {"flops": 0.0, "flash_flops": 0.0, "flash_bytes": 0.0,
           "decode_flops": 0.0, "decode_bytes": 0.0}
    for p, o in lengths:
        out["flops"] += request_flops(shape, p, o)
        f, b = flash_work(shape, p)
        out["flash_flops"] += f
        out["flash_bytes"] += b
        f, b = decode_attn_work(shape, p, o)
        out["decode_flops"] += f
        out["decode_bytes"] += b
    return out

