"""Roofline-term extraction from compiled dry-run artifacts.

Sources (per the brief):
  * ``compiled.cost_analysis()``   -> HLO flops / bytes (PER-DEVICE program:
    XLA SPMD emits one partitioned module, so these are per-chip numbers).
  * ``compiled.as_text()``         -> collective ops; cost_analysis does not
    cover them, so we parse result shapes + replica groups per instruction.

Two collective-byte conventions are recorded:
  * ``operand`` — the brief's "sum operand sizes of every collective".
  * ``wire``    — ring-algorithm bytes actually serialized per device
                  (all-reduce 2(g-1)/g, all-gather/reduce-scatter (g-1)/g,
                  all-to-all (g-1)/g, collective-permute 1x).

Hardware model: the dry-run's target chip (repro.launch.mesh.peaks).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from repro.launch.mesh import DRYRUN_DEVICE_KIND, peaks

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_LINE_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\(?[^=]*?\)?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of one 'f32[a,b]{...}' or '(f32[..], bf16[..])' string."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 1


@dataclass
class CollectiveStats:
    counts: Dict[str, int] = field(default_factory=dict)
    operand_bytes: Dict[str, int] = field(default_factory=dict)
    wire_bytes: Dict[str, int] = field(default_factory=dict)

    @property
    def total_operand(self) -> int:
        return sum(self.operand_bytes.values())

    @property
    def total_wire(self) -> int:
        return sum(self.wire_bytes.values())

    def to_dict(self) -> Dict[str, Any]:
        return {"counts": self.counts, "operand_bytes": self.operand_bytes,
                "wire_bytes": self.wire_bytes,
                "total_operand": self.total_operand,
                "total_wire": self.total_wire}


def collective_stats(hlo_text: str) -> CollectiveStats:
    """Per-device collective bytes from a partitioned HLO module."""
    st = CollectiveStats()
    seen_done = set()
    for line in hlo_text.splitlines():
        m = _LINE_RE.match(line)
        if not m:
            continue
        if "-done(" in line:  # async pair: count the -start only
            continue
        shape_str, op = m.group(1), m.group(2)
        res = _shape_bytes(shape_str)
        g = _group_size(line)
        if op == "all-reduce":
            operand = res
            wire = int(2 * (g - 1) / g * res)
        elif op == "all-gather":
            operand = res // max(g, 1)
            wire = int((g - 1) / g * res)
        elif op == "reduce-scatter":
            operand = res * g
            wire = (g - 1) * res
        elif op == "all-to-all":
            operand = res
            wire = int((g - 1) / g * res)
        else:  # collective-permute
            operand = res
            wire = res
        st.counts[op] = st.counts.get(op, 0) + 1
        st.operand_bytes[op] = st.operand_bytes.get(op, 0) + operand
        st.wire_bytes[op] = st.wire_bytes.get(op, 0) + wire
    return st


def roofline_terms(flops_per_dev: float, bytes_per_dev: float,
                   coll: CollectiveStats) -> Dict[str, float]:
    """The three roofline terms in SECONDS (per step, per chip)."""
    hw = peaks(DRYRUN_DEVICE_KIND)
    t_compute = flops_per_dev / hw["peak_flops_bf16"]
    t_memory = bytes_per_dev / hw["hbm_bw"]
    t_coll_operand = coll.total_operand / hw["ici_bw"]
    t_coll_wire = coll.total_wire / hw["ici_bw"]
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll_operand, "collective_wire_s": t_coll_wire}
    dom = max(("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k])
    terms["dominant"] = dom
    # roofline fraction: useful-compute share of the binding resource
    bound = max(t_compute, t_memory, t_coll_operand)
    terms["roofline_fraction"] = t_compute / bound if bound > 0 else 0.0
    return terms


def model_flops(cfg, shape) -> float:
    """Analytic MODEL_FLOPS: 6·N·D train, 2·N·D inference (N = active
    params, D = tokens processed globally this step)."""
    n = cfg.n_active_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        if cfg.family == "vlm":
            tokens += shape.global_batch * cfg.n_img_tokens \
                - shape.global_batch * cfg.n_img_tokens  # text-only targets
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def memory_stats_dict(mem) -> Dict[str, int]:
    if mem is None:
        return {}
    out = {}
    for k in ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "alias_size_in_bytes",
              "temp_size_in_bytes"):
        if hasattr(mem, k):
            out[k] = int(getattr(mem, k))
    if out:
        out["peak_bytes_per_device"] = (
            out.get("argument_size_in_bytes", 0)
            + out.get("output_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0)
            + out.get("temp_size_in_bytes", 0))
    return out
