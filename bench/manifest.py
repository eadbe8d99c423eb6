"""``BENCHMARK.json`` and the files it names, found by name.

A cell is ``workloads[i]``; its configuration is ``bench/configs/<config>
.json`` (the path is the ``file`` of its ``configs`` entry), whose
``model_type`` names its family's module ``bench/models/<model_type>.py``
(the reference's forward and weights, and a request's useful work), its
traffic ``bench/traffic/<traffic>.json``, and each metric it reports a
reader ``bench/metrics/<metric>.py`` with ``read(ctx) -> float | None``.
Adding a cell, a configuration, a family, a mix or a metric adds files and
entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys
from types import ModuleType
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict      # the configuration file's contents
    family: ModuleType  # bench/models/<model_type>.py
    traffic: dict     # the mix's file contents
    end_to_end: List[dict]
    per_layer: List[dict]


def load(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(root: str, rel: str) -> dict:
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def _module(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up there
    spec.loader.exec_module(mod)
    return mod


def family(cfg: dict, root: str = ROOT) -> ModuleType:
    """The module of the configuration's model family,
    ``bench/models/<model_type>.py``, loaded once per path."""
    rel = os.path.join("bench", "models", cfg["model_type"] + ".py")
    path = os.path.join(root, rel)
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"configuration {cfg.get('name')!r}: model_type "
            f"{cfg['model_type']!r} has no module {rel}")
    name = "bench_family_" + re.sub(r"\W", "_", cfg["model_type"])
    mod = sys.modules.get(name)
    if mod is None or mod.__file__ != path:
        mod = _module(path, name)
    return mod


def reports(metric: dict, cell: str) -> bool:
    """Whether ``cell`` reports ``metric`` (no ``workloads`` key: every
    cell)."""
    return cell in metric.get("workloads", [cell])


def cell(name: str, root: str = ROOT, bench: Optional[dict] = None) -> Cell:
    bench = bench if bench is not None else load(root)
    w = next((w for w in bench["workloads"] if w["name"] == name), None)
    if w is None:
        raise KeyError(f"no workload {name!r}; known: "
                       f"{[w['name'] for w in bench['workloads']]}")
    c = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = _json(root, c["file"])
    return Cell(name=name, chips=int(w["chips"]), config=config,
                family=family(config, root),
                traffic=_json(root, os.path.join(
                    "bench", "traffic", w["traffic"] + ".json")),
                end_to_end=[m for m in bench["end_to_end"]
                            if reports(m, name)],
                per_layer=[m for m in bench["per_layer"] if reports(m, name)])


def reader(metric: str, root: str = ROOT) -> Callable:
    """The ``read`` function of ``bench/metrics/<metric>.py``, or of the
    quantity's file without the suffix after the last dot."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    if not os.path.exists(path) and "." in metric:
        path = os.path.join(root, "bench", "metrics",
                            metric.rsplit(".", 1)[0] + ".py")
    return _module(path, "bench_metric_" + re.sub(r"\W", "_", metric)).read


def read_metrics(metrics: List[dict], ctx, root: str = ROOT
                 ) -> Dict[str, dict]:
    """{name: {"value", "unit"}} of each metric whose reader found
    something to read."""
    out = {}
    for m in metrics:
        v = reader(m["name"], root)(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out
