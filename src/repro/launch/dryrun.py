import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# The two lines above MUST precede every other import (jax locks the device
# count at first init).  This module is the multi-pod dry-run launcher:
# for every (architecture x input-shape) cell it lowers + compiles the
# pjit step on the production mesh and records memory / cost / collective
# analysis for EXPERIMENTS.md (§Dry-run, §Roofline).
"""
Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-2b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out artifacts/dryrun]
  PYTHONPATH=src python -m repro.launch.dryrun --all --both-meshes
"""
import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax

from repro.compat import cost_analysis_dict, jit_sharded, use_mesh
from repro.configs.base import ARCH_IDS, SHAPES, cells, get_config
from repro.launch.analysis import (collective_stats, memory_stats_dict,
                                   model_flops, roofline_terms)
from repro.launch.mesh import DRYRUN_DEVICE_KIND, make_production_mesh, peaks
from repro.steps import make_step

# per-arch strategy: 2-D weight sharding where TP-only cannot fit HBM
TRAIN_STRATEGY = {
    "nemotron-4-340b": "fsdp_tp",
}
# perf-config overrides installed by the §Perf hillclimbs (see EXPERIMENTS.md
# §Perf for the hypothesis->change->measure log).  Keyed by (arch, shape);
# reproduce with tools/perf_iter.py or --perf here.
def _perf_overrides() -> Dict[Any, Dict[str, Any]]:
    import dataclasses as _dc

    from repro.configs.base import get_config as _gc

    gm = _gc("granite-moe-3b-a800m")
    hy = _gc("hymba-1.5b")
    return {
        ("granite-moe-3b-a800m", "train_4k"): {
            "moe": _dc.replace(gm.moe, routing_impl="ep_gather",
                               n_experts_padded=48),
            "attention_impl": "blockwise",  # deploy; probe with blockwise_u
            "attention_partitioning": "seq",
        },
        ("hymba-1.5b", "prefill_32k"): {
            "attention_partitioning": "seq",
            "attention_impl": "blockwise",
            "ssm": _dc.replace(hy.ssm, scan_impl="chunked", chunk=1024),
        },
        ("gemma-2b", "train_4k"): {
            "attention_partitioning": "seq",
        },
    }

# Accounting mode per arch.  "probe": compile the FULL config scanned (the
# compile-succeeds proof + memory analysis), then unrolled L=1/L=2 probes
# whose per-layer deltas extrapolate exact flops/bytes/collectives — XLA's
# cost analysis visits while-loop bodies ONCE, so a scanned module
# undercounts by ~L; unrolling the full stack is exact but compiles for
# minutes-to-hours on the big archs.  "direct": full unroll (xlstm's 12
# heterogeneous layers are unrolled by definition).
ACCOUNTING = {"xlstm-125m": "direct"}


def default_strategy(arch: str, shape_name: str) -> str:
    if SHAPES[shape_name].kind == "train":
        return TRAIN_STRATEGY.get(arch, "tp")
    return "tp"


def _compile_once(cfg, shape, mesh, strategy):
    t0 = time.time()
    bundle = make_step(cfg, mesh, shape, strategy=strategy)
    with use_mesh(mesh):
        jf = jit_sharded(bundle.fn, mesh,
                         in_shardings=bundle.in_shardings,
                         out_shardings=bundle.out_shardings,
                         donate_argnames=bundle.donate_argnames)
        lowered = jf.lower(*bundle.input_specs.values())
        compiled = lowered.compile()
    t = time.time() - t0
    cost = cost_analysis_dict(compiled)
    return {
        "compile_s": t,
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": collective_stats(compiled.as_text()),
        "mem": memory_stats_dict(compiled.memory_analysis()),
    }


def _extrapolate(base: Dict, per_layer: Dict, n_extra: int) -> Dict[str, Any]:
    """base (L=1 probe) + n_extra * per-layer delta, per metric."""
    out = {"flops": base["flops"] + n_extra * per_layer["flops"],
           "bytes": base["bytes"] + n_extra * per_layer["bytes"]}
    operand, wire, counts = {}, {}, {}
    keys = set(base["coll"].operand_bytes) | set(per_layer["coll_operand"])
    for k in keys:
        operand[k] = int(base["coll"].operand_bytes.get(k, 0)
                         + n_extra * per_layer["coll_operand"].get(k, 0))
        wire[k] = int(base["coll"].wire_bytes.get(k, 0)
                      + n_extra * per_layer["coll_wire"].get(k, 0))
        counts[k] = int(base["coll"].counts.get(k, 0)
                        + n_extra * per_layer["coll_counts"].get(k, 0))
    out["collectives"] = {"counts": counts, "operand_bytes": operand,
                          "wire_bytes": wire,
                          "total_operand": sum(operand.values()),
                          "total_wire": sum(wire.values())}
    return out


def _layer_delta(p1: Dict, p2: Dict) -> Dict[str, Any]:
    d = {"flops": max(p2["flops"] - p1["flops"], 0.0),
         "bytes": max(p2["bytes"] - p1["bytes"], 0.0),
         "coll_operand": {}, "coll_wire": {}, "coll_counts": {}}
    keys = set(p1["coll"].operand_bytes) | set(p2["coll"].operand_bytes)
    for k in keys:
        d["coll_operand"][k] = max(p2["coll"].operand_bytes.get(k, 0)
                                   - p1["coll"].operand_bytes.get(k, 0), 0)
        d["coll_wire"][k] = max(p2["coll"].wire_bytes.get(k, 0)
                                - p1["coll"].wire_bytes.get(k, 0), 0)
        d["coll_counts"][k] = max(p2["coll"].counts.get(k, 0)
                                  - p1["coll"].counts.get(k, 0), 0)
    return d


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             strategy: Optional[str] = None, overrides: Optional[Dict] = None,
             verbose: bool = True, mode: Optional[str] = None) -> Dict[str, Any]:
    overrides = dict(overrides or {})
    cfg = get_config(arch, **overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    strategy = strategy or default_strategy(arch, shape_name)
    mode = mode or ACCOUNTING.get(arch, "probe")

    # 1) the production compile: FULL config exactly as deployed
    full = _compile_once(cfg, shape, mesh, strategy)

    # 2) accounting
    import dataclasses as _dc

    if mode == "direct":
        acc_cfg = _dc.replace(cfg, layer_impl="unroll") \
            if cfg.layer_impl != "unroll" else cfg
        direct = _compile_once(acc_cfg, shape, mesh, strategy) \
            if cfg.layer_impl != "unroll" else full
        acct = {"flops": direct["flops"], "bytes": direct["bytes"],
                "collectives": direct["coll"].to_dict()}
        probe_info = {"mode": "direct"}
    else:
        p1 = _compile_once(_dc.replace(cfg, layer_impl="unroll", n_layers=1),
                           shape, mesh, strategy)
        p2 = _compile_once(_dc.replace(cfg, layer_impl="unroll", n_layers=2),
                           shape, mesh, strategy)
        delta = _layer_delta(p1, p2)
        acct = _extrapolate(p1, delta, cfg.n_layers - 1)
        # (encdec note: probes replace only n_layers; the unrolled encoder
        #  stack stays full-size inside both probes, so its cost is exact.)
        probe_info = {"mode": "probe", "probe_flops": [p1["flops"], p2["flops"]],
                      "layer_flops": delta["flops"],
                      "probe_compile_s": [round(p1["compile_s"], 2),
                                          round(p2["compile_s"], 2)]}

    coll = acct["collectives"] if isinstance(acct["collectives"], dict) \
        else acct["collectives"]

    class _C:  # adapt dict back into the roofline interface
        total_operand = coll["total_operand"]
        total_wire = coll["total_wire"]

    terms = roofline_terms(acct["flops"], acct["bytes"], _C)
    n_chips = mesh.devices.size
    mf = model_flops(cfg, shape)
    mem = full["mem"]
    record = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "strategy": strategy, "kind": shape.kind,
        "n_chips": n_chips,
        "compile_s": round(full["compile_s"], 2),
        "accounting": probe_info,
        "hlo_flops_per_dev": acct["flops"],
        "hlo_bytes_per_dev": acct["bytes"],
        "scanned_flops_per_dev": full["flops"],
        "collectives": coll,
        "collectives_scanned": full["coll"].to_dict(),
        "memory": mem,
        "roofline": terms,
        "model_flops_global": mf,
        "model_flops_per_dev": mf / n_chips,
        "useful_flops_ratio": (mf / n_chips / acct["flops"])
        if acct["flops"] else None,
        "hbm_fit": (mem.get("peak_bytes_per_device", 0)
                    <= peaks(DRYRUN_DEVICE_KIND)["hbm_bytes"])
        if mem else None,
        "n_params": cfg.n_params(),
        "n_active_params": cfg.n_active_params(),
    }
    if verbose:
        print(f"[dryrun] {arch:24s} {shape_name:12s} "
              f"{record['mesh']:8s} {strategy:8s} "
              f"compile={full['compile_s']:6.1f}s "
              f"flops/dev={acct['flops']:.3e} bytes/dev={acct['bytes']:.3e} "
              f"coll={coll['total_operand']:.3e}B "
              f"peakmem={mem.get('peak_bytes_per_device', 0)/2**30:.2f}GiB "
              f"dominant={terms['dominant']} "
              f"useful={record['useful_flops_ratio'] or 0:.2f}")
        print(f"  memory_analysis: {mem}")
        print(f"  cost_analysis: flops={acct['flops']:.4e} "
              f"bytes={acct['bytes']:.4e}")
    return record


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", choices=ARCH_IDS)
    p.add_argument("--shape", choices=list(SHAPES))
    p.add_argument("--all", action="store_true", help="every runnable cell")
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--both-meshes", action="store_true")
    p.add_argument("--strategy", choices=["tp", "fsdp_tp"])
    p.add_argument("--out", default="artifacts/dryrun")
    p.add_argument("--skip-existing", action="store_true")
    p.add_argument("--perf", action="store_true",
                   help="apply the §Perf hillclimb overrides where defined")
    args = p.parse_args()
    perf_map = _perf_overrides() if args.perf else {}

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    todo = []
    if args.all:
        for arch, shape_name, status in cells():
            todo.append((arch, shape_name))
    else:
        if not (args.arch and args.shape):
            p.error("--arch and --shape (or --all) required")
        todo.append((args.arch, args.shape))

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for multi in meshes:
        mesh_tag = "multi" if multi else "single"
        os.makedirs(os.path.join(args.out, mesh_tag), exist_ok=True)
        for arch, shape_name in todo:
            path = os.path.join(args.out, mesh_tag,
                                f"{arch}__{shape_name}.json")
            if args.skip_existing and os.path.exists(path):
                print(f"[dryrun] skip existing {path}")
                continue
            try:
                rec = run_cell(arch, shape_name, multi_pod=multi,
                               strategy=args.strategy,
                               overrides=perf_map.get((arch, shape_name)))
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
            except Exception as e:
                traceback.print_exc()
                failures.append((mesh_tag, arch, shape_name, f"{type(e).__name__}: {e}"))
    if failures:
        print("\nFAILURES:")
        for f_ in failures:
            print(" ", f_)
        raise SystemExit(1)
    print("\nALL DRY-RUN CELLS OK")


if __name__ == "__main__":
    main()
