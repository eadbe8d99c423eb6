#!/usr/bin/env python3
"""The readings a cell's correctness limit is set from, on the chip.

    python3 bench/control.py --workload phi3-code --seconds 20 --seeds 1,2,3
    python3 bench/control.py --workload phi3-code --seconds 20 --seeds 1,2,3 \\
        --fault stale_cache

For each seed, in one process: the cell's service with that seed's weights,
a short window of the cell's own traffic, then, over the sample a run
compares, the cell's own check (``bench/cell.py: check`` and ``passed``)
twice: on the served tokens (the program) and on the tokens that the int8
control puts first (the control).  With ``--fault`` the program runs with
that fault of ``bench/faults.py`` planted, and the control is not read.
One JSON line per seed, then a summary line: ``{"lower": max program
reading, "upper": min control reading}``.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import cell as C, manifest  # noqa: E402
from bench.faults import FAULTS  # noqa: E402
from bench.run import device_or_exit, enable_compile_cache  # noqa: E402


def readings(cell: manifest.Cell, seed: int, seconds: float,
             t_start: float, control: bool) -> dict:
    """The program's and (with ``control``) the control's checks of one
    seed."""
    w = C.serve_window(cell, seed, seconds, False, t_start)
    g = C.compared_gaps(cell, seed, w.outcomes, control=control)
    row = {"seed": seed, "requests": len(w.outcomes)}
    for side in ("served", "control") if control else ("served",):
        checks = C.check(cell.config, w.outcomes, g[side])
        name = "program" if side == "served" else "control"
        row[name] = checks["mean_logit_gap"]["value"]
        row[name + "_correct"] = C.passed(checks)
        row[name + "_widest"] = float(g[side].max())
        row[name + "_exact"] = float((g[side] == 0).mean())
        row[name + "_checks"] = checks
    return row


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seeds", required=True,
                   help="comma-separated seeds, one service each")
    p.add_argument("--fault", choices=sorted(FAULTS))
    args = p.parse_args()
    cell = manifest.cell(args.workload)
    device_or_exit(cell.chips)
    enable_compile_cache()
    if args.fault:
        setattr(*FAULTS[args.fault]())
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        rows.append(readings(cell, seed, args.seconds, time.time(),
                             control=not args.fault))
        print(json.dumps(rows[-1]), flush=True)
    summary = {"workload": args.workload, "fault": args.fault,
               "lower": max(r["program"] for r in rows)}
    if not args.fault:
        summary["upper"] = min(r["control"] for r in rows)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
