#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest offered rate the service
keeps up with, by a staircase of rates on the chip.

    python3 bench/sweep.py --workload phi3-router-x4 --seed 7 \\
        --rates 0.6,0.7,0.8 --step-seconds 40

One process, one set-up and warm-up of the cell's service, then one open
loop of the cell's mix whose rate steps up every ``--step-seconds``, each
step a window of the mix at that rate (its own sizes and gaps, as
``traffic.open_requests`` makes them).  After the last answer, per step:
the offered rate; the completion rate, answers that came in the step's
interval shifted by the lowest step's median latency, over its length; the
p50 and p90 latency of the step's requests (from due to answer); and the
requests each replica took.  A step keeps up when its completion rate is
at least ``KEEP_UP`` of its offered rate and its p90 is under
``P90_GROWTH`` times the lowest step's.  The knee is the highest rate
whose step keeps up (a step of a few tens of requests counts its
completions coarsely, so a low step may miss by one answer).  One JSON line
per step, then a summary line.  Nothing here is checked against the reference: the cell's own
runs do that.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Optional  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import cell as C, manifest, traffic, work  # noqa: E402
from bench.run import device_or_exit, enable_compile_cache  # noqa: E402

KEEP_UP = 0.95     # completion rate over offered rate
P90_GROWTH = 2.0   # p90 over the lowest step's p90


def staircase(mix: dict, rates, step_s: float, seed: int, vocab: int):
    """The requests of every step, due one step after another."""
    steps = []
    for k, rate in enumerate(rates):
        reqs = traffic.open_requests(dict(mix, rate_rps=rate), step_s,
                                     seed + k, vocab)
        for r in reqs:
            r.index += 100_000 * k
            r.due += k * step_s
        steps.append(reqs)
    return steps


def judge(rates, steps, outcomes, t0: float, step_s: float,
          took) -> list:
    by_index = {o.request.index: o for o in outcomes}
    rows = []
    for k, (rate, reqs) in enumerate(zip(rates, steps)):
        outs = [by_index[r.index] for r in reqs]
        lat = [o.done - t0 - o.request.due for o in outs]
        rows.append({"rate_rps": rate, "requests": len(outs),
                     "failed": sum(not o.ok for o in outs),
                     "offered_rps": len(outs) / step_s,
                     "p50_ms": 1e3 * work.percentile(lat, 50),
                     "p90_ms": 1e3 * work.percentile(lat, 90)})
    shift = rows[0]["p50_ms"] / 1e3
    for k, row in enumerate(rows):
        lo, hi = t0 + k * step_s + shift, t0 + (k + 1) * step_s + shift
        done = sum(lo <= o.done < hi for o in outcomes if o.ok)
        row["completed_rps"] = done / step_s
        row["keeps_up"] = (
            not row["failed"]
            and row["completed_rps"] >= KEEP_UP * row["offered_rps"]
            and row["p90_ms"] < P90_GROWTH * rows[0]["p90_ms"])
    rows[-1]["replica_requests"] = took
    return rows


def knee(rows) -> Optional[float]:
    """The highest rate whose step keeps up; None if none does."""
    return max((row["rate_rps"] for row in rows if row["keeps_up"]),
               default=None)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--rates", required=True,
                   help="comma-separated rates (requests/s), rising")
    p.add_argument("--step-seconds", type=float, default=40.0)
    args = p.parse_args()
    cell = manifest.cell(args.workload)
    if cell.traffic["loop"] != "open":
        sys.exit(f"{args.workload}: a sweep needs an open-loop mix")
    device_or_exit(cell.chips)
    enable_compile_cache()
    rates = [float(r) for r in args.rates.split(",")]
    vocab = int(cell.config["vocab_size"])
    steps = staircase(cell.traffic, rates, args.step_seconds, args.seed,
                      vocab)
    with C.warmed_service(cell, args.seed) as (svc, _):
        C.log(f"set-up {time.time() - T_START:.3f}s; sweeping {rates}")
        before = svc.requests()
        t0 = time.perf_counter()
        outcomes = traffic.run_open(svc.send, [r for s in steps for r in s],
                                    t0, max_threads=256)
        took = [n - before[j] for j, n in svc.requests().items()]
    rows = judge(rates, steps, outcomes, t0, args.step_seconds, took)
    for row in rows:
        print(json.dumps(row), flush=True)
    k = knee(rows)
    print(json.dumps({"workload": args.workload, "knee_rps": k,
                      "rate_rps_at_0.8": None if k is None
                      else round(0.8 * k, 3)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
