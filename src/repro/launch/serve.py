"""Serving driver: batched requests through the continuous-batching engine.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --requests 12 \
      --max-batch 4 --max-new 8

Reports throughput (tokens/sec, requests/sec) and per-request latency
percentiles (submit -> finish, so queueing inside the engine counts).
``--json`` emits the summary as one machine-readable JSON object instead of
prose — the shape benchmark tooling can diff.
"""
import argparse
import json
import time

import numpy as np

from repro.configs.base import ARCH_IDS, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import ServingEngine
from repro.steps import init_model


def _pct(sorted_vals, p):
    if not sorted_vals:
        return None
    return sorted_vals[min(len(sorted_vals) - 1, int(len(sorted_vals) * p))]


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="gemma-2b", choices=ARCH_IDS)
    p.add_argument("--requests", type=int, default=12)
    p.add_argument("--max-batch", type=int, default=4)
    p.add_argument("--max-new", type=int, default=8)
    p.add_argument("--prefill-len", type=int, default=16)
    p.add_argument("--max-len", type=int, default=64)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--json", action="store_true",
                   help="emit the summary as one JSON object")
    args = p.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch)
    if cfg.family == "encdec":
        raise SystemExit("serve targets decoder LMs; whisper decode is "
                         "exercised via tests/test_arch_smoke.py")
    _, params = init_model(cfg, seed=args.seed, max_seq=args.max_len)
    eng = ServingEngine(cfg, params, max_batch=args.max_batch,
                        max_len=args.max_len, prefill_len=args.prefill_len)
    rng = np.random.RandomState(args.seed)
    t0 = time.time()
    submit_t = {}
    ids = []
    for _ in range(args.requests):
        rid = eng.submit(list(rng.randint(1, cfg.vocab,
                                          size=args.prefill_len)),
                         max_new_tokens=args.max_new)
        submit_t[rid] = time.time()
        ids.append(rid)
    # pump the engine by hand (instead of run_until_idle) so each request's
    # finish time — and with it the latency distribution — is observable
    finish_t = {}
    pending = set(ids)
    for _ in range(100_000):
        if not pending:
            break
        eng.step()
        now = time.time()
        for rid in list(pending):
            if rid in eng.finished:
                finish_t[rid] = now
                pending.discard(rid)
    dt = time.time() - t0
    results = {rid: r.generated for rid, r in eng.finished.items()}

    lat = sorted(finish_t[rid] - submit_t[rid] for rid in ids
                 if rid in finish_t)
    toks = eng.stats["tokens"]
    summary = {
        "arch": args.arch, "requests": args.requests,
        "completed": len(finish_t), "tokens": toks,
        "wall_s": round(dt, 4),
        "tokens_per_s": round(toks / dt, 2) if dt > 0 else None,
        "requests_per_s": round(len(finish_t) / dt, 2) if dt > 0 else None,
        "latency_p50_s": _pct(lat, 0.50),
        "latency_p90_s": _pct(lat, 0.90),
        "latency_p99_s": _pct(lat, 0.99),
        "decode_ticks": eng.stats["decode_ticks"],
        "prefills": eng.stats["prefills"],
    }
    if args.json:
        print(json.dumps(summary))
        return
    for rid in ids[:4]:
        print(f"[serve] req {rid}: {results[rid]}")
    print(f"[serve] {summary['completed']}/{args.requests} requests, "
          f"{toks} tokens in {dt:.2f}s ({summary['tokens_per_s']} tok/s, "
          f"{summary['requests_per_s']} req/s)")
    print(f"[serve] latency p50={summary['latency_p50_s']:.4f}s "
          f"p90={summary['latency_p90_s']:.4f}s "
          f"p99={summary['latency_p99_s']:.4f}s "
          f"({summary['decode_ticks']} ticks, "
          f"{summary['prefills']} prefills)")


if __name__ == "__main__":
    main()
