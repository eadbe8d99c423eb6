"""End-to-end driver: REAL training dispatched through the Bridge Operator.

A BridgeJob whose payload is a genuine repro training loop (jaxlocal
backend): the operator creates the controller pod, the pod submits the job
over the REST API, training runs with framework checkpointing, loss history
and checkpoints land in the object store, and the CR status mirrors it all.

Default: a reduced gemma config for a few hundred steps (CPU-friendly).
--full trains the real xlstm-125m (~125M params) — the same command a
production pod would run; on this 1-core container budget ~hours.

  PYTHONPATH=src python examples/train_end_to_end.py [--steps 300] [--full]
"""
import argparse
import json
import time

from repro.core import BridgeEnvironment, DONE


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=300)
    p.add_argument("--arch", default="gemma-2b")
    p.add_argument("--full", action="store_true",
                   help="train the real xlstm-125m config (slow on CPU)")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--seq", type=int, default=32)
    args = p.parse_args()

    payload = {
        "arch": "xlstm-125m" if args.full else args.arch,
        "steps": args.steps, "batch": args.batch, "seq": args.seq,
        "checkpoint_every": max(args.steps // 10, 1),
        "workdir": "ckpts:runs/e2e", "lr": 1e-2,
    }
    if args.full:
        payload["preset"] = "published"  # xlstm-125m at its published widths

    with BridgeEnvironment(default_duration=0.05) as env:
        spec = env.make_spec("jaxlocal", script=json.dumps(payload),
                             updateinterval=0.2,
                             jobproperties={"OutputFileName": "train.out"})
        env.submit("e2e-train", spec)
        print(f"bridged training submitted ({payload['steps']} steps)...")
        t0 = time.time()
        while True:
            job = env.registry.get("e2e-train")
            if job.status.terminal():
                break
            time.sleep(0.5)
        print(f"state={job.status.state} after {time.time()-t0:.1f}s")
        assert job.status.state == DONE, job.status.message

        hist_key = [k for k in env.s3.list("ckpts", "runs/e2e/")
                    if "history" in k][0]
        hist = json.loads(env.s3.get("ckpts", hist_key))
        n = len(hist)
        print(f"loss curve ({n} steps): "
              f"{hist[0]:.3f} -> {hist[n//2]:.3f} -> {hist[-1]:.3f}")
        ckpts = [k for k in env.s3.list("ckpts", "runs/e2e/") if "MANIFEST" in k]
        print(f"checkpoints in object store: {len(ckpts)}")
        assert hist[-1] < hist[0], "training must reduce loss"
        print("end-to-end bridged training complete")


if __name__ == "__main__":
    main()
