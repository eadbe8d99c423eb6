"""Training driver: the same pjit train step the dry-run lowers, executed
on the locally available devices, with framework checkpointing.

Local smoke scale (CPU container):
  PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --smoke \
      --steps 50 --batch 4 --seq 64 --ckpt-dir /tmp/run1 --ckpt-every 10

Production scale: the identical code path with --data/--model sized to the
pod (the dry-run proves lowering for 16x16 / 2x16x16).  XLA's latency-hiding
scheduler overlaps the TP collectives with compute
(--xla_tpu_enable_latency_hiding_scheduler on real TPU; documented here
because this container has no TPU to pass it to).
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.compat import jit_sharded, use_mesh
from repro.configs.base import ARCH_IDS, ShapeConfig, get_config, get_smoke_config
from repro.core.objectstore import ObjectStore
from repro.data import DataConfig, SyntheticDataset, with_frontend_stubs
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models.params import init_params
from repro.models.transformer import model_defs
from repro.optim import AdamWConfig, adamw_init
from repro.steps import make_train_step


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="gemma-2b", choices=ARCH_IDS)
    p.add_argument("--smoke", action="store_true", help="reduced config")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--lr", type=float, default=1e-2)
    p.add_argument("--data", type=int, default=1, help="data mesh dim")
    p.add_argument("--model", type=int, default=1, help="model mesh dim")
    p.add_argument("--strategy", default="tp", choices=["tp", "fsdp_tp"])
    p.add_argument("--no-zero1", action="store_true")
    p.add_argument("--no-remat", action="store_true")
    p.add_argument("--ckpt-dir", default="")
    p.add_argument("--ckpt-every", type=int, default=0)
    p.add_argument("--log-every", type=int, default=10)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()
    enable_compile_cache()

    cfg = (get_smoke_config if args.smoke else get_config)(args.arch)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    mesh = make_local_mesh(args.data, args.model)
    bundle = make_train_step(cfg, mesh, shape,
                             opt_cfg=AdamWConfig(lr=args.lr,
                                                 total_steps=args.steps,
                                                 warmup_steps=max(args.steps // 10, 1)),
                             strategy=args.strategy,
                             zero1=not args.no_zero1,
                             remat=not args.no_remat)
    ds = SyntheticDataset(DataConfig(cfg.vocab, args.seq, args.batch,
                                     seed=args.seed))
    defs = model_defs(cfg, max_seq=args.seq)
    params = init_params(jax.random.PRNGKey(args.seed), defs)
    opt_state = adamw_init(params)

    mgr, start = None, 0
    if args.ckpt_dir and args.ckpt_every:
        mgr = CheckpointManager(ObjectStore(root=args.ckpt_dir), "ckpt", "run")
        resumed = mgr.restore_latest({"params": params, "opt": opt_state})
        if resumed:
            start, tree, _ = resumed
            params, opt_state = tree["params"], tree["opt"]
            print(f"[train] resumed from step {start}")

    with use_mesh(mesh):
        step_fn = jit_sharded(bundle.fn, mesh,
                              in_shardings=bundle.in_shardings,
                              out_shardings=bundle.out_shardings,
                              donate_argnames=bundle.donate_argnames)
        t0 = time.time()
        for step in range(start, args.steps):
            batch = {k: jnp.asarray(v) for k, v in
                     with_frontend_stubs(ds.batch(step), cfg,
                                         seed=args.seed).items()}
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            if (step + 1) % args.log_every == 0 or step == start:
                dt = (time.time() - t0) / max(step - start + 1, 1)
                print(f"[train] step {step + 1:5d} "
                      f"loss={float(metrics['loss']):.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"lr={float(metrics['lr']):.2e} {dt*1e3:.0f} ms/step")
            if mgr and (step + 1) % args.ckpt_every == 0:
                mgr.save_async(step + 1, {"params": params, "opt": opt_state},
                               extra={"loss": float(metrics["loss"])})
    if mgr:
        mgr.wait()
        mgr.save(args.steps, {"params": params, "opt": opt_state})
        print(f"[train] checkpointed at {args.ckpt_dir}")
    print("[train] done")


if __name__ == "__main__":
    main()
