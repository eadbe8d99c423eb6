"""jaxlocal: the backend whose jobs are REAL distributed JAX training runs.

The paper treats remote jobs as opaque scripts; this backend closes the loop
by making the job a genuine ``repro`` training loop with framework
checkpointing, so bridge-level restart-resume (config-map job id) composes
with step-level checkpoint-resume (CheckpointManager) — the two-level fault
tolerance story of DESIGN.md §6.

Job script = JSON::

    {"arch": "gemma-2b", "steps": 200, "batch": 8, "seq": 64,
     "checkpoint_every": 20, "workdir": "ckpts:runs/demo", "lr": 3e-3,
     "task": "affine", "crash_at_step": 0}

``preset`` picks the model: ``"smoke"`` (the default) builds the arch's
reduced same-family config, ``"published"`` its published widths; either
way ``config_overrides`` is applied on top (e.g. ``{"n_layers": 4}`` to cut
depth, ``{"attention_impl": "pallas"}`` for the Mosaic kernels).

``crash_at_step`` > 0 makes the job fail at that step (fault-injection for
tests): a resubmitted job with the same workdir resumes from the last
checkpoint rather than step 0.

Each cluster owns a :class:`DevicePool`: a job runs on the least-used local
device, so replicas of a service spread one per chip on a multi-chip host.
With one device every job shares it.

The REST dialect is slurmrestd (this is "our SLURM": same API, real work),
so the generic controller drives it with the plain SlurmAdapter.
"""
from __future__ import annotations

import contextlib
import json
import threading
import time
from typing import Any, Dict, Iterator, Optional

import numpy as np

from repro.core.backends import base as B
from repro.core.backends.slurm import SlurmAdapter, make_server as make_slurm_server
from repro.core.objectstore import ObjectStore
from repro.core.rest import FaultProfile, RestServer


class JaxLocalAdapter(SlurmAdapter):
    image = "jaxpod"
    # same dialect as slurmrestd, so the same capability set (incl. arrays
    # and squeue-style BATCH_STATUS — the batch route comes with the server)
    capabilities = SlurmAdapter.capabilities


PRESETS = ("smoke", "published")


def job_config(spec: Dict[str, Any]):
    """The ``ModelConfig`` a job script asks for (see the module docstring)."""
    from repro.configs.base import get_config, get_smoke_config

    preset = spec.get("preset", "smoke")
    if preset not in PRESETS:
        raise ValueError(f"unknown preset {preset!r}; known: {PRESETS}")
    make = get_smoke_config if preset == "smoke" else get_config
    return make(spec.get("arch", "gemma-2b"),
                **dict(spec.get("config_overrides", {})))


class DevicePool:
    """Hands each job the least-used local device for its lifetime.

    Devices are listed lazily, at the first lease, so building a cluster
    never touches JAX's backend."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._devices: Optional[list] = None
        self.leases: Dict[str, Any] = {}  # job id -> device

    @contextlib.contextmanager
    def lease(self, job_id: str) -> Iterator[Any]:
        import jax

        with self._lock:
            if self._devices is None:
                self._devices = jax.local_devices()
            held = list(self.leases.values())
            dev = min(self._devices, key=held.count)
            self.leases[job_id] = dev
        try:
            with jax.default_device(dev):
                yield dev
        finally:
            with self._lock:
                self.leases.pop(job_id, None)


def make_train_step(cfg, opt_cfg):
    """The jitted train step of ``train_job``: (params, opt_state, batch) ->
    (params, opt_state, metrics), updating params and optimizer state in
    place (both are donated)."""
    import jax

    from repro.models.transformer import forward_train
    from repro.optim import adamw_update

    def step_fn(params, opt_state, batch):
        def loss_fn(p):
            return forward_train(p, cfg, batch, remat=False)

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        new_p, new_o, om = adamw_update(grads, opt_state, params, opt_cfg)
        return new_p, new_o, dict(metrics, **om)

    return jax.jit(step_fn, donate_argnums=(0, 1))


def train_job(spec: Dict[str, Any], store: ObjectStore,
              cancel: Optional[threading.Event] = None,
              log: Optional[list] = None) -> Dict[str, Any]:
    """Run (or resume) one training job.  Returns final metrics.

    Importable directly (examples/tests) or via the cluster payload below.
    """
    import jax.numpy as jnp

    from repro.checkpoint.manager import CheckpointManager
    from repro.data.pipeline import DataConfig, SyntheticDataset
    from repro.optim import AdamWConfig, adamw_init
    from repro.steps import init_model

    steps = int(spec.get("steps", 50))
    batch_sz = int(spec.get("batch", 4))
    seq = int(spec.get("seq", 32))
    ckpt_every = int(spec.get("checkpoint_every", 0))
    lr = float(spec.get("lr", 1e-3))
    crash_at = int(spec.get("crash_at_step", 0))

    cfg = job_config(spec)
    ds = SyntheticDataset(DataConfig(vocab=cfg.vocab, seq_len=seq,
                                     global_batch=batch_sz,
                                     task=spec.get("task", "affine"),
                                     seed=int(spec.get("seed", 0))))
    opt_cfg = AdamWConfig(lr=lr, warmup_steps=min(20, steps // 4 + 1),
                          total_steps=steps)

    _, params = init_model(cfg, seed=int(spec.get("seed", 0)), max_seq=seq)
    opt_state = adamw_init(params)

    mgr = None
    start_step = 0
    if ckpt_every and spec.get("workdir"):
        bucket, prefix = ObjectStore.parse_ref(spec["workdir"])
        mgr = CheckpointManager(store, bucket, prefix,
                                keep=int(spec.get("keep_checkpoints", 3)))
        resumed = mgr.restore_latest({"params": params, "opt": opt_state})
        if resumed is not None:
            start_step, tree, _extra = resumed
            params, opt_state = tree["params"], tree["opt"]

    step_fn = make_train_step(cfg, opt_cfg)
    history = []
    for step in range(start_step, steps):
        if cancel is not None and cancel.is_set():
            if mgr:
                mgr.wait()
            return {"state": "cancelled", "step": step, "history": history}
        if crash_at and step == crash_at and step > start_step:
            # simulated node failure mid-run (AFTER making some progress)
            raise RuntimeError(f"injected crash at step {step}")
        batch = {k: jnp.asarray(v) for k, v in ds.batch(step).items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        loss = float(metrics["loss"])
        history.append(loss)
        if log is not None:
            log.append((step, loss))
        if mgr and ckpt_every and (step + 1) % ckpt_every == 0:
            mgr.save_async(step + 1, {"params": params, "opt": opt_state},
                           extra={"loss": loss})
    if mgr:
        mgr.wait()
        mgr.save(steps, {"params": params, "opt": opt_state},
                 extra={"loss": history[-1] if history else None})
    return {"state": "done", "step": steps, "history": history,
            "final_loss": history[-1] if history else None,
            "start_step": start_step}


def serve_job(spec: Dict[str, Any], job: B.ClusterJob,
              cluster: B.SimulatedCluster) -> int:
    """Serve-mode replica: host a real ``ServingEngine`` behind the cluster's
    ``POST /.../invoke`` route until cancelled.

    The payload thread is the engine pump (continuous batching over the
    shared KV cache); REST worker threads call ``job.handler`` which enqueues
    a request and parks on a condition variable until the pump moves it to
    ``finished``.  A replica killed mid-request raises out of the handler
    (HTTP 500), which the service router treats as a replica fault and
    retries elsewhere — accepted requests are never silently dropped.
    Serve jobs NEVER auto-complete: only a cancel ends them.

    The handler is installed (and the replica turns ready) only once the
    engine's programs are compiled; ``engine.json`` among the job's outputs
    then records the device, the set-up and compile seconds, and which
    programs contain a Mosaic kernel (``tpu_custom_call``).
    """
    import jax

    from repro.serving.engine import ServingEngine
    from repro.steps import init_model

    arch = spec.get("arch", "gemma-2b")
    max_len = int(spec.get("max_len", 64))
    prefill_len = int(spec.get("prefill_len", 16))
    cfg = job_config(spec)
    t0 = time.perf_counter()
    _, params = init_model(cfg, seed=int(spec.get("seed", 0)),
                           max_seq=max_len)
    params = jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    eng = ServingEngine(cfg, params,
                        max_batch=int(spec.get("max_batch", 4)),
                        max_len=max_len, prefill_len=prefill_len)
    device = jax.tree_util.tree_leaves(params)[0].devices().pop()
    job.outputs["engine.json"] = json.dumps({
        "model": cfg.name, "device": device.id,
        "device_kind": device.device_kind, "init_s": init_s,
        "compile_s": eng.compile_s,
        "mosaic": {name: "tpu_custom_call" in prog.as_text()
                   for name, prog in eng.programs.items()},
    }).encode()
    cond = threading.Condition()
    results: Dict[int, Any] = {}

    def handler(body: Any) -> Dict[str, Any]:
        body = body or {}
        prompt = [int(t) for t in body.get("prompt", [])]
        with cond:
            if job._cancel.is_set():
                raise RuntimeError("replica shutting down")
            rid = eng.submit(prompt,
                             max_new_tokens=int(body.get("max_new_tokens", 8)),
                             eos_id=body.get("eos_id"))
            cond.notify_all()
            while rid not in results:
                if job._cancel.is_set():
                    raise RuntimeError("replica cancelled mid-request")
                cond.wait(timeout=0.05)
            req = results.pop(rid)
        return {"tokens": req.generated, "served_by": job.id, "arch": arch,
                "device": device.id}

    job.handler = handler
    try:
        while not job._cancel.is_set():
            with cond:
                busy = (bool(eng.pending)
                        or any(s is not None for s in eng.slots))
                if not busy:
                    cond.wait(timeout=0.02)
                    continue
                eng.step()
                if eng.finished:
                    results.update(eng.finished)
                    eng.finished.clear()
                    cond.notify_all()
        return -1
    finally:
        job.handler = None
        with cond:
            cond.notify_all()  # release parked handlers to see the cancel


def jax_train_payload(store: ObjectStore, devices: DevicePool) -> B.Payload:
    def run(job: B.ClusterJob, cluster: B.SimulatedCluster) -> int:
        spec = json.loads(job.script)
        with devices.lease(job.id):
            if spec.get("mode") == "serve":
                return serve_job(spec, job, cluster)
            result = train_job(spec, store, cancel=job._cancel)
        job.outputs[job.properties.get("OutputFileName", "train.out")] = (
            json.dumps({k: v for k, v in result.items() if k != "history"})
            .encode())
        if result["state"] == "cancelled":
            return -1
        # publish the loss curve to S3 (output upload per paper §4)
        if spec.get("workdir"):
            bucket, prefix = ObjectStore.parse_ref(spec["workdir"])
            store.put(bucket, f"{prefix}/history_{job.id}.json",
                      json.dumps(result["history"]).encode())
        return 0

    return run


def make_jaxlocal_cluster(store: ObjectStore, name: str = "jaxlocal",
                          slots: int = 2,
                          start_numbering: int = 7000) -> B.SimulatedCluster:
    # start_numbering is per-cluster so a second jaxlocal resource (serving
    # across managers) hands out non-overlapping job ids
    devices = DevicePool()
    cluster = B.SimulatedCluster(name=name, slots=slots,
                                 payload=jax_train_payload(store, devices),
                                 start_numbering=start_numbering)
    cluster.devices = devices
    return cluster


def make_server(cluster: B.SimulatedCluster, token: str = "",
                fault: FaultProfile = None) -> RestServer:
    return make_slurm_server(cluster, token=token, fault=fault)
