"""The system under test: ``BridgeService`` replicas, one per chip, behind
``ServiceHandle.router()``, started and stopped.

This is the one module of the benchmark that imports the program.  The
start/stop and ``engine.json`` reading follow ``chip_smoke.py``.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import time
from typing import Any, Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

READY_TIMEOUT_S = 900.0
REQUEST_TIMEOUT_S = 300.0
STOP_TIMEOUT_S = 120.0


def job_script(cfg: dict, seed: int) -> dict:
    """The replica's job script: the program's model selection and engine
    sizes from the configuration file, and the seed its weights come from."""
    return {"mode": "serve", **cfg["program"], **cfg["serving"], "seed": seed}


class Service:
    """A ``BridgeEnvironment`` with ``replicas`` serving replicas, each on a
    device of its own (``jaxlocal``'s device pool); use as a context
    manager.  ``send`` is the client's request path."""

    def __init__(self, script: dict, replicas: int = 1):
        self.script = script
        self.replicas = replicas
        self.env = None
        self.handle = None
        self.router = None
        self.engines: List[Dict[str, Any]] = []  # engine.json, by replica

    def __enter__(self) -> "Service":
        from repro.core import BridgeEnvironment, HealthProbeSpec

        self.env = BridgeEnvironment(slots=max(2, self.replicas)).start()
        try:
            spec = self.env.make_service_spec(
                "jaxlocal", replicas=self.replicas,
                script=json.dumps(self.script), updateinterval=0.5,
                # a replica makes its weights and compiles before it turns
                # ready
                health=HealthProbeSpec(failure_threshold=5,
                                       startup_failure_threshold=int(
                                           READY_TIMEOUT_S / 0.5)))
            self.handle = self.env.bridge.submit_service("bench", spec)
            self.handle.wait_ready(timeout=READY_TIMEOUT_S)
            jobs = self.env.clusters["jaxlocal"].jobs
            self.engines = [
                json.loads(jobs[e["job_id"]].outputs["engine.json"])
                for e in sorted(self.handle.endpoints(),
                                key=lambda e: e["replica"])]
            devices = [e["device"] for e in self.engines]
            if len(set(devices)) != len(devices):
                raise RuntimeError(f"replicas share a device: {devices}")
            self.router = self.handle.router(request_timeout=REQUEST_TIMEOUT_S)
        except BaseException:
            self.stop()
            raise
        return self

    def send(self, req) -> List[int]:
        out = self.router.request({"prompt": req.prompt,
                                   "max_new_tokens": req.max_new})
        return out["tokens"]

    def requests(self) -> Dict[str, int]:
        """Requests the router has sent to each replica so far, by job id
        (every ready replica, those not yet sent any at 0)."""
        stats = self.router.stats()
        return {e["job_id"]: stats.get(e["job_id"], {}).get("requests", 0)
                for e in self.handle.endpoints()}

    def stop(self) -> None:
        """Kill the service and wait until every replica's payload has
        returned, so that its weights and cache can be freed."""
        if self.env is None:
            return
        from repro.core.backends.base import TERMINAL

        try:
            if self.handle is not None:
                self.handle.cancel()
                self.handle.wait(timeout=STOP_TIMEOUT_S)
            jobs = self.env.clusters["jaxlocal"].jobs
            deadline = time.time() + STOP_TIMEOUT_S
            while (any(j.state not in TERMINAL for j in list(jobs.values()))
                   and time.time() < deadline):
                time.sleep(0.05)
        finally:
            self.env.stop()
            self.env = self.handle = self.router = None
            gc.collect()

    def __exit__(self, *exc) -> None:
        self.stop()
