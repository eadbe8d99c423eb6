"""Request routing for BridgeService — the data-plane half of serving.

``ServiceHandle`` is the kubectl-style control surface over one BridgeService
CR (scale / kill / wait-ready, mirroring ``JobHandle``).  ``ServiceEndpoint``
is the request router: it load-balances invocations across the replicas the
service reports READY, re-resolving ``status.endpoints`` from the registry on
every request so that a condemned replica is drained the same tick the
control plane flips its ``ready`` flag.

Routing policy is least-outstanding-requests: among ready replicas, pick the
one with the fewest in-flight invocations (ties broken by total request
count, then replica index).  Adapter connections are cached per
``(resourceURL, image, resourcesecret)`` target, so every endpoint on the
same resource manager shares one ``Channel`` — connection reuse is the
channel memo's job, not the router's.

Delivery contract: a request is retried on another replica when the attempt
fails in a way that indicts the REPLICA (transport error, 404 gone,
503 unready, 5xx crash) — so killing a replica mid-traffic loses no accepted
request.  The failed replica is locally suspended for a short TTL to stop
the router hammering it before the control plane condemns it.  The flip side
is at-least-once execution across replicas on failure: a replica that dies
AFTER executing but before replying will have its request re-executed
elsewhere.  Status codes that indict the REQUEST (4xx other than 404) are
raised to the caller unretried.
"""
from __future__ import annotations

import dataclasses
import json
import threading
import time
import uuid
from collections import deque
from typing import Any, Dict, List, Optional

from repro.core.backends import base as B
from repro.core.resource import (BridgeService, BridgeServiceSpec,
                                 BridgeServiceStatus, ValidationError)
from repro.core.rest import TransportError
from repro.tracing import span


class NoReadyReplicas(RuntimeError):
    """No replica answered within the request budget."""


@dataclasses.dataclass(frozen=True)
class ServiceHandle:
    """A client-side reference to one BridgeService CR."""
    bridge: Any
    name: str
    namespace: str = "default"

    def service(self) -> Optional[BridgeService]:
        return self.bridge.registry.get(self.name, self.namespace)

    def status(self) -> BridgeServiceStatus:
        svc = self.service()
        if svc is None:
            raise KeyError(
                f"BridgeService {self.namespace}/{self.name} not found")
        return svc.status

    def endpoints(self) -> List[dict]:
        """``status.endpoints`` — one dict per replica:
        {replica, slice, resourceURL, image, resourcesecret, job_id, ready}."""
        return [dict(e) for e in self.status().endpoints]

    def ready_replicas(self) -> int:
        return self.status().ready_replicas

    def wait_ready(self, replicas: Optional[int] = None,
                   timeout: float = 30.0) -> BridgeService:
        """Block until at least ``replicas`` (default: spec.replicas) report
        ready, or raise TimeoutError.  A terminal service can never become
        ready and fails fast."""
        deadline = time.time() + timeout
        svc = None
        while time.time() < deadline:
            svc = self.service()
            if svc is not None:
                want = replicas if replicas is not None else svc.spec.replicas
                if svc.status.ready_replicas >= want:
                    return svc
                if svc.status.terminal():
                    raise NoReadyReplicas(
                        f"BridgeService {self.namespace}/{self.name} is "
                        f"terminal ({svc.status.state})")
            time.sleep(0.01)
        raise TimeoutError(
            f"BridgeService {self.namespace}/{self.name} not ready after "
            f"{timeout}s (ready={svc.status.ready_replicas if svc else '?'})")

    def scale(self, replicas: int) -> "ServiceHandle":
        """Resize the service to ``replicas``; the reconciler submits or
        condemns exactly the delta (scale-down drains the highest replica
        indices first)."""
        if replicas < 1:
            raise ValidationError("service replicas must be >= 1")

        def guarded(spec: BridgeServiceSpec) -> BridgeServiceSpec:
            cur = self.service()
            if cur is not None and cur.status.terminal():
                raise ValidationError(
                    f"cannot scale terminal BridgeService "
                    f"{self.namespace}/{self.name} ({cur.status.state})")
            return dataclasses.replace(spec, replicas=replicas)

        self.bridge.registry.update_spec(self.name, guarded, self.namespace)
        return self

    def wait_reconciled(self, timeout: float = 30.0) -> BridgeService:
        return self.bridge.wait_reconciled(self.name, self.namespace,
                                           timeout=timeout)

    def cancel(self) -> None:
        """Kill the service: cancel every replica, settle the CR KILLED."""
        self.bridge.registry.update_spec(
            self.name, lambda s: dataclasses.replace(s, kill=True),
            self.namespace)

    def wait(self, timeout: float = 30.0) -> BridgeService:
        """Block until terminal (only a kill makes a service terminal)."""
        return self.bridge.wait(self.name, self.namespace, timeout=timeout)

    def delete(self) -> None:
        self.bridge.delete(self.name, self.namespace)

    def autoscale_status(self) -> Dict[str, Any]:
        """Mirrored autoscaler state ({} unless ``spec.autoscale`` is set):
        ``{desired, min, max, signals: {outstanding, p99_s, reports},
        last_scale_up, last_scale_down}``."""
        return dict(self.status().autoscale or {})

    def router(self, **kwargs) -> "ServiceEndpoint":
        return ServiceEndpoint(self.bridge, self.name, self.namespace,
                               **kwargs)


class ServiceEndpoint:
    """Load-balancing request router over one BridgeService's replicas."""

    def __init__(self, bridge: Any, name: str, namespace: str = "default",
                 request_timeout: float = 30.0,
                 suspend_ttl: float = 0.5,
                 latency_window: int = 256,
                 report_interval: float = 0.25,
                 report_load: Optional[bool] = None,
                 retired_window: int = 16):
        self.bridge = bridge
        self.name = name
        self.namespace = namespace
        self.request_timeout = request_timeout
        self.suspend_ttl = suspend_ttl
        self._latency_window = latency_window
        self._mu = threading.Lock()
        # adapter per target: all endpoints behind one manager share a Channel
        self._adapters: Dict[tuple, B.ResourceAdapter] = {}
        # job_id -> suspended-until (local short fuse after a failed attempt)
        self._down: Dict[str, float] = {}
        # job_id -> live counters for THIS replica incarnation
        self._stats: Dict[str, Dict[str, Any]] = {}
        # last N replaced incarnations' counters (stats() still reports a
        # recently-dead jid; the ring bound is what stops unbounded growth)
        self._retired: deque = deque(maxlen=retired_window)
        # load reporting (the autoscaler's input): None = only when the
        # service declares spec.autoscale; True/False force it either way
        self._report_load = report_load
        self._report_interval = report_interval
        self._router_id = uuid.uuid4().hex[:8]
        self._next_report = 0.0
        self._last_report_ts = 0.0
        self._last_report_requests = 0

    # -- endpoint resolution ----------------------------------------------

    def _ready_endpoints(self) -> List[dict]:
        svc = self.bridge.registry.get(self.name, self.namespace)
        if svc is None:
            raise KeyError(
                f"BridgeService {self.namespace}/{self.name} not found")
        now = time.time()
        current = {e["job_id"] for e in svc.status.endpoints
                   if e.get("job_id")}
        with self._mu:
            # prune replaced incarnations and stale suspensions so a
            # long-lived router under replica churn stays O(replicas):
            # retired counters move to the ring (in-flight requests still
            # hold the SAME dict, so their decrements keep landing)
            for jid in [j for j in self._stats if j not in current]:
                st = self._stats.pop(jid)
                st["retired_at"] = now
                self._retired.append(st)
            for jid in [j for j, until in self._down.items()
                        if until <= now or j not in current]:
                del self._down[jid]
        eps = []
        for e in svc.status.endpoints:
            if not e.get("ready") or not e.get("job_id"):
                continue
            if self._down.get(e["job_id"], 0.0) > now:
                continue
            eps.append(e)
        self._maybe_report(svc, now)
        return eps

    def _adapter_for(self, ep: dict) -> B.ResourceAdapter:
        key = (ep["resourceURL"], ep["image"], ep["resourcesecret"])
        with self._mu:
            ad = self._adapters.get(key)
        if ad is None:
            ad = self.bridge.connect_adapter(*key)
            with self._mu:
                ad = self._adapters.setdefault(key, ad)
        return ad

    def _entry(self, ep: dict) -> Dict[str, Any]:
        jid = ep["job_id"]
        with self._mu:
            st = self._stats.get(jid)
            if st is None:
                st = self._stats[jid] = {
                    "replica": ep["replica"], "job_id": jid,
                    "requests": 0, "errors": 0, "outstanding": 0,
                    "latencies": deque(maxlen=self._latency_window),
                }
        return st

    # -- load reporting (router -> control plane) --------------------------

    def _maybe_report(self, svc: BridgeService, now: float) -> None:
        """Publish this router's per-replica load snapshot into the service
        config map (key ``loadreport_<router-id>``) at most once per
        ``report_interval``.  The ServiceProtocol merges every router's
        report — staleness-bounded by the TTL carried in the report itself —
        into the autoscale signals; see ``spec.autoscale``.  Off unless the
        service opted into autoscaling (keeps the cm byte-identical for
        plain services) or ``report_load=True`` forced it."""
        if self._report_load is False:
            return
        if self._report_load is None and getattr(
                svc.spec, "autoscale", None) is None:
            return
        if now < self._next_report:
            return
        store = getattr(self.bridge, "statestore", None)
        if store is None:
            return
        with self._mu:
            self._next_report = now + self._report_interval
            replicas: Dict[str, Dict[str, Any]] = {}
            lat_all: List[float] = []
            total_requests = 0
            outstanding = 0
            for jid, st in self._stats.items():
                lat = sorted(st["latencies"])
                replicas[jid] = {
                    "replica": st["replica"],
                    "outstanding": st["outstanding"],
                    "requests": st["requests"],
                    "p50_s": lat[len(lat) // 2] if lat else None,
                    "p99_s": lat[min(len(lat) - 1,
                                     int(len(lat) * 0.99))] if lat else None,
                }
                lat_all.extend(lat)
                total_requests += st["requests"]
                outstanding += st["outstanding"]
            window = now - self._last_report_ts
            rate = ((total_requests - self._last_report_requests) / window
                    if self._last_report_ts and window > 0 else 0.0)
            self._last_report_ts = now
            self._last_report_requests = total_requests
        lat_all.sort()
        report = {
            "router": self._router_id, "ts": now,
            # consumed-by TTL: the control plane drops (and prunes) reports
            # from routers that stopped publishing — a dead client must not
            # freeze the load signal at its last value
            "ttl": max(3 * self._report_interval, 1.0),
            "outstanding": outstanding,
            "rate_rps": round(rate, 3),
            "p50_s": lat_all[len(lat_all) // 2] if lat_all else None,
            "p99_s": lat_all[min(len(lat_all) - 1,
                                 int(len(lat_all) * 0.99))]
                     if lat_all else None,
            "replicas": replicas,
        }
        try:
            cm = store.get(f"{self.namespace}/{self.name}-bridge-cm")
            cm.update({f"loadreport_{self._router_id}": json.dumps(report)})
        except KeyError:
            pass  # no cm yet (service still admitting): report next time

    def _pick(self, eps: List[dict]) -> dict:
        """Least outstanding requests; ties fall to fewest total requests,
        then lowest replica index (deterministic)."""
        def load(ep):
            st = self._entry(ep)
            return (st["outstanding"], st["requests"], ep["replica"])
        return min(eps, key=load)

    # -- the request path --------------------------------------------------

    @staticmethod
    def _replica_fault(exc: Exception) -> bool:
        """True when the failure indicts the replica (retry elsewhere)."""
        if isinstance(exc, TransportError):
            return True
        if isinstance(exc, B.InvokeError):
            return exc.status == 404 or exc.status >= 500
        return False

    def request(self, payload: Any,
                timeout: Optional[float] = None) -> Any:
        """Route one invocation to the least-loaded ready replica.

        Replica-fault failures are retried on another replica until the
        request budget runs out; request-fault failures (4xx) raise
        immediately.  With no ready replica, the call parks and re-resolves
        until one appears or the budget is spent."""
        with span("router.request"):
            return self._request(payload, timeout)

    def _request(self, payload: Any, timeout: Optional[float]) -> Any:
        deadline = time.time() + (timeout if timeout is not None
                                  else self.request_timeout)
        last_exc: Optional[Exception] = None
        while True:
            eps = self._ready_endpoints()
            if not eps:
                if time.time() >= deadline:
                    raise NoReadyReplicas(
                        f"no ready replica for {self.namespace}/{self.name} "
                        f"within the request budget"
                    ) from last_exc
                time.sleep(0.01)
                continue
            ep = self._pick(eps)
            st = self._entry(ep)
            adapter = self._adapter_for(ep)
            with self._mu:
                st["requests"] += 1
                st["outstanding"] += 1
            t0 = time.time()
            try:
                result = adapter.invoke(ep["job_id"], payload)
            except Exception as exc:
                with self._mu:
                    st["outstanding"] -= 1
                    st["errors"] += 1
                if not self._replica_fault(exc):
                    raise
                last_exc = exc
                # short local suspension: stop re-picking a replica the
                # control plane has not yet condemned
                with self._mu:
                    self._down[ep["job_id"]] = time.time() + self.suspend_ttl
                if time.time() >= deadline:
                    raise NoReadyReplicas(
                        f"request to {self.namespace}/{self.name} exhausted "
                        f"its budget retrying failed replicas") from exc
                continue
            with self._mu:
                st["outstanding"] -= 1
                st["latencies"].append(time.time() - t0)
            return result

    __call__ = request

    # -- observability -----------------------------------------------------

    def stats(self) -> Dict[str, Dict[str, Any]]:
        """Per-replica-incarnation counters, keyed by remote job id:
        {replica, job_id, requests, errors, outstanding, p50_s, p99_s,
        retired}.  Live incarnations come from the live table; recently
        replaced ones (``retired: True``) from the bounded retired ring, so
        a jid stays reportable for a while after its replica is replaced.
        Each incarnation owns its own latency window — a replacement starts
        from an empty deque, never averaging across incarnations."""
        out: Dict[str, Dict[str, Any]] = {}
        with self._mu:
            entries = ([(st, True) for st in self._retired]
                       + [(st, False) for st in self._stats.values()])
            for st, retired in entries:
                lat = sorted(st["latencies"])
                out[st["job_id"]] = {
                    "replica": st["replica"], "job_id": st["job_id"],
                    "requests": st["requests"], "errors": st["errors"],
                    "outstanding": st["outstanding"],
                    "p50_s": lat[len(lat) // 2] if lat else None,
                    "p99_s": lat[min(len(lat) - 1,
                                     int(len(lat) * 0.99))] if lat else None,
                    "retired": retired,
                }
        return out
