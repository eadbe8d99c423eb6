"""From the program's spans in a profiler trace to the per-layer numbers of
the router, the replica's host loop and the engine.

The program marks its served path with ``jax.profiler.TraceAnnotation``
spans (the catalogue is the docstring of ``repro.tracing``).  They are host
events of the trace (``DeviceTrace.host``), kept by ``devtrace.load`` from
50 us up.  Every Python thread's line there has the same name, so a span is
told apart by its name alone: each name is emitted by one kind of thread,
a client's or the replica's pump.  The names are copied here because the
benchmark imports the program in ``bench/serve.py`` only.

Every reading is ``None`` without a trace, without a device plane or
without the spans it needs (a program that records none).
"""
from __future__ import annotations

import heapq
from typing import Dict, Iterable, List, Optional, Tuple

from bench.devtrace import DeviceTrace, Event

# client threads
ROUTER_REQUEST = "router.request"
REPLICA_REQUEST = "replica.request"
REPLICA_ENQUEUE = "replica.enqueue"
REPLICA_WAIT = "replica.wait"
# the replica's pump thread
REPLICA_STEP = "replica.step"
REPLICA_IDLE = "replica.idle"
ENGINE_STEP = "engine.step"
ENGINE_ADMIT = "engine.admit"
ENGINE_DECODE = "engine.decode"
ENGINE_SAMPLE = "engine.sample"
ENGINE_RETIRE = "engine.retire"

CLIENT = (ROUTER_REQUEST, REPLICA_REQUEST, REPLICA_ENQUEUE, REPLICA_WAIT)
PUMP = (REPLICA_STEP, REPLICA_IDLE, ENGINE_STEP, ENGINE_ADMIT, ENGINE_DECODE,
        ENGINE_SAMPLE, ENGINE_RETIRE)
NAMES = CLIENT + PUMP


def traced(ctx) -> Optional[DeviceTrace]:
    """The window's trace, if it has a device plane."""
    t = ctx.trace
    return t if t is not None and t.devices else None


def total(trace: DeviceTrace, name: str) -> Tuple[float, int]:
    """(nanoseconds, count) of the host events named ``name``."""
    hits = [e.dur_ns for e in trace.host if e.name == name]
    return sum(hits), len(hits)


def innermost(events: Iterable[Event]) -> List[Tuple[float, float, str]]:
    """[(start, end, name)]: the stretches that ``events`` cover, in order,
    each named by the shortest event covering it (for the spans of one
    thread, which nest, the innermost)."""
    evs = sorted(events, key=lambda e: e.start_ns)
    bounds = sorted({t for e in evs for t in (e.start_ns, e.end_ns)})
    heap: List[Tuple[float, int, Event]] = []
    out: List[Tuple[float, float, str]] = []
    k = 0
    for a, b in zip(bounds, bounds[1:]):
        while k < len(evs) and evs[k].start_ns <= a:
            heapq.heappush(heap, (evs[k].dur_ns, k, evs[k]))
            k += 1
        while heap and heap[0][2].end_ns <= a:
            heapq.heappop(heap)
        if heap:
            out.append((a, b, heap[0][2].name))
    return out


def idle_by_span(trace: DeviceTrace) -> Optional[Dict[str, float]]:
    """Nanoseconds of the first device's idle time between consecutive
    operations, per innermost pump span covering them (time that no pump
    span covers is left out); ``None`` without pump spans."""
    segs = innermost(e for e in trace.host if e.name in PUMP)
    if not segs:
        return None
    busy = trace.busy(trace.devices[0])
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
    out = {name: 0.0 for name in PUMP}
    i = j = 0
    while i < len(gaps) and j < len(segs):
        (gs, ge), (ss, se, name) = gaps[i], segs[j]
        if min(ge, se) > max(gs, ss):
            out[name] += min(ge, se) - max(gs, ss)
        if ge <= se:
            i += 1
        else:
            j += 1
    return out


def idle_share(ctx, names: Iterable[str]) -> Optional[float]:
    """Percent of the window in which the device was idle between two
    operations while the innermost pump span was one of ``names``."""
    t = traced(ctx)
    by = idle_by_span(t) if t is not None else None
    if by is None:
        return None
    return 100.0 * sum(by[n] for n in names) / 1e9 / ctx.window_s
