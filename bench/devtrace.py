"""From a profiler trace to the numbers the per-layer metrics read.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into plain
events; ``DeviceTrace`` reduces them.  The reduction is plain Python over
(plane, line, name, start, duration) records, so a test can check it on a
small recorded excerpt (``bench/trace_excerpt.json``).

On a TPU the device planes are ``/device:TPU:<n>``; their line ``XLA
Modules`` holds one event per execution of a compiled program (named after
the jitted function, e.g. ``jit_decode(...)``) and ``XLA Ops`` one event
per operation, named by its HLO instruction; a loop's event spans its
body's.  Busy time is the union of the operation intervals, averaged
over the device planes that ran anything.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES, OPS = "XLA Modules", "XLA Ops"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def short_name(hlo: str) -> str:
    """``%_decode_attention.6 = bf16[...] custom-call(...)`` ->
    ``_decode_attention.6``: an operation event's name is its whole HLO
    instruction."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def load(path: str, host_min_ns: float = 5e4) -> List[Event]:
    """The program and operation events of the device planes, and the host
    events of at least ``host_min_ns``, as plain records."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        dev = bool(DEVICE_PLANE.match(plane.name))
        if not dev and not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            if dev and line.name not in (MODULES, OPS):
                continue
            for e in line.events:
                dur = float(e.duration_ns)
                if not dev and dur < host_min_ns:
                    continue
                name = short_name(e.name) if line.name == OPS else e.name
                out.append(Event(plane.name, line.name, name,
                                 float(e.start_ns), dur))
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


class DeviceTrace:
    def __init__(self, events: Sequence[Event]):
        self.events = list(events)
        self.ops = [e for e in self.events
                    if DEVICE_PLANE.match(e.plane) and e.line == OPS]
        self.modules = [e for e in self.events
                        if DEVICE_PLANE.match(e.plane) and e.line == MODULES]
        self.host = [e for e in self.events if not DEVICE_PLANE.match(e.plane)]
        self.devices = sorted({e.plane for e in self.ops})

    def busy(self, plane: str) -> List[Tuple[float, float]]:
        return union((e.start_ns, e.end_ns) for e in self.ops
                     if e.plane == plane)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        tot = sum(e - s for d in self.devices for s, e in self.busy(d))
        return tot / len(self.devices) / 1e9

    def module_time(self, program: str) -> Tuple[float, int]:
        """(seconds, executions) of the compiled program ``jit_<program>``."""
        pat = re.compile(rf"^jit_{re.escape(program)}(\W|$)")
        hits = [e for e in self.modules if pat.match(e.name)]
        return sum(e.dur_ns for e in hits) / 1e9, len(hits)

    def kernel_time(self, kernel: str) -> Tuple[float, int]:
        """(seconds, calls) of the Pallas kernel ``kernel``: its custom
        call carries the name of the jitted wrapper that made it."""
        pat = re.compile(rf"^{re.escape(kernel)}(\.\d+)?$")
        hits = [e for e in self.ops if pat.match(e.name)]
        return sum(e.dur_ns for e in hits) / 1e9, len(hits)

    def self_times(self) -> Dict[str, float]:
        """Nanoseconds per operation name (numbering stripped), each event
        less the events nested in it (a loop holds its body's operations)."""
        tot: Dict[str, float] = {}
        for plane in self.devices:
            stack: List[List] = []  # [end, name, duration, nested time]
            evs = sorted((e for e in self.ops if e.plane == plane),
                         key=lambda e: (e.start_ns, -e.dur_ns))

            def close(until: float) -> None:
                while stack and stack[-1][0] <= until:
                    end, key, dur, child = stack.pop()
                    tot[key] = tot.get(key, 0.0) + dur - child
                    if stack:
                        stack[-1][3] += dur

            for e in evs:
                close(e.start_ns)
                stack.append([e.end_ns, re.sub(r"\.\d+$", "", e.name),
                              e.dur_ns, 0.0])
            close(float("inf"))
        return tot

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` operation names that took the most device time of
        their own: [[name, seconds], ...]."""
        top = sorted(self.self_times().items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9] for k, v in top]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The ``n`` longest stretches with no operation on a device, over
        every device, each named by the host event that covers most of it
        (what the host was doing) and the program that ran next on that
        device: [["<host event> -> <next program>", seconds], ...]."""
        gaps = []
        for plane in self.devices:
            busy = self.busy(plane)
            gaps += [(b[0] - a[1], a[1], b[0], plane)
                     for a, b in zip(busy, busy[1:])]
        out = []
        for length, s, e, plane in sorted(gaps, reverse=True)[:n]:
            nxt = min(((t.start_ns, t.name) for t in self.modules
                       if t.plane == plane and t.start_ns >= e - 1),
                      default=(0, "end"))[1]
            out.append([f"{self._host_at(s, e)} -> {nxt.split('(')[0]}",
                        length / 1e9])
        return out

    def _host_at(self, s: float, e: float) -> str:
        """The host event with the most overlap with [s, e), the innermost
        (shortest) among equals."""
        best = (0.0, 0.0, "host idle")
        for h in self.host:
            if h.dur_ns <= 0 or h.end_ns <= s or h.start_ns >= e:
                continue
            ov = min(e, h.end_ns) - max(s, h.start_ns)
            best = max(best, (ov, -h.dur_ns, h.name))
        return best[2]


def reduce_dir(trace_dir: str) -> DeviceTrace:
    return DeviceTrace(load(find_xplane(trace_dir)))
