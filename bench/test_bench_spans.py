"""CPU checks of the readings made from the program's spans
(bench/spans.py and the five readers), on a hand-made trace."""
import re

import pytest

from bench import manifest, spans, traffic, work
from bench.cell import Context
from bench.devtrace import DeviceTrace, Event

DEV, HOST = "/device:TPU:0", "/host:CPU"
MS = 1e6
SPAN_METRICS = ("transport_ms.tput", "enqueue_wait_ms.tput",
                "decode_batch.tput", "engine_idle_share.tput",
                "pump_idle_share.tput")


def _op(start, end):
    return Event(DEV, "XLA Ops", "fusion.1", start * MS, (end - start) * MS)


def _span(name, start, end):
    return Event(HOST, "python", name, start * MS, (end - start) * MS)


# device operations, in ms: idle between them at [9, 13], [14, 16],
# [21, 24], [26.5, 27], [32, 35], [36, 41]
OPS = [_op(2.5, 9), _op(13, 14), _op(16, 21), _op(24, 26.5), _op(27, 32),
       _op(35, 36), _op(41, 42)]
PUMP = [
    # a decode tick: idle inside engine.sample [9, 10], engine.decode
    # [10, 12] and [15, 16], engine.retire [12, 13] and [14, 15]
    _span("replica.step", 0, 20), _span("engine.step", 1, 19),
    _span("engine.decode", 2, 18), _span("engine.sample", 3, 10),
    _span("engine.retire", 12, 15),
    # between two replica.steps [21, 22] no pump span covers the idle
    # device; then replica.step [22, 22.5], engine.step [22.5, 23],
    # engine.admit [23, 24]; a second tick, idle in engine.sample
    # [26.5, 27]
    _span("replica.step", 22, 30), _span("engine.step", 22.5, 29.5),
    _span("engine.admit", 23, 25), _span("engine.decode", 25.5, 29),
    _span("engine.sample", 26, 28.5),
    # waiting for work: idle in replica.idle [32, 35] and [36, 40]; [40, 41]
    # is covered by no pump span
    _span("replica.idle", 31, 40),
]
CLIENT = [
    # two requests; their spans overlap every idle stretch above, and
    # request 1's enqueue is shorter than the pump spans it overlaps
    _span("router.request", 0.5, 45), _span("replica.request", 1, 44),
    _span("replica.enqueue", 9.5, 11.5), _span("replica.wait", 11.5, 43.5),
    # request 2's enqueue took under 50 us: the loader dropped it
    _span("router.request", 20.5, 39.5), _span("replica.request", 21, 39),
    _span("replica.wait", 21.2, 38.8),
]
ENGINE_MS = 1 + 2 + 1 + 1 + 1 + 0.5 + 1 + 0.5   # 8 ms
PUMP_MS = 0.5 + 3 + 4                           # 7.5 ms


def _ctx(events, window_ms=50.0):
    cell = manifest.cell("phi3-code")
    outs = [traffic.Outcome(traffic.Request(0, [1, 2], 2), sent=0.0,
                            done=0.04, tokens=[5, 6]),
            traffic.Outcome(traffic.Request(1, [3], 1), sent=0.02,
                            done=0.039, tokens=[7])]
    return Context(loop="closed", outcomes=outs, t0=0.0,
                   t_end=window_ms / 1e3, setup_s=1.0, cfg=cell.config,
                   family=cell.family,
                   peak=work.PEAKS["TPU v5 lite"],
                   trace=DeviceTrace(events) if events is not None else None)


def _read(ctx):
    return {m: manifest.reader(m)(ctx) for m in SPAN_METRICS}


def test_span_readings_on_a_hand_made_trace():
    ctx = _ctx(OPS + PUMP + CLIENT)
    got = _read(ctx)
    # (44.5 + 19) ms in the router less (43 + 18) in the handler, 2 requests
    assert got["transport_ms.tput"] == pytest.approx(1.25)
    # 2 ms of enqueue (the other under 50 us, so 0) over 2 requests
    assert got["enqueue_wait_ms.tput"] == pytest.approx(1.0)
    # 3 served tokens over 2 engine.decode ticks
    assert got["decode_batch.tput"] == pytest.approx(1.5)
    assert got["engine_idle_share.tput"] == pytest.approx(
        100 * ENGINE_MS / 50)
    assert got["pump_idle_share.tput"] == pytest.approx(100 * PUMP_MS / 50)
    # together at most the device's idle share; the rest is the window's
    # edges and the idle time no pump span covers
    assert (got["engine_idle_share.tput"] + got["pump_idle_share.tput"]
            <= ctx.idle_share())


def test_client_spans_do_not_change_the_attribution():
    by = spans.idle_by_span(DeviceTrace(OPS + PUMP + CLIENT))
    assert by == spans.idle_by_span(DeviceTrace(OPS + PUMP))
    want = {"engine.sample": 1.5, "engine.decode": 3.0, "engine.retire": 2.0,
            "engine.step": 0.5, "engine.admit": 1.0, "replica.step": 0.5,
            "replica.idle": 7.0}
    assert {k: v / MS for k, v in by.items() if v} == pytest.approx(want)


def test_innermost_names_each_stretch_by_the_shortest_covering_span():
    segs = spans.innermost([_span("replica.step", 0, 10),
                            _span("engine.decode", 2, 8),
                            _span("engine.sample", 3, 5)])
    assert [(s / MS, e / MS, n) for s, e, n in segs] == [
        (0, 2, "replica.step"), (2, 3, "engine.decode"),
        (3, 5, "engine.sample"), (5, 8, "engine.decode"),
        (8, 10, "replica.step")]
    assert spans.innermost([]) == []


@pytest.mark.parametrize("events", [None, OPS, PUMP + CLIENT],
                         ids=["no-trace", "no-spans", "no-device-plane"])
def test_span_readings_are_absent_without_spans_or_device(events):
    assert _read(_ctx(events)) == {m: None for m in SPAN_METRICS}


def test_span_names_are_the_programs_catalogue():
    import repro.tracing

    catalogue = re.findall(r"^``([a-z]+\.[a-z]+)``", repro.tracing.__doc__,
                           re.M)
    assert len(catalogue) == len(set(catalogue))
    assert set(spans.NAMES) == set(catalogue)
    assert len(spans.NAMES) == len(set(spans.NAMES))
