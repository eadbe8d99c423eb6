"""The served path's spans (``repro.tracing``) as ``jax.profiler`` records
them, on the CPU at smoke size, read back with the benchmark's loader."""
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import devtrace  # noqa: E402

SMOKE_MODEL = {"preset": "smoke", "arch": "phi3-mini-3.8b",
               "config_overrides": {"attention_impl": "pallas"}}
SMOKE_ENGINE = {"max_batch": 2, "max_len": 96, "prefill_len": 16}


def _start(trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def _host_spans(trace_dir):
    """{name: [Event]} of the trace's host events, none dropped for being
    short."""
    events = devtrace.load(devtrace.find_xplane(str(trace_dir)),
                           host_min_ns=0)
    out = {}
    for e in events:
        if not devtrace.DEVICE_PLANE.match(e.plane):
            out.setdefault(e.name, []).append(e)
    return out


def _args(trace_dir, name):
    """The args of every host event named ``name``, as dicts."""
    from jax.profiler import ProfileData

    return [dict(e.stats)
            for plane in ProfileData.from_file(
                devtrace.find_xplane(str(trace_dir))).planes
            for line in plane.lines for e in line.events if e.name == name]


def _inside(inner, outers):
    return any(o.start_ns <= inner.start_ns and inner.end_ns <= o.end_ns
               for o in outers)


@pytest.fixture(scope="module")
def engine_trace(tmp_path_factory):
    """A smoke engine, 2 slots, 5 requests of 4, 2, 6, 3 and 5 tokens, run
    to the end under the profiler: (engine, generated tokens, spans, trace
    path).  The last three are admitted while another slot decodes."""
    from repro.configs.base import get_smoke_config
    from repro.serving import ServingEngine
    from repro.steps import init_model

    cfg = get_smoke_config("phi3-mini-3.8b")
    _, params = init_model(cfg, max_seq=64)
    eng = ServingEngine(cfg, params, max_batch=2, max_len=48, prefill_len=8)
    rng = np.random.RandomState(0)
    for n in (4, 2, 6, 3, 5):
        eng.submit(list(rng.randint(1, cfg.vocab, size=8)), max_new_tokens=n)
    trace_dir = tmp_path_factory.mktemp("engine-trace")
    _start(trace_dir)
    try:
        results = eng.run_until_idle()
    finally:
        jax.profiler.stop_trace()
    return eng, results, _host_spans(trace_dir), trace_dir


def test_engine_spans_count_what_its_stats_count(engine_trace):
    eng, results, spans, _ = engine_trace
    assert len(results) == 5
    assert len(spans["engine.decode"]) == eng.stats["decode_ticks"] > 0
    assert len(spans["engine.admit"]) == eng.stats["prefills"] == 5
    assert (len(spans["engine.sample"]) == len(spans["engine.retire"])
            == eng.stats["decode_ticks"])


def test_engine_spans_nest(engine_trace):
    _, _, spans, _ = engine_trace
    steps = spans["engine.step"]
    for e in spans["engine.decode"] + spans["engine.admit"]:
        assert _inside(e, steps), e
    for e in spans["engine.sample"] + spans["engine.retire"]:
        assert _inside(e, spans["engine.decode"]), e


def test_decode_batch_from_the_spans_is_tokens_per_tick(engine_trace):
    """Tokens over engine.decode spans is the mean active slots per tick,
    and the ticks' ``active`` args add up to the tokens."""
    eng, results, spans, trace_dir = engine_trace
    tokens = sum(len(t) for t in results.values())
    assert tokens == eng.stats["tokens"] == 20
    batch = tokens / len(spans["engine.decode"])
    assert batch == eng.stats["tokens"] / eng.stats["decode_ticks"]
    assert 1 < batch <= 2
    active = [a["active"] for a in _args(trace_dir, "engine.decode")]
    assert sum(active) == tokens


def test_admit_spans_count_the_joins(engine_trace):
    """Each ``engine.admit`` carries ``decoding``, the slots already
    decoding when it began; those with one or more are the engine's
    ``joins``."""
    eng, _, _, trace_dir = engine_trace
    decoding = [a["decoding"] for a in _args(trace_dir, "engine.admit")]
    assert decoding == [0, 0, 1, 1, 1]
    assert sum(d >= 1 for d in decoding) == eng.stats["joins"] == 3


def test_request_spans_through_a_bridge_service(tmp_path):
    """Through the router to a smoke replica: one router.request,
    replica.request, replica.enqueue and replica.wait per request, the
    handler's inside the router's; the pump's spans nest likewise."""
    import concurrent.futures as cf

    from bench.serve import Service

    script = {"mode": "serve", **SMOKE_MODEL, **SMOKE_ENGINE, "seed": 0}
    rng = np.random.RandomState(1)
    prompts = [list(map(int, rng.randint(1, 256, size=n)))
               for n in (5, 9, 16)]
    with Service(script) as svc:
        svc.router.request({"prompt": prompts[0], "max_new_tokens": 2})
        _start(tmp_path)
        try:
            with cf.ThreadPoolExecutor(3) as pool:
                outs = list(pool.map(lambda p: svc.router.request(
                    {"prompt": p, "max_new_tokens": 3}), prompts))
        finally:
            jax.profiler.stop_trace()
    assert [len(o["tokens"]) for o in outs] == [3, 3, 3]
    spans = _host_spans(tmp_path)
    for name in ("router.request", "replica.request", "replica.enqueue",
                 "replica.wait"):
        assert len(spans.get(name, [])) == 3, name
    for e in spans["replica.request"]:
        assert _inside(e, spans["router.request"]), e
    for e in spans["replica.enqueue"] + spans["replica.wait"]:
        assert _inside(e, spans["replica.request"]), e
    assert spans["replica.step"]
    for e in spans["engine.step"]:
        assert _inside(e, spans["replica.step"]), e
