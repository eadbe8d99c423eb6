"""The replica's pump (``serve_job``) on the CPU at smoke size: a request
joins a running batch between decode ticks, and a cancel fails every
request the replica holds, wherever it is."""
import concurrent.futures as cf
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SMOKE_MODEL = {"preset": "smoke", "arch": "phi3-mini-3.8b",
               "config_overrides": {"attention_impl": "pallas"}}
PREFILL_LEN = 16
WAIT_S = 120.0  # bound on any one wait of these tests


def _engines(monkeypatch, cls):
    """Make ``serve_job`` build ``cls`` (a ``ServingEngine`` subclass) and
    return the list that collects the engines it builds."""
    from repro.serving import engine

    made = []

    class Recorded(cls):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            made.append(self)

    monkeypatch.setattr(engine, "ServingEngine", Recorded)
    return made


def _prompt(rng, n):
    return list(map(int, rng.randint(1, 256, size=n)))


def test_a_request_joins_a_running_batch(monkeypatch):
    """A short request sent while a long one decodes is admitted into the
    free slot at once and returns first; both get the tokens they get when
    served alone."""
    from bench.serve import Service
    from repro.serving.engine import ServingEngine

    class Engine(ServingEngine):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.decoding = threading.Event()  # a slot has decoded a token

        def step(self):
            out = super().step()
            if any(r is not None and r.generated for r in self.slots):
                self.decoding.set()
            return out

    made = _engines(monkeypatch, Engine)
    script = {"mode": "serve", **SMOKE_MODEL, "max_batch": 2, "max_len": 96,
              "prefill_len": PREFILL_LEN, "seed": 0}
    rng = np.random.RandomState(2)
    long = {"prompt": _prompt(rng, 12), "max_new_tokens": 40}
    short = {"prompt": _prompt(rng, 9), "max_new_tokens": 2}
    with Service(script) as svc:
        (eng,) = made
        alone = [svc.router.request(b)["tokens"] for b in (long, short)]
        assert eng.stats["joins"] == 0
        eng.decoding.clear()
        with cf.ThreadPoolExecutor(2) as pool:
            f_long = pool.submit(svc.router.request, long)
            assert eng.decoding.wait(WAIT_S)
            f_short = pool.submit(svc.router.request, short)
            done, _ = cf.wait([f_long, f_short], timeout=WAIT_S,
                              return_when=cf.FIRST_COMPLETED)
            assert done == {f_short}
            together = [f.result(WAIT_S)["tokens"] for f in (f_long, f_short)]
    assert [len(t) for t in together] == [40, 2]
    assert together == alone
    assert eng.stats["joins"] >= 1


def test_cancel_fails_requests_in_the_inbox_the_queue_and_a_slot(
        monkeypatch):
    """With one request in the engine's only slot, one queued behind it in
    the engine and one in the pump's inbox, a cancel makes every handler
    raise, and the stopped pump fails every result it still held."""
    from repro.core.backends import base as B
    from repro.core.backends import jaxlocal
    from repro.serving.engine import ServingEngine

    held, release = threading.Event(), threading.Event()

    class Engine(ServingEngine):
        def step(self):
            # hold the pump while a request waits behind a decoding one
            if self.pending and self.slots[0] is not None and not held.is_set():
                held.set()
                release.wait(WAIT_S)
            return super().step()

    _engines(monkeypatch, Engine)
    put = threading.Semaphore(0)  # released once a handler waits
    results = []

    class Result(cf.Future):
        def __init__(self):
            super().__init__()
            self.waited = False
            results.append(self)

        def result(self, timeout=None):
            if not self.waited:
                self.waited = True
                put.release()
            return super().result(timeout)

    monkeypatch.setattr(jaxlocal, "Future", Result)
    spec = {"mode": "serve", **SMOKE_MODEL, "max_batch": 1, "max_len": 64,
            "prefill_len": PREFILL_LEN, "seed": 0}
    job = B.ClusterJob(id="r0", script=json.dumps(spec))
    rng = np.random.RandomState(3)
    with cf.ThreadPoolExecutor(4) as pool:
        pump = pool.submit(jaxlocal.serve_job, spec, job, None)
        try:
            deadline = time.monotonic() + WAIT_S
            while job.handler is None:  # the engine compiles first
                assert not pump.done(), pump.exception()
                assert time.monotonic() < deadline
                time.sleep(0.05)
            handler = job.handler
            with pytest.raises(ValueError, match="prefill_len"):
                handler({"prompt": _prompt(rng, PREFILL_LEN + 1)})
            assert put.acquire(timeout=WAIT_S)
            slot = pool.submit(handler, {"prompt": _prompt(rng, 8),
                                         "max_new_tokens": 40})
            assert put.acquire(timeout=WAIT_S)
            queued = pool.submit(handler, {"prompt": _prompt(rng, 8),
                                           "max_new_tokens": 2})
            assert put.acquire(timeout=WAIT_S) and held.wait(WAIT_S)
            inbox = pool.submit(handler, {"prompt": _prompt(rng, 8),
                                          "max_new_tokens": 2})
            assert put.acquire(timeout=WAIT_S)
            job._cancel.set()
            for f in (slot, queued, inbox):
                with pytest.raises(RuntimeError, match="cancelled"):
                    f.result(timeout=5)
        finally:
            job._cancel.set()
            release.set()
        assert pump.result(timeout=WAIT_S) == -1
    assert job.handler is None
    assert len(results) == 4
    assert all(isinstance(r.exception(0), RuntimeError) for r in results[1:])
