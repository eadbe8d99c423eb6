"""Traffic: one general generator driven by a mix's data file, and the two
client loops that send it.

A mix (``bench/traffic/<name>.json``) gives::

    {"source": "...",            # the published trace the sizes follow
     "loop": "open" | "closed",
     "rate_rps": 0.7,            # open loop: arrivals per second
     "clients": 8,               # closed loop: clients, each waits for its reply
     "prompt_tokens": [64, 512], # log-uniform between the two, inclusive
     "output_tokens": [16, 128], # log-uniform; sent as max_new_tokens
     "warmup": {"requests": 8, "output_tokens": 4}}

Every seed gets the same sizes and gaps, in another order.  Each draw is a
quantile of its distribution: request ``i`` lies in block ``i // STRATA``,
each block holds one quantile from each of ``STRATA`` equal strata, and
where in its stratum a block's quantile lies follows a van der Corput
sequence over the blocks.  The seed only shuffles prompt sizes, output
sizes and arrival gaps within each block, and draws the token ids.  So any
run of whole blocks carries the same work, and a long request cannot pile
up in one part of the window.
"""
from __future__ import annotations

import concurrent.futures as cf
import dataclasses
import math
import threading
import time
from typing import Callable, Iterator, List, Optional, Sequence

import numpy as np

# a stream of the seed's generator per purpose, so that adding one never
# shifts another
_PROMPTS, _OUTPUTS, _GAPS, _TOKENS, _WARMUP = range(5)
STRATA = 8  # equal strata of each size distribution, one draw each per block


@dataclasses.dataclass
class Request:
    index: int
    prompt: List[int]
    max_new: int
    due: float = 0.0  # open loop: seconds after the window's start


@dataclasses.dataclass
class Outcome:
    request: Request
    sent: float                 # clock readings (time.perf_counter)
    done: float
    tokens: Optional[List[int]] = None
    error: Optional[str] = None

    @property
    def ok(self) -> bool:
        return self.error is None


def _vdc(n: int) -> float:
    """Van der Corput radical inverse of ``n`` in base 2 (n >= 1)."""
    x, denom = 0.0, 1.0
    while n:
        denom *= 2
        n, bit = divmod(n, 2)
        x += bit / denom
    return x


def stratified(n: int, strata: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` quantiles in (0, 1): block ``b`` holds one from each stratum at
    offset ``vdc(b + 1)`` within it, in an order drawn from ``rng``.  A last
    partial block of ``m`` takes ``m`` strata spread evenly."""
    out = []
    for b in range(math.ceil(n / strata)):
        m = min(strata, n - b * strata)
        js = [int((i + 0.5) * strata / m) for i in range(m)]
        qs = [(j + _vdc(b + 1)) / strata for j in js]
        out.extend(rng.permutation(qs))
    return np.asarray(out, dtype=np.float64)


def loguniform(q: np.ndarray, lo: int, hi: int) -> np.ndarray:
    return np.rint(np.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
                   ).astype(int)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([stream, seed])


def _sizes(mix: dict, n: int, seed: int):
    prompts = loguniform(stratified(n, STRATA, _rng(seed, _PROMPTS)),
                         *mix["prompt_tokens"])
    outs = loguniform(stratified(n, STRATA, _rng(seed, _OUTPUTS)),
                      *mix["output_tokens"])
    return prompts, outs


def _request(i: int, prompt: int, out: int, seed: int, vocab: int
             ) -> Request:
    toks = np.random.default_rng([_TOKENS, seed, i])
    return Request(i, toks.integers(1, vocab, int(prompt)).tolist(), int(out))


def open_requests(mix: dict, seconds: float, seed: int, vocab: int
                  ) -> List[Request]:
    """Open loop: the ``rate_rps * seconds`` requests due in the window,
    with their due times."""
    n = max(1, round(float(mix["rate_rps"]) * seconds))
    prompts, outs = _sizes(mix, n, seed)
    gaps = -np.log1p(-stratified(n - 1, STRATA, _rng(seed, _GAPS))
                     ) / float(mix["rate_rps"])
    due = np.concatenate([[0.0], np.cumsum(gaps)])
    reqs = [_request(i, p, o, seed, vocab)
            for i, (p, o) in enumerate(zip(prompts, outs))]
    for r, t in zip(reqs, due):
        r.due = float(t)
    return reqs


def closed_pool(mix: dict, seed: int, vocab: int, depth: int = 4096
                ) -> Iterator[Request]:
    """Closed loop: requests made as clients take them, from a pool deeper
    than any window uses."""
    prompts, outs = _sizes(mix, depth, seed)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        yield _request(i, p, o, seed, vocab)


def warmup_requests(mix: dict, seed: int, vocab: int) -> List[Request]:
    """A handful of requests spanning the mix's prompt sizes, with short
    outputs: they run every program shape the window uses."""
    w = mix.get("warmup", {})
    n = int(w.get("requests", 4))
    lo, hi = mix["prompt_tokens"]
    rng = _rng(seed, _WARMUP)
    sizes = loguniform((np.arange(n) + 0.5) / n, lo, hi)
    return [Request(-1 - i, rng.integers(1, vocab, int(p)).tolist(),
                    int(w.get("output_tokens", 4)))
            for i, p in enumerate(sizes)]


Send = Callable[[Request], List[int]]


def _attempt(send: Send, req: Request, sent: float) -> Outcome:
    try:
        tokens = send(req)
        return Outcome(req, sent, time.perf_counter(), tokens=list(tokens))
    except Exception as e:  # noqa: BLE001 - a failed request is counted
        return Outcome(req, sent, time.perf_counter(),
                       error=f"{type(e).__name__}: {e}")


def run_concurrent(send: Send, reqs: Sequence[Request]) -> List[Outcome]:
    """All at once, each on a thread of its own (warm-up)."""
    with cf.ThreadPoolExecutor(max_workers=max(1, len(reqs))) as pool:
        return list(pool.map(
            lambda r: _attempt(send, r, time.perf_counter()), reqs))


def run_open(send: Send, reqs: Sequence[Request], t0: float,
             max_threads: int = 64) -> List[Outcome]:
    """Send each request at ``t0 + due`` whatever the state of the earlier
    ones; return once every one has been answered."""
    futures = []
    with cf.ThreadPoolExecutor(max_workers=max_threads) as pool:
        for r in sorted(reqs, key=lambda r: r.due):
            wait = t0 + r.due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            futures.append(pool.submit(_attempt, send, r,
                                       time.perf_counter()))
        return [f.result() for f in futures]


def run_closed(send: Send, reqs: Iterator[Request], clients: int, t0: float,
               seconds: float) -> List[Outcome]:
    """``clients`` loops that each send their next request when the last
    one returns, until ``t0 + seconds``; every request sent by then is
    waited for."""
    lock = threading.Lock()
    outs: List[Outcome] = []
    it = iter(reqs)

    def client() -> None:
        while True:
            with lock:
                if time.perf_counter() >= t0 + seconds:
                    return
                req = next(it)
            o = _attempt(send, req, time.perf_counter())
            with lock:
                outs.append(o)

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return sorted(outs, key=lambda o: o.request.index)
