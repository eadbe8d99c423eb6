"""One run of one cell: set up, warm up, the measured window, the check of
what it served, and the result line."""
from __future__ import annotations

import contextlib
import dataclasses
import math
import shutil
import sys
import tempfile
import time
from types import ModuleType
from typing import Dict, Iterator, List, Optional, Tuple

from bench import manifest, reference, traffic, work
from bench.devtrace import DeviceTrace, reduce_dir
from bench.serve import Service, job_script

STEP_PROGRAMS = ("prefill", "insert", "decode")  # the engine's programs
# the Pallas kernels' custom calls, named after the program's jitted wrappers
FLASH_KERNEL, DECODE_KERNEL = "_flash_attention", "_decode_attention"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


@dataclasses.dataclass
class Context:
    """What a metric reader reads."""
    loop: str
    outcomes: List[traffic.Outcome]
    t0: float                      # window start (time.perf_counter)
    t_end: float                   # last answer of the window
    setup_s: float
    cfg: dict                      # the configuration file's contents
    family: ModuleType             # its model family (bench/models/)
    peak: Dict[str, float]
    trace: Optional[DeviceTrace] = None
    # requests the router sent each replica in the window
    replica_requests: Optional[List[int]] = None

    @property
    def window_s(self) -> float:
        return self.t_end - self.t0

    @property
    def served(self) -> List[traffic.Outcome]:
        return [o for o in self.outcomes if o.ok]

    def work(self) -> Dict[str, float]:
        return work.totals(self.family, self.cfg,
                           ((len(o.request.prompt), len(o.tokens))
                            for o in self.served))

    # -- device-trace readings (None without a trace or without the event)

    def idle_share(self) -> Optional[float]:
        if self.trace is None or not self.trace.devices:
            return None
        return 100.0 * (1.0 - self.trace.busy_s() / self.window_s)

    def program_ms(self, program: str) -> Optional[float]:
        if self.trace is None:
            return None
        s, n = self.trace.module_time(program)
        return 1e3 * s / n if n else None

    def step_mfu(self) -> Optional[float]:
        if self.trace is None:
            return None
        s = sum(self.trace.module_time(p)[0] for p in STEP_PROGRAMS)
        if s <= 0:
            return None
        return (100.0 * self.work().get("flops", 0.0)
                / (s * self.peak["flops"]))

    def roofline(self, kernel: str, key: str) -> Optional[float]:
        """Share of ``kernel``'s device time that its useful work needs at
        the peaks; ``key`` names the kernel's pair of the family's useful
        work, ``<key>_flops`` and ``<key>_bytes`` (work.totals)."""
        if self.trace is None:
            return None
        s, n = self.trace.kernel_time(kernel)
        w = self.work()
        if not n or s <= 0 or w.get(key + "_bytes", 0.0) <= 0:
            return None
        least = work.least_time(w[key + "_flops"], w[key + "_bytes"],
                                self.peak)
        return 100.0 * least / s


def _start_trace(trace_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def window(svc: Service, mix: dict, seconds: float, seed: int, vocab: int):
    """The measured window; returns (t0, outcomes)."""
    if mix["loop"] == "open":
        reqs = traffic.open_requests(mix, seconds, seed, vocab)
        t0 = time.perf_counter()
        return t0, traffic.run_open(svc.send, reqs, t0)
    pool = traffic.closed_pool(mix, seed, vocab)
    t0 = time.perf_counter()
    return t0, traffic.run_closed(svc.send, pool, int(mix["clients"]), t0,
                                  seconds)


def compared_gaps(cell: manifest.Cell, seed: int,
                  outcomes: List[traffic.Outcome],
                  control: bool = False) -> dict:
    """The reference's gaps (``bench/reference.py: gaps``) over the sample
    of served requests that a run compares."""
    cfg = cell.config
    done = [(o.request.prompt, o.tokens) for o in outcomes
            if o.ok and o.tokens]
    picked = reference.sample(done, seed, int(cfg["correct"]["sample_tokens"]))
    return reference.gaps(cell.family, cfg, seed, [done[i] for i in picked],
                          control)


def check(cfg: dict, outcomes: List[traffic.Outcome], gaps
          ) -> Dict[str, Dict[str, float]]:
    """The numbers that decide ``correct``, each with its limit: requests
    that failed, answers of the wrong length, how many served tokens were
    compared, and the mean of ``gaps``, each compared token's gap below the
    reference's best logit (``PERF.md`` says why the mean and not the
    widest gap)."""
    limits = cfg["correct"]
    served = [o for o in outcomes if o.ok]
    short = sum(len(o.tokens) != o.request.max_new for o in served)
    return {"failed": {"value": len(outcomes) - len(served), "limit": 0},
            "wrong_length": {"value": short, "limit": 0},
            "compared_tokens": {"value": len(gaps),
                                "limit": int(limits["sample_tokens"])},
            "mean_logit_gap": {"value": float(gaps.mean()) if len(gaps)
                               else math.inf,
                               "limit": float(limits["mean_logit_gap"])}}


def passed(checks: Dict[str, Dict[str, float]]) -> bool:
    c = checks
    return (c["failed"]["value"] <= c["failed"]["limit"]
            and c["wrong_length"]["value"] <= c["wrong_length"]["limit"]
            and c["compared_tokens"]["value"] >= c["compared_tokens"]["limit"]
            and c["mean_logit_gap"]["value"] <= c["mean_logit_gap"]["limit"])


@dataclasses.dataclass
class Window:
    """What one measured window produced."""
    t0: float
    t_end: float
    setup_s: float
    outcomes: List[traffic.Outcome]
    peak_bytes: Optional[int]
    trace: Optional[DeviceTrace]
    devices: List[int]             # each replica's device id
    warm_requests: List[int]       # requests each replica took in warm-up
    replica_requests: List[int]    # and in the window


def warm_up(svc: Service, mix: dict, seed: int, vocab: int) -> List[int]:
    """The mix's warm-up requests, all at once, through the router; then,
    one at a time, more of them until every replica has taken its share
    (the router sends an idle service's next request to the replica that
    has taken the fewest).  Returns the requests each replica took."""
    reqs = traffic.warmup_requests(mix, seed, vocab)
    share = max(1, len(reqs) // svc.replicas)
    warm = traffic.run_concurrent(svc.send, reqs)
    extra = 0
    while min(svc.requests().values()) < share and extra < len(reqs):
        warm += traffic.run_concurrent(svc.send, [reqs[extra]])
        extra += 1
    bad = [o.error for o in warm if not o.ok]
    if bad:
        raise RuntimeError(f"warm-up failed: {bad[0]}")
    took = list(svc.requests().values())
    if min(took) < share:
        raise RuntimeError(f"warm-up did not reach every replica: {took}")
    return took


@contextlib.contextmanager
def warmed_service(cell: manifest.Cell, seed: int
                   ) -> Iterator[Tuple[Service, List[int]]]:
    """The cell's service, one replica per chip, set up and warmed up; with
    the warm-up requests each replica took."""
    cfg = cell.config
    with Service(job_script(cfg, seed), replicas=cell.chips) as svc:
        for e in svc.engines:
            log(f"replica ready on device {e.get('device')} "
                f"({e.get('device_kind')}): weights "
                f"{float(e.get('init_s', 0.0)):.3f}s, compile "
                f"{float(e.get('compile_s', 0.0)):.3f}s, Mosaic "
                f"{e.get('mosaic')}")
        yield svc, warm_up(svc, cell.traffic, seed, int(cfg["vocab_size"]))


def peak_bytes() -> Optional[int]:
    """``peak_bytes_in_use`` of the fullest local device."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def serve_window(cell: manifest.Cell, seed: int, seconds: float,
                 trace: bool, t_start: float) -> Window:
    """Set up the service, warm up, run the window (traced or not), read
    the memory peak and stop the service."""
    import jax

    mix = cell.traffic
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        with warmed_service(cell, seed) as (svc, warm):
            if trace_dir:
                _start_trace(trace_dir)
            setup_s = time.time() - t_start
            before = svc.requests()
            t0, outcomes = window(svc, mix, seconds, seed,
                                  int(cell.config["vocab_size"]))
            t_end = max(o.done for o in outcomes)
            if trace_dir:
                jax.profiler.stop_trace()
            took = [n - before[j] for j, n in svc.requests().items()]
            devices = [e["device"] for e in svc.engines]
            peak = peak_bytes()
        init_s = [float(e.get("init_s", 0.0)) for e in svc.engines]
        compile_s = [float(e.get("compile_s", 0.0)) for e in svc.engines]
        log(f"setup {setup_s:.3f}s (weights {max(init_s):.3f}s, compile "
            f"{max(compile_s):.3f}s, slowest replica); window "
            f"{t_end - t0:.3f}s; peak_bytes_in_use {peak} (fullest device)")
        log(f"replicas on devices {devices}: warm-up requests {warm}, "
            f"window requests {took}")
        _log_traffic(mix, t0, outcomes)
        dtrace = reduce_dir(trace_dir) if trace_dir else None
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return Window(t0, t_end, setup_s, outcomes, peak, dtrace, devices, warm,
                  took)


def run(cell: manifest.Cell, seed: int, seconds: float, trace: bool,
        t_start: float, device_kind: str) -> dict:
    """One run of ``cell``; returns the result object."""
    import jax

    w = serve_window(cell, seed, seconds, trace, t_start)
    t = time.perf_counter()
    gaps = compared_gaps(cell, seed, w.outcomes)["served"]
    log(f"check: {len(gaps)} served tokens against the reference in "
        f"{time.perf_counter() - t:.3f}s; exact argmax share "
        f"{float((gaps == 0).mean()) if len(gaps) else 0:.4f}, widest gap "
        f"{float(gaps.max()) if len(gaps) else math.inf:.6f}")
    checks = check(cell.config, w.outcomes, gaps)
    ctx = Context(loop=cell.traffic["loop"], outcomes=w.outcomes, t0=w.t0,
                  t_end=w.t_end, setup_s=w.setup_s, cfg=cell.config,
                  family=cell.family, peak=work.peaks(device_kind),
                  trace=w.trace, replica_requests=w.replica_requests)
    metrics = manifest.read_metrics(
        cell.per_layer if trace else cell.end_to_end, ctx)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": w.peak_bytes}
    result = {"correct": passed(checks), "attempted": len(w.outcomes),
              "failed": checks["failed"]["value"], "metrics": metrics,
              "device": device}
    result["replicas"] = {"devices": w.devices,
                          "warm_requests": w.warm_requests,
                          "window_requests": w.replica_requests}
    if w.trace is not None:
        device["busy_s"] = w.trace.busy_s()
        device["window_s"] = ctx.window_s
        result["breakdown"] = {"device_ops": w.trace.top_ops(10),
                               "idle_gaps": w.trace.idle_gaps(10)}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"compared {name}: {c['value']} (limit {c['limit']})")
    return result


def _log_traffic(mix: dict, t0: float, outcomes: List[traffic.Outcome]
                 ) -> None:
    ok = [o for o in outcomes if o.ok]
    log(f"requests sent {len(outcomes)}, succeeded {len(ok)}, failed "
        f"{len(outcomes) - len(ok)}")
    for o in outcomes:
        if not o.ok:
            log(f"request {o.request.index} failed: {o.error}")
    if mix["loop"] == "open" and outcomes:
        late = [1e3 * (o.sent - t0 - o.request.due) for o in outcomes]
        log(f"generator lateness ms: p50 {work.percentile(late, 50):.3f} "
            f"max {max(late):.3f}")
