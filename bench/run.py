#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chip this process finds.

    python3 bench/run.py --workload phi3-code --seed 7 --seconds 51 --trace 0

Set-up (one replica per chip the cell asks for: each one's weights and
compile, and a warm-up that runs every program shape on every replica),
then a measured window of ``--seconds`` of the cell's
traffic through ``ServiceHandle.router()``, then the check of what was
served against the plain reference.  With ``--trace 0`` the result carries
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics read
from a profiler trace of the window.  The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (``busy_s`` and ``window_s`` too when traced), ``replicas``
(each replica's device, and the requests it took in the warm-up and in the
window), ``breakdown`` when traced, and ``checks`` last: each compared number with its limit
(``compared_tokens`` has to reach its limit, the others may not pass
theirs).

Exits 2, printing no result, when JAX's first device is not a TPU in the
peak table or there are fewer chips than the cell asks for.
"""
import time

T_START = time.time()  # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import manifest, work  # noqa: E402

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def device_or_exit(chips: int):
    """The first device, if it is a TPU of the peak table and there are at
    least ``chips``; otherwise exit 2."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or dev.device_kind not in work.PEAKS:
        print(f"[bench] no TPU of the peak table: JAX's first device is "
              f"{dev.platform!r} ({dev.device_kind!r})", file=sys.stderr)
        sys.exit(2)
    if len(devices) < chips:
        print(f"[bench] the cell needs {chips} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        sys.exit(2)
    return dev


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), caching every program."""
    import jax

    path = os.environ.get(CACHE_ENV) or os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args()

    cell = manifest.cell(args.workload)
    dev = device_or_exit(cell.chips)
    print(f"[bench] device {dev.platform} {dev.device_kind!r}; compile "
          f"cache {enable_compile_cache()}", file=sys.stderr, flush=True)

    from bench import cell as run_cell

    result = run_cell.run(cell, args.seed, args.seconds, bool(args.trace),
                          T_START, dev.device_kind)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
