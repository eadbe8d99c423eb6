"""On-chip benchmark of the bridge's served path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1``
runs one cell of ``BENCHMARK.json`` in one process on the chip.  Everything
a cell is made of is found by name: ``bench/configs/<config>.json``,
``bench/traffic/<traffic>.json`` and ``bench/metrics/<metric>.py``.  The
yardstick (traffic generation, percentiles, peaks, work counts, trace
reduction and the plain reference) lives here and imports nothing from
``src/``; only ``bench/serve.py`` drives the program under test.
"""
