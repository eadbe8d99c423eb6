"""On-chip benchmark of the bridge's served path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1``
runs one cell of ``BENCHMARK.json`` in one process on the chips it asks
for, one replica on each.  Everything a cell is made of is found by name:
``bench/configs/<config>.json``, its model family
``bench/models/<model_type>.py``, ``bench/traffic/<traffic>.json`` and
``bench/metrics/<metric>.py``.  The yardstick (traffic generation,
percentiles, peaks, work counts, trace reduction and the plain reference)
lives here and imports nothing from ``src/``; only ``bench/serve.py``
drives the program under test.  ``bench/sweep.py`` finds an open-loop
cell's knee.
"""
