"""The benchmark of DIGEST training on TPU: ``python3 bench/run.py``.

Everything a cell needs is found by name from ``BENCHMARK.json``:
configurations in ``configs/``, graph recipes in ``graphs/``, traffic
(sync interval, store precision, pull transport) in ``traffic/``, limits
of the correctness comparison in ``limits/`` and per-layer metric readers
in ``metrics/``.  The yardstick (graph generation, the plain reference,
FLOP and byte counts, peaks and the trace reduction) lives here too and
imports nothing of the program except where it drives it in ``cell``.
"""
