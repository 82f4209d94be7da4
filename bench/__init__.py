"""Chip benchmark of the batched LP solver.

Everything the benchmark needs lives in this directory and is found by the
names in ``BENCHMARK.json``: a configuration in ``configs/<name>.json``, a
traffic mix in ``traffic/<name>.json`` and a metric reader in
``metrics/<name>.py``.  ``run.py`` is the command; ``calibrate.py`` reads
the correctness limits' two readings.
"""
