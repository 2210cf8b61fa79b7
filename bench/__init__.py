"""The serving benchmark: ``python3 bench/run.py --workload <cell> ...``.

Everything a cell needs is found by name from ``BENCHMARK.json`` at the
checkout's root: a configuration file under ``configs/``, a traffic mix
under ``traffic/``, one reader per per-layer metric under ``metrics/`` and
one operation and byte count per kernel under ``roofline/``.
"""
