"""On-chip serving benchmark: ``python3 bench/run.py --workload <cell> ...``.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives it:
``configs/<config>.json``, ``traffic/<mix>.json``, ``metrics/<metric>.py``.
"""
