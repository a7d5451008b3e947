"""On-chip serving benchmark: ``python3 bench/run.py --workload <cell> ...``.

Everything that belongs to one configuration, traffic mix, architecture or
per-layer metric is a file of its own, found by the name ``BENCHMARK.json``
or a configuration file gives it: ``configs/<config>.json``,
``traffic/<mix>.json``, ``models/<bench_model>.py``, ``metrics/<metric>.py``.

A model module (named by a configuration file's ``"bench_model"``) defines:
``arch_config(conf)``, the program's configuration checked against the
file's published keys; ``shapes(conf)``, the dict its counts take;
``logits(weights, conf, tokens, rows, fp8=False)``, the float32 reference
forward (``bench/reference.py`` holds the comparison and its pieces);
``tiny(conf)``, the keys that make a copy for the CPU tests; and the counts
the readers ask for, each ``f(shapes, step)`` of one ``bench/loop.Step``
(``prefill_flops``, ``decode_flops``, ``decode_bytes``,
``decode_attn_bytes``, ...). A reader reports nothing where the module
lacks a count or returns ``None``. A reader file or a model module may
declare ``COUNTERS``, engine attributes the traced run keeps per step and
over the window (``bench/loop.serve``).
"""
