"""The chip benchmark: open-loop kernel-task traffic through ``Hydra.dispatch``.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once.  Everything that belongs to one
configuration, traffic mix, kernel or metric is a file of its own, found by
the name ``BENCHMARK.json`` gives it (``spec.py``).
"""
