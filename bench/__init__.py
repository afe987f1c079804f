"""On-chip benchmark of the predicate engine's served path.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` (see ``bench/harness.py``).
"""
