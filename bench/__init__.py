"""The benchmark of the Nekbone solve: harness, yardstick and cells.

`python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell of `BENCHMARK.json`.  Everything that decides a number lives
here and imports nothing of the program but the system under test: the
traffic generator (`traffic.py`), the plain reference operator
(`reference.py`), the counts of operations and bytes (`counts.py`), the
peaks (`peaks.json`) and the reduction from trace to metrics
(`tracing.py`).  Configurations, cells, traffic mixes and per-layer metric
readers are files of their own, found by name (`specs.py`).
"""
