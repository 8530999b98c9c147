"""Chip benchmark of the stream compiler: decoder-layer stages timed per step.

Run one cell with ``python bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``; the cells, metrics and bounds are listed in
``BENCHMARK.json`` at the root of the repository.
"""
