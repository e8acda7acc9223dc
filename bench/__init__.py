"""Chip benchmark of the protected store: cells, metrics and references.

Run one cell with ``python3 bench/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>`` from the repository root; ``BENCHMARK.json``
names the cells.  Nothing here is imported by the library.
"""
