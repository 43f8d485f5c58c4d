"""On-chip benchmark: cells, traffic, per-layer readers and references.

Run one cell once with ``python3 bench/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``; ``BENCHMARK.json`` at the checkout's root
names the cells.
"""
