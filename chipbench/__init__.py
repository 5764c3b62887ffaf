"""Chip benchmark for the GRASP repository: cells, traffic, references,
trace reduction and the work counts that turn traces into metrics.

Run one cell with ``python chipbench/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``; ``BENCHMARK.json`` lists the cells.
"""
