"""On-chip serving benchmark: cells, traffic, metrics and the reference.

Run one cell with ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the repository root.
"""
