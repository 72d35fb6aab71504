"""On-chip serving benchmark: one cell is one configuration under one
traffic mix; ``python3 bench/run.py --workload <config>.<traffic> ...``."""
