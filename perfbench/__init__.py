"""The chip benchmark: ``python3 perfbench/run.py --workload <cell> ...``."""
