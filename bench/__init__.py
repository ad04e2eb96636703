"""Chip benchmark: ``python3 bench/run.py --workload <cell> ...`` (see run.py)."""
