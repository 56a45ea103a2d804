"""The chip benchmark: ``python benchmarks/chip/run.py --workload <cell>``."""
