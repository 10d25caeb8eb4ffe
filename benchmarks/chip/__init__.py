"""The chip benchmark: `python3 benchmarks/chip/run.py --workload <name> ...`."""
