"""Chip benchmark of the robust trainer: ``python benchmarks/chip/run.py --help``."""
