"""The benchmark: see run.py."""
