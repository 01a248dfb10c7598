"""The on-chip benchmark: see ``bench/run.py``."""
