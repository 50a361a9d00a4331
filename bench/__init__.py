"""The on-chip benchmark: one cell per run of ``python bench/run.py``."""
