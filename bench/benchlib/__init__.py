"""Library of the on-chip benchmark (bench/run.py)."""
