"""The on-chip benchmark of the SpMM engine: ``python3 bench/run.py``."""
