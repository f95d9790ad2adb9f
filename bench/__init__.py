"""Chip benchmark of the tAPP-served path: ``python3 bench/run.py --help``."""
