"""The on-chip benchmark of the serving path: see ``run.py``."""
