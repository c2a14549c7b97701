"""Chip benchmark of the paged serving engine; see ``run.py``."""
