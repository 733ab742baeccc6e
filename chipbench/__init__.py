"""Chip benchmark of the served path (see run.py)."""
