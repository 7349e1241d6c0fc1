"""Chip benchmark of the serving engine (see run.py)."""
