"""Chip benchmark of the served GP-EI decision path (see bench/run.py)."""
