"""Chip benchmark of the served bulk-bitwise query path (see run.py)."""
