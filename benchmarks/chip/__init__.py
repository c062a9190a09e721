"""Chip benchmark of the cardinality-estimation service (see run.py)."""
