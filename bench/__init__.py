"""Chip benchmark of DP-SGD training: one cell per run (see run.py)."""
