"""On-chip benchmark of the low-rank training step (see run.py)."""
