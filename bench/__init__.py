"""On-chip benchmark of the approximate-wireless FL round (see run.py)."""
