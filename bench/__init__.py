"""The on-chip benchmark: one command that runs one cell once (``run.py``)."""
