"""The on-chip benchmark: one cell per run, ``python3 -m bench.run``."""
