"""The repository's benchmark: one command, one cell, one run (see run.py)."""
