"""The chip benchmark: a data-driven harness, see ``harness.py``."""
