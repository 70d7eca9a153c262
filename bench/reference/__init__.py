"""Plain references of the configurations (see bench/check.py)."""
