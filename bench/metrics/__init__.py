"""Metric readers, one file per metric name (see bench/harness.py)."""
