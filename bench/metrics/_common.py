"""Helpers the metric readers share (not a metric)."""
import math
import statistics


def latencies_ms(run):
    return [(r.t_done - r.t_submit) * 1e3 for r in run.completed]


def p95(values):
    """Nearest-rank 95th percentile; None below 20 samples (then fewer
    than one sample lies beyond it)."""
    if len(values) < 20:
        return None
    s = sorted(values)
    return s[math.ceil(0.95 * len(s)) - 1]


def median(values):
    return statistics.median(values) if values else None


def per_query_ms(run, seconds):
    n = len(run.completed)
    return seconds / n * 1e3 if n else None
