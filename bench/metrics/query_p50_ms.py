"""Median client-side latency (submit until best, frontier and Pareto
set are in hand) of the queries answered in the window, ms."""
from bench.metrics._common import latencies_ms, median


def read(run):
    return median(latencies_ms(run))
