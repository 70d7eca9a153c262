"""95th-percentile client-side latency of the queries answered in the
window (nearest rank), ms."""
from bench.metrics._common import latencies_ms, p95


def read(run):
    return p95(latencies_ms(run))
