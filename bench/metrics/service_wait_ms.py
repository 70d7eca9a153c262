"""Median, over queries answered in the window, of the client's wait for
the service's answer minus the ``Study.search_requests`` span that
priced it: queueing, coalescing and fan-out, ms.  Deduplicated queries
ride another request's search and have no span of their own."""
from bench.metrics._common import median


def read(run):
    return median([r.wait_s * 1e3 for r in run.completed
                   if r.wait_s is not None])
