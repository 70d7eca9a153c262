"""The dispatcher's coalescing wait (``repro.serve.coalesce``: first
request dequeued until the window closes or the batch is full), per
query answered in the window, ms."""
from bench.metrics._program import window_ms


def read(run):
    return window_ms(run, "repro.serve.coalesce")
