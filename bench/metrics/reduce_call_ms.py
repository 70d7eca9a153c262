"""The device reduction as the host sees it (``repro.dse.reduce``: the
``gridax`` call, its sync, and the copy of the cost grid to the host),
per query answered in the window, ms."""
from bench.metrics._program import window_ms


def read(run):
    return window_ms(run, "repro.dse.reduce")
