"""Frontier materialisation (``repro.dse.frontier``: ``DSEResult.within``
on a grid result, the ``DSEPoint``s within 15%), per query answered in
the window, ms."""
from bench.metrics._program import window_ms


def read(run):
    return window_ms(run, "repro.dse.frontier")
