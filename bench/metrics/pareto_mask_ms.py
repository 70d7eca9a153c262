"""The Pareto mask (``repro.dse.pareto_mask``: ``_pareto_mask`` over every
candidate), per query answered in the window, ms."""
from bench.metrics._program import window_ms


def read(run):
    return window_ms(run, "repro.dse.pareto_mask")
