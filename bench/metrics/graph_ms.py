"""Graph expansion (``repro.study.layers``: one ``Workload.layers`` call,
the service's pre-validation and the search's own), per query answered
in the window, ms."""
from bench.metrics._program import window_ms


def read(run):
    return window_ms(run, "repro.study.layers")
