"""The conv composition's device call (``repro.dse.compose_device``:
``gridax.conv_cycles``, its sync and the copy of the cost matrices to
the host), per query answered in the window, ms."""
from bench.metrics._program import window_ms


def read(run):
    return window_ms(run, "repro.dse.compose_device")
