"""The energy grids a Pareto set needs (``repro.dse.energy_grids``:
``_EnergyFields.grids`` over every candidate), per query answered in
the window, ms."""
from bench.metrics._program import window_ms


def read(run):
    return window_ms(run, "repro.dse.energy_grids")
