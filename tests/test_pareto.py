"""The Pareto mask (``dse._pareto_mask``) against the plain walk it
replaced: a lexsort of every candidate by (cycles, energy, index) and a
Python loop that keeps a candidate whose energy is below every energy
before it.  The mask must agree bit for bit on every input, ties, infs
and NaNs included, and its span must record how many candidates the
staircase prefilter left for the exact pass."""
import numpy as np
import pytest

from repro.core import INFER_PRESETS, tracing
from repro.core.dse import _pareto_mask
from repro.core.study import Study, Workload


def walk(cycles, energy):
    """The oracle: the lexsort-and-walk, one candidate at a time."""
    n = len(cycles)
    order = np.lexsort((np.arange(n), energy, cycles))
    keep = np.zeros(n, dtype=bool)
    best_e = np.inf
    for i in order:
        if energy[i] < best_e:
            keep[i] = True
            best_e = energy[i]
    return keep


def _random(n, seed=0):
    rng = np.random.default_rng(seed)
    cycles = rng.integers(1_000, 5_000, n)
    return cycles, cycles * 0.5 + rng.normal(0.0, 300.0, n)


def _staircase(n, seed):
    """Whole-numbered cycles and energies, anti-correlated, with ties:
    a long frontier, most of it off the prefilter's pivots."""
    rng = np.random.default_rng(seed)
    cycles = rng.integers(0, 200, n).astype(float)
    return cycles, 200.0 - cycles + rng.integers(0, 20, n)


def _inf_cycles(n=1_000):
    cycles, energy = _staircase(n, seed=4)
    rng = np.random.default_rng(7)
    low, high = rng.random(n) < 0.03, rng.random(n) < 0.03
    cycles[low], energy[low] = -np.inf, 300.0 + rng.integers(0, 20, n)[low]
    cycles[high], energy[high] = np.inf, -rng.integers(0, 5, n)[high]
    return cycles, energy


def _inf_nan_energy(n=1_000):
    cycles, energy = _staircase(n, seed=8)
    rng = np.random.default_rng(9)
    energy[rng.random(n) < 0.05] = np.inf
    energy[rng.random(n) < 0.1] = np.nan
    energy[np.argmax(cycles)] = -np.inf
    return cycles, energy


def _anti(n=2_000):
    cycles = np.random.default_rng(5).permutation(n).astype(np.int64)
    return cycles, (n - cycles).astype(float)


def _grid():
    kw = dict(sizes=(32, 64, 128, 256), bws=(32, 64, 128, 256), tol=0.5)
    r = Study(INFER_PRESETS[16], backend="numpy", store=None,
              **kw).search(Workload("alexnet"), 256, 256)
    return r.grid.costs.ravel(), r._grid_energy()["E_total"].ravel()


CASES = {
    "empty": lambda: (np.zeros(0, np.int64), np.zeros(0)),
    "one": lambda: (np.array([7]), np.array([1.0])),
    "two": lambda: (np.array([3, 3]), np.array([2.0, 2.0])),
    "prime": lambda: _random(997, seed=1),
    "non_square": lambda: _random(150, seed=2),
    "int64_cycles": lambda: _random(10_000, seed=3),
    "float64_cycles": lambda: tuple(a.astype(float) / 7
                                    for a in _random(10_000, seed=6)),
    "duplicates": lambda: (np.tile(np.array([5, 5, 4, 6, 4]), 300),
                           np.tile(np.array([1.0, 1.0, 2.0, 0.5, 2.0]),
                                   300)),
    "inf_cycles": _inf_cycles,
    "inf_nan_energy": _inf_nan_energy,
    "nan_energy": lambda: (np.arange(50), np.full(50, np.nan)),
    "ties": lambda: _staircase(3_000, seed=10),
    "anti_correlated": _anti,
    "study_grid": _grid,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_pareto_mask_is_the_walk(case):
    cycles, energy = CASES[case]()
    got = _pareto_mask(cycles, energy)
    assert got.dtype == bool and got.shape == (len(cycles),)
    assert np.array_equal(got, walk(cycles, energy))


@pytest.mark.parametrize("case", sorted(CASES))
def test_pareto_mask_records_its_survivors(case):
    cycles, energy = CASES[case]()
    tracing.clear()
    mask = _pareto_mask(cycles, energy)
    [s] = tracing.spans("repro.dse.pareto_mask")
    assert s.n == len(cycles)
    assert mask.sum() <= s.info["survivors"] <= len(cycles)
    if case == "anti_correlated":
        assert s.info["survivors"] == len(cycles) == mask.sum()
