"""A whole run on the CPU (the look for a chip skipped) at a small size,
with the timed path broken underneath: ``correct`` has to come out
false for each fault a DSE answer can have, and true without one."""
import copy
import json
import os
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness  # noqa: E402

SMALL = [256, 512, 1024, 2048]


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """A checkout-shaped directory with one small cell: the inference
    configuration on a 4-point lattice, 2 clients, 2 budget pairs."""
    root = tmp_path_factory.mktemp("checkout")
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    os.symlink(ROOT / "bench" / "metrics", root / "bench" / "metrics")
    os.symlink(ROOT / "src", root / "src")
    cfg = json.loads((ROOT / "bench" / "configs" / "r50-infer-hi3.json")
                     .read_text())
    cfg = copy.deepcopy(cfg)
    cfg["lattice"] = {"sizes_kb": SMALL, "bws": SMALL, "tol": 0.15}
    cfg["budgets"] = [[2048, 2048], [1024, 2048]]
    cfg["network"]["batch"] = 3
    (root / "bench" / "configs" / "small.json").write_text(json.dumps(cfg))
    mix = {"clients": 2, "objectives": {"cycles": 2, "energy": 1, "edp": 1},
           "check": {"fraction": 1.0, "max": 16}}
    (root / "bench" / "traffic" / "small-sweep.json").write_text(
        json.dumps(mix))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "small", "source": "test",
                         "file": "bench/configs/small.json", "reduced": [],
                         "why": "test"}]
    bench["workloads"] = [{"name": "small.sweep", "config": "small",
                           "traffic": "small-sweep", "chips": 1,
                           "why": "test"}]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def bump_best(costs, best):
    """The device grid with its best candidate one cycle dearer."""
    flat = np.array(costs).ravel()
    flat[best] += 1
    return flat.reshape(np.shape(costs))


def plant(monkeypatch, fault):
    from repro.core import dse, gridax
    if fault == "best index":
        orig = gridax.reduce_cycles_many

        def swapped(*a, **kw):
            return [(c, w, b) for c, b, w in orig(*a, **kw)]
        monkeypatch.setattr(gridax, "reduce_cycles_many", swapped)
    elif fault == "grid value":
        orig_r, orig_o = gridax.reduce_cycles_many, gridax.outer_add

        def bumped(*a, **kw):
            return [(bump_best(c, b), b, w) for c, b, w in orig_r(*a, **kw)]

        def bumped_grid(*a, **kw):
            c = orig_o(*a, **kw)
            return bump_best(c, int(np.argmin(np.asarray(c))))
        monkeypatch.setattr(gridax, "reduce_cycles_many", bumped)
        monkeypatch.setattr(gridax, "outer_add", bumped_grid)
    elif fault == "pareto member dropped":
        orig = dse._pareto_mask

        def dropped(cycles, energy):
            keep = orig(cycles, energy)
            keep[np.nonzero(keep)[0][-1]] = False
            return keep
        monkeypatch.setattr(dse, "_pareto_mask", dropped)


@pytest.mark.parametrize("fault", [None, "best index", "grid value",
                                   "pareto member dropped"])
def test_run_is_correct_only_without_a_fault(small_root, monkeypatch, fault):
    if fault is not None:
        import repro.core.gridax          # noqa: F401 (planted below)
        plant(monkeypatch, fault)
    res = harness.run("small.sweep", 2 ** 31 + 5, 1.5, False,
                      t0=time.perf_counter(), root=small_root,
                      require_chip=False, log=lambda s: None)
    assert res["correct"] is (fault is None), res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["check"]["answers_compared"]["value"] > 0
    assert (res["check"]["answers_wrong"]["value"] == 0) is (fault is None)
    assert set(res["metrics"]) >= {"candidates_per_s", "setup_s"}
    assert list(res)[-1] == "check"
