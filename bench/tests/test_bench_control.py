"""The comparison that decides ``correct`` at a size a test run holds:
the plain reference agrees with the program bit for bit, and its
control (the reference in float32) fails the comparison."""
import copy
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import check  # noqa: E402

SMALL = (256, 512, 1024, 2048)


def small_cfg(name):
    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                     .read_text())
    cfg = copy.deepcopy(cfg)
    cfg["lattice"] = {"sizes_kb": list(SMALL), "bws": list(SMALL),
                      "tol": 0.15}
    return cfg


def program_answer(cfg, key, backend):
    from repro.core.energy import EnergyModel
    from repro.core.hardware import HardwareSpec
    from repro.core.study import Study, Workload
    kb, bw, obj, batch = key
    lat = cfg["lattice"]
    study = Study(HardwareSpec(**cfg["hardware"]), sizes=lat["sizes_kb"],
                  bws=lat["bws"], tol=lat["tol"],
                  energy_model=EnergyModel(**cfg["energy"]), store=None,
                  workers=0, backend=backend)
    net = cfg["network"]
    res = study.search(Workload(net["net"], training=net["training"],
                                batch=batch), kb, bw, objective=obj)
    return {"best": check.as_tuple(res.best),
            "worst": check.as_tuple(res.worst),
            "points": [check.as_tuple(p) for p in res.points],
            "pareto": [check.as_tuple(p) for p in res.pareto()],
            "n_candidates": res.n_candidates}


KEYS = [(2048, 2048, obj, 32) for obj in ("cycles", "energy", "edp")] \
    + [(1024, 2048, "cycles", 5)]


@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_reference_agrees_with_the_program(backend):
    cfg = small_cfg("r50-train-ht3")
    kept = [(k, program_answer(cfg, k, backend)) for k in KEYS]
    numbers, _ = check.compare(kept, check.reference(cfg))
    assert check.within_limits(numbers), numbers


@pytest.mark.parametrize("name,batch", [("r50-train-ht3", 32),
                                        ("r50-infer-hi3", 200)])
def test_float32_control_fails(name, batch):
    cfg = small_cfg(name)
    keys = [(2048, 2048, obj, batch) for obj in ("cycles", "energy", "edp")]
    low = check.reference(cfg, precision="float32")
    numbers, _ = check.compare([(k, vars(low.answer(*k))) for k in keys],
                               check.reference(cfg))
    assert not check.within_limits(numbers), numbers
