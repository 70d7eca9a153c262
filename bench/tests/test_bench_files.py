"""Every cell of BENCHMARK.json resolves its files by name, and the file
keeps to the benchmark contract's limits."""
import json
import re
import sys
from dataclasses import asdict
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import generator, harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_its_files(cell):
    bench, entry, cfg, mix = harness.load_cell(cell)
    assert cfg["name"] == entry["config"]
    assert (ROOT / "bench" / "reference" / f"{cfg['reference']}.py").is_file()
    for traced in (False, True):
        metrics = harness.cell_metrics(bench, cell, traced)
        assert metrics, "every cell reports end-to-end and per-layer metrics"
        for m in metrics:
            assert callable(harness.reader(m["name"]))
    names = {m["name"] for m in harness.cell_metrics(bench, cell, False)}
    assert "setup_s" in names and len(names) >= 2
    assert next(generator.queries(mix, cfg, 1)).size_budget_kb > 0


def test_contract_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for name in (CELLS + [c["name"] for c in BENCH["configs"]]
                 + [m["name"] for m in BENCH["end_to_end"]
                    + BENCH["per_layer"]]):
        assert NAME.match(name), name
    for w in BENCH["workloads"]:
        assert w["chips"] == 1 and len(w["why"]) <= 200
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", CELLS):
            assert m["moves"] in {x["name"] for x in
                                  harness.cell_metrics(BENCH, cell, False)}
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        # a configuration states its cuts: keys of its own file
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert isinstance(c["reduced"], list) and len(c["reduced"]) <= 16
        assert all(isinstance(k, str) and NAME.match(k) and k in cfg
                   for k in c["reduced"]), c["reduced"]
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cfg_name,preset", [("r50-infer-hi3", "infer"),
                                             ("r50-train-ht3", "train")])
def test_configs_are_the_papers_presets(cfg_name, preset):
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core import INFER_PRESETS, TRAIN_PRESETS
    from repro.core.dse import BWS, SIZES_KB
    from repro.core.energy import DEFAULT_ENERGY
    cfg = json.loads((ROOT / "bench" / "configs"
                      / f"{cfg_name}.json").read_text())
    hw = (INFER_PRESETS if preset == "infer" else TRAIN_PRESETS)[64]
    assert cfg["hardware"] == asdict(hw)
    assert cfg["energy"] == asdict(DEFAULT_ENERGY)
    assert cfg["lattice"] == {"sizes_kb": list(SIZES_KB), "bws": list(BWS),
                              "tol": 0.15}
    assert cfg["budgets"] == [[2048, 2048]]           # Table VIII, 64x64


def test_peak_table_refuses_unknown_devices():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")
