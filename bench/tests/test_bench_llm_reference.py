"""The plain reference for GEMM + SIMD graphs (``simdit_llm``) against
the program, at the published widths of DeepSeek-V2-Lite and a sequence
a test run holds: bit-identical answers on both backends, for cycles,
energy and EDP, inference and training; its float32 control fails the
comparison; the configuration file's model numbers are the ones the
reference prices; and the cell's two readers read its spans."""
import copy
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import check, harness, spans  # noqa: E402
from bench.reference import simdit_llm  # noqa: E402
from bench.tests.test_bench_control import small_cfg  # noqa: E402

NAME = "dsv2-lite-train-ht3"
SEQ = 64


def llm_cfg(training):
    cfg = small_cfg(NAME)
    cfg["network"] = dict(cfg["network"], seq=SEQ, training=training)
    return cfg


def program_answer(cfg, key, backend):
    from repro.core.energy import EnergyModel
    from repro.core.hardware import HardwareSpec
    from repro.core.study import Study, Workload
    kb, bw, obj, batch = key
    lat, net = cfg["lattice"], cfg["network"]
    study = Study(HardwareSpec(**cfg["hardware"]), sizes=lat["sizes_kb"],
                  bws=lat["bws"], tol=lat["tol"],
                  energy_model=EnergyModel(**cfg["energy"]), store=None,
                  workers=0, backend=backend)
    res = study.search(Workload(net["net"], training=net["training"],
                                batch=batch, seq=net["seq"]),
                       kb, bw, objective=obj)
    return {"best": check.as_tuple(res.best),
            "worst": check.as_tuple(res.worst),
            "points": [check.as_tuple(p) for p in res.points],
            "pareto": [check.as_tuple(p) for p in res.pareto()],
            "n_candidates": res.n_candidates}


KEYS = [(2048, 2048, obj, 4) for obj in ("cycles", "energy", "edp")] \
    + [(1024, 2048, "cycles", 1)]


@pytest.mark.parametrize("training", [True, False],
                         ids=["training", "inference"])
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_reference_agrees_with_the_program(backend, training):
    cfg = llm_cfg(training)
    kept = [(k, program_answer(cfg, k, backend)) for k in KEYS]
    numbers, wrong = check.compare(kept, check.reference(cfg))
    assert check.within_limits(numbers), (numbers, wrong)
    assert kept[0][1]["n_candidates"] > 1


def test_float32_control_fails():
    cfg = llm_cfg(True)
    keys = [(2048, 2048, obj, 4) for obj in ("cycles", "energy", "edp")]
    low = check.reference(cfg, precision="float32")
    numbers, _ = check.compare([(k, vars(low.answer(*k))) for k in keys],
                               check.reference(cfg))
    assert not check.within_limits(numbers), numbers


def test_a_model_number_unlike_the_published_one_raises():
    cfg = copy.deepcopy(llm_cfg(True))
    assert cfg["moe_intermediate_size"] == 1408
    cfg["moe_intermediate_size"] = 2816
    with pytest.raises(ValueError, match="moe_intermediate_size"):
        check.reference(cfg).answer(2048, 2048, "cycles", 4)


def test_training_graph_counts():
    """Every GEMM gets a dX and a dW GEMM; the weight GEMMs, and the
    RMSNorm gains, a parameter update; the SIMD ops a mirrored backward."""
    net = simdit_llm.mla_moe_model(simdit_llm.NETS["deepseek-v2-lite"], 1,
                                   SEQ)
    full = simdit_llm.training_graph(net)
    gemms = [l for l in net if isinstance(l, simdit_llm.Gemm)]
    simds = [l for l in net if not isinstance(l, simdit_llm.Gemm)]
    norms = [l for l in simds if l.op == "rmsnorm"]
    weights = [g for g in gemms if g.param]
    assert len(weights) == len(gemms) - 2 * 27      # scores, A.V per layer
    assert len(full) == (len(net) + 2 * len(gemms) + len(weights)
                         + len(simds) + len(norms))


def test_the_cells_readers_read_its_spans():
    """Set-up and a few closed-loop queries through ``DSEService``, as the
    harness runs the cell: every per-layer reader the cell lists that
    reads the program or the client reads a number, the conv device
    call 0 (the graph has no conv layers), the GEMM composition and the
    lowering positive, and a lowering is a part of its graph expansion.
    The device-trace readers need the chip's trace."""
    import jax
    from repro.core import TRAIN_PRESETS, Study, Workload
    from repro.core.dse import clear_table_caches
    from repro.core.tiling import clear_tiling_caches
    from repro.serve import DSEClient, DSERequest, DSEService
    t0 = time.perf_counter()
    clear_table_caches()
    clear_tiling_caches()
    jax.clear_caches()
    rec = spans.Recorder()
    rec.install()
    study = Study(TRAIN_PRESETS[64], sizes=(256, 512, 1024, 2048),
                  bws=(256, 512, 1024, 2048), backend="jax", store=None,
                  workers=0)
    wl = Workload("deepseek-v2-lite", training=True, batch=1, seq=SEQ)
    svc = DSEService(study, coalesce_window_s=0.002)
    client = DSEClient(svc)
    try:
        client.query(wl, 2048, 2048)
        records = []
        t_start = time.perf_counter()
        for _ in range(3):
            req = DSERequest(wl, 2048, 2048)
            r = harness.Record(time.perf_counter())
            res = client.submit(req).result()
            r.t_result = time.perf_counter()
            res.points, res.pareto()
            r.t_done = time.perf_counter()
            r.wait_s = r.t_result - r.t_submit - spans.search_span(rec, req)
            records.append(r)
        t_end = time.perf_counter()
    finally:
        svc.close()
        rec.uninstall()
        clear_table_caches()
    run = harness.Run(setup_s=t_start - t0, t_start=t_start, t_end=t_end,
                      records=records, stats=(None, None), tables=({}, {}),
                      recorder=rec)
    bench = harness.load_cell(f"{NAME}.warm-sweep")[0]
    listed = {m["name"]: m["source"] for m in
              harness.cell_metrics(bench, f"{NAME}.warm-sweep", True)}
    read = {n: harness.reader(n)(run) for n, src in listed.items()
            if src != "device_trace"}
    assert {"gemm_compose_ms", "llm_lower_ms", "graph_ms",
            "compose_device_ms", "compose_ms"} <= set(read)
    assert all(v is not None for v in read.values()), read
    assert read["compose_device_ms"] == 0
    assert all(v > 0 for n, v in read.items() if n != "compose_device_ms"), \
        read
    assert read["llm_lower_ms"] < read["graph_ms"]
    assert read["gemm_compose_ms"] <= read["compose_ms"]
