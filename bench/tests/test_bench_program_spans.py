"""The readers of the program's own spans (``repro.core.tracing``), on a
run built from a small CPU ``DSEService`` window: each reads a positive
number, and each partitions the benchmark's outside metric it splits."""
import dataclasses
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness, spans  # noqa: E402

LATTICE = (256, 512, 1024, 2048)
PROGRAM = ("coalesce_wait_ms", "graph_ms", "table_fetch_ms",
           "compose_device_ms",
           "reduce_call_ms", "frontier_ms", "energy_grid_ms",
           "pareto_mask_ms", "setup_build_s", "setup_compile_s")


@pytest.fixture(scope="module")
def run():
    """Set-up (cold tables, fresh jit caches, one warm-up query), then a
    window of closed-loop queries, as ``bench/harness.py`` runs a cell."""
    import jax
    from repro.core import INFER_PRESETS, Study, Workload
    from repro.core.dse import clear_table_caches
    from repro.core.tiling import clear_tiling_caches
    from repro.serve import DSEClient, DSERequest, DSEService
    t0 = time.perf_counter()
    clear_table_caches()
    clear_tiling_caches()
    jax.clear_caches()
    rec = spans.Recorder()
    rec.install()
    study = Study(INFER_PRESETS[64], sizes=LATTICE, bws=LATTICE,
                  backend="jax", store=None, workers=0)
    svc = DSEService(study, max_batch=16, coalesce_window_s=0.02)
    client = DSEClient(svc)
    wl = Workload("resnet50", batch=3)
    try:
        res = client.query(wl, 2048, 2048)
        res.points, res.pareto()
        stats0, tables0 = svc.stats(), study.cache_stats()
        records = []
        t_start = time.perf_counter()
        for _ in range(5):
            req = DSERequest(wl, 2048, 2048)
            r = harness.Record(time.perf_counter())
            res = client.submit(req).result()
            r.t_result = time.perf_counter()
            res.points, res.pareto()
            r.t_done = time.perf_counter()
            r.n_candidates = res.n_candidates
            r.wait_s = r.t_result - r.t_submit - spans.search_span(rec, req)
            records.append(r)
        t_end = time.perf_counter()
        stats1, tables1 = svc.stats(), study.cache_stats()
    finally:
        svc.close()
        rec.uninstall()
        clear_table_caches()
    return harness.Run(setup_s=t_start - t0, t_start=t_start, t_end=t_end,
                       records=records, stats=(stats0, stats1),
                       tables=(tables0, tables1), recorder=rec)


def read(name, run):
    return harness.reader(name)(run)


@pytest.mark.parametrize("name", PROGRAM)
def test_reader_reads_a_positive_number(run, name):
    assert read(name, run) > 0


def test_every_program_metric_is_in_both_cells():
    bench = harness.load_cell("r50-train-ht3.warm-sweep")[0]
    for cell in [w["name"] for w in bench["workloads"]]:
        names = {m["name"] for m in harness.cell_metrics(bench, cell, True)}
        assert names >= set(PROGRAM), cell


@pytest.fixture(params=[False, True], ids=["all-answered", "last-open"])
def window(run, request):
    """The run, or the run with its window closing inside the last
    query's float steps, so that query is not answered."""
    if not request.param:
        return run
    last = run.records[-1]
    return dataclasses.replace(run, t_end=(last.t_result + last.t_done) / 2)


def test_spans_partition_the_outside_metrics(window):
    run = window
    assert read("coalesce_wait_ms", run) <= read("service_wait_ms", run)
    assert read("table_fetch_ms", run) + read("compose_device_ms", run) \
        <= read("compose_ms", run)
    assert sum(read(n, run) for n in ("frontier_ms", "energy_grid_ms",
                                      "pareto_mask_ms")) \
        <= read("float_steps_ms", run)
    assert read("setup_build_s", run) + read("setup_compile_s", run) \
        < run.setup_s


def test_readers_find_nothing_in_a_program_without_spans(run, monkeypatch):
    import repro.core
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    monkeypatch.delattr(repro.core, "tracing")
    for name in PROGRAM:
        assert read(name, run) is None, name
