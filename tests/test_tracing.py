"""repro.core.tracing: the spans at the layer boundaries of the query
path — their parents and request ids through ``DSEService`` (on the
watchdog thread too), the per-name bound, build spans only where a table
is built, compile spans named by their jit, and the same spans on a
profiler trace's host plane, inside the benchmark's annotations."""
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro.core import INFER_PRESETS, Study, Workload, tracing
from repro.core.dse import clear_table_caches
from repro.core import tiling
from repro.core.layers import SimdLayer
from repro.core.tiling import clear_tiling_caches
from repro.serve import DSEService

ROOT = Path(__file__).resolve().parents[1]
HW = INFER_PRESETS[64]
LATTICE = (256, 512, 1024, 2048)


@pytest.fixture(autouse=True)
def _fresh():
    clear_table_caches()
    clear_tiling_caches()
    tracing.clear()
    yield
    tracing.clear()
    clear_table_caches()


def _study(backend="numpy"):
    return Study(HW, sizes=LATTICE, bws=LATTICE, backend=backend,
                 store=None, workers=0)


def _one(name):
    found = tracing.spans(name)
    assert len(found) == 1, (name, found)
    return found[0]


@pytest.mark.parametrize("batch_timeout_s", [None, 300.0],
                         ids=["inline", "watchdog"])
def test_parents_and_request_ids_through_the_service(batch_timeout_s):
    with DSEService(_study(), batch_timeout_s=batch_timeout_s,
                    coalesce_window_s=0.005) as svc:
        ticket = svc.submit(Workload("resnet18", batch=2), 2048, 2048)
        res = ticket.result(timeout=60)
        stats = svc.stats()
    rid = (ticket.rid,)
    request = _one("repro.serve.request")
    coalesce = _one("repro.serve.coalesce")
    search = _one("repro.study.search")
    compose = _one("repro.dse.compose")
    assert request.rid == coalesce.rid == search.rid == rid
    assert (coalesce.n, search.n) == (1, 1)
    # the service's latency is the request span, read once
    assert stats.latency_samples == 1
    assert stats.latency_p50_s == request.seconds
    assert coalesce.t0 >= request.t0 and request.t1 >= search.t1
    assert coalesce.t1 <= search.t0
    # the dispatcher hands request ids down without a span of its own
    assert coalesce.parent is None and search.parent is None
    assert compose.parent == search.sid and compose.rid == rid
    assert set(tracing._kept) == {
        "repro.serve.request", "repro.serve.coalesce", "repro.study.layers",
        "repro.study.search", "repro.dse.compose", "repro.dse.table_fetch",
        "repro.dse.table_build"}
    fetches = tracing.spans("repro.dse.table_fetch")
    assert len(fetches) == 2             # conv, then simd
    assert {f.parent for f in fetches} == {compose.sid}
    assert all(f.rid == rid and f.n > 0 for f in fetches)
    # one expansion validates the request, one prices it
    validate, priced = tracing.spans("repro.study.layers")
    assert validate.parent is None and priced.parent == search.sid
    assert validate.rid == priced.rid == rid
    assert validate.n == priced.n == len(Workload("resnet18",
                                                  batch=2).layers())
    on_watchdog = search.thread != coalesce.thread
    assert on_watchdog is (batch_timeout_s is not None)
    assert validate.thread == coalesce.thread
    assert res.n_candidates > 0


def test_a_group_carries_every_request_id():
    svc = DSEService(_study(), autostart=False, coalesce_window_s=0.005)
    tickets = [svc.submit(Workload(net, batch=1), 2048, 2048)
               for net in ("resnet18", "alexnet")]
    dup = svc.submit(Workload("resnet18", batch=1), 2048, 2048)
    svc.start()
    for t in tickets:
        t.result(timeout=60)
    svc.close()
    rids = tuple(t.rid for t in tickets)
    assert dup.rid == tickets[0].rid and len(set(rids)) == 2
    assert _one("repro.serve.coalesce").rid == rids
    assert _one("repro.study.search").rid == rids
    # each request's validation carries its own id
    assert sorted(s.rid for s in tracing.spans("repro.study.layers")
                  if s.parent is None) == [(r,) for r in rids]
    assert _one("repro.study.search").n == 2
    assert sorted(s.rid for s in tracing.spans("repro.serve.request")) == \
        [(r,) for r in rids]


def test_a_rare_span_survives_a_frequent_one(monkeypatch):
    monkeypatch.setattr(tracing, "MAXLEN", 4)
    tracing.clear()
    with tracing.span("rare"):
        pass
    for i in range(10):
        with tracing.span("frequent", n=i):
            pass
    assert [s.n for s in tracing.spans("frequent")] == [6, 7, 8, 9]
    assert len(tracing.spans("rare")) == 1
    t_rare = tracing.spans("rare")[0].t0
    assert tracing.spans("frequent", t1=t_rare) == []
    assert tracing.total("rare") == tracing.spans("rare")[0].seconds


def test_span_records_its_interval_count_and_nesting():
    with tracing.span("outer", rid=7) as outer:
        with tracing.span("inner") as inner:
            inner.n = 3
        late = tracing.begin("late")
    late.end()
    assert (inner.parent, inner.rid, inner.n) == (outer.sid, (7,), 3)
    assert outer.t0 <= inner.t0 <= inner.t1 <= outer.t1
    assert late.parent == outer.sid and late.rid == (7,)
    assert tracing.total("outer", outer.t0, outer.t1) == outer.seconds
    assert tracing.total("outer", outer.t1, float("inf")) == 0


def test_requests_hands_down_ids_without_a_span():
    with tracing.span("outer", rid=7) as outer:
        with tracing.requests((8, 9)):
            with tracing.span("inner") as inner:
                pass
            late = tracing.begin("late")
        after = tracing.begin("after")
    late.end()
    after.end()
    assert (inner.parent, inner.rid) == (outer.sid, (8, 9))
    assert (late.parent, late.rid) == (outer.sid, (8, 9))
    assert (after.parent, after.rid) == (outer.sid, (7,))
    with tracing.requests(5):
        with tracing.span("top") as top:
            pass
    assert (top.parent, top.rid) == (None, (5,))
    assert set(tracing._kept) == {"outer", "inner", "late", "after", "top"}


def test_table_build_only_on_a_cold_query():
    study = _study()
    wl = Workload("resnet18", batch=2)
    study.search(wl, 2048, 2048)
    builds = tracing.spans("repro.dse.table_build")
    assert builds and sum(s.n for s in builds) > 0
    assert all(s.t1 >= s.t0 for s in builds)
    tracing.clear()
    study.search(wl, 2048, 2048)
    assert tracing.spans("repro.dse.table_build") == []
    assert len(tracing.spans("repro.dse.table_fetch")) == 2
    # the SIMD tiling derivation is a build too, and only where it derives
    simd = [l for l in wl.layers() if isinstance(l, SimdLayer)]
    shapes = len({tiling._simd_layer_key(l) for l in simd})
    clear_tiling_caches()
    tiling.prefill_simd_tilings(HW, [64 * 1024, 128 * 1024], simd)
    assert _one("repro.dse.table_build").n == 2 * shapes > 0
    tiling.prefill_simd_tilings(HW, [64 * 1024, 128 * 1024], simd)
    assert len(tracing.spans("repro.dse.table_build")) == 1


def test_compile_spans_carry_the_jit_name():
    from repro.core import gridax
    # shapes no other test compiles, so the jit traces and compiles here
    conv = np.arange(3 * 5, dtype=np.int64).reshape(3, 5)
    simd = np.arange(2 * 7, dtype=np.int64).reshape(2, 7)
    s3_of, b3_of = np.array([0, 2, 1, 2]), np.array([4, 0, 3])
    v_of, w_of = np.array([1, 0, 1, 1]), np.array([6, 5, 0])
    with tracing.span("caller", rid=11) as caller:
        (costs, bi, wi), = gridax.reduce_cycles_many(
            [conv], [simd], s3_of, b3_of, v_of, w_of)
    want = conv[np.ix_(s3_of, b3_of)] + simd[np.ix_(v_of, w_of)]
    assert np.array_equal(np.asarray(costs), want)
    assert (bi, wi) == (int(want.argmin()), int(want.argmax()))
    found = tracing.spans("repro.jax.compile")
    events = {(s.info["event"].rsplit("/", 1)[-1], s.info["fun_name"])
              for s in found}
    assert ("jaxpr_trace_duration", "_reduce_cycles_impl") in events
    assert ("backend_compile_duration", "jit(_reduce_cycles_impl)") in events
    assert all(s.parent == caller.sid and s.rid == (11,) for s in found)
    assert all(caller.t0 <= s.t0 <= s.t1 <= caller.t1 for s in found)


def test_the_numpy_path_never_imports_jax():
    code = ("import sys\n"
            "from repro.core import INFER_PRESETS, Study, Workload, tracing\n"
            "lat = (32, 64, 128)\n"
            "s = Study(INFER_PRESETS[16], sizes=lat, bws=lat, tol=0.5, "
            "backend='numpy', store=None)\n"
            "r = s.search(Workload('alexnet'), 256, 256)\n"
            "r.points, r.pareto()\n"
            "assert tracing.spans('repro.dse.pareto_mask')\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    env.pop("REPRO_DSE_BACKEND", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr


def test_spans_sit_inside_the_benchmark_annotations_on_a_cpu_trace(
        tmp_path):
    import jax
    sys.path.insert(0, str(ROOT))
    from bench import spans as bench_spans
    from bench import trace as bench_trace
    study = _study(backend="jax")
    wl = Workload("resnet18", batch=2)
    study.search(wl, 2048, 2048)                 # build and compile first
    rec = bench_spans.Recorder()
    rec.install()
    tracing.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    rec.annotate = True
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        t0 = time.perf_counter()
        for _ in range(3):
            study.search_requests([(wl, 2048, 2048)])
        t1 = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
        rec.annotate = False
        rec.uninstall()
    host = bench_trace.events(bench_trace.latest_xplane(str(tmp_path)),
                              lambda p, l: not p.startswith("/device:"))
    evs = [e for line in host.values() for e in line]

    def named(name):
        return sorted((a, b) for n, a, b in evs if n == name)

    for inner, outer in (("repro.study.search", "bench.search"),
                         ("repro.dse.table_fetch", "bench.compose"),
                         ("repro.dse.reduce", "bench.search")):
        got, around = named(inner), named(outer)
        rec_s = sorted(s.seconds for s in tracing.spans(inner, t0, t1))
        assert len(got) == len(rec_s) > 0, inner
        for a, b in got:
            assert any(c <= a and b <= d for c, d in around), inner
        # the annotation opens just before the record and closes just
        # after it
        for (a, b), s in zip(sorted(got, key=lambda g: g[1] - g[0]), rec_s):
            assert 0 <= (b - a) * 1e-9 - s < 5e-4, inner
    # the device call sits inside the reduction span
    for a, b in named("bench.device"):
        assert any(c <= a and b <= d for c, d in named("repro.dse.reduce"))
