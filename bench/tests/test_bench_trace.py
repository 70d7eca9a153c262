"""The reduction from a profiler trace to device metrics, checked on a
small trace recorded on the CPU (where XLA's operations run on host
threads, so the test selects those lines as the "device")."""
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

REDUCE, COMPOSE = "repro.dse.reduce", "repro.dse.compose_device"
FETCH = "repro.dse.table_fetch"           # a program span with no device call


def test_union_and_gaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.gaps([(0, 3), (5, 8)], -1, 10) == [(-1, 0), (3, 5), (8, 10)]
    assert trace.gaps([(0, 10)], 2, 5) == []


def test_op_label():
    assert trace.op_label(
        '%custom-call.14 = s64[]{:T(128)} custom-call(u32[]{:T(128)} %a), '
        'custom_call_target="X64Combine"') == "%custom-call.14 " \
        "custom-call:X64Combine"
    assert trace.op_label("%fusion.1 = u32[311,175]{1,0:T(8,128)S(1)} "
                          "fusion(u32[175,175] %c), kind=kCustom") == \
        "%fusion.1 fusion"
    assert trace.op_label("iota_reduce_fusion") == "iota_reduce_fusion"


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda a, b: jnp.argmin(a[:, None] + b[None, :]))
    g = jax.jit(lambda a: jnp.cumsum(a * a))
    a = np.arange(2000, dtype=np.float32)
    f(a, a).block_until_ready()
    g(a).block_until_ready()
    d = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            for _ in range(4):
                with jax.profiler.TraceAnnotation("bench.compose"):
                    time.sleep(0.02)
                with jax.profiler.TraceAnnotation("bench.device"):
                    with jax.profiler.TraceAnnotation(REDUCE):
                        f(a, a).block_until_ready()
                time.sleep(0.01)          # spans lie beyond SLACK_NS
                with jax.profiler.TraceAnnotation(FETCH):
                    time.sleep(0.001)     # next to COMPOSE, launches nothing
                with jax.profiler.TraceAnnotation(COMPOSE):
                    g(a).block_until_ready()
                time.sleep(0.01)
                f(a, a).block_until_ready()        # in no program span
    finally:
        jax.profiler.stop_trace()
    return trace.latest_xplane(d)


def cpu_ops(plane, line):
    return plane.startswith("/host:CPU") and "XLA" in line


def test_summary_of_a_cpu_trace(cpu_trace):
    s = trace.summarize(cpu_trace, select=cpu_ops)
    assert 0.08 < s.window_s < 5
    assert 0 < s.busy_s <= s.op_s and s.busy_s < s.window_s
    assert s.ops and s.ops[0][1] > 0
    assert abs(sum(t for _, t in s.idle_gaps) + s.busy_s - s.window_s) \
        < 1e-6 or len(s.idle_gaps) == 10
    assert any(n == "bench.compose" and t >= 0.015 for n, t in s.idle_gaps)


def test_a_trace_without_device_ops_is_an_error(cpu_trace):
    with pytest.raises(ValueError):
        trace.summarize(cpu_trace)            # no /device: plane on a CPU


def test_gap_goes_to_the_innermost_span_covering_most_of_it():
    spans = [("bench.search", 0, 100), ("bench.compose", 10, 90),
             ("bench.device", 20, 40), ("bench.client", 0, 100)]
    assert trace.host_label((0, 100), spans) == "bench.compose"   # 60 of 100
    assert trace.host_label((20, 45), spans) == "bench.device"
    assert trace.host_label((95, 200), spans) == "host: none"


def test_device_time_splits_by_program_span(cpu_trace):
    """Each launching span takes its own calls' device time, a span that
    launches nothing takes none, however near, and the parts add up to
    the device time of the window."""
    s = trace.summarize(cpu_trace, select=cpu_ops)
    by = s.op_s_by_span
    assert set(by) == {REDUCE, COMPOSE, trace.OTHER}
    assert all(t > 0 for t in by.values()), by
    assert sum(by.values()) == pytest.approx(s.op_s, rel=1e-9)


def test_the_launching_span_is_the_innermost_program_span_of_a_launch():
    launch = trace.LAUNCH_PREFIX + "f)"
    main = [("repro.study.search", 0, 100), ("repro.dse.compose", 10, 50),
            (FETCH, 10, 20), (COMPOSE, 20, 30), (launch, 22, 28),
            (REDUCE, 60, 70), (launch, 61, 62), (launch, 63, 64),
            ("bench.device", 59, 71), (launch, 80, 81)]   # in search only
    other = [(FETCH, 40, 45), (launch, 90, 91)]     # outside any span
    assert trace.launching_spans([main, other]) == [
        ("repro.study.search", 0, 100), (COMPOSE, 20, 30), (REDUCE, 60, 70)]
    assert trace.launching_spans([other]) == []


def test_device_time_goes_to_the_launching_span_nearest_each_op():
    """Only the midpoint counts; an op the trace places up to
    ``SLACK_NS`` outside a span is the span's, since the trace's device
    clock is off the host's by up to about half a millisecond; of two
    spans that reach it, the nearer takes it, the inner on a tie."""
    ms = 1e6
    spans = [(REDUCE, 10 * ms, 20 * ms), (COMPOSE, 12 * ms, 13 * ms),
             (REDUCE, 20 * ms, 22 * ms), (COMPOSE, 40 * ms, 60 * ms)]
    ops = [("a", 11 * ms, 15 * ms),     # in two: the inner, COMPOSE
           ("b", 21 * ms, 23 * ms),     # midpoint in the second REDUCE
           ("c", 23 * ms, 29 * ms),     # midpoint 4 ms after it
           ("d", 37 * ms, 38.6 * ms),   # 2.2 ms before COMPOSE
           ("e", 37.5 * ms, 38.5 * ms),  # 2 ms before COMPOSE
           ("f", 60 * ms, 63 * ms),     # midpoint 1.5 ms after COMPOSE
           ("g", 205 * ms, 210 * ms),   # far from every span
           ("h", 19 * ms, 20.2 * ms)]   # in REDUCE, 0.4 ms before the next
    assert trace.by_span(ops, spans) == pytest.approx(
        {REDUCE: 3.2e-3, COMPOSE: 8e-3, trace.OTHER: 12.6e-3})
    # an outer span that launches too takes what lies nearer to it
    outer = [("repro.study.search", 0, 200 * ms), *spans]
    assert trace.by_span(ops, outer) == pytest.approx(
        {REDUCE: 3.2e-3, COMPOSE: 4e-3, "repro.study.search": 11.6e-3,
         trace.OTHER: 5e-3})
    assert trace.by_span(ops, []) == pytest.approx({trace.OTHER: 23.8e-3})
