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
    a = np.arange(2000, dtype=np.float32)
    f(a, a).block_until_ready()
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
                    f(a, a).block_until_ready()
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
