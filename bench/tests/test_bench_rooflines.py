"""The roofline readers: the bytes the conv composition kernel must
move, counted from its operands' shapes, and the device time of each
reader's own program span as the denominator."""
import dataclasses
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness, spans, trace  # noqa: E402
from bench.metrics import compose_roofline  # noqa: E402
from bench.metrics.compose_roofline import kernel_bytes  # noqa: E402

PEAKS = {"hbm_bytes_per_s": 1e9}
T, U, B, G = 2, 3, 4, 5


@pytest.mark.parametrize("shape", [(T, U, B, G), (7, 11, 3, 4)])
def test_kernel_bytes_are_the_arrays_of_a_real_call(shape):
    """Recorded through the wrapper the harness installs for a reader's
    ``CALLS``, the count equals the bytes of the arrays the program's
    call reads and writes: at T, U, B, G = 2, 3, 4, 5, div int32 [6x2x3]
    144, occ int64 [4x2x3] 192, divisors int64 [3x3x4] 288, w int64
    [5x3] 120 and the int64 result [5x2x4] 320."""
    from repro.core import gridax
    n_t, n_u, n_b, n_g = shape
    rng = np.random.default_rng(7)
    div = rng.integers(1, 1000, (6, n_t, n_u)).astype(np.int32)
    occ = rng.integers(0, 50, (4, n_t, n_u)).astype(np.int64)
    bw = np.array([[32, 64, 128, 2048, 96][:n_b]] * 3, dtype=np.int64)
    w = rng.integers(0, 3, (n_g, n_u)).astype(np.int64)
    rec = spans.Recorder(targets=(), calls=compose_roofline.CALLS)
    rec.install()
    try:
        out = gridax.conv_cycles(div, occ, bw, w)
    finally:
        rec.uninstall()
    (_, _, ops), = rec.calls[compose_roofline.KERNEL]
    divisors = np.stack([gridax._divisors(b) for b in bw])
    assert out.shape == (n_g, n_t, n_b) and out.dtype == np.int64
    assert kernel_bytes(*ops) == (div.nbytes + occ.nbytes + divisors.nbytes
                                  + w.nbytes + np.asarray(out).nbytes)
    if shape == (T, U, B, G):
        assert kernel_bytes(*ops) == 144 + 192 + 288 + 120 + 320


def test_the_harness_wraps_the_calls_its_readers_name(tmp_path):
    """A reader names the program calls it reads in ``CALLS``; a new
    kernel's share needs its metric file alone."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cell in bench["workloads"]:
        assert harness.calls_of(bench, cell["name"]) == \
            [compose_roofline.KERNEL]
    (tmp_path / "bench" / "metrics").mkdir(parents=True)
    (tmp_path / "bench" / "metrics" / "new_roofline.py").write_text(
        'CALLS = (("repro.core.gridax", "outer_add"),)\n'
        "def read(run):\n    return None\n")
    new = {"workloads": [{"name": "x"}],
           "per_layer": [{"name": "new_roofline", "workloads": ["x"]}]}
    assert harness.calls_of(new, "x", tmp_path) == \
        [("repro.core.gridax", "outer_add")]


def fake_run(by_span, device_calls=(), kernel_calls=()):
    rec = spans.Recorder(targets=())
    rec.device_calls = list(device_calls)
    rec.calls[compose_roofline.KERNEL] = list(kernel_calls)
    dev = trace.DeviceSummary(window_s=10.0, busy_s=1.0,
                              op_s=sum(by_span.values()), ops=[],
                              op_s_by_span=by_span)
    return harness.Run(setup_s=1.0, t_start=0.0, t_end=10.0, records=[],
                       stats=(None, None), tables=({}, {}), recorder=rec,
                       device=dev, peaks=PEAKS)


def test_reduce_roofline_divides_by_the_reduce_span_only():
    run = fake_run({"repro.dse.reduce": 0.004,
                    "repro.dse.compose_device": 0.1, "other": 0.01},
                   device_calls=[(1.0, 1.1, 25_000), (2.0, 2.1, 25_000),
                                 (11.0, 11.1, 10 ** 9)])   # after the window
    # 50,000 candidates x 8 B at 1 GB/s = 0.4 ms of 4 ms
    assert harness.reader("reduce_roofline")(run) == pytest.approx(10.0)


def test_compose_roofline_divides_by_the_compose_span_only():
    ops = (((6, T, U), 4), ((4, T, U), 8), ((3, B), 8), ((G, U), 8))
    run = fake_run({"repro.dse.reduce": 0.5,
                    "repro.dse.compose_device": 1064e-9 * 4, "other": 0.0},
                   kernel_calls=[(3.0, 3.1, ops), (-1.0, -0.9, ops)])
    assert harness.reader("compose_roofline")(run) == pytest.approx(25.0)


@pytest.mark.parametrize("name", ["reduce_roofline", "compose_roofline"])
def test_a_roofline_with_nothing_to_read_is_left_out(name):
    run = fake_run({"repro.dse.reduce": 0.0,
                    "repro.dse.compose_device": 0.0, "other": 0.1},
                   device_calls=[(1.0, 1.1, 100)],
                   kernel_calls=[(1.0, 1.1, (((6, 1, 1), 4),) * 4)])
    assert harness.reader(name)(run) is None
    assert harness.reader(name)(dataclasses.replace(run, device=None)) \
        is None
