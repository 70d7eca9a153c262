"""The spans the benchmark wraps around the program's calls."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import spans  # noqa: E402


@pytest.mark.parametrize("target", [
    ("repro.core.no_such_module", "f", "compose"),
    ("repro.core.dse", "no_such_function", "device"),
    ("repro.core.dse", "_GridEngine.no_such_method", "compose"),
    ("repro.core.dse", "NoSuchClass.conv_matrices", "compose"),
])
def test_missing_program_attribute_raises(target):
    with pytest.raises(spans.MissingAttribute):
        spans.Recorder(targets=spans.TARGETS + (target,))
    with pytest.raises(spans.MissingAttribute):
        spans.Recorder(calls=[target[:2]])      # a call a reader names


def test_spans_cover_a_device_search_and_come_off_again():
    from repro.core import INFER_PRESETS, dse, gridax
    from repro.core.study import Study, Workload
    before = (dse._GridEngine.conv_matrices, gridax.reduce_cycles_many,
              gridax.conv_cycles)
    kernel = ("repro.core.gridax", "conv_cycles")
    rec = spans.Recorder(calls=[kernel])
    rec.install()
    try:
        study = Study(INFER_PRESETS[64], sizes=(256, 512, 1024, 2048),
                      bws=(256, 512, 1024, 2048), backend="jax", store=None,
                      workers=0)
        res = study.search_requests([(Workload("resnet50", batch=3),
                                      2048, 2048)])[0]
    finally:
        rec.uninstall()
    assert (dse._GridEngine.conv_matrices, gridax.reduce_cycles_many,
            gridax.conv_cycles) == before
    for kind in ("search", "compose", "device"):
        assert rec.spans[kind], kind
    assert [c for _, _, c in rec.device_calls] == [res.n_candidates]
    (_, _, ops), = rec.calls[kernel]          # one conv composition call
    assert [len(shape) for shape, _ in ops] == [3, 3, 2, 2]
    assert rec.total("search", 0, float("inf")) >= \
        rec.total("compose", 0, float("inf"))
