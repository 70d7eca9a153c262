"""Table builds in set-up (``repro.dse.table_build``: batch, scalar and
pool builds and the SIMD tiling derivation; never a cache hit), s."""
from bench.metrics._program import setup_spans


def read(run):
    found = setup_spans(run, "repro.dse.table_build")
    return None if found is None else sum(s.t1 - s.t0 for s in found)
