"""Host GEMM composition (``repro.dse.gemm_compose``: the GEMM table
fetch and the per-size-triple loop that sums each triple's GEMM cost
columns), per query answered in the window, ms."""
from bench.metrics._program import window_ms


def read(run):
    return window_ms(run, "repro.dse.gemm_compose")
