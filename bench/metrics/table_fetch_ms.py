"""Table cache lookups inside grid composition (``repro.dse.table_fetch``:
the batch builder's cache check and the ``get_*_table`` calls, one
pass per matrix kind), per query answered in the window, ms."""
from bench.metrics._program import window_ms


def read(run):
    return window_ms(run, "repro.dse.table_fetch")
