"""Grid composition (``_GridEngine.conv_matrices``, ``simd_matrices``,
``gemm_matrices``), table fetches inside it included, per query
answered in the window, ms."""
from bench.metrics._common import per_query_ms


def read(run):
    if run.recorder is None:
        return None
    return per_query_ms(run, run.recorder.total("compose", run.t_start,
                                                run.t_end))
