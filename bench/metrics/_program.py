"""Helpers for the readers of the program's own spans
(``repro.core.tracing``); not a metric.  A program without that module
records no spans, and its readers return None."""
from bench.metrics._common import per_query_ms


def tracing():
    try:
        from repro.core import tracing
    except ImportError:
        return None
    return tracing


def window_ms(run, name):
    """ms of ``name`` spans per query answered in the window: the spans
    that started from the window's start until the last answered query
    was done.  The query still open at the window's end is not answered,
    so its spans are left out, as its time is by the client-side
    metrics."""
    t = tracing()
    if t is None:
        return None
    done = [r.t_done for r in run.completed]
    if not done:
        return None
    return per_query_ms(run, t.total(name, run.t_start, max(done)))


def setup_spans(run, name):
    """``name`` spans that started in set-up, from process start to the
    first timed query; None where the program records no spans."""
    t = tracing()
    if t is None:
        return None
    return t.spans(name, run.t_start - run.setup_s, run.t_start)
