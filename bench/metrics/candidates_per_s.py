"""Candidates priced per second: the ``n_candidates`` of every query
answered in the window, over the window (host clock, client side)."""


def read(run):
    total = sum(r.n_candidates for r in run.completed)
    return total / run.window_s if total else None
