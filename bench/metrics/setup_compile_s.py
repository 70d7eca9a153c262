"""JAX's tracing, lowering and backend compiles in set-up
(``repro.jax.compile`` spans of the ``/jax/core/compile/`` events), as
the union of their intervals: a jitted function traced inside another
one's trace is counted once.  Compile-cache reads happen inside the
backend compile, so they are not added, s."""
from bench.metrics._program import setup_spans
from bench.trace import union


def read(run):
    found = setup_spans(run, "repro.jax.compile")
    if found is None:
        return None
    return sum(b - a for a, b in union(
        [(s.t0, s.t1) for s in found
         if s.info["event"].startswith("/jax/core/compile/")]))
