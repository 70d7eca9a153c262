"""Spans the benchmark records around the program's own calls.

The program has no spans of its own, so the benchmark wraps module
attributes from the outside (as ``chip_smoke.device_recorder`` does) and
records, per call, the thread and the host-clock interval.  A wrapped
name that is missing raises ``MissingAttribute``: a renamed function
must fail the benchmark, never read as zero time.  With a trace
running, every span is also a ``jax.profiler.TraceAnnotation`` named
``bench.<kind>``, so the host's activity sits on the device trace's
clock.
"""
from __future__ import annotations

import functools
import importlib
import threading
import time
from typing import Dict, List, Optional, Tuple

# (module, attribute path, span kind)
TARGETS: Tuple[Tuple[str, str, str], ...] = (
    ("repro.core.study", "Study.search_requests", "search"),
    ("repro.core.dse", "_GridEngine.conv_matrices", "compose"),
    ("repro.core.dse", "_GridEngine.simd_matrices", "compose"),
    ("repro.core.dse", "_GridEngine.gemm_matrices", "compose"),
    ("repro.core.gridax", "reduce_cycles_many", "device"),
    ("repro.core.gridax", "outer_add", "device"),
)


class MissingAttribute(AttributeError):
    pass


def resolve(module: str, path: str):
    """(owner object, attribute name, current value) of ``module.path``;
    raises ``MissingAttribute`` when any part is absent."""
    try:
        owner = importlib.import_module(module)
    except ImportError as e:
        raise MissingAttribute(f"{module}: {e}") from e
    *parents, name = path.split(".")
    for p in parents:
        if not hasattr(owner, p):
            raise MissingAttribute(f"{module}.{path}: no {p!r}")
        owner = getattr(owner, p)
    if not hasattr(owner, name):
        raise MissingAttribute(f"{module}.{path}: no {name!r}")
    return owner, name, getattr(owner, name)


def _operands(args) -> Tuple[Tuple[Tuple[int, ...], int], ...]:
    """(shape, bytes per element) of each array argument, in order."""
    return tuple((tuple(a.shape), a.dtype.itemsize) for a in args
                 if hasattr(a, "shape") and hasattr(a, "dtype"))


def _candidates(out) -> int:
    """Candidates in a device call's result: one grid, or a list of
    (grid, best, worst) per network."""
    if hasattr(out, "size"):
        return int(out.size)
    return sum(int(o[0].size) for o in out)


class Recorder:
    """Per-kind lists of (thread id, start, end) on ``time.perf_counter``,
    the ``search`` span of every request (by ``id``), the candidates
    each outermost device call returned, and, for each (module,
    attribute path) in ``calls`` (which metric readers name in their
    ``CALLS``), the (start, end, operand shapes) of every call."""

    def __init__(self, targets=TARGETS, calls=()):
        self.spans: Dict[str, List[Tuple[int, float, float]]] = {}
        self.search_of: Dict[int, Tuple[float, float]] = {}
        self.device_calls: List[Tuple[float, float, int]] = []
        self.calls: Dict[Tuple[str, str], List[Tuple[float, float, tuple]]] \
            = {c: [] for c in calls}
        self.annotate = False
        self._depth = threading.local()
        self._saved: List[tuple] = []
        self._resolved = [resolve(m, p) + (kind,)
                          for m, p, kind in targets]
        self._called = [resolve(*c) + (c,) for c in calls]

    def install(self) -> None:
        for owner, name, fn, kind in self._resolved:
            self._saved.append((owner, name, fn))
            setattr(owner, name, self._wrap(fn, kind))
        for owner, name, fn, c in self._called:
            self._saved.append((owner, name, fn))
            setattr(owner, name, self._wrap_call(fn, self.calls[c]))

    def uninstall(self) -> None:
        while self._saved:
            owner, name, fn = self._saved.pop()
            setattr(owner, name, fn)

    def _wrap(self, fn, kind: str):
        spans = self.spans.setdefault(kind, [])
        label = f"bench.{kind}"
        rec = self

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            depth = getattr(rec._depth, kind, 0)
            setattr(rec._depth, kind, depth + 1)
            ann = None
            if rec.annotate:
                import jax
                ann = jax.profiler.TraceAnnotation(label)
                ann.__enter__()
            t0 = time.perf_counter()
            try:
                out = fn(*args, **kwargs)
            finally:
                t1 = time.perf_counter()
                if ann is not None:
                    ann.__exit__(None, None, None)
                setattr(rec._depth, kind, depth)
            spans.append((threading.get_ident(), t0, t1))
            if kind == "search":
                for r in args[1]:
                    rec.search_of[id(r)] = (t0, t1)
            elif kind == "device" and depth == 0:
                rec.device_calls.append((t0, t1, _candidates(out)))
            return out
        return wrapper

    @staticmethod
    def _wrap_call(fn, calls: list):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            calls.append((t0, time.perf_counter(),
                          _operands([*args, *kwargs.values()])))
            return out
        return wrapper

    def total(self, kind: str, t0: float, t1: float) -> float:
        """Seconds of ``kind`` spans that started in [t0, t1)."""
        return sum(b - a for _, a, b in self.spans.get(kind, ())
                   if t0 <= a < t1)


def annotate(label: str, on: bool):
    """A ``TraceAnnotation`` when a trace runs, else a no-op context."""
    if not on:
        import contextlib
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(label)


def search_span(rec: Optional[Recorder], request) -> Optional[float]:
    if rec is None:
        return None
    s = rec.search_of.get(id(request))
    return None if s is None else s[1] - s[0]
