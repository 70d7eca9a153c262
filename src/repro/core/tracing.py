"""Spans at the layer boundaries of the DSE query path.

A span is one named interval of the program's own work on
``time.perf_counter``, the clock a client of the service reads too: a
request from submit until its answer is set, a coalescing window, one
graph expansion, one composition, one table build, one device
reduction, one float step.  Each records its span id, the id of the
span it runs inside (from a ``contextvars`` variable, so a parent
follows the work onto another thread only where that thread runs in a
copy of the context), the request ids it was priced for, the thread,
``t0``/``t1`` and an optional count ``n`` at the same boundary
(candidates, tables, layers).  ``requests`` hands request ids to the
spans opened inside a block without recording a span of its own.

Recording is always on.  Spans are kept in memory, in one bounded deque
per name (``MAXLEN``), so a frequent name never pushes a rare one out:
the set-up's compiles and table builds outlive any window of queries.
The busiest names (``repro.study.layers``, ``repro.dse.table_fetch``)
record two spans a query, about 940 in 51 s of back-to-back ResNet-50
inference queries on a TPU v5e host; ``MAXLEN`` keeps four times that.
``total`` and ``spans`` read them back by the interval their starts
fall in.

Once JAX is loaded, a span entered with ``with span(...)`` is also a
``jax.profiler.TraceAnnotation`` of the same name, so a profiler trace
shows it on the device trace's clock.  Two kinds have no annotation: a
span opened with ``begin`` (it may close on another thread) and a
compile span (JAX reports it after the fact).  Nothing here imports
JAX: the numpy search path stays free of it.
"""
from __future__ import annotations

import contextvars
import itertools
import sys
import threading
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple, Union

MAXLEN = 4096           # spans kept per name
COMPILE = "repro.jax.compile"
# JAX's duration events a compile span records: trace, lowering and
# backend compile, and the persistent-cache read inside the last
COMPILE_PREFIX = "/jax/core/compile/"
CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"

RequestIds = Optional[Tuple[int, ...]]

_span_ids = itertools.count(1)
_request_ids = itertools.count(1)
_current: contextvars.ContextVar[Union[None, "Span", "_Requests"]] = \
    contextvars.ContextVar("repro_tracing_current", default=None)
_kept: Dict[str, Deque["Span"]] = {}
_watching_jax = False


class Span:
    """One recorded interval; ``t1`` is None until it ends."""
    __slots__ = ("name", "sid", "parent", "rid", "thread", "t0", "t1", "n",
                 "info")

    def __init__(self, name: str, rid: Union[None, int, Tuple[int, ...]],
                 n: Optional[int]):
        parent = _current.get()
        if rid is None:
            rid = None if parent is None else parent.rid
        else:
            rid = _rids(rid)
        self.name = name
        self.sid = next(_span_ids)
        self.parent = None if parent is None else parent.sid
        self.rid: RequestIds = rid
        self.thread = threading.get_ident()
        self.n = n
        self.info: Optional[dict] = None
        self.t0 = time.perf_counter()
        self.t1: Optional[float] = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def end(self) -> float:
        """Close a span opened with ``begin``, keep it, and return its
        duration in seconds."""
        self.t1 = time.perf_counter()
        _keep(self)
        return self.t1 - self.t0

    def __repr__(self) -> str:
        return (f"Span({self.name!r}, sid={self.sid}, parent={self.parent}, "
                f"rid={self.rid}, n={self.n}, t0={self.t0}, t1={self.t1})")


def _keep(s: Span) -> None:
    d = _kept.get(s.name)
    if d is None:
        d = _kept.setdefault(s.name, deque(maxlen=MAXLEN))
    d.append(s)


def _annotation(name: str):
    """A ``TraceAnnotation`` once JAX is loaded (a module still being
    imported has no ``profiler`` yet), else None."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    return None if profiler is None else profiler.TraceAnnotation(name)


def _rids(rid: Union[int, Tuple[int, ...]]) -> Tuple[int, ...]:
    return (rid,) if isinstance(rid, int) else rid


class _Requests:
    """What ``requests`` puts in the context: the enclosing span's id, as
    the parent of the spans opened inside, and their request ids."""
    __slots__ = ("sid", "rid")

    def __init__(self, sid: Optional[int], rid: Tuple[int, ...]):
        self.sid, self.rid = sid, rid


class requests:
    """``with requests(rid):`` the spans opened inside the block carry
    ``rid`` (an id or a tuple of ids) and keep the enclosing span as
    their parent.  Nothing is recorded for the block itself."""
    __slots__ = ("_rid", "_token")

    def __init__(self, rid: Union[int, Tuple[int, ...]]):
        self._rid = _rids(rid)

    def __enter__(self) -> None:
        parent = _current.get()
        self._token = _current.set(
            _Requests(None if parent is None else parent.sid, self._rid))

    def __exit__(self, *exc) -> bool:
        _current.reset(self._token)
        return False


class span:
    """``with span(name, rid=None, n=None) as s:`` records one span around
    the block and makes it the parent of the spans opened inside.  A
    ``rid`` of None takes the parent's request ids.  The block may set
    ``s.n`` or ``s.rid`` once it knows them."""
    __slots__ = ("_name", "_rid", "_n", "_span", "_token", "_ann")

    def __init__(self, name: str,
                 rid: Union[None, int, Tuple[int, ...]] = None,
                 n: Optional[int] = None):
        self._name, self._rid, self._n = name, rid, n

    def __enter__(self) -> Span:
        self._ann = _annotation(self._name)
        if self._ann is not None:
            self._ann.__enter__()
        s = self._span = Span(self._name, self._rid, self._n)
        self._token = _current.set(s)
        return s

    def __exit__(self, *exc) -> bool:
        s = self._span
        s.t1 = time.perf_counter()
        _current.reset(self._token)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _keep(s)
        return False


def begin(name: str, rid: Union[None, int, Tuple[int, ...]] = None,
          n: Optional[int] = None) -> Span:
    """Open a span that ``Span.end`` closes, on any thread.  It takes its
    parent from the caller's context but is never a parent itself."""
    return Span(name, rid, n)


def new_request_id() -> int:
    return next(_request_ids)


def spans(name: str, t0: float = float("-inf"),
          t1: float = float("inf")) -> List[Span]:
    """The kept spans of ``name`` that started in ``[t0, t1)``, oldest
    first."""
    d = _kept.get(name)
    if d is None:
        return []
    return [s for s in d.copy() if t0 <= s.t0 < t1]


def total(name: str, t0: float = float("-inf"),
          t1: float = float("inf")) -> float:
    """Seconds of the kept spans of ``name`` that started in
    ``[t0, t1)``."""
    return sum(s.t1 - s.t0 for s in spans(name, t0, t1))


def clear() -> None:
    """Forget every kept span; new deques take the current ``MAXLEN``."""
    _kept.clear()


def _on_jax_duration(event: str, duration_secs: float, **kwargs) -> None:
    if not (event.startswith(COMPILE_PREFIX) or event == CACHE_READ):
        return
    s = Span(COMPILE, None, None)
    s.t1 = s.t0
    s.t0 -= duration_secs
    s.info = {"event": event, "fun_name": kwargs.get("fun_name")}
    _keep(s)


def watch_jax_compiles() -> None:
    """Record every trace, lowering, backend compile and compile-cache
    read JAX reports as a ``repro.jax.compile`` span, ``(now - secs,
    now)``, with the event and the jit's ``fun_name`` in ``info``.  The
    device path's entry calls this; later calls do nothing."""
    global _watching_jax
    if _watching_jax:
        return
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
    _watching_jax = True
