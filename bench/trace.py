"""Reduce a JAX profiler trace (``.xplane.pb``) to device metrics.

Device operations are the events on the ``XLA Ops`` line of each
``/device:`` plane.  Busy time is the union of their intervals, averaged
over the devices; per-operation time is the sum of their durations.  An
idle gap is an interval of the window (the host annotation
``bench.window``) in which no operation runs on a device; it is named
by the ``bench.*`` host span kind (``bench/spans.py``) that covers most
of it, each part going to the innermost kind that covers it, or
``host: none`` where the host was mostly in none of them.

Device time is also split by the program's own spans, with no list of
names: a launching span is the innermost host annotation named
``repro.*`` around a launch (JAX's ``PjitFunction(...)`` event, where a
jitted call is dispatched) on the same host thread, and each operation
goes to the launching span nearest its midpoint, or to ``other``.  The
program's device calls end in a sync, so the work a span launches ends
inside it; but the trace places the device's clock against the host's
only to within about half a millisecond, so a span reaches ``SLACK_NS``
past both of its ends, and an operation that two spans reach goes to
the one its midpoint lies nearer, the innermost on a tie.  A span that
launches nothing takes no device time, however near.  Times are
seconds; event times are nanoseconds on the trace's own clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import (Callable, Dict, Iterable, List, Optional, Sequence,
                    Tuple)

Interval = Tuple[float, float]
Event = Tuple[str, float, float]          # name, start, end

OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:"
HOST_PREFIX = "bench."
WINDOW = "bench.window"
# innermost first: a gap inside a device call is the device call's
HOST_ORDER = ("bench.device", "bench.compose",
              "bench.float_steps", "bench.search", "bench.client")
PROGRAM_PREFIX = "repro."
LAUNCH_PREFIX = "PjitFunction("
OTHER = "other"
# On a TPU v5e the trace read device ops up to 0.5 ms before the host
# span that launched them.
SLACK_NS = 2e6


def latest_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def op_label(text: str) -> str:
    """A device op event's HLO text shortened to its name and kind
    (``%fusion.1 fusion``, ``%custom-call.3 custom-call:X64Combine``)."""
    name, _, rest = text.partition(" = ")
    if not rest:
        return text
    m = re.search(r" ([a-z][a-z0-9_-]*)\(", rest)
    kind = m.group(1) if m else "?"
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    return f"{name} {kind}" + (f":{target.group(1)}" if target else "")


def is_device_ops(plane: str, line: str) -> bool:
    return plane.startswith(DEVICE_PREFIX) and line == OPS_LINE


def events(path: str, select: Callable[[str, str], bool],
           by_line: bool = False) -> Dict[object, List[Event]]:
    """Per plane, or per (plane, line) with ``by_line``, the (name,
    start_ns, end_ns) of the events on the lines ``select(plane name,
    line name)`` picks."""
    import jax
    out: Dict[object, List[Event]] = defaultdict(list)
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            if select(plane.name, line.name):
                key = (plane.name, line.name) if by_line else plane.name
                out[key] += [(e.name, e.start_ns,
                              e.start_ns + e.duration_ns)
                             for e in line.events]
    return dict(out)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def gaps(busy: Sequence[Interval], t0: float, t1: float) -> List[Interval]:
    """The parts of [t0, t1] that ``busy`` (merged, sorted) leaves."""
    out, cur = [], t0
    for a, b in busy:
        if a > cur:
            out.append((cur, min(a, t1)))
        cur = max(cur, b)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1))
    return [(a, b) for a, b in out if b > a]


def _minus(ivs: List[Interval], cut: List[Interval]) -> List[Interval]:
    """``ivs`` (merged, sorted) without ``cut`` (merged, sorted)."""
    out = []
    for a, b in ivs:
        out += gaps([(max(c, a), min(d, b)) for c, d in cut
                     if d > a and c < b], a, b)
    return out


def host_label(gap: Interval, spans: Sequence[Tuple[str, float, float]]
               ) -> str:
    """The host span kind that takes most of ``gap``, each part of the
    gap going to the innermost kind (``HOST_ORDER``) that covers it."""
    a, b = gap
    rest, best, best_t = [(a, b)], "host: none", 0.0
    for kind in HOST_ORDER:
        cover = union([(max(s, a), min(e, b)) for n, s, e in spans
                       if n == kind and e > a and s < b])
        left = _minus(rest, cover)
        took = sum(y - x for x, y in rest) - sum(y - x for x, y in left)
        if took > best_t:
            best, best_t = kind, took
        rest = left
    if sum(y - x for x, y in rest) > best_t:
        best = "host: none"
    return best


def launching_spans(lines: Iterable[Sequence[Event]]) -> List[Event]:
    """The program spans that launch device work: on each host thread's
    line (a sequence of events), the innermost ``repro.*`` span around
    each ``PjitFunction(...)`` event, by start."""
    out = set()
    for evs in lines:
        open_: List[Event] = []
        for e in sorted(evs, key=lambda e: (e[1], -e[2])):
            while open_ and open_[-1][2] < e[1]:
                open_.pop()
            if e[0].startswith(PROGRAM_PREFIX):
                open_.append(e)
            elif e[0].startswith(LAUNCH_PREFIX) and open_:
                out.add(open_[-1])
    return sorted(out, key=lambda e: e[1])


def by_span(ops: Sequence[Event], launchers: Sequence[Event]
            ) -> Dict[str, float]:
    """Seconds of the ``ops`` per span name: each op goes to the span of
    ``launchers`` (sorted by start) that its midpoint lies nearest,
    within ``SLACK_NS`` of it, the shortest on a tie, else to
    ``OTHER``."""
    starts = [a for _, a, _ in launchers]
    longest = max((b - a for _, a, b in launchers), default=0.0)
    out: Dict[str, float] = defaultdict(float)
    for _, a, b in ops:
        mid = (a + b) / 2
        lo = bisect.bisect_left(starts, mid - SLACK_NS - longest)
        hi = bisect.bisect_right(starts, mid + SLACK_NS)
        near = [(max(s - mid, mid - e, 0.0), e - s, n)
                for n, s, e in launchers[lo:hi]
                if s - SLACK_NS <= mid <= e + SLACK_NS]
        out[min(near)[2] if near else OTHER] += (b - a) * 1e-9
    return dict(out)


@dataclass
class DeviceSummary:
    window_s: float
    busy_s: float                      # union of op intervals, per device
    op_s: float                        # sum of op durations, all devices
    ops: List[Tuple[str, float]]       # per op name, most time first
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)
    # op_s split by launching program span (``by_span``)
    op_s_by_span: Dict[str, float] = field(default_factory=dict)


def summarize(path: str, select: Callable[[str, str], bool] = is_device_ops,
              top: int = 10) -> DeviceSummary:
    """Device busy time, per-op time and per-launching-span time inside
    the window (the host annotation ``bench.window``), and the ``top``
    longest idle gaps of the first device, named by host spans."""
    host = events(path, lambda p, l: not p.startswith(DEVICE_PREFIX),
                  by_line=True)
    spans = [e for evs in host.values() for e in evs
             if e[0].startswith(HOST_PREFIX)]
    windows = [(a, b) for n, a, b in spans if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"{path}: {len(windows)} {WINDOW} annotations")
    t0, t1 = windows[0]
    per_plane = events(path, select)
    if not per_plane:
        raise ValueError(f"{path}: no device operation events")
    busy, op_s, all_ops = [], 0.0, []
    per_op: Dict[str, float] = defaultdict(float)
    first_busy: Optional[List[Interval]] = None
    for plane in sorted(per_plane):
        clipped = [(n, max(a, t0), min(b, t1))
                   for n, a, b in per_plane[plane] if b > t0 and a < t1]
        u = union([(a, b) for _, a, b in clipped])
        busy.append(sum(b - a for a, b in u))
        all_ops += clipped
        for n, a, b in clipped:
            per_op[op_label(n)] += (b - a) * 1e-9
            op_s += (b - a) * 1e-9
        if first_busy is None:
            first_busy = u
    longest = sorted(gaps(first_busy or [], t0, t1),
                     key=lambda g: g[0] - g[1])[:top]
    named = [(host_label(g, spans), (g[1] - g[0]) * 1e-9) for g in longest]
    return DeviceSummary(
        window_s=(t1 - t0) * 1e-9,
        busy_s=sum(busy) / len(busy) * 1e-9,
        op_s=op_s,
        ops=sorted(per_op.items(), key=lambda x: -x[1])[:top],
        idle_gaps=named[:top],
        op_s_by_span=by_span(all_ops, launching_spans(host.values())))
