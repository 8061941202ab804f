"""The program's obs spans on the device trace's clock.

The obs ``Tracer`` stamps its spans in ``time.perf_counter`` seconds; the
profiler stamps its events in nanoseconds on a clock of its own. The
benchmark reads both clocks at the two ends of its window: the ``window``
annotation on the profiler's clock (``ev.window``) and ``ctx.t0``/``ctx.t1``
on perf_counter, read just inside it (``ev.span_window``). The line through
those two anchors maps any perf_counter instant onto the trace; its slope
takes up a drift between the clocks over the window. A mapped instant errs
by the gap between opening the annotation and reading ``t0`` (microseconds
on a host), which :func:`containment` measures against the benchmark's
``submit`` annotations.

A trace without the program's spans (a program that records none) gives the
readers nothing to read: they return None.
"""
from __future__ import annotations

from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from bench.lib import trace

#: the client thread of the open-loop runner (its ``submit`` calls)
CLIENT = "MainThread"


def clock(ev) -> Optional[Callable[[float], float]]:
    """perf_counter seconds -> trace ns, by the line through the anchors."""
    (s0, s1), (w0, w1) = ev.span_window, ev.window
    if s1 <= s0 or w1 <= w0:
        return None
    slope = (w1 - w0) / (s1 - s0)
    return lambda t: w0 + (t - s0) * slope


def mapped(ev, names: Sequence[str]) -> List[Tuple[Dict, float, float]]:
    """(span, start ns, end ns) of every obs span named in ``names``."""
    to_ns = clock(ev)
    if to_ns is None:
        return []
    return [
        (s, to_ns(s["t0"]), to_ns(s["t0"] + s["dur"]))
        for s in ev.spans
        if s["name"] in names
    ]


def started_in_window(ev, name: str, entries: Optional[Sequence[str]] = None) -> List[Dict]:
    """Spans ``name`` that started inside the window (perf_counter clock);
    ``entries`` keeps ``lock_wait`` spans of those entry points only."""
    lo, hi = ev.span_window
    return [
        s
        for s in ev.spans
        if s["name"] == name
        and lo <= s["t0"] < hi
        and (entries is None or s["args"].get("entry") in entries)
    ]


def p95_ms(spans: Iterable[Dict]) -> Optional[float]:
    """95th percentile of the spans' durations in ms (numpy's linear rule)."""
    durs = [s["dur"] for s in spans]
    if not durs:
        return None
    return float(np.percentile(durs, 95) * 1e3)


def overlap_ns(a: Sequence[Tuple[float, float]], b: Sequence[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted lists of disjoint pieces."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_while_pct(ev, names: Sequence[str]) -> Optional[float]:
    """Share of the window in which a mapped span of ``names`` was open and
    no op ran on the device, in percent, mean over chips."""
    spans = mapped(ev, names)
    if not spans or not ev.ops:
        return None
    lo, hi = ev.window
    waiting = trace.merged(((a, b) for _, a, b in spans), lo, hi)
    open_ns = sum(b - a for a, b in waiting)
    idle = [
        open_ns - overlap_ns(waiting, trace.merged(((o.start, o.end) for o in ops), lo, hi))
        for ops in ev.ops.values()
    ]
    return 100.0 * sum(idle) / len(idle) / (hi - lo)


def open_at(ev, t_ns: float, names: Sequence[str]) -> List[str]:
    """Names of the mapped spans of ``names`` open at trace instant ``t_ns``
    (``lock_wait`` with its entry point), sorted and without repeats."""
    out = set()
    for s, a, b in mapped(ev, names):
        if a <= t_ns < b:
            entry = s["args"].get("entry")
            out.add(f"{s['name']}:{entry}" if entry else s["name"])
    return sorted(out)


def containment(ev) -> Optional[Dict[str, float]]:
    """How well the mapped client-side spans sit in the ``submit`` annotations.

    Every ``pending`` span, and every ``lock_wait`` of ``submit`` or
    ``flush_ready`` on the client thread, starts inside a ``submit`` call of
    the runner. Once mapped each should start inside a ``submit``
    annotation; ``outside`` counts those that do not, and ``max_outside_ns``
    is the farthest any lies from the nearest annotation (0 when all are
    inside). ``lead_ns_max`` is the largest distance from an annotation's
    start to a span start inside it.
    """
    calls = sorted((a, b) for n, a, b in ev.host if n == "submit")
    spans = [
        a
        for s, a, _ in mapped(ev, ("pending", "lock_wait"))
        if s["name"] == "pending"
        or (s["tid"] == CLIENT and s["args"].get("entry") in ("submit", "flush_ready"))
    ]
    if not calls or not spans:
        return None
    starts = np.array([a for a, _ in calls])
    outside, far, lead = 0, 0.0, 0.0
    for t in spans:
        k = int(np.searchsorted(starts, t, side="right")) - 1
        if k >= 0 and t <= calls[k][1]:
            lead = max(lead, t - calls[k][0])
            continue
        outside += 1
        gaps = [calls[k + 1][0] - t] if k + 1 < len(calls) else []
        if k >= 0:
            gaps.append(t - calls[k][1])
        far = max(far, min(gaps))
    return {"spans": len(spans), "outside": outside, "max_outside_ns": far, "lead_ns_max": lead}
