"""From the profiler's trace to what the per-layer metrics read.

A traced run records the measured window with ``jax.profiler``. The trace's
device planes are named ``/device:TPU:<k>``; their ``XLA Ops`` line holds one
event per HLO instruction that ran, named by the instruction's HLO text
(``%sort.25 = (s32[8,636104]...) sort(...)``), and ``Async XLA Ops`` holds
the in-flight spans of asynchronous copies and collectives. Host planes hold
the benchmark's own ``jax.profiler.TraceAnnotation`` spans (``window``,
``sort_call``, ``submit``, ...). Host and device events share one clock, in
nanoseconds.

:class:`Evidence` is the reduction every metric reader gets: the window, the
device ops of each chip, the benchmark's host spans, the number of timed calls
and the program's own obs spans. The functions below are the arithmetic the
readers share.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
#: the host spans the benchmark writes around its calls into each layer
ANNOTATIONS = ("window", "sort_call", "result_wait", "submit", "generate", "lax_sort")
#: HLO opcodes of the exchange between chips (sync and async forms)
COLLECTIVES = frozenset(
    {
        "all-to-all",
        "all-gather",
        "all-gather-start",
        "all-gather-done",
        "all-reduce",
        "all-reduce-start",
        "all-reduce-done",
        "collective-permute",
        "collective-permute-start",
        "collective-permute-done",
        "reduce-scatter",
        "ragged-all-to-all",
    }
)


@dataclasses.dataclass(frozen=True)
class Op:
    """One device op: its HLO instruction name, opcode and interval (ns)."""

    name: str
    opcode: str
    start: float
    end: float


@dataclasses.dataclass
class Evidence:
    """What one traced run gives its per-layer readers."""

    window: Tuple[float, float]  # ns, the benchmark's ``window`` span
    ops: Dict[int, List[Op]]  # chip -> its ``XLA Ops`` events
    async_ops: Dict[int, List[Op]]  # chip -> its ``Async XLA Ops`` events
    host: List[Tuple[str, float, float]]  # the benchmark's host spans (ns)
    calls: int = 0  # timed calls (sorts or batches) inside the window
    spans: List[Dict] = dataclasses.field(default_factory=list)  # obs spans
    span_window: Tuple[float, float] = (0.0, 0.0)  # perf_counter seconds
    compiles: int = 0  # executables built inside the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def region(self, name: str) -> Optional[Tuple[float, float]]:
        """The first host span of that name, if the run wrote one."""
        return next(((a, b) for n, a, b in self.host if n == name), None)


def opcode(hlo_text: str) -> str:
    """The opcode of one HLO instruction as the trace names it.

    ``%sort.25 = (s32[8]{0}, s32[8]{0}) sort(...)`` gives ``sort``; an event
    that is not HLO text gives its name without a numeric suffix.
    """
    head, sep, rest = hlo_text.partition(" = ")
    if not sep:
        return re.sub(r"\.\d+$", "", hlo_text.lstrip("%"))
    rest = rest.lstrip()
    if rest.startswith("("):  # a tuple shape: skip to its closing bracket
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                rest = rest[i + 1 :]
                break
    else:
        rest = rest.partition(" ")[2]
    return rest.lstrip().partition("(")[0].strip()


def instruction(hlo_text: str) -> str:
    """``%sort.25 = ...`` gives ``sort.25``."""
    return hlo_text.partition(" = ")[0].lstrip("%")


def merged(intervals: Iterable[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The union of ``intervals`` within [lo, hi], as disjoint sorted pieces."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def union_ns(intervals: Iterable[Tuple[float, float]], lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` clipped to [lo, hi]."""
    return sum(b - a for a, b in merged(intervals, lo, hi))


def busy_ns(ev: Evidence, chip: int, lo: Optional[float] = None, hi: Optional[float] = None) -> float:
    lo, hi = (ev.window if lo is None else (lo, hi))
    return union_ns(((o.start, o.end) for o in ev.ops.get(chip, ())), lo, hi)


def mean_busy_s(ev: Evidence) -> Optional[float]:
    """Seconds of the window in which an op ran, averaged over the chips."""
    if not ev.ops:
        return None
    return sum(busy_ns(ev, c) for c in ev.ops) / len(ev.ops) / 1e9


def idle_share_pct(ev: Evidence) -> Optional[float]:
    """1 − busy/window, in percent, averaged over the chips."""
    busy = mean_busy_s(ev)
    if busy is None or ev.window_s <= 0:
        return None
    return 100.0 * (1.0 - busy / ev.window_s)


def opcode_ms_per_call(ev: Evidence, opcodes: Sequence[str]) -> Optional[float]:
    """Device ms of ops with these opcodes per timed call, mean over chips.

    None where no such op ran in the window, or no call was timed.
    """
    want = frozenset(opcodes)
    lo, hi = ev.window
    per_chip = [
        sum(max(0.0, min(o.end, hi) - max(o.start, lo)) for o in ops if o.opcode in want)
        for ops in ev.ops.values()
    ]
    if not ev.calls or not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) / ev.calls / 1e6


def collective_ms_per_call(ev: Evidence) -> Optional[float]:
    """Device ms per call in which a collective was running, mean over chips.

    The union of the synchronous collective ops and the in-flight spans of
    asynchronous ones, so a start/done pair is not counted twice.
    """
    lo, hi = ev.window
    per_chip = []
    for chip in ev.ops:
        spans = [
            (o.start, o.end)
            for o in [*ev.ops.get(chip, ()), *ev.async_ops.get(chip, ())]
            if o.opcode in COLLECTIVES
        ]
        per_chip.append(union_ns(spans, lo, hi))
    if not ev.calls or not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) / ev.calls / 1e6


def region_device_ms(ev: Evidence, name: str) -> Optional[float]:
    """Device busy ms inside the host span ``name``, mean over chips."""
    reg = ev.region(name)
    if reg is None or not ev.ops:
        return None
    return sum(busy_ns(ev, c, *reg) for c in ev.ops) / len(ev.ops) / 1e6


def host_activity(ev: Evidence, t: float) -> str:
    """The benchmark spans open at instant ``t``, innermost last."""
    open_ = sorted(
        ((a, n) for n, a, b in ev.host if a <= t < b and n != "window"), key=lambda x: x[0]
    )
    return "+".join(n for _, n in open_) or "none"


def breakdown(ev: Evidence, top: int = 10) -> Dict[str, List[List]]:
    """The device ops that took most time and the longest idle gaps.

    Op seconds are summed over the chips and divided by their number. Each
    idle gap is named by the benchmark spans open at its middle, with its
    chip in front when there are several.
    """
    lo, hi = ev.window
    total: Dict[str, float] = {}
    for ops in ev.ops.values():
        for o in ops:
            d = min(o.end, hi) - max(o.start, lo)
            if d > 0:
                key = f"{instruction(o.name)} ({o.opcode})"
                total[key] = total.get(key, 0.0) + d
    n = max(1, len(ev.ops))
    device_ops = sorted(([k, v / n / 1e9] for k, v in total.items()), key=lambda x: -x[1])
    gaps = []
    for chip, ops in ev.ops.items():
        busy = merged(((o.start, o.end) for o in ops), lo, hi)
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                label = host_activity(ev, (a + b) / 2)
                gaps.append([f"chip{chip}:{label}" if len(ev.ops) > 1 else label, (b - a) / 1e9])
    gaps.sort(key=lambda x: -x[1])
    return {"device_ops": device_ops[:top], "idle_gaps": gaps[:top]}


# --------------------------------------------------------------- loading
def newest_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    return max(paths, key=os.path.getmtime)


def from_planes(planes, chips: Optional[Sequence[int]] = None) -> Tuple[Dict, Dict, List, Tuple[float, float]]:
    """(ops, async_ops, host spans, window) from profiler planes.

    ``planes`` is ``jax.profiler.ProfileData(...).planes`` or anything with
    the same ``name``/``lines``/``events`` shape; ``chips`` limits the device
    planes read. The window is the first ``window`` host span.
    """
    ops: Dict[int, List[Op]] = {}
    async_ops: Dict[int, List[Op]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            if chips is not None and chip not in chips:
                continue
            for line in plane.lines:
                dest = {OPS_LINE: ops, ASYNC_LINE: async_ops}.get(line.name)
                if dest is None:
                    continue
                dest[chip] = [
                    Op(e.name, opcode(e.name), e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                    if e.name in ANNOTATIONS
                ]
    host.sort(key=lambda h: h[1])
    window = next(((a, b) for n, a, b in host if n == "window"), None)
    if window is None:
        raise ValueError("the trace holds no 'window' span")
    for chip in list(async_ops):
        ops.setdefault(chip, [])
    return ops, async_ops, host, window


def load(log_dir: str, chips: Optional[Sequence[int]] = None):
    """:func:`from_planes` of the newest trace under ``log_dir``."""
    import jax

    data = jax.profiler.ProfileData.from_file(newest_xplane(log_dir))
    return from_planes(data.planes, chips)
