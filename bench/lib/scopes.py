"""Device time by BSP superstep, read from the op names in the trace.

The program names each superstep with ``jax.named_scope``, so every device
op's ``op_name`` metadata holds a path such as
``jit(run)/vmap(ph5_exchange)/gather``; a fused op carries the path of its
root. :func:`scope_of` takes the innermost superstep named on that path,
with the transform wrappers (``vmap(...)``, ``jit(...)``) removed.

A TPU trace's ``XLA Ops`` events carry no op name of their own (their
stats are a device offset and duration). The names come from the HLO that
xprof recovers from the trace: its ``hlo_stats`` tool names each (program
id, HLO op) with its framework op name, and each op event's program is the
``XLA Modules`` event (``jit_run(<program id>)``) it ran in.

XLA drops the metadata of some ops it builds when it rewrites the program:
on the TPU a scatter becomes a custom fusion (its root, the scatter, without
a name), sometimes after a sort of its indices. Such an op takes the scope
of the last named op that ran before it in the same program execution;
:func:`split` reports the busy time that named ops cover on their own, and
with these ops attributed.

Traces are re-read from ``harness.TRACE_DIR`` (the readers get only the
:class:`trace.Evidence`), once per trace file. A program that names no
supersteps, or a trace with no TPU plane, gives the readers nothing to
read: they return None.
"""
from __future__ import annotations

import bisect
import functools
import json
import os
import re
from typing import Dict, List, NamedTuple, Optional, Tuple

from bench.lib import trace

#: the superstep scopes the program names, in pipeline order
SCOPES = (
    "ph2_local_sort",
    "ph3_splitters",
    "radix_count",
    "ph4_partition",
    "ph5_exchange",
    "ph6_merge",
)
MODULES_LINE = "XLA Modules"
_WRAPPER = re.compile(r"^[\w.\-]*\((.*)\)$")
_PROGRAM = re.compile(r"\((\d+)\)$")


class ScopedOp(NamedTuple):
    name: str  # the event's name (the HLO instruction's text)
    scope: Optional[str]  # superstep scope, None if unscoped
    start: float  # ns
    end: float
    named: bool  # the scope is the op's own, not the op's before it


def scope_of(op_name: str) -> Optional[str]:
    """The innermost superstep scope on a name-stack path, or None.

    ``jit(run)/vmap(ph5_exchange)/gather`` gives ``ph5_exchange``; where
    XLA joined the metadata of merged ops with ``;``, the first path counts.
    """
    found = None
    for part in op_name.split(";")[0].split("/"):
        while True:
            m = _WRAPPER.match(part)
            if not m:
                break
            part = m.group(1)
        if part in SCOPES:
            found = part
    return found


def hlo_op_names(path: str) -> Dict[Tuple[str, str], str]:
    """(program id, HLO op) -> framework op name, from xprof's hlo_stats."""
    from xprof.convert import raw_to_tool_data

    data, _ = raw_to_tool_data.xspace_to_tool_data([path], "hlo_stats", {})
    if not data:
        return {}
    table = json.loads(data)
    cols = [c["id"] for c in table["cols"]]
    want = [cols.index(k) for k in ("program_id", "hlo_op_name", "tf_op_name")]
    out = {}
    for row in table["rows"]:
        prog, op, name = (row["c"][i]["v"] for i in want)
        out[(str(prog), str(op))] = str(name or "")
    return out


def _attributed(events, modules, names) -> List[ScopedOp]:
    """One chip's op events with their scopes, in time order."""
    starts = [a for a, _, _ in modules]
    out: List[ScopedOp] = []
    run, last = None, None  # the module execution and its last named scope
    for e in sorted(events, key=lambda e: e.start_ns):
        k = bisect.bisect_right(starts, e.start_ns) - 1
        inside = k >= 0 and e.start_ns < modules[k][1]
        if (k if inside else None) != run:
            run, last = (k if inside else None), None
        program = modules[k][2] if inside else ""
        own = scope_of(names.get((program, trace.instruction(e.name)), ""))
        if own is not None:
            last = own
        out.append(ScopedOp(e.name, own or last, e.start_ns, e.start_ns + e.duration_ns, own is not None))
    return out


@functools.lru_cache(maxsize=2)
def _load(path: str, mtime: float) -> Dict[int, List[ScopedOp]]:
    """Per chip the scoped ops of one trace file."""
    import jax

    del mtime  # part of the cache key only: a rewritten file is read again
    data = jax.profiler.ProfileData.from_file(path)
    planes = {}
    for plane in data.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        modules = sorted(
            (e.start_ns, e.start_ns + e.duration_ns, p.group(1))
            for e in lines.get(MODULES_LINE, ())
            if (p := _PROGRAM.search(e.name))
        )
        planes[int(m.group(1))] = (lines.get(trace.OPS_LINE, []), modules)
    names = hlo_op_names(path) if planes else {}
    return {chip: _attributed(evs, modules, names) for chip, (evs, modules) in planes.items()}


def scoped_ops(ev) -> Optional[Dict[int, List[ScopedOp]]]:
    """The newest trace's ops of the chips ``ev`` read, with their scopes.

    None where there is no trace, no TPU plane, or no op carries a scope.
    """
    from bench.lib import harness

    try:
        path = trace.newest_xplane(str(harness.TRACE_DIR))
    except FileNotFoundError:
        return None
    ops = _load(path, os.path.getmtime(path))
    ops = {c: v for c, v in ops.items() if c in ev.ops}
    if not any(o.scope for v in ops.values() for o in v):
        return None
    return ops


def scope_ns(ops: List[ScopedOp], scope: Optional[str], lo: float, hi: float) -> float:
    """Device ns in [lo, hi] in which an op of ``scope`` ran (a union, so
    an op nested in another of the same scope counts once)."""
    return trace.union_ns(((o.start, o.end) for o in ops if o.scope == scope), lo, hi)


def scope_ms_per_call(ev, scope: str) -> Optional[float]:
    """Device ms of ops scoped ``scope`` inside the window, per timed call,
    mean over chips; None where no such op ran or no call was timed."""
    ops = scoped_ops(ev)
    if ops is None or not ev.calls:
        return None
    lo, hi = ev.window
    per_chip = [scope_ns(v, scope, lo, hi) for v in ops.values()]
    if not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) / ev.calls / 1e6


def split(ev) -> Optional[Dict[str, object]]:
    """Busy device seconds of the window by scope, mean over chips.

    ``scopes`` maps each scope (and ``unscoped``) to seconds; ``coverage``
    is the share of the busy time in which a scoped op ran, and
    ``coverage_named`` the share in which an op named its scope itself.
    """
    ops = scoped_ops(ev)
    if ops is None:
        return None
    lo, hi = ev.window
    n = len(ops)
    secs = {s: sum(scope_ns(v, s, lo, hi) for v in ops.values()) / n / 1e9 for s in SCOPES}
    secs["unscoped"] = sum(scope_ns(v, None, lo, hi) for v in ops.values()) / n / 1e9
    busy = sum(trace.union_ns(((o.start, o.end) for o in v), lo, hi) for v in ops.values()) / n / 1e9
    scoped, named = (
        sum(trace.union_ns(((o.start, o.end) for o in v if keep(o)), lo, hi) for v in ops.values()) / n / 1e9
        for keep in (lambda o: o.scope, lambda o: o.named)
    )
    return {
        "scopes": {k: v for k, v in secs.items() if v},
        "busy_s": busy,
        "coverage": scoped / busy if busy else None,
        "coverage_named": named / busy if busy else None,
    }


def top_ops(ev, top: int = 12) -> List[List]:
    """The costliest ops of the window with their scope: [op, scope, s];
    a scope an op took from the op before it is marked ``(after)``."""
    ops = scoped_ops(ev)
    if ops is None:
        return []
    lo, hi = ev.window
    total: Dict[Tuple[str, Optional[str]], float] = {}
    for v in ops.values():
        for o in v:
            d = min(o.end, hi) - max(o.start, lo)
            if d > 0:
                scope = o.scope if o.named or o.scope is None else f"{o.scope} (after)"
                key = (trace.instruction(o.name), scope)
                total[key] = total.get(key, 0.0) + d
    rows = sorted(([k[0], k[1], s / len(ops) / 1e9] for k, s in total.items()), key=lambda r: -r[2])
    return rows[:top]
