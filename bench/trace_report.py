"""One traced run of a cell, and what its trace says beyond the result line.

    python3 bench/trace_report.py --workload <cell> --seed <n> --seconds <s> [--out FILE]

Runs the cell as ``bench/run.py --trace 1`` does and prints one JSON line:
the result line; the end-to-end numbers of the traced run itself, to read
the cost of tracing against an untraced run of the same seed; the device
time by superstep scope and the share of the busy time the scopes cover;
the costliest ops with their scopes; the share of the window the device
idled while each kind of program span was open, and the longest idle gaps
named by those spans; and how well the program's spans, mapped onto the
trace's clock, sit in the benchmark's ``submit`` annotations. The benchmark's
own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time

T_START = time.perf_counter()

from pathlib import Path  # noqa: E402

_ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench.lib import harness, program_spans, scopes, trace  # noqa: E402

#: the program's spans whose open intervals name the device's idle time
SPANS = ("pending", "lock_wait", "queue", "form", "launch", "flight", "prepare", "route")


def idle_gaps(ev, top: int = 10):
    """The longest idle gaps of the first chip, each with the program spans
    and the benchmark annotations open at its middle."""
    if not ev.ops:
        return []
    lo, hi = ev.window
    ops = next(iter(ev.ops.values()))
    busy = trace.merged(((o.start, o.end) for o in ops), lo, hi)
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    gaps.sort(key=lambda g: g[0] - g[1])
    return [
        {
            "s": (b - a) / 1e9,
            "bench": trace.host_activity(ev, (a + b) / 2),
            "program": program_spans.open_at(ev, (a + b) / 2, SPANS),
        }
        for a, b in gaps[:top]
    ]


def report(ev, out, result) -> dict:
    idle = {
        " + ".join(names): program_spans.idle_while_pct(ev, names)
        for names in (("pending", "queue"), ("pending",), ("queue",), ("lock_wait",), ("flight",))
    }
    return {
        "result": result,
        "traced_end_to_end": out.values,
        "calls": out.calls,
        "scopes": scopes.split(ev),
        "top_ops": scopes.top_ops(ev),
        "idle_while_pct": idle,
        "idle_gaps": idle_gaps(ev),
        "clock": program_spans.containment(ev),
        "spans": {n: len([s for s in ev.spans if s["name"] == n]) for n in SPANS},
    }


def keep_evidence(out: Path, line: str, ev) -> None:
    """The report, the trace file and the obs spans with both anchors, so
    the run can be read again without the chip."""
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(line + "\n")
    shutil.copy(trace.newest_xplane(str(harness.TRACE_DIR)), out.with_suffix(".xplane.pb"))
    kept = {"window": ev.window, "span_window": ev.span_window, "calls": ev.calls, "spans": ev.spans}
    out.with_suffix(".spans.json").write_text(json.dumps(kept))


def main(argv) -> int:
    ap = argparse.ArgumentParser(prog="bench/trace_report.py", description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", help="also write the report to FILE, and beside it the trace and the spans")
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"trace_report: {cell.name} needs {cell.chips} TPU chips", file=sys.stderr)
        return 3
    harness.configure_compile_cache()
    kept = {}
    evidence = harness.evidence

    def keep(ctx, out):  # the harness builds the evidence; keep it and the outcome
        kept["out"], kept["ev"] = out, evidence(ctx, out)
        return kept["ev"]

    harness.evidence = keep
    result = harness.run_cell(cell, args.seed, args.seconds, True, devices[: cell.chips], T_START)
    line = json.dumps({"workload": cell.name, "seed": args.seed, **report(kept["ev"], kept["out"], result)})
    if args.out:
        keep_evidence(Path(args.out), line, kept["ev"])
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
