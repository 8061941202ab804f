"""Runs one cell of ``BENCHMARK.json`` and prints its result line.

The cell's name leads to everything else by file name alone: its entry in
``BENCHMARK.json`` names a configuration and a traffic mix; the
configuration's ``file`` holds the sizes and the ``entry`` (a module under
``bench/runners``) that drives the window; the mix is
``bench/traffic/<traffic>.json``; each per-layer metric is read by
``bench/metrics/<metric>.py``. A new cell is new files and new entries.

A run: set-up (data from the seed, the cell's programs warmed) → the window
(``--seconds`` of timed work; with ``--trace 1`` under the profiler) → the
device's peak memory → the check against the plain reference. The last line
of standard output is the result; the numbers compared, each beside its
limit, are the last lines of standard error and the result's last key.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

ROOT = Path(__file__).resolve().parents[2]
#: JAX's persistent compile cache: a fixed directory inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
#: where a traced run writes its profile (replaced on every traced run)
TRACE_DIR = ROOT / "bench_out" / "trace"


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads``, with the files it names, loaded."""

    name: str
    chips: int
    config_name: str
    config: Dict
    traffic_name: str
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    root: Path


def load_benchmark(root: Path = ROOT) -> Dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def find_cell(name: str, root: Path = ROOT) -> Cell:
    """Everything the cell ``name`` needs, found by name from ``root``."""
    bench = load_benchmark(root)
    try:
        w = next(w for w in bench["workloads"] if w["name"] == name)
    except StopIteration:
        raise LookupError(f"no workload {name!r} in {root / 'BENCHMARK.json'}") from None
    c = next(c for c in bench["configs"] if c["name"] == w["config"])
    with open(root / c["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    # a metric without a "workloads" list: every cell (end to end), or every
    # cell that reports the end-to-end metric it moves (per layer)
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [
        m for m in bench["per_layer"]
        if (name in m["workloads"] if "workloads" in m else m["moves"] in moved)
    ]
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config_name=c["name"],
        config=config,
        traffic_name=w["traffic"],
        traffic=traffic,
        end_to_end=e2e,
        per_layer=per_layer,
        root=root,
    )


def metric_reader(name: str, root: Path = ROOT):
    """The module ``bench/metrics/<name>.py`` (names may hold dots)."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def runner(cell: Cell):
    """The module under ``bench/runners`` that drives this cell's window."""
    return importlib.import_module(f"bench.runners.{cell.config['entry']}")


@dataclasses.dataclass
class Outcome:
    """What a runner hands back once its window closed and it checked."""

    attempted: int
    failed: int
    values: Dict[str, float]  # end-to-end metric name -> value
    checks: Dict[str, Tuple[float, float]]  # compared number -> (value, limit)
    calls: int = 0  # timed calls (sorts or batches) in the window
    spans: List[Dict] = dataclasses.field(default_factory=list)  # obs spans
    regions: Tuple[str, ...] = ()  # host spans after the window to report


class Context:
    """The harness's side of a run, handed to the runner.

    The runner makes its data, warms its programs, then opens
    :meth:`window` around its timed work; with ``trace`` the profiler runs
    from just before the window until :meth:`end_trace`.
    """

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool, devices, t_start: float):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.devices = list(devices)
        self.t_start = t_start
        self.t0: Optional[float] = None  # perf_counter at the window's start
        self.t1: Optional[float] = None
        self.window_compiles = 0
        self.memory_peak_bytes: Optional[int] = None
        self._tracing = False

    def info(self, what: str, **fields) -> None:
        """One line of standard output ahead of the result."""
        print(json.dumps({"info": what, **fields}, default=str), flush=True)

    def annotate(self, name: str):
        """A host span in the profiler's trace (traced runs only)."""
        if not self._tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    @contextlib.contextmanager
    def window(self):
        import jax

        from bench.lib.device import CompileClock

        if self.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            self._tracing = True
        with CompileClock() as clock, self.annotate("window"):
            self.t0 = time.perf_counter()
            try:
                yield self
            finally:
                self.t1 = time.perf_counter()
        self.window_compiles = clock.executables

    def end_trace(self) -> None:
        if self._tracing:
            import jax

            jax.profiler.stop_trace()
            self._tracing = False

    def record_peak(self) -> None:
        from bench.lib.device import peak_bytes_in_use, peaks

        self.memory_peak_bytes = peak_bytes_in_use(self.devices)
        hbm = peaks(self.devices[0].device_kind)["hbm_bytes"] if self.devices[0].platform == "tpu" else None
        self.info(
            "memory",
            peak_bytes_in_use=self.memory_peak_bytes,
            hbm_bytes=hbm,
            share_of_hbm=None if hbm is None else self.memory_peak_bytes / hbm,
        )


def evidence(ctx: Context, out: Outcome):
    """The traced run's :class:`bench.lib.trace.Evidence`."""
    from bench.lib import trace

    ops, async_ops, host, window = trace.load(
        str(TRACE_DIR), chips=[d.id for d in ctx.devices]
    )
    return trace.Evidence(
        window=window,
        ops=ops,
        async_ops=async_ops,
        host=host,
        calls=out.calls,
        spans=out.spans,
        span_window=(ctx.t0, ctx.t1),
        compiles=ctx.window_compiles,
    )


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices, t_start: float, **impl) -> Dict:
    """Set-up, window and check of one cell on ``devices``; the result line.

    ``impl`` reaches the runner untouched (tests and the control put another
    sort in the program's place through it).
    """
    ctx = Context(cell, seed, seconds, trace, devices, t_start)
    out = runner(cell).run(ctx, **impl)
    ctx.end_trace()
    dev = ctx.devices[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(ctx.devices),
        "memory_peak_bytes": ctx.memory_peak_bytes,
    }
    ctx.info(
        "window",
        seconds=ctx.t1 - ctx.t0,
        calls=out.calls,
        compiles_in_window=ctx.window_compiles,
        setup_s=ctx.t0 - ctx.t_start,
    )
    metrics: Dict[str, Dict] = {}
    breakdown = None
    if trace:
        from bench.lib import trace as tr

        ev = evidence(ctx, out)
        for m in cell.per_layer:
            value = metric_reader(m["name"], cell.root).read(ev)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = tr.mean_busy_s(ev)
        device["window_s"] = ev.window_s
        breakdown = tr.breakdown(ev)
        for name in out.regions:
            ctx.info(
                "region",
                name=name,
                device_ms=tr.region_device_ms(ev, name),
                window_device_ms_per_call=(device["busy_s"] or 0) * 1e3 / max(out.calls, 1),
            )
    else:
        values = {"setup_s": ctx.t0 - ctx.t_start, **out.values}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    checks = {k: {"value": v, "limit": lim} for k, (v, lim) in out.checks.items()}
    result = {
        "correct": all(v <= lim for v, lim in out.checks.values()),
        "attempted": out.attempted,
        "failed": out.failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def configure_compile_cache() -> None:
    """Keep JAX's persistent compile cache in the checkout, for every program."""
    import jax

    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="bench/run.py", description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv, t_start: float) -> int:
    args = parse(argv)
    cell = find_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU found (JAX sees {devices[0].platform}); refusing to run", file=sys.stderr)
        return 3
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, JAX sees {len(devices)}", file=sys.stderr)
        return 3
    configure_compile_cache()
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices[: cell.chips], t_start)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
