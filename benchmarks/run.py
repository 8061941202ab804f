"""Benchmark harness — one function per paper table. CSV to stdout.

    PYTHONPATH=src python -m benchmarks.run [--full] [--tables table1,table3]
                                            [--json OUT]

Default (quick) sizes keep a single-CPU-core run to a few minutes; --full
uses the paper's 1M/4M/8M sizes. ``--json OUT`` additionally writes every
emitted row as ``OUT/BENCH_<table>.json`` (inputs are seeded, so the files
form a diffable perf trajectory across commits). The simulated-processor
methodology and the predicted-vs-observed framing are described in
benchmarks/common.py and EXPERIMENTS.md §Paper-validation.
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks import tables
from benchmarks.common import emit, t_comp_per_cmp, write_json
from repro.compile_cache import enable_compile_cache

M = 1 << 20


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-size inputs (1M/4M/8M)")
    ap.add_argument("--tables", type=str, default="all")
    ap.add_argument(
        "--json", type=str, default=None, metavar="OUT",
        help="also write BENCH_<table>.json files into the OUT directory",
    )
    ap.add_argument(
        "--trace", type=str, default=None, metavar="OUT",
        help="run a traced profile sort and write a Chrome trace_event JSON "
        "to OUT (open in chrome://tracing or Perfetto), then print the "
        "fitted (g, L) cost report",
    )
    args = ap.parse_args()
    enable_compile_cache()

    if args.full:
        from benchmarks import common

        common.REPEATS = 4
        sizes_12 = [M, 4 * M]
        n_3 = 8 * M
        n_phase = 4 * M
        sizes_10 = [M, 4 * M]
        n_9 = 8 * M
        ps = (8, 16, 32, 64)
    else:
        sizes_12 = [M // 4]
        n_3 = M // 4
        n_phase = M // 4
        sizes_10 = [M // 16, M // 4]
        n_9 = M // 4
        ps = (8, 16, 32)

    want = None if args.tables == "all" else set(args.tables.split(","))

    def go(name, fn, *a, **kw):
        if want is not None and name not in want:
            return
        t0 = time.time()
        fn(*a, **kw)
        emit("meta", {"table": name, "wall_s": round(time.time() - t0, 1)})

    emit("meta", {"t_comp_per_cmp_ns": round(t_comp_per_cmp() * 1e9, 3)})
    go("table1", tables.table_1_2_runtime_by_distribution, sizes_12, p=32)
    go("table3", tables.table_3_scalability, n_3, ps=ps)
    go("table4_7", tables.tables_4_7_phase_breakdown, n_phase, ps=ps)
    go("table9", tables.table_8_9_comparisons, n_9, ps=ps)
    go("table10", tables.table_10_scalability_four_variants, sizes_10, ps=ps)
    go("table11", tables.table_11_dsq_vs_44, M // 4, ps=ps)
    go("bsi", tables.table_bsi_baseline, M // 4)
    go("bsp_model", tables.table_bsp_model_validation, n_3 if not args.full else 8 * M)
    go("duplicates", tables.table_duplicate_handling_overhead, M // 4)
    go("capacity", tables.table_capacity_retry, M // 4 if not args.full else 4 * M,
       p=16 if not args.full else 64)
    go("hotpath", tables.table_hotpath, M // 16 if not args.full else M, p=8)
    go("radix", tables.table_radix, M // 16 if not args.full else M,
       p=8 if not args.full else 16)
    go("obs", tables.table_obs, M // 16 if not args.full else M // 4, p=8)
    go("delta", tables.table_delta, M // 16 if not args.full else M, p=8)
    go("service", tables.table_service, n_requests=64,
       total=M // 16 if not args.full else M, p=8 if not args.full else 16)
    go("planner", tables.table_planner, n_requests=64,
       total=M // 16 if not args.full else M, p=8 if not args.full else 16)
    go("soak", tables.table_service_soak,
       n_requests=48 if not args.full else 128,
       total=M // 32 if not args.full else M // 4,
       arrival_hz=400.0 if not args.full else 800.0)
    go("chaos", tables.table_chaos, n_requests=64,
       total=M // 32 if not args.full else M // 4)

    if args.json:
        for path in write_json(args.json):
            emit("meta", {"json": path})

    if args.trace:
        traced_profile(args.trace, full=args.full)


def traced_profile(out: str, full: bool) -> None:
    """One traced run per route; Chrome trace to ``out`` + cost report.

    The profile sorts the balanced [U] mix through the sampling and the
    radix routes at two sizes each (the (g, L) regression needs h to
    vary), saves the merged timeline as Chrome ``trace_event`` JSON and
    prints the fitted-machine cost report: effective g (s/word), L
    (s/superstep), and per-superstep predicted-vs-measured rows.
    """
    import json

    import jax.numpy as jnp

    from repro import obs
    from repro.core import SortConfig, bsp_sort_safe, datagen

    p, n_p = (16, M // 64) if full else (8, M // 128)
    tracer = obs.Tracer()
    for route, kw in (
        ("sample", dict(pair_capacity="whp")),
        ("radix", dict(route="radix", pair_capacity="exact")),
    ):
        for scale in (1, 2):
            base = dict(
                p=p, n_per_proc=n_p * scale, routing="a2a_dense", **kw
            )
            x = jnp.asarray(datagen.generate("U", p, n_p * scale, seed=21))
            bsp_sort_safe(x, SortConfig(**base))  # warm: compile untimed
            bsp_sort_safe(x, SortConfig(obs=tracer, **base))
    path = tracer.save(out)
    with open(path) as f:
        problems = obs.validate_chrome_trace(json.load(f))
    problems += obs.validate_spans(tracer)
    rep = tracer.cost_report()
    fit = rep["fit"]
    emit(
        "trace",
        {"path": path, "valid": not problems, "spans": len(tracer.spans),
         "fit_ok": fit["ok"], "n_samples": fit["n_samples"],
         "g_s_per_word": round(fit["g_s_per_word"], 9),
         "l_s": round(fit["l_s"], 6), "r2": round(fit["r2"], 4),
         "max_imbalance": round(rep["max_imbalance"], 4)},
    )
    for row in rep["supersteps"]:
        emit("trace", row)
    for msg in problems:
        print(f"trace: INVALID: {msg}", file=sys.stderr)


if __name__ == "__main__":
    main()
