"""Find the service's knee: open-loop windows at several fixed rates.

    python3 bench/sweep.py --workload service.zipf.open --seed <n> \\
        --seconds 20 --rates 4,6,8,10,12

Run once on the chip, by hand, to choose the rate and the deadline written
into the cell's files; the benchmark's own runs never search for a rate.
First it times ``flush`` of full batches (the median is the deadline
``flush_after_s`` the cell uses), then it runs the cell's window at each
rate with that deadline and prints one line per rate: the latencies and
the keys served against the keys offered. The run's own ``service`` line
shows whether a backlog grew: the median latency of the window's last
quarter of requests against its first.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench.lib import gen, harness  # noqa: E402


def flight_seconds(cell: harness.Cell, seed: int, repeats: int = 7) -> list:
    """Walls of ``flush`` for full batches of the mix's own request sizes."""
    from bench.runners import service_open

    svc = service_open.make_service(cell.config)
    cap = int(cell.config["max_batch_keys"])
    walls = []
    for r in range(repeats):
        reqs = gen.open_loop_requests(cell.traffic, gen.substream(seed, 10, r), 30.0)
        batch, total = [], 0
        for _, keys in reqs:
            if total + keys.size > cap:
                continue
            batch.append(keys)
            total += keys.size
        futures = [svc.submit(k) for k in batch]
        t = time.perf_counter()
        svc.flush()
        walls.append(time.perf_counter() - t)
        for f in futures:
            f.result()
    return walls[1:]  # the first may build programs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="service.zipf.open")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True, help="requests per second, comma-separated")
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("sweep: no TPU found", file=sys.stderr)
        return 3
    harness.configure_compile_cache()
    from bench.runners import service_open

    bucket = service_open.make_service(cell.config).former.bucket
    service_open.warm(cell.config, cell.traffic, args.seed, bucket)
    walls = flight_seconds(cell, args.seed)
    deadline = statistics.median(walls)
    print(json.dumps({"flight_s": walls, "median_flight_s": deadline}), flush=True)
    config = {**cell.config, "flush_after_s": deadline}
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        c = dataclasses.replace(cell, config=config, traffic={**cell.traffic, "rate_per_s": rate})
        ctx = harness.Context(c, gen.substream(args.seed, 11, i), args.seconds, False, devices[:1], time.perf_counter())
        out = service_open.run(ctx)  # its info lines show a growing backlog
        sizes = [k.size for _, k in gen.open_loop_requests(c.traffic, ctx.seed, args.seconds)]
        row = {"rate_per_s": rate, "requests": out.attempted, "failed": out.failed}
        row["compiles_in_window"] = ctx.window_compiles
        row.update(offered_keys_per_s=sum(sizes) / args.seconds, **out.values, checks=out.checks)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
