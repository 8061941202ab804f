"""The sort service under open-loop traffic: ``SortService.submit`` → future.

Requests arrive on the mix's schedule whether or not earlier ones are done
(independent users). At each arrival the client submits and calls
``flush_ready``, which launches every batch that is full enough; the
service's driver thread fires the ``flush_after_s`` deadline and completes
flights. A watcher thread notes when each future resolves. Each request is
timed from when it was due, so a late generator or a stalled service shows
in the latency; how late the generator ran is printed on its own line.

Set-up warms every program the window can reach: with the capacity tier
pinned, a batch's program depends only on its pow2 bucket and on whether it
holds one request (raw int32 keys) or several (int64 composites), so the
set-up sorts one batch of each kind for every bucket the mix's sizes and
the batch cap allow.
"""
from __future__ import annotations

import concurrent.futures
import gc
import importlib
import queue
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from bench.lib import gen


def make_service(config: Dict, tracer=None):
    from repro.service import ServiceConfig, SortService

    return SortService(
        ServiceConfig(
            p=int(config["p"]),
            algorithm=config["algorithm"],
            pair_capacity=config["pair_capacity"],
            max_batch_keys=int(config["max_batch_keys"]),
            max_in_flight=int(config["max_in_flight"]),
            flush_after_s=float(config["flush_after_s"]),
            obs=tracer,
        )
    )


def warm_batches(config: Dict, mix: Dict, bucket) -> List[List[int]]:
    """Request sizes of one batch per (bucket, one or several requests)."""
    p, cap = int(config["p"]), int(config["max_batch_keys"])
    sizes = gen.block_size_set(mix)
    batches = [[b * p] for b in sorted({bucket(int(s)) for s in sizes})]
    b = bucket(int(sizes[0] + sizes[1]))
    while b <= bucket(cap):
        batches.append([b * p // 2, b * p // 2])
        b *= 2
    return batches


def warm(config: Dict, mix: Dict, seed: int, bucket) -> int:
    """Sort one batch of each reachable kind through the public path.

    Each batch goes through a service of its own, several at a time: the
    compiles run side by side (XLA releases the interpreter lock), and every
    service shares the process's compiled programs. Batches of one request
    go first, then those of several, so that no two threads build the
    program of one bucket at once.
    """
    batches = warm_batches(config, mix, bucket)

    def sort_one(j: int) -> None:
        svc = make_service(config)
        futures = [
            svc.submit(gen.uniform(1, s, seed=gen.substream(seed, 9, j, k))[0])
            for k, s in enumerate(batches[j])
        ]
        svc.flush()
        for f in futures:
            f.result()

    with concurrent.futures.ThreadPoolExecutor(int(config.get("warm_threads", 8))) as pool:
        for solo in (True, False):
            jobs = [pool.submit(sort_one, j) for j, b in enumerate(batches) if (len(b) == 1) == solo]
            for job in jobs:
                job.result()
    return len(batches)


class Watcher(threading.Thread):
    """Notes the instant each submitted future resolves (polls every 0.5 ms)."""

    def __init__(self, futures: List, done_at: List[Optional[float]]) -> None:
        super().__init__(name="bench-watcher", daemon=True)
        self.futures, self.done_at = futures, done_at
        self.new: "queue.SimpleQueue[int]" = queue.SimpleQueue()
        self.stop = threading.Event()

    def run(self) -> None:
        pending: List[int] = []
        while not self.stop.is_set():
            while not self.new.empty():
                pending.append(self.new.get())
            now = time.perf_counter()
            still = []
            for i in pending:
                if self.futures[i].done():
                    self.done_at[i] = now
                else:
                    still.append(i)
            pending = still
            time.sleep(0.0005)


def run(ctx, service=None):
    from bench.lib.harness import Outcome

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    reference = importlib.import_module(f"bench.references.{cfg['reference']}")
    requests = gen.open_loop_requests(mix, ctx.seed, ctx.seconds)
    tracer = None
    if service is None:
        if ctx.trace:
            from repro.obs import Tracer

            tracer = Tracer()
        svc = make_service(cfg, tracer)
        ctx.info("warm", batches=warm(cfg, mix, ctx.seed, svc.former.bucket))
    else:
        svc = service
    n = len(requests)
    futures: List = [None] * n
    done_at: List[Optional[float]] = [None] * n
    late = np.zeros(n)
    watcher = Watcher(futures, done_at)
    watcher.start()
    svc.start_driver(interval_s=float(cfg["driver_interval_s"]))
    batches_before = getattr(svc, "batches_dispatched", 0)
    try:
        with ctx.window():
            t0 = ctx.t0
            for i, (due, keys) in enumerate(requests):
                with ctx.annotate("generate"):
                    delay = t0 + due - time.perf_counter()
                    if delay > 0:
                        time.sleep(delay)
                late[i] = time.perf_counter() - (t0 + due)
                with ctx.annotate("submit"):
                    futures[i] = svc.submit(keys)
                    svc.flush_ready()
                watcher.new.put(i)
            with ctx.annotate("generate"):
                time.sleep(max(0.0, t0 + ctx.seconds - time.perf_counter()))
        batches = getattr(svc, "batches_dispatched", 0) - batches_before
        t_close = t0 + ctx.seconds
        with ctx.annotate("result_wait"):
            give_up = t_close + float(cfg["drain_s"])
            while time.perf_counter() < give_up and not all(f.done() for f in futures):
                time.sleep(0.005)
            time.sleep(0.002)  # let the watcher note the last resolutions
    finally:
        svc.stop_driver()
        watcher.stop.set()
        watcher.join(timeout=5.0)
    ctx.end_trace()
    ctx.record_peak()

    due_at = t0 + np.array([d for d, _ in requests])
    sizes = np.array([k.size for _, k in requests])
    ok = np.array([done_at[i] is not None and futures[i].exception() is None for i in range(n)])
    end = np.array([done_at[i] if done_at[i] is not None else time.perf_counter() for i in range(n)])
    latency = end - due_at
    served = sizes[ok & (end <= t_close)].sum()
    ctx.info(
        "generator",
        requests=n,
        late_p50_ms=float(np.percentile(late, 50) * 1e3),
        late_p95_ms=float(np.percentile(late, 95) * 1e3),
        late_max_ms=float(late.max() * 1e3),
    )
    ctx.info(
        "service",
        batches_in_window=batches,
        completed_in_window=int((ok & (end <= t_close)).sum()),
        latency_first_quarter_ms=float(np.median(latency[: max(1, n // 4)]) * 1e3),
        latency_last_quarter_ms=float(np.median(latency[-max(1, n // 4) :]) * 1e3),
        retries=getattr(getattr(svc, "stats", None), "retries", None),
        buckets=getattr(svc, "bucket_counts", None),
    )
    results = {i: futures[i].result() for i in range(n) if ok[i]}
    spans = list(tracer.spans) if tracer is not None else []
    del futures, svc, tracer
    gc.collect()

    wrong = 0
    for i, res in results.items():
        want_keys, want_order = reference.stable_sort(requests[i][1])
        if reference.mismatches(res.keys, want_keys) or reference.mismatches(res.order, want_order):
            wrong += 1
    missing = int(n - ok.sum())
    return Outcome(
        attempted=n,
        failed=missing,
        values={
            "req_latency_p50_ms": float(np.percentile(latency, 50) * 1e3),
            "req_latency_p95_ms": float(np.percentile(latency, 95) * 1e3),
            "served_keys_per_s": float(served / ctx.seconds),
        },
        checks={"requests_wrong": (wrong, 0), "requests_missing": (missing, 0)},
        calls=batches,
        spans=spans,
    )
