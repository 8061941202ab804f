"""The closed loop of the bulk cells: back-to-back sorts of resident input.

One client sorts, waits for the whole result, and sorts again, cycling
through the mix's distinct inputs with a fresh splitter seed for every call.
The window starts with the first timed call and closes when the last call
that started inside ``--seconds`` has finished; ``sort_keys_per_s`` is every
such call's keys over that time. A sample of the calls, drawn from the seed,
keeps its output for the check against the plain reference.
"""
from __future__ import annotations

import gc
import importlib
import time
from typing import Dict, List, Tuple

import numpy as np

from bench.lib import gen


class Reservoir:
    """A uniform sample of ``k`` items from a stream of unknown length."""

    def __init__(self, k: int, rng: np.random.Generator) -> None:
        self.k, self.rng, self.seen = int(k), rng, 0
        self.items: List[Tuple[int, object]] = []

    def offer(self, index: int, item) -> None:
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append((index, item))
        else:
            j = int(self.rng.integers(0, self.seen))
            if j < self.k:
                self.items[j] = (index, item)


def run(ctx, target):
    """Set-up, window and check of a bulk cell; ``target`` runs the sorts.

    ``target`` has ``p``, ``put(keys)``, ``payload()``, ``launch(x, v,
    call)``, ``wait(handle)``, ``host_outputs(handle)`` and ``tier(handle)``.
    """
    from bench.lib.harness import Outcome

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    reference = importlib.import_module(f"bench.references.{cfg['reference']}")
    n = target.n
    n_p = n // target.p
    keys = gen.closed_loop_inputs(mix, target.p, n_p, ctx.seed)
    xs = [target.put(k) for k in keys]
    payload = target.payload()
    target.warm(xs[0], payload)
    keep = Reservoir(int(mix["checked_calls"]), np.random.default_rng(gen.substream(ctx.seed, 6)))
    tiers: Dict[str, int] = {}
    host_gaps: List[float] = []
    with ctx.window():
        t_end = ctx.t0
        calls = 0
        while calls == 0 or t_end - ctx.t0 < ctx.seconds:
            t_issue = time.perf_counter()
            if calls:
                host_gaps.append(t_issue - t_end)
            with ctx.annotate("sort_call"):
                handle = target.launch(xs[calls % len(xs)], payload, calls)
            with ctx.annotate("result_wait"):
                target.wait(handle)
            t_end = time.perf_counter()
            tier = target.tier(handle)
            tiers[tier] = tiers.get(tier, 0) + 1
            keep.offer(calls, handle)
            calls += 1
            del handle
    elapsed = t_end - ctx.t0
    ctx.record_peak()
    target.after_window(xs[0], payload, ctx)
    ctx.end_trace()
    ctx.info(
        "loop",
        calls=calls,
        tiers=tiers,
        host_gap_mean_s=float(np.mean(host_gaps)) if host_gaps else 0.0,
        host_gap_max_s=max(host_gaps, default=0.0),
    )
    outputs = [(i, target.host_outputs(h)) for i, h in keep.items]
    del xs, payload, keep
    target.release()
    gc.collect()

    keys_wrong = payload_wrong = 0
    refs = {}
    for i, (got_keys, got_payload) in sorted(outputs, key=lambda x: x[0] % len(keys)):
        k = i % len(keys)
        if k not in refs:
            refs = {k: reference.stable_sort(keys[k])}  # one reference in memory
        want_keys, want_order = refs[k]
        keys_wrong += reference.mismatches(got_keys, want_keys)
        payload_wrong += reference.mismatches(got_payload, want_order)
    ctx.info("checked", calls=[i for i, _ in outputs])
    return Outcome(
        attempted=calls,
        failed=0,
        values={"sort_keys_per_s": calls * n / elapsed},
        checks={"keys_wrong": (keys_wrong, 0), "payload_wrong": (payload_wrong, 0)},
        calls=calls,
        regions=target.regions if ctx.trace else (),
    )
