"""Bring-up smoke run: the BSP sort and the sort service on a TPU chip.

    python chip_smoke.py               # one chip: phases (a)-(d)
    python chip_smoke.py --chips 4     # the four-chip mesh phase, nothing else
    python chip_smoke.py --rehearse    # tiny sizes on whatever JAX finds (CPU)

Every phase drives the public entry points (``bsp_sort_safe``,
``bsp_sort_sharded_safe``, ``SortService``) and checks what comes out
against a plain numpy reference: ``np.sort`` for keys and a stable
``np.argsort`` for payloads. Each phase prints one JSON line with its sizes,
the check, the capacity tier or route that won, compile seconds (JAX's own
compile events), wall seconds (compile included, so not a benchmark) and
the device's ``peak_bytes_in_use`` so far; raw sorts also report the wall
of a second, compiled call. The last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

A failed check exits 1 before that line. Without ``--rehearse`` the run
refuses (exit 2, no result) unless JAX's first device is a TPU.
"""
from __future__ import annotations

import argparse
import collections
import concurrent.futures
import dataclasses
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import SortConfig, datagen  # noqa: E402
from repro.core.api import (  # noqa: E402
    bsp_sort_safe,
    bsp_sort_sharded_safe,
    default_executor,
    gathered_output,
)
from repro.service import ServiceConfig, SortService  # noqa: E402

#: phase sizes (keys). ``chip`` is what users of the sort would call real;
#: ``rehearse`` only exercises the control flow.
SIZES = {
    "chip": dict(
        p=8,
        raw=1 << 26,  # (a): 2^26 int32 keys + one int32 payload = 512 MiB
        escalate=1 << 23,  # (b)
        service=1 << 22,  # (c): the 64-request zipf mix, in total
        stream=1 << 20,  # (c): each of the two near-sorted stream submits
        pallas_np=1 << 18,  # (d): keys per proc
        mesh_per_chip=1 << 25,  # --chips 4
        mesh_compare_per_chip=1 << 22,  # --chips 4, against the vmap runner
    ),
    "rehearse": dict(
        p=8,
        raw=1 << 12,
        escalate=1 << 10,
        service=1 << 12,
        stream=1 << 11,
        pallas_np=1 << 8,
        mesh_per_chip=1 << 10,
        mesh_compare_per_chip=1 << 8,
    ),
}
HBM_BYTES = 16 * 10**9  # one v5e chip
MESH_ROUTINGS = ("a2a_dense", "ring", "allgather")
COMPILE_EVENT_PREFIX = "/jax/core/compile/"
BACKEND_COMPILE_EVENT = COMPILE_EVENT_PREFIX + "backend_compile_duration"


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling while open, and the
    executables it built (fresh or from the persistent cache)."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.executables = 0
        self._lock = threading.Lock()  # compiles may report from threads

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith(COMPILE_EVENT_PREFIX):
            with self._lock:
                self.seconds += duration
                self.executables += event == BACKEND_COMPILE_EVENT

    def __enter__(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def _peak_bytes(dev) -> int | None:
    stats = dev.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def terminal_rung_bytes(p: int, n: int, arrays: int) -> int:
    """Bytes the ladder's terminal rung holds: p·n int32 words per array."""
    return p * n * 4 * arrays


def _stable_reference(keys: np.ndarray):
    """(np.sort of the keys, stable argsort) of the flattened input."""
    order = np.argsort(keys.reshape(-1), kind="stable")
    return keys.reshape(-1)[order], order.astype(np.int32)


class Run:
    """Prints the phase lines and remembers whether every check held."""

    def __init__(self, dev) -> None:
        self.dev = dev
        self.failed = []

    def report(self, phase: str, ok: bool, **row) -> None:
        row = {"phase": phase, "match": bool(ok), **row}
        row["peak_bytes"] = _peak_bytes(self.dev)
        print(json.dumps(row, default=str), flush=True)
        if not ok:
            self.failed.append(phase)

    def sort_phase(self, phase, x, payload, ref, cfg, runner, **row):
        """One overflow-safe sort, checked against the numpy reference, then
        the same call again with everything compiled."""
        with CompileClock() as clock:
            t0 = time.perf_counter()
            res, vbufs, stats = runner(x, cfg, values=[payload])
            jax.block_until_ready((res.buf, vbufs))
            wall = time.perf_counter() - t0
        keys = gathered_output(res)
        vals = gathered_output(dataclasses.replace(res, buf=vbufs[0]))
        ok = np.array_equal(keys, ref[0]) and np.array_equal(vals, ref[1])
        del res, vbufs
        with CompileClock() as warm_clock:
            t0 = time.perf_counter()
            jax.block_until_ready(runner(x, cfg, values=[payload])[:2])
            warm = time.perf_counter() - t0
        self.report(
            phase,
            ok,
            n=cfg.n,
            p=cfg.p,
            algorithm=cfg.algorithm,
            route=cfg.route,
            routing=cfg.routing,
            tier=stats.last_tier,
            retries=stats.retries,
            compile_s=clock.seconds,
            wall_s=wall,
            warm_wall_s=warm,
            warm_compile_s=warm_clock.seconds,
            **row,
        )
        return keys, vals, stats


def phase_raw(run: Run, sz, seed: int) -> None:
    """(a) ``bsp_sort_safe`` on the vmap runner: det and iran, then radix."""
    p, n = sz["p"], sz["raw"]
    need = terminal_rung_bytes(p, n, arrays=2)
    run.report(
        "a.reckoning",
        need < HBM_BYTES,
        n=n,
        p=p,
        arrays=2,
        terminal_rung_bytes=need,
        hbm_bytes=HBM_BYTES,
    )
    keys = datagen.uniform(p, n // p, seed=seed)
    payload = np.arange(n, dtype=np.int32).reshape(p, n // p)
    ref = _stable_reference(keys)
    x, v = jnp.asarray(keys), jnp.asarray(payload)
    # the production w.h.p. pair capacity; the ladder escalates past it
    cfgs = [
        SortConfig(
            p=p, n_per_proc=n // p, algorithm=algorithm, route=route,
            pair_capacity="whp", seed=seed,
        )
        for algorithm, route in (("det", "sample"), ("iran", "sample"), ("det", "radix"))
    ]
    # the radix rung's capacity comes from the counts, at run time
    ex = default_executor()
    _compile_ahead(run, "a", [
        (
            ex.prepare_vmap(cfg, 1),
            (x, v),
            [ex.route_vmap(cfg.tier_ladder()[0][1], 1)] if cfg.route == "sample" else [],
        )
        for cfg in cfgs
    ])
    for cfg in cfgs:
        run.sort_phase(f"a.{cfg.algorithm}.{cfg.route}", x, v, ref, cfg, bsp_sort_safe)


def _adversarial(p: int, n_p: int) -> np.ndarray:
    """Constant but distinct per proc: every key of a run in one bucket."""
    return np.repeat((np.arange(p, dtype=np.int32) * 1000)[:, None], n_p, axis=1)


def phase_escalation(run: Run, sz, seed: int) -> None:
    """(b) adversarial input: must escalate (retries ≥ 1) and still sort."""
    p, n = sz["p"], sz["escalate"]
    keys = _adversarial(p, n // p)
    payload = np.arange(n, dtype=np.int32).reshape(p, n // p)
    cfg = SortConfig(
        p=p, n_per_proc=n // p, algorithm="iran", pair_capacity="whp", seed=seed
    )
    x, v = jnp.asarray(keys), jnp.asarray(payload)
    ex = default_executor()
    _compile_ahead(run, "b", [
        (ex.prepare_vmap(cfg, 1), (x, v), [ex.route_vmap(t, 1) for _, t in cfg.tier_ladder()])
    ])
    _, _, stats = run.sort_phase(
        "b.escalation",
        x,
        v,
        _stable_reference(keys),
        cfg,
        bsp_sort_safe,
        terminal_rung_bytes=terminal_rung_bytes(p, n, arrays=2),
    )
    run.report("b.retried", stats.retries >= 1, retries=stats.retries)


def _request_ok(arr: np.ndarray, res) -> bool:
    return np.array_equal(res.keys, np.sort(arr, kind="stable")) and np.array_equal(
        res.order, np.argsort(arr, kind="stable")
    )


def phase_service(run: Run, sz, seed: int) -> None:
    """(c) the sort service: a zipf-size request mix, then a delta stream."""
    p, total = sz["p"], sz["service"]
    sizes = datagen.zipf_sizes(64, total, seed=seed)
    mixes = ("U", "G", "DD", "zipf")
    requests = [
        datagen.generate(mixes[i % len(mixes)], 1, int(s), seed=seed + i)[0]
        for i, s in enumerate(sizes)
    ]
    # batches of up to 2^20 keys: each new (bucket, route, capacity) is a
    # TPU compile of 10-30 s, and the default 2^16 cap compiles ~45 programs
    # for this mix against ~9 here
    svc = SortService(ServiceConfig(p=p, max_batch_keys=1 << 20, seed=seed))
    with CompileClock() as clock:
        t0 = time.perf_counter()
        futures = [svc.submit(a) for a in requests]
        results = [f.result() for f in futures]
        wall = time.perf_counter() - t0
    matched = sum(_request_ok(a, r) for a, r in zip(requests, results))
    run.report(
        "c.service",
        matched == len(requests),
        requests=len(requests),
        matched=matched,
        n=int(sizes.sum()),
        p=p,
        sizes=[int(sizes.min()), int(sizes.max())],
        batches=svc.batches_dispatched,
        start_tiers=svc.start_tiers,
        tiers=dict(collections.Counter(r.tier for r in results)),
        buckets=svc.bucket_counts,
        retries=svc.stats.retries,
        compile_s=clock.seconds,
        wall_s=wall,
    )

    # the second submit is a Δ small enough for the view to fold it in
    n = sz["stream"]
    parts = [
        datagen.near_sorted(n, 0.02, "appended", seed=seed),
        datagen.near_sorted(n // 16, 0.02, "appended", seed=seed + 1),
    ]
    with CompileClock() as clock:
        t0 = time.perf_counter()
        results = [svc.submit(a, stream="smoke").result() for a in parts]
        wall = time.perf_counter() - t0
    history = np.concatenate(parts)
    ok = (
        _request_ok(parts[0], results[0])
        and _request_ok(history, results[1])
        and results[1].tier == "delta"
    )
    run.report(
        "c.stream",
        ok,
        n=int(history.size),
        p=p,
        submits=len(parts),
        tiers=[r.tier for r in results],
        compile_s=clock.seconds,
        wall_s=wall,
    )


def _compile_together(run: Run, phase: str, programs) -> list:
    """Compile ``(jitted fn, args)`` pairs side by side, one thread each.

    XLA releases the interpreter lock while it compiles, so the programs
    build in parallel instead of one after another, and a later call with
    the same arguments reuses each executable.
    """
    with CompileClock() as clock:
        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(len(programs)) as pool:
            futures = [
                pool.submit(lambda fn, args: fn.lower(*args).compile(), fn, args)
                for fn, args in programs
            ]
            compiled = [f.result() for f in futures]
        wall = time.perf_counter() - t0
    run.report(phase, True, programs=len(programs), compile_s=clock.seconds, wall_s=wall)
    return compiled


def _compile_ahead(run: Run, phase: str, plans) -> tuple:
    """Compile the programs a phase will run before it runs them.

    ``plans`` holds ``(prepare_fn, args, route_fns)``. The prepare programs
    compile side by side and run once, then the route programs (each takes
    the prepared state and a rung's rng) compile side by side. Returns the
    compiled prepare and route programs, in order.
    """
    prepared = _compile_together(
        run, f"{phase}.compile_prepare", [(f, a) for f, a, _ in plans]
    )
    rng = jax.random.key_data(jax.random.fold_in(jax.random.key(0), 0))
    routes = []
    for prepare, args, route_fns in plans:
        prep = prepare(*args)
        routes += [(fn, (prep, rng)) for fn in route_fns]
    return prepared, _compile_together(run, f"{phase}.compile_routes", routes)


def phase_pallas(run: Run, sz, seed: int) -> None:
    """(d) Pallas local sort + merge: byte-identical to XLA, real kernels."""
    p, n_p = sz["p"], sz["pallas_np"]
    keys = datagen.uniform(p, n_p, seed=seed)
    x = jnp.asarray(keys)
    base = dict(p=p, n_per_proc=n_p, algorithm="det", merge="tree", seed=seed)
    cfgs = {
        "xla": SortConfig(**base),
        "pallas": SortConfig(**base, local_sort="bitonic", merge_backend="pallas"),
    }
    # det's first rung holds any input, so it is the rung that runs
    rungs = {name: cfg.tier_ladder()[0] for name, cfg in cfgs.items()}
    ex = default_executor()
    prepared, routed = _compile_ahead(run, "d", [
        (ex.prepare_vmap(cfg, 0), (x,), [ex.route_vmap(rungs[name][1], 0)])
        for name, cfg in cfgs.items()
    ])
    # the Pallas programs: Ph2 (bitonic tiles) in prepare, Ph6 (merge tiles)
    # in the route rung
    kernels = {
        stage: compiled[1].as_text().count("tpu_custom_call")
        for stage, compiled in (("prepare", prepared), ("route", routed))
    }
    out = {}
    for name, cfg in cfgs.items():  # XLA first: it warms the small helpers
        traced = sum(ex.trace_counts.values())
        with CompileClock() as clock:
            t0 = time.perf_counter()
            res, _, stats = bsp_sort_safe(x, cfg)
            jax.block_until_ready(res.buf)
            wall = time.perf_counter() - t0
        retraced = sum(ex.trace_counts.values()) - traced
        out[name] = (res, stats, clock, wall, retraced)
    (rx, *_), (rp, stats, clock, wall, retraced) = out["xla"], out["pallas"]
    # the Pallas call built no executable and traced nothing anew, so the
    # programs that ran are the executables inspected above
    reused = clock.executables == 0 and retraced == 0
    identical = np.array_equal(np.asarray(rx.buf), np.asarray(rp.buf)) and np.array_equal(
        np.asarray(rx.count), np.asarray(rp.count)
    )
    sorted_ok = np.array_equal(gathered_output(rp), np.sort(keys.ravel()))
    on_tpu = run.dev.platform == "tpu"
    run.report(
        "d.pallas",
        identical
        and sorted_ok
        and stats.last_tier == rungs["pallas"][0]
        and reused
        and (not on_tpu or min(kernels.values()) > 0),
        n=p * n_p,
        p=p,
        identical_to_xla=identical,
        sorted=sorted_ok,
        tpu_custom_calls=kernels,
        ran_inspected_programs=reused,
        tier=stats.last_tier,
        compile_s=clock.seconds,
        executables_built=clock.executables,
        wall_s=wall,
    )


def _sharded_input(run: Run, mesh, keys, payload, label: str):
    """Put (p, n_p) keys and payload on the mesh, one row per device."""
    sharding = NamedSharding(mesh, P("procs"))
    xs, vs = jax.device_put(keys, sharding), jax.device_put(payload, sharding)
    row = (1, keys.shape[1])
    placed = {
        name: sorted(s.device.id for s in a.addressable_shards if s.data.shape == row)
        for name, a in (("keys", xs), ("payload", vs))
    }
    ids = sorted(d.id for d in mesh.devices.flat)
    run.report(
        f"mesh.{label}.sharded_input",
        all(v == ids for v in placed.values())
        and all(len(a.sharding.device_set) == len(ids) for a in (xs, vs)),
        n_per_proc=keys.shape[1],
        shards=placed,
    )
    return xs, vs


def phase_mesh(run: Run, sz, seed: int) -> None:
    """--chips 4: ``bsp_sort_sharded_safe`` on a 4-device ``procs`` mesh.

    At full size each routing, and an adversarial input that must escalate,
    is checked against numpy. The byte-for-byte comparison with the p=4
    vmap runner is made at ``mesh_compare_per_chip`` keys per chip: on one
    chip the vmap runner holds all four procs' buffers, and its allgather
    programs at full size take ten minutes to compile (described v5e).
    """
    devices = jax.devices()
    p = len(devices)
    mesh = Mesh(np.array(devices), ("procs",))
    ex = default_executor()

    def mesh_runner(x, cfg, values):
        return bsp_sort_sharded_safe(x, mesh, "procs", cfg, values=values)

    def configs(n_p):
        return {
            routing: SortConfig(
                p=p, n_per_proc=n_p, algorithm="iran", routing=routing,
                pair_capacity="whp", seed=seed,
            )
            for routing in MESH_ROUTINGS
        }

    full, small = sz["mesh_per_chip"], sz["mesh_compare_per_chip"]
    cfgs, small_cfgs = configs(full), configs(small)
    keys = datagen.uniform(p, full, seed=seed)
    payload = np.arange(p * full, dtype=np.int32).reshape(p, full)
    xs, vs = _sharded_input(run, mesh, keys, payload, "full")
    adversarial = jax.device_put(_adversarial(p, full), xs.sharding)
    small_keys = datagen.uniform(p, small, seed=seed + 1)
    small_payload = np.arange(p * small, dtype=np.int32).reshape(p, small)
    xs_small, vs_small = _sharded_input(run, mesh, small_keys, small_payload, "compare")
    x1, v1 = jnp.asarray(small_keys), jnp.asarray(small_payload)

    # one prepare program per (runner, size) serves every routing; then the
    # first rung of each routing's ladder, and every a2a_dense rung at full
    # size for the adversarial input
    rungs = [t for _, t in cfgs["a2a_dense"].tier_ladder()]
    rungs += [cfgs[r].tier_ladder()[0][1] for r in ("ring", "allgather")]
    small_rungs = [cfg.tier_ladder()[0][1] for cfg in small_cfgs.values()]
    _compile_ahead(run, "mesh", [
        (
            ex.prepare_sharded(cfgs["ring"], mesh, "procs", 1),
            (xs, vs),
            [ex.route_sharded(t, mesh, "procs", 1) for t in rungs],
        ),
        (
            ex.prepare_sharded(small_cfgs["ring"], mesh, "procs", 1),
            (xs_small, vs_small),
            [ex.route_sharded(t, mesh, "procs", 1) for t in small_rungs],
        ),
        (
            ex.prepare_vmap(small_cfgs["ring"], 1),
            (x1, v1),
            [ex.route_vmap(t, 1) for t in small_rungs],
        ),
    ])

    ref = _stable_reference(keys)
    for routing, cfg in cfgs.items():
        run.sort_phase(f"mesh.{routing}", xs, vs, ref, cfg, mesh_runner)
    # every proc's run is one key, so the stable order is the input order
    adv_ref = (_adversarial(p, full).reshape(-1), payload.reshape(-1))
    _, _, stats = run.sort_phase(
        "mesh.a2a_dense.escalation", adversarial, vs, adv_ref, cfgs["a2a_dense"],
        mesh_runner,
    )
    run.report("mesh.retried", stats.retries >= 1, retries=stats.retries)

    small_ref = _stable_reference(small_keys)
    for routing, cfg in small_cfgs.items():
        mk, mv, ms = run.sort_phase(
            f"mesh.{routing}.compare", xs_small, vs_small, small_ref, cfg, mesh_runner
        )
        vk, vv, vst = run.sort_phase(
            f"vmap.{routing}.compare", x1, v1, small_ref, cfg, bsp_sort_safe
        )
        run.report(
            f"mesh.{routing}.vs_vmap",
            np.array_equal(mk, vk)
            and np.array_equal(mv, vv)
            and (ms.last_tier, ms.retries) == (vst.last_tier, vst.retries),
            tiers=[ms.last_tier, vst.last_tier],
        )


def run_phases(chips: int, rehearse: bool, seed: int = 0) -> dict:
    """Run the phases for ``chips``; return the device record of the run."""
    devices = jax.devices()
    dev = devices[0]
    sz = SIZES["rehearse" if rehearse else "chip"]
    run = Run(dev)
    if chips == 4:
        phase_mesh(run, sz, seed)
    else:
        for phase in (phase_raw, phase_escalation, phase_pallas, phase_service):
            phase(run, sz, seed)
    if run.failed:
        raise SystemExit(f"chip_smoke: failed checks: {run.failed}")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument(
        "--rehearse", action="store_true",
        help="tiny sizes on whatever backend JAX finds (CPU rehearsal)",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees {devices[0].platform})", file=sys.stderr)
        return 2
    if args.chips == 4 and len(devices) != 4:
        print(f"chip_smoke: --chips 4 needs four devices, found {len(devices)}",
              file=sys.stderr)
        return 2
    if not args.rehearse:
        enable_compile_cache()
    device = run_phases(args.chips, args.rehearse, args.seed)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
