"""The program's own instrumentation: superstep scopes in the compiled
programs of both runners, the sorts' ``prepare``/``route`` spans, the
service's ``pending``/``lock_wait`` spans, and a traced launch that never
blocks on the device."""
import json
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.chaos import FaultPlan
from repro.core import SortConfig, datagen, gathered_output
from repro.core.api import SortExecutor, _radix_exact_ladder, bsp_sort_safe_launch
from repro.core.primitives import SUPERSTEPS
from repro.service import ServiceConfig, SortService

from host_devices import run_on_host_devices

pytestmark = pytest.mark.fast

P, N_P = 8, 512
_WRAPPER = re.compile(r"^\w*\((.*)\)$")
#: the sharded cases run on four host devices, one processor each
SHARDED_P = 4
#: (name, SortConfig keywords) of the programs the sharded cases compile
SHARDED_CASES = {"sharded-iran": dict(algorithm="iran"), "sharded-det": dict(algorithm="det")}

_SHARDED_SRC = """
import dataclasses, json, sys
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec
from repro import obs
from repro.core import SortConfig, datagen
from repro.core.api import SortExecutor, bsp_sort_sharded_safe
cases, n_p = json.loads(sys.argv[1]), int(sys.argv[2])
p = len(jax.devices())
mesh = Mesh(np.array(jax.devices()), ("procs",))
shard = NamedSharding(mesh, PartitionSpec("procs"))
x = jax.device_put(jnp.asarray(datagen.generate("U", p, n_p, seed=3)), shard)
v = jax.device_put(jnp.arange(p * n_p, dtype=jnp.int32).reshape(p, n_p), shard)
rng = jax.random.key_data(jax.random.key(0))
out = {}
for name, kw in cases.items():
    cfg = SortConfig(p=p, n_per_proc=n_p, pair_capacity="whp", **kw)
    ex = SortExecutor()
    prepare = ex.prepare_sharded(cfg, mesh, "procs", 1)
    route = ex.route_sharded(cfg.tier_ladder()[0][1], mesh, "procs", 1)
    prep = prepare(x, v)
    t = obs.Tracer()
    bsp_sort_sharded_safe(x, mesh, "procs", dataclasses.replace(cfg, obs=t), values=[v], executor=ex)
    out[name] = {
        "prepare": prepare.lower(x, v).compile().as_text(),
        "route": route.lower(prep, rng).compile().as_text(),
        "spans": [(s["name"], s["args"]) for s in t.spans],
    }
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def sharded_programs():
    """Compiled prepare/route text and sort spans of each sharded case,
    from a subprocess on ``SHARDED_P`` host devices (this process keeps
    seeing one device)."""
    out = run_on_host_devices(_SHARDED_SRC, json.dumps(SHARDED_CASES), str(N_P), devices=SHARDED_P, timeout=300)
    return json.loads(out.splitlines()[-1])


def scopes_in(hlo_text: str) -> set:
    """Superstep scopes named on any op_name path of a compiled program."""
    found = set()
    for name in re.findall(r'op_name="([^"]*)"', hlo_text):
        for path in name.split(";"):
            for part in path.split("/"):
                while _WRAPPER.match(part):
                    part = _WRAPPER.match(part).group(1)
                if part in SUPERSTEPS:
                    found.add(part)
    return found


@pytest.mark.parametrize(
    "kw,prepare_scopes,route_scopes",
    [
        (dict(algorithm="det"), {"ph2_local_sort", "ph3_splitters"},
         {"ph4_partition", "ph5_exchange", "ph6_merge"}),
        (dict(algorithm="iran"), {"ph2_local_sort"},
         {"ph3_splitters", "ph4_partition", "ph5_exchange", "ph6_merge"}),
        (dict(algorithm="ran"), set(),
         {"ph3_splitters", "ph4_partition", "ph5_exchange", "ph6_merge"}),
        (dict(algorithm="iran", route="radix", pair_capacity="exact"),
         {"ph2_local_sort", "radix_count"}, {"ph5_exchange", "ph6_merge"}),
        (dict(algorithm="iran", merge="tree"), {"ph2_local_sort"},
         {"ph3_splitters", "ph4_partition", "ph5_exchange", "ph6_merge"}),
        (dict(algorithm="iran", routing="ring"), {"ph2_local_sort"},
         {"ph3_splitters", "ph4_partition", "ph5_exchange", "ph6_merge"}),
        (dict(algorithm="iran", routing="allgather"), {"ph2_local_sort"},
         {"ph3_splitters", "ph4_partition", "ph5_exchange", "ph6_merge"}),
        # the sharded runner: one processor per device under shard_map, Ph5 a
        # real all-to-all between the devices
        (SHARDED_CASES["sharded-iran"], {"ph2_local_sort"},
         {"ph3_splitters", "ph4_partition", "ph5_exchange", "ph6_merge"}),
        (SHARDED_CASES["sharded-det"], {"ph2_local_sort", "ph3_splitters"},
         {"ph4_partition", "ph5_exchange", "ph6_merge"}),
    ],
    ids=["det", "iran", "ran", "radix", "iran-tree", "iran-ring", "iran-allgather",
         "sharded-iran", "sharded-det"],
)
def test_compiled_programs_carry_their_superstep_scopes(kw, prepare_scopes, route_scopes, request):
    case = request.node.callspec.id
    if case in SHARDED_CASES:
        texts = request.getfixturevalue("sharded_programs")[case]
        assert scopes_in(texts["prepare"]) == prepare_scopes
        assert scopes_in(texts["route"]) == route_scopes
        # every all-to-all of the route, the Ph5 exchange's included, sits
        # under ph5_exchange
        a2a = [line for line in texts["route"].splitlines() if " all-to-all(" in line]
        assert a2a and all(scopes_in(line) == {"ph5_exchange"} for line in a2a)
        return
    x = jnp.asarray(datagen.generate("U", P, N_P, seed=3))
    v = jnp.arange(P * N_P, dtype=jnp.int32).reshape(P, N_P)
    cfg = SortConfig(p=P, n_per_proc=N_P, **kw)
    ex = SortExecutor()
    prepare = ex.prepare_vmap(cfg, 1)
    prep = prepare(x, v)
    tier = cfg.tier_ladder()[0][1]
    if cfg.route == "radix":
        tier = _radix_exact_ladder(cfg, prep)[0][1]
    route = ex.route_vmap(tier, 1)
    rng = jax.random.key_data(jax.random.key(0))
    assert scopes_in(prepare.lower(x, v).compile().as_text()) == prepare_scopes
    assert scopes_in(route.lower(prep, rng).compile().as_text()) == route_scopes


@pytest.mark.parametrize(
    "kw",
    [dict(pair_capacity="whp"), dict(route="radix", pair_capacity="exact")],
    ids=["sample", "radix"],
)
def test_traced_launch_never_blocks_on_the_device(kw, monkeypatch):
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready", lambda a: calls.append(1) or real(a))
    x = jnp.asarray(datagen.generate("U", P, N_P, seed=5))
    t = obs.Tracer()
    flight = bsp_sort_safe_launch(x, SortConfig(p=P, n_per_proc=N_P, obs=t, **kw))
    res, _, _ = flight.wait()
    assert calls == []
    assert np.array_equal(gathered_output(res), np.sort(np.asarray(x).ravel()))
    assert [s["name"] for s in t.spans] == ["prepare", "route"]
    assert not [p for p in t.points if p["name"] == "distribution"]


@pytest.mark.parametrize("runner", ["vmap", "sharded"])
def test_sort_spans_name_their_runner_and_routing(runner, request):
    if runner == "sharded":
        spans = request.getfixturevalue("sharded_programs")["sharded-iran"]["spans"]
    else:
        x = jnp.asarray(datagen.generate("U", P, N_P, seed=5))
        t = obs.Tracer()
        bsp_sort_safe_launch(
            x, SortConfig(p=P, n_per_proc=N_P, algorithm="iran", pair_capacity="whp", obs=t)
        ).wait()
        spans = [(s["name"], s["args"]) for s in t.spans]
    assert [name for name, _ in spans] == ["prepare", "route"]
    assert all(args["runner"] == runner for _, args in spans)
    assert all(args["routing"] == "a2a_dense" for _, args in spans)


def _arrays(sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(-1000, 1000, s).astype(np.int32) for s in sizes]


def _wait_done(futs, timeout=60.0):
    """Wait for futures without claiming them (a claim flushes by itself)."""
    t_end = time.perf_counter() + timeout
    while not all(f.done() for f in futs):
        assert time.perf_counter() < t_end, "futures did not resolve"
        time.sleep(0.002)


def _flush_by(trigger, svc, arrays):
    if trigger == "size":
        futs = [svc.submit(a) for a in arrays]  # max_pending=len(arrays)
        svc.dispatcher.drain()
    elif trigger == "ready":
        futs = [svc.submit(a) for a in arrays]
        assert svc.flush_ready(min_keys=1)
        svc.dispatcher.drain()
    elif trigger == "claim":
        futs = [svc.submit(a) for a in arrays]
        futs[0].result()
    elif trigger == "manual":
        futs = [svc.submit(a) for a in arrays]
        svc.flush()
    else:  # deadline: the driver thread flushes once the oldest is overdue
        svc.start_driver(interval_s=0.002)
        try:
            futs = [svc.submit(a) for a in arrays]
            _wait_done(futs)
        finally:
            svc.stop_driver()
    return futs


@pytest.mark.parametrize("trigger", ["size", "ready", "claim", "manual", "deadline"])
def test_traced_service_records_one_pending_span_per_request(trigger):
    arrays = _arrays([100, 37, 250], seed=7)
    t = obs.Tracer()
    svc = SortService(
        ServiceConfig(
            p=8,
            max_batch_keys=1 << 12,
            pair_capacity="exact",
            max_pending=len(arrays) if trigger == "size" else None,
            flush_after_s=0.2 if trigger == "deadline" else None,
            obs=t,
        ),
        executor=SortExecutor(),
    )
    futs = _flush_by(trigger, svc, arrays)
    for a, f in zip(arrays, futs):
        assert np.array_equal(f.result().keys, np.sort(a))
    pending = [s for s in t.spans if s["name"] == "pending"]
    assert sorted(s["args"]["rid"] for s in pending) == [f.rid for f in futs]
    assert {s["args"]["trigger"] for s in pending} == {trigger}
    assert [s["args"]["n_keys"] for s in sorted(pending, key=lambda s: s["args"]["rid"])] == [
        a.size for a in arrays
    ]
    # each request's wait ends where its batch's queue span starts
    queue = min(s["t0"] for s in t.spans if s["name"] == "queue")
    assert all(s["t0"] + s["dur"] <= queue + 1e-3 for s in pending)
    if trigger == "deadline":
        assert all(s["dur"] >= 0.2 for s in pending)
    waits = [s for s in t.spans if s["name"] == "lock_wait"]
    assert {s["args"]["entry"] for s in waits} >= {"submit"}
    assert obs.validate_spans(t) == []


def test_lock_wait_records_the_wait_behind_a_flight_held_by_the_driver():
    """The driver thread holds the service lock while it waits out a flight
    (here a straggling one), so a submit on the client thread waits too."""
    t = obs.Tracer()
    plan = FaultPlan(straggle_flights=(0,), straggle_s=0.5)
    svc = SortService(
        ServiceConfig(p=8, max_batch_keys=1 << 12, pair_capacity="exact", obs=t, chaos=plan),
        executor=SortExecutor(),
    )
    first = svc.submit(_arrays([300], seed=1)[0])
    svc.flush_async()
    svc.start_driver(interval_s=0.002)
    try:
        t_end = time.perf_counter() + 30.0
        while not plan.injected_total:  # the driver is now inside the straggling flight
            assert time.perf_counter() < t_end, "the driver never reached the flight"
            time.sleep(0.001)
        second = svc.submit(_arrays([50], seed=2)[0])
        _wait_done([first])
    finally:
        svc.stop_driver()
    svc.flush()
    assert second.result().keys.size == 50
    client = threading.current_thread().name
    waits = [
        s for s in t.spans
        if s["name"] == "lock_wait" and s["tid"] == client and s["args"]["entry"] == "submit"
    ]
    assert max(s["dur"] for s in waits) >= 0.1
    assert any(
        s["name"] == "lock_wait" and s["args"]["entry"] == "run_pending" for s in t.spans
    )


def test_untraced_service_takes_the_plain_lock():
    svc = SortService(ServiceConfig(p=8), executor=SortExecutor())
    assert svc._locked("submit") is svc._lock
