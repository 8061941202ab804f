"""Async dispatch: futures, in-flight batches, failsink fault isolation,
bounded unclaimed store, admission-aware forming, telemetry memoization."""
import sys
import threading
import time

import numpy as np
import pytest

from repro import obs
from repro.core import SortExecutor, sort_segments
from repro.service import (
    BatchFormer,
    ServiceConfig,
    SortFuture,
    SortService,
    SortServiceError,
)

pytestmark = pytest.mark.fast

POISON_LEN = 777  # unique request length the poison monkeypatches key on


def _arrays(sizes, seed=0, span=2**31):  # a small span: many equal keys
    rng = np.random.default_rng(seed)
    return [rng.integers(-span, span, s).astype(np.int32) for s in sizes]


def test_submit_returns_future_without_dispatching():
    """Acceptance: submit() queues and returns — nothing launches until a
    flush trigger or a claim forces it."""
    svc = SortService(ServiceConfig(p=8), executor=SortExecutor())
    arrays = _arrays([100, 300, 50])
    futs = [svc.submit(a) for a in arrays]
    assert all(isinstance(f, SortFuture) and not f.done() for f in futs)
    assert svc.pending == 3
    assert svc.dispatcher.idle and svc.dispatcher.launches == 0
    for a, f in zip(arrays, futs):
        res = f.result()  # the only blocking point
        assert np.array_equal(res.keys, np.sort(a))
        assert np.array_equal(a[res.order], res.keys)


def test_futures_path_byte_identical_to_fused_sync_path():
    """Acceptance: results claimed through futures are byte-identical to the
    core fused segmented sort (itself acceptance-tested against the
    per-request ``bsp_sort_safe`` reference in test_service.py)."""
    sizes = [5, 333, 64, 1000, 7, 512]
    arrays = _arrays(sizes, seed=9)
    ref = sort_segments(arrays, p=8)
    svc = SortService(ServiceConfig(p=8), executor=SortExecutor())
    futs = [svc.submit(a) for a in arrays]
    svc.flush()
    for i, f in enumerate(futs):
        res = f.result()
        assert res.keys.dtype == ref.keys[i].dtype == np.int32
        assert np.array_equal(res.keys, ref.keys[i])
        assert np.array_equal(res.order, ref.order[i])


def test_multiple_batches_in_flight_overlap():
    """The pipeline keeps max_in_flight batches launched at once: with four
    formed batches, two fly before anything is awaited, and later launches
    happen while earlier flights' device work is outstanding."""
    svc = SortService(
        ServiceConfig(p=8, max_batch_keys=400, max_in_flight=2),
        executor=SortExecutor(),
    )
    arrays = _arrays([300, 300, 300, 300], seed=3)
    futs = [svc.submit(a) for a in arrays]
    svc.flush_async()
    assert svc.dispatcher.in_flight == 2  # both slots filled, none awaited
    assert svc.dispatcher.launches == 2
    assert not any(f.done() for f in futs)
    svc.flush()  # drain the pipeline
    tele = svc.telemetry()["dispatch"]
    assert tele["in_flight_peak"] >= 2
    assert tele["overlapped_launches"] >= 1  # launched under outstanding work
    for a, f in zip(arrays, futs):
        assert np.array_equal(f.result().keys, np.sort(a))


def test_poison_request_failsink_isolates_and_resolves_solo(monkeypatch):
    """Satellite: one poison request in a fused batch. The failsink bisects
    until the poison stands alone; every innocent request completes, the
    poison sorts solo in its own bucket, and nothing raises."""
    import repro.service.dispatch as disp_mod

    orig = disp_mod.segmented_sort_launch

    def poisoned(packed, **kw):  # fails only while fused with others
        if POISON_LEN in packed.sizes and len(packed.sizes) > 1:
            raise RuntimeError("ladder exhausted (simulated)")
        return orig(packed, **kw)

    monkeypatch.setattr(disp_mod, "segmented_sort_launch", poisoned)
    svc = SortService(ServiceConfig(p=8), executor=SortExecutor())
    arrays = _arrays([300, 300, POISON_LEN, 300, 300], seed=5)
    futs = [svc.submit(a) for a in arrays]
    out = svc.flush()
    assert set(out) == {f.rid for f in futs}  # no rid lost
    for a, f in zip(arrays, futs):
        res = f.result()
        assert np.array_equal(res.keys, np.sort(a))
    poison = futs[2].result()
    assert poison.failsink  # routed through the failsink
    assert poison.n_per_proc == 128  # solo pow2 bucket for 777 keys over p=8
    tele = svc.telemetry()["dispatch"]
    assert tele["failsink_splits"] >= 1
    assert tele["failsink_errors"] == 0
    assert tele["failsink_resolved"] >= 1


def test_poison_request_failsink_terminal_error_spares_the_batch(monkeypatch):
    """Satellite: a request that fails even solo resolves with a
    SortServiceError naming its rid — every other request in the original
    batch still completes, and flush() itself never raises."""
    import repro.service.dispatch as disp_mod

    orig = disp_mod.segmented_sort_launch

    def poisoned(packed, **kw):  # fails every dispatch containing the rid
        if POISON_LEN in packed.sizes:
            raise RuntimeError("backend error (simulated)")
        return orig(packed, **kw)

    monkeypatch.setattr(disp_mod, "segmented_sort_launch", poisoned)
    svc = SortService(ServiceConfig(p=8), executor=SortExecutor())
    arrays = _arrays([200, POISON_LEN, 200, 200], seed=6)
    futs = [svc.submit(a) for a in arrays]
    svc.flush()  # does NOT raise: the failure lives on the poison future
    for i, (a, f) in enumerate(zip(arrays, futs)):
        if i == 1:
            continue
        assert np.array_equal(f.result().keys, np.sort(a))
    exc = futs[1].exception()
    assert isinstance(exc, SortServiceError)
    assert exc.rids == (futs[1].rid,) and str(futs[1].rid) in str(exc)
    with pytest.raises(SortServiceError):
        futs[1].result()
    with pytest.raises(SortServiceError):
        svc.take_result(futs[1])
    tele = svc.telemetry()
    assert tele["requests_failed"] == 1
    assert tele["dispatch"]["failsink_errors"] == 1
    # bisection isolated the poison; its one solo retry also failed
    assert tele["dispatch"]["failsink_splits"] >= 2
    assert tele["dispatch"]["failsink_solo_retries"] >= 1


def test_sort_many_surfaces_failure_as_service_error_not_keyerror(monkeypatch):
    """Satellite: the blocking conveniences never raise a bare KeyError for
    a failed request — they surface the SortServiceError naming the rid,
    and the other requests' results stay claimable."""
    import repro.service.dispatch as disp_mod

    orig = disp_mod.segmented_sort_launch

    def poisoned(packed, **kw):
        if POISON_LEN in packed.sizes:
            raise RuntimeError("backend error (simulated)")
        return orig(packed, **kw)

    monkeypatch.setattr(disp_mod, "segmented_sort_launch", poisoned)
    svc = SortService(ServiceConfig(p=8), executor=SortExecutor())
    arrays = _arrays([100, POISON_LEN, 150], seed=7)
    with pytest.raises(SortServiceError) as ei:
        svc.sort_many(arrays)
    assert ei.value.rids == (1,)  # the poison's rid, by submit order
    for rid, a in [(0, arrays[0]), (2, arrays[2])]:
        assert np.array_equal(svc.take_result(rid).keys, np.sort(a))
    # claiming an unknown/failed rid is a SortServiceError too, not KeyError
    with pytest.raises(SortServiceError, match="rid=1"):
        svc.take_result(1)


def test_unclaimed_store_bounded_with_eviction_counter():
    """Satellite: the unclaimed-result store is capped with oldest-first
    eviction; the eviction is telemetry-counted and the SortFuture's cached
    result survives it."""
    svc = SortService(
        ServiceConfig(p=8, max_unclaimed=4), executor=SortExecutor()
    )
    arrays = _arrays([50] * 6, seed=8)
    futs = [svc.submit(a) for a in arrays]
    out = svc.flush()
    assert set(out) == {f.rid for f in futs[2:]}  # oldest two evicted
    assert svc.evicted_results == 2
    assert svc.telemetry()["evicted_results"] == 2
    with pytest.raises(SortServiceError, match="evicted"):
        svc.take_result(futs[0].rid)  # store copy is gone
    res0 = futs[0].result()  # ...but the future's cached copy is not
    assert np.array_equal(res0.keys, np.sort(arrays[0]))
    assert np.array_equal(svc.take_result(futs[5]).keys, np.sort(arrays[5]))


def test_telemetry_latency_stats_memoized_per_completion(monkeypatch):
    """Satellite: polling telemetry() must not rescan the latency window
    when nothing new completed — quantiles recompute only after new
    results land."""
    svc = SortService(ServiceConfig(p=8), executor=SortExecutor())
    svc.sort_many(_arrays([100, 200, 300], seed=10))
    calls = {"n": 0}
    orig = np.quantile

    def counting(*args, **kw):
        calls["n"] += 1
        return orig(*args, **kw)

    monkeypatch.setattr(np, "quantile", counting)
    first = svc.telemetry()
    after_first = calls["n"]
    assert after_first >= 1 and first["lat_p99_ms"] > 0
    for _ in range(5):  # soak-loop polling: no new completions, no rescans
        again = svc.telemetry()
    assert calls["n"] == after_first
    assert again["lat_p99_ms"] == first["lat_p99_ms"]
    svc.sort_one(np.arange(64, dtype=np.int32)[::-1].copy())
    svc.telemetry()  # a new completion invalidates the memo
    assert calls["n"] > after_first


def test_form_ready_holds_partial_tail_and_flush_ready_launches_full():
    """Admission-aware forming: full batches dispatch, the underfilled tail
    is held for more traffic (and a plain flush clears it)."""
    former = BatchFormer(p=8, max_batch_keys=1000, min_n_per_proc=8)
    reqs = [(i, np.zeros(s, np.int32)) for i, s in enumerate([600, 300, 200])]
    ready, held = former.form_ready(reqs, min_keys=500)
    assert [b.rids for b in ready] == [[0, 1]]  # 900 keys: full enough
    assert [rid for rid, _ in held] == [2]  # 200-key tail held, FIFO order
    # default threshold is half the cap
    ready2, held2 = former.form_ready(reqs)
    assert [b.rids for b in ready2] == [[0, 1]] and len(held2) == 1
    assert former.form_ready([]) == ([], [])

    svc = SortService(
        ServiceConfig(p=8, max_batch_keys=1000), executor=SortExecutor()
    )
    arrays = _arrays([600, 300, 200], seed=11)
    futs = [svc.submit(a) for a in arrays]
    assert svc.flush_ready(min_keys=500)  # launches the 900-key batch only
    assert svc.pending == 1  # the tail stays queued
    assert svc.flush_triggers.get("ready") == 1
    assert not svc.flush_ready(min_keys=500)  # still underfilled: no-op
    svc.flush()  # deadline/manual path clears the held tail
    assert svc.pending == 0
    for a, f in zip(arrays, futs):
        assert np.array_equal(f.result().keys, np.sort(a))


def _assert_stable_sorted(arrays, futs):
    for a, f in zip(arrays, futs):
        res = f.result()
        assert np.array_equal(res.keys, np.sort(a))
        assert np.array_equal(res.order, np.argsort(a, kind="stable"))


def test_flush_ready_launches_underfilled_tail_when_dispatcher_idle():
    """Work-conserving release: with nothing queued or in flight there is
    no device work for the tail to fuse behind, so it launches at once."""
    svc = SortService(
        ServiceConfig(p=8, max_batch_keys=1 << 12, pair_capacity="exact"),
        executor=SortExecutor(),
    )
    arrays = _arrays([300, 100, 250], seed=40, span=50)  # 650 keys < 2048
    futs = [svc.submit(a) for a in arrays]
    assert svc.dispatcher.idle
    assert svc.flush_ready()  # underfilled, yet launched
    assert svc.pending == 0 and svc.dispatcher.in_flight == 1
    assert svc.flush_triggers == {"idle": 1}
    _assert_stable_sorted(arrays, futs)


def test_held_tail_launches_when_run_pending_lands_the_flight():
    """While a flight is outstanding flush_ready holds the tail; the
    driver pump that completes the flight launches it, long before the
    flush_after_s deadline."""
    svc = SortService(
        ServiceConfig(
            p=8, max_batch_keys=1 << 12, pair_capacity="exact",
            flush_after_s=60.0,
        ),
        executor=SortExecutor(),
    )
    arrays = _arrays([400, 120], seed=41, span=50)
    t0 = time.perf_counter()
    first = svc.submit(arrays[0])
    assert svc.flush_ready()  # idle: launches alone
    second = svc.submit(arrays[1])
    assert not svc.flush_ready()  # a flight is outstanding: held
    assert svc.pending == 1 and not second.done()
    assert svc.run_pending(max_steps=1)  # lands the flight, launches the tail
    assert first.done() and svc.pending == 0
    assert svc.dispatcher.in_flight == 1
    assert svc.flush_triggers == {"idle": 2}
    _assert_stable_sorted(arrays, [first, second])
    assert time.perf_counter() - t0 < 30.0  # not the 60 s deadline


def test_arrivals_during_a_flight_fuse_into_one_idle_batch():
    t = obs.Tracer()
    svc = SortService(
        ServiceConfig(
            p=8, max_batch_keys=1 << 12, pair_capacity="exact",
            flush_after_s=60.0, obs=t,
        ),
        executor=SortExecutor(),
    )
    arrays = _arrays([500, 90, 210, 130], seed=42, span=50)
    futs = [svc.submit(arrays[0])]
    assert svc.flush_ready()
    for a in arrays[1:]:  # each arrival meets the outstanding flight
        futs.append(svc.submit(a))
        assert not svc.flush_ready()
    assert svc.pending == 3
    svc.run_pending(max_steps=1)
    assert svc.flush_triggers == {"idle": 2}
    svc.dispatcher.drain()
    queued = [s["args"]["n_rids"] for s in t.spans if s["name"] == "queue"]
    assert queued == [1, 3]  # the three held arrivals rode one batch
    pending = [s for s in t.spans if s["name"] == "pending"]
    assert {s["args"]["trigger"] for s in pending} == {"idle"}
    _assert_stable_sorted(arrays, futs)


def test_deadline_only_service_under_driver_never_reads_idle():
    """The release applies only to tails flush_ready held: plain submits
    under the driver thread still wait for flush_after_s."""
    t = obs.Tracer()
    svc = SortService(
        ServiceConfig(
            p=8, max_batch_keys=1 << 12, pair_capacity="exact",
            flush_after_s=0.05, obs=t,
        ),
        executor=SortExecutor(),
    )
    arrays = _arrays([200, 70, 330], seed=43, span=50)
    def wait_done(futs):  # without claiming: a claim flushes by itself
        t_end = time.perf_counter() + 60.0
        while not all(f.done() for f in futs):
            assert time.perf_counter() < t_end, "futures did not resolve"
            time.sleep(0.002)

    svc.start_driver(interval_s=0.002)
    try:
        futs = [svc.submit(arrays[0])]
        wait_done(futs)  # a flight came and went: the dispatcher is idle
        futs += [svc.submit(a) for a in arrays[1:]]
        wait_done(futs)
    finally:
        svc.stop_driver()
    assert set(svc.flush_triggers) == {"deadline"}
    pending = [s for s in t.spans if s["name"] == "pending"]
    assert len(pending) == 3
    assert {s["args"]["trigger"] for s in pending} == {"deadline"}
    assert all(s["dur"] >= 0.05 for s in pending)
    _assert_stable_sorted(arrays, futs)


def test_idle_release_strands_no_tail_under_concurrent_submitters():
    """Stress: submitter threads race the driver thread for the service
    lock. With no deadline at all, only the idle release can launch a held
    tail, so a tail stranded while the dispatcher idles would hang here."""
    svc = SortService(
        ServiceConfig(p=8, max_batch_keys=256, pair_capacity="exact"),
        executor=SortExecutor(),
    )
    per_thread, n_threads = 25, 6
    arrays = _arrays([100 + i % 21 for i in range(per_thread * n_threads)], seed=44, span=50)
    futs = [None] * len(arrays)

    def submitter(t: int) -> None:
        for i in range(t, len(arrays), n_threads):
            futs[i] = svc.submit(arrays[i])
            svc.flush_ready()  # 100-120 keys: alone a tail, a pair is ready

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    svc.start_driver(interval_s=0.001)
    try:
        threads = [threading.Thread(target=submitter, args=(t,)) for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120.0)
            assert not th.is_alive()
        t_end = time.perf_counter() + 120.0
        while not all(f.done() for f in futs):
            assert time.perf_counter() < t_end, "a held tail was stranded"
            time.sleep(0.005)
    finally:
        svc.stop_driver()
        sys.setswitchinterval(interval)
    assert svc.pending == 0 and svc.dispatcher.idle
    assert svc.requests_done == len(arrays)
    assert set(svc.flush_triggers) <= {"ready", "idle"}
    _assert_stable_sorted(arrays, futs)


def test_two_poison_requests_in_one_batch_both_isolated(monkeypatch):
    """Multi-poison failsink: two poison requests fused into one batch are
    BOTH bisected down to terminal solo failures naming their own rid, and
    every innocent in the batch completes."""
    import repro.service.dispatch as disp_mod

    orig = disp_mod.segmented_sort_launch
    POISON_LEN_2 = 778

    def poisoned(packed, **kw):  # each poison fails every dispatch it rides
        if POISON_LEN in packed.sizes or POISON_LEN_2 in packed.sizes:
            raise RuntimeError("backend error (simulated)")
        return orig(packed, **kw)

    monkeypatch.setattr(disp_mod, "segmented_sort_launch", poisoned)
    svc = SortService(
        # breaker off: this test pins the pure-bisection path
        ServiceConfig(p=8, breaker_threshold=0),
        executor=SortExecutor(),
    )
    sizes = [300, POISON_LEN, 250, POISON_LEN_2, 200, 350]
    arrays = _arrays(sizes, seed=12)
    futs = [svc.submit(a) for a in arrays]
    svc.flush()  # never raises
    for i, (a, f) in enumerate(zip(arrays, futs)):
        if i in (1, 3):
            exc = f.exception()
            assert isinstance(exc, SortServiceError), (i, exc)
            assert exc.rids == (f.rid,) and f"rid={f.rid}" in str(exc)
        else:
            assert f.exception() is None, (i, f.exception())
            assert np.array_equal(f.result().keys, np.sort(a))
    tele = svc.telemetry()["dispatch"]
    assert tele["failsink_errors"] == 2
    assert svc.telemetry()["requests_failed"] == 2


def test_backoff_does_not_starve_innocents_behind_retry_queue(monkeypatch):
    """Backoff ordering: while a failed batch's retries back off, freshly
    enqueued innocent batches launch ahead of them — the pump scans past
    backing-off entries instead of waiting at the queue head."""
    import repro.service.dispatch as disp_mod

    orig = disp_mod.segmented_sort_launch
    launched = []

    def recording(packed, **kw):
        launched.append(tuple(packed.sizes))
        if POISON_LEN in packed.sizes:
            raise RuntimeError("backend error (simulated)")
        return orig(packed, **kw)

    monkeypatch.setattr(disp_mod, "segmented_sort_launch", recording)
    svc = SortService(
        ServiceConfig(
            p=8,
            failsink_backoff_s=0.2,
            failsink_backoff_max_s=0.2,
            breaker_threshold=0,
            max_in_flight=1,
        ),
        executor=SortExecutor(),
    )
    poison_fut = svc.submit(_arrays([POISON_LEN], seed=13)[0])
    svc.flush_async()  # poison launches solo, fails, requeues with backoff
    assert launched == [(POISON_LEN,)]  # retry is parked behind not_before
    a = _arrays([200], seed=14)[0]
    innocent = svc.submit(a)
    res = innocent.result()  # must NOT wait out the poison's 0.2s backoff
    assert np.array_equal(res.keys, np.sort(a))
    # the innocent launched ahead of the backed-off retry: the pump scanned
    # past the not_before-gated head instead of blocking on it
    first_retry = launched.index((POISON_LEN,), 1) if \
        launched.count((POISON_LEN,)) > 1 else len(launched)
    assert launched.index((200,)) < first_retry, launched
    with pytest.raises(SortServiceError, match=f"rid={poison_fut.rid}"):
        poison_fut.result()  # drives through the backoff window to terminal
    assert launched.count((POISON_LEN,)) == 2  # original + its one solo retry
