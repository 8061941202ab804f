"""The program's spans on the trace's clock, and the service readers."""
import pytest

from bench.lib import program_spans
from bench.lib.trace import Evidence, Op

from conftest import ROOT

# perf_counter seconds [10, 40] <-> trace ns [5e9, 5e9 + 30e9 * (1 + 2e-5)]: the
# trace's clock runs 20 ppm fast against perf_counter over the window
DRIFT = 2e-5
W0, W1 = 5e9, 5e9 + 30e9 * (1 + DRIFT)


def span(name, t0, dur, tid="MainThread", **args):
    return {"name": name, "cat": "service", "tid": tid, "t0": t0, "dur": dur, "args": args}


def evidence(spans=(), ops=None, host=()):
    return Evidence(
        window=(W0, W1),
        ops={0: list(ops or [])},
        async_ops={},
        host=[("window", W0, W1), *host],
        spans=list(spans),
        span_window=(10.0, 40.0),
    )


def ns(t):
    """Where perf_counter ``t`` lies on the trace under the drift above."""
    return W0 + (t - 10.0) * 1e9 * (1 + DRIFT)


@pytest.mark.parametrize("t", [10.0, 12.5, 25.0, 40.0, 41.0])
def test_clock_maps_through_both_anchors_with_drift(t):
    to_ns = program_spans.clock(evidence())
    assert to_ns(t) == pytest.approx(ns(t), abs=1e-3)


def test_clock_refuses_degenerate_anchors():
    ev = evidence()
    ev.span_window = (3.0, 3.0)
    assert program_spans.clock(ev) is None
    assert program_spans.mapped(ev, ("pending",)) == []


def test_p95_of_spans_started_in_the_window():
    spans = [span("pending", 10.0 + i, 0.01 * (i + 1), rid=i) for i in range(20)]
    spans += [span("pending", 9.0, 5.0), span("pending", 40.0, 5.0)]  # outside
    spans += [span("lock_wait", 11.0, 0.5, entry="submit"), span("lock_wait", 12.0, 0.2, entry="run_pending")]
    ev = evidence(spans)
    got = program_spans.p95_ms(program_spans.started_in_window(ev, "pending"))
    assert got == pytest.approx((0.19 + 0.05 * 0.01) * 1e3)
    waits = program_spans.started_in_window(ev, "lock_wait", entries=("submit", "flush_ready"))
    assert [s["dur"] for s in waits] == [0.5]
    assert program_spans.p95_ms([]) is None


def test_overlap_of_disjoint_piece_lists():
    a = [(0, 10), (20, 30), (40, 50)]
    b = [(5, 25), (45, 60)]
    assert program_spans.overlap_ns(a, b) == 5 + 5 + 5
    assert program_spans.overlap_ns(a, []) == 0


def test_idle_while_waiting_counts_only_idle_time_with_a_span_open():
    # pending open [12, 14] s and queue [13, 15] s: waiting over [12, 15] s on
    # the trace; the device runs [13.5, 14.5] s of it, so 2 s idle in 30 s
    spans = [span("pending", 12.0, 2.0, rid=0), span("queue", 13.0, 2.0), span("flight", 14.0, 5.0)]
    ops = [Op("%fusion.11 = ...", "fusion", ns(13.5), ns(14.5))]
    got = program_spans.idle_while_pct(evidence(spans, ops), ("pending", "queue"))
    assert got == pytest.approx(100 * 2.0 / 30.0, rel=1e-6)
    assert program_spans.idle_while_pct(evidence(spans, ops), ("lock_wait",)) is None
    no_device = evidence(spans)
    no_device.ops = {}
    assert program_spans.idle_while_pct(no_device, ("pending", "queue")) is None


def test_open_at_names_spans_with_their_entry():
    spans = [span("pending", 12.0, 2.0, rid=0), span("lock_wait", 12.5, 1.0, entry="submit")]
    ev = evidence(spans)
    assert program_spans.open_at(ev, ns(13.0), ("pending", "lock_wait")) == ["lock_wait:submit", "pending"]
    assert program_spans.open_at(ev, ns(20.0), ("pending", "lock_wait")) == []


def test_containment_of_client_spans_in_submit_annotations():
    host = [("submit", ns(12.0) - 1e3, ns(12.1)), ("submit", ns(20.0) - 1e3, ns(20.2))]
    spans = [
        span("pending", 12.0, 1.0, rid=0),
        span("lock_wait", 12.05, 0.01, entry="submit"),
        span("pending", 20.0, 1.0, rid=1),
        span("lock_wait", 30.0, 0.01, tid="driver", entry="run_pending"),  # not the client's
    ]
    got = program_spans.containment(evidence(spans, host=host))
    assert got["spans"] == 3 and got["outside"] == 0 and got["max_outside_ns"] == 0
    assert got["lead_ns_max"] == pytest.approx(0.05e9 * (1 + DRIFT) + 1e3, rel=1e-6)
    late = evidence([span("pending", 12.2, 0.1, rid=2)], host=host)
    out = program_spans.containment(late)
    assert out["outside"] == 1
    assert out["max_outside_ns"] == pytest.approx(0.1e9 * (1 + DRIFT), rel=1e-6)
    assert program_spans.containment(evidence(spans)) is None


SERVICE_READERS = ["pending_wait_ms_p95.service", "lock_wait_ms_p95.service", "idle_while_queued_pct.service"]


def reader(name):
    from bench.lib import harness

    return harness.metric_reader(name, ROOT)


@pytest.mark.parametrize("name", SERVICE_READERS)
def test_service_readers_return_nothing_without_the_programs_spans(name):
    # a program that records no pending/lock_wait span (nor a queue span)
    ev = evidence([span("form", 12.0, 0.01), span("flight", 12.0, 0.9)], [Op("x", "fusion", ns(12), ns(13))])
    assert reader(name).read(ev) is None


def test_service_readers_on_synthetic_spans():
    spans = [span("pending", 11.0 + i, 0.1 * (i + 1), rid=i, n_keys=100, trigger="ready") for i in range(10)]
    spans += [span("lock_wait", 11.0 + i, 0.001 * i, entry="flush_ready") for i in range(10)]
    spans += [span("queue", 30.0, 1.0)]
    ops = [Op("%fusion.11 = ...", "fusion", ns(11.0), ns(20.0))]
    ev = evidence(spans, ops)
    assert reader("pending_wait_ms_p95.service").read(ev) == pytest.approx(955.0)
    assert reader("lock_wait_ms_p95.service").read(ev) == pytest.approx(8.55)
    # waiting: ten pending pieces and the queue [30, 31]; the device, busy
    # over [11, 20], covers all but the last piece [20, 21]: 2 s idle of 30
    assert reader("idle_while_queued_pct.service").read(ev) == pytest.approx(100 * 2 / 30, rel=1e-6)
