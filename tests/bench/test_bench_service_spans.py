"""A traced service run at tiny size on the CPU: the program's own spans
reach the readers, and map onto the trace's clock inside the benchmark's
annotations."""
import time

import jax

from bench.lib import harness, program_spans
from bench.lib.trace import Op


def test_traced_service_run_reads_the_programs_spans(tiny_root, monkeypatch):
    kept = {}
    build = harness.evidence

    def keep(ctx, out):
        kept["ev"] = build(ctx, out)
        return kept["ev"]

    monkeypatch.setattr(harness, "evidence", keep)
    cell = harness.find_cell("service.zipf.open", tiny_root)
    result = harness.run_cell(cell, 2**31 + 19, 1.0, True, jax.devices()[:1], time.perf_counter())
    assert result["correct"]
    metrics = result["metrics"]
    assert metrics["pending_wait_ms_p95.service"]["value"] > 0
    assert metrics["lock_wait_ms_p95.service"]["value"] >= 0
    # the CPU's trace has no device plane: no idle time to read there
    assert "idle_while_queued_pct.service" not in metrics

    ev = kept["ev"]
    pending = [s for s in ev.spans if s["name"] == "pending"]
    assert sorted(s["args"]["rid"] for s in pending) == list(range(result["attempted"]))
    clock = program_spans.containment(ev)
    assert clock["spans"] > len(pending)  # the client's lock waits too
    assert clock["max_outside_ns"] < 50e6  # the anchors' gap, not a clock offset

    # the same spans against a device busy over the window's first half
    lo, hi = ev.window
    ev.ops = {0: [Op("%fusion.11 = ...", "fusion", lo, (lo + hi) / 2)]}
    got = harness.metric_reader("idle_while_queued_pct.service", tiny_root).read(ev)
    assert 0 < got < 100
