"""Host ms per batch in the service front end: obs ``form`` + ``launch`` spans.

``form`` is fingerprint, plan and pack; ``launch`` is the sort's launch,
which in a traced run blocks on the prepare stage and holds any host read
the route makes. Spans that start inside the window count.
"""
LAYER = "service front end"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "req_latency_p95_ms"


def read(ev):
    lo, hi = ev.span_window
    inside = [s for s in ev.spans if lo <= s["t0"] < hi and s["name"] in ("form", "launch")]
    batches = sum(1 for s in inside if s["name"] == "form")
    if not batches:
        return None
    return sum(s["dur"] for s in inside) / batches * 1e3
