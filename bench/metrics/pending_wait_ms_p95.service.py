"""95th percentile of the time a request waits to be batched, in ms.

One obs ``pending`` span per request, from its ``submit`` to the moment its
batch enters the dispatcher queue (flushed by size, deadline, readiness or
a claim), over the requests submitted inside the window. A program that
records no such span leaves this metric without its subject.
"""
from bench.lib import program_spans

LAYER = "service front end"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "req_latency_p95_ms"


def read(ev):
    return program_spans.p95_ms(program_spans.started_in_window(ev, "pending"))
