"""95th percentile of the client's waits for the service lock, in ms.

The obs ``lock_wait`` spans of the client's entry points, ``submit`` and
``flush_ready``, that started inside the window: how long a caller waited
to take the lock, e.g. while the driver thread held it through a flight. A
program that records no such span leaves this metric without its subject.
"""
from bench.lib import program_spans

LAYER = "service front end"
UNIT = "ms"
SOURCE = "program_span"
MOVES = "req_latency_p95_ms"


def read(ev):
    spans = program_spans.started_in_window(ev, "lock_wait", entries=("submit", "flush_ready"))
    return program_spans.p95_ms(spans)
