"""Share of the service window in which no op ran on the device."""
from bench.lib import trace

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "req_latency_p95_ms"


def read(ev):
    return trace.idle_share_pct(ev)
