"""Share of the bulk window in which no op ran on the device, mean over chips."""
from bench.lib import trace

LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"
MOVES = "sort_keys_per_s"


def read(ev):
    return trace.idle_share_pct(ev)
