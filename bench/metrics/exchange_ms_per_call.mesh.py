"""Device ms per sort call in which a collective ran, mean over chips.

All-to-all, all-gather, all-reduce, collective-permute and reduce-scatter,
synchronous or in flight: the Ph5 h-relation and the small collectives of
the splitter and count supersteps.
"""
from bench.lib import trace

LAYER = "Ph5 exchange"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "sort_keys_per_s"


def read(ev):
    return trace.collective_ms_per_call(ev)
