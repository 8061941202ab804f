"""Device ms of XLA ``sort`` ops per sort call, mean over chips.

With ``local_sort="lax"`` and ``merge="sort"`` these are the Ph2 local sort
and the Ph6 merge. A program that moves either off XLA's sort (a Pallas
kernel, a tree merge) leaves this metric without its subject.
"""
from bench.lib import trace

LAYER = "Ph2 local sort and Ph6 merge"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "sort_keys_per_s"


def read(ev):
    return trace.opcode_ms_per_call(ev, ["sort"])
