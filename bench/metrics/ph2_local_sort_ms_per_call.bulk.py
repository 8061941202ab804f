"""Device ms per sort call of the ops scoped ``ph2_local_sort``, mean over chips.

Ph2, the stable local sort of each processor's run (and of its payload).

The program names the superstep with ``jax.named_scope``;
``bench/lib/scopes.py`` reads each op's scope from the trace. A program
that does not name it leaves this metric without its subject.
"""
from bench.lib import scopes

LAYER = "Ph2 local sort"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "sort_keys_per_s"


def read(ev):
    return scopes.scope_ms_per_call(ev, "ph2_local_sort")
