"""Device ms per sort call of the ops scoped ``ph5_exchange``, mean over chips.

Ph5, the h-relation: slicing each run into destination rows, the exchange
itself (gathers on one chip's vmap runner, collectives across chips) and
compacting what arrived by source.

The program names the superstep with ``jax.named_scope``;
``bench/lib/scopes.py`` reads each op's scope from the trace. A program
that does not name it leaves this metric without its subject.
"""
from bench.lib import scopes

LAYER = "Ph5 exchange"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "sort_keys_per_s"


def read(ev):
    return scopes.scope_ms_per_call(ev, "ph5_exchange")
