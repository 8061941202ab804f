"""Device ms per sort call of the ops scoped ``ph6_merge``, mean over chips.

Ph6, the stable merge of the received runs (a re-sort or the rank-merge
tree, as the configuration says).

The program names the superstep with ``jax.named_scope``;
``bench/lib/scopes.py`` reads each op's scope from the trace. A program
that does not name it leaves this metric without its subject.
"""
from bench.lib import scopes

LAYER = "Ph6 merge"
UNIT = "ms"
SOURCE = "device_trace"
MOVES = "sort_keys_per_s"


def read(ev):
    return scopes.scope_ms_per_call(ev, "ph6_merge")
