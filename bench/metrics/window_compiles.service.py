"""Executables JAX built or loaded inside the service window."""
LAYER = "compile"
UNIT = "count"
SOURCE = "program_counter"
MOVES = "req_latency_p95_ms"


def read(ev):
    return float(ev.compiles)
