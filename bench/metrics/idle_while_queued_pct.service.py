"""Share of the window in which requests waited and the device was idle, in %.

The obs ``pending`` and ``queue`` spans, put on the device trace's clock by
``bench/lib/program_spans.py``: the time in which one of them was open and
no op ran on the device, over the window, mean over chips. Work was
waiting while the chip had none.
"""
from bench.lib import program_spans

LAYER = "device"
UNIT = "%"
SOURCE = "program_span"
MOVES = "req_latency_p95_ms"


def read(ev):
    return program_spans.idle_while_pct(ev, ("pending", "queue"))
