"""The control: the plain reference, unstable, in the program's place.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 10

Each configuration states that its sort is stable. The control is numpy's
unstable sort (``np.argsort(kind="quicksort")``) put where the program's
sort runs, with everything else of a run as it is: the same inputs from the
seed, the same window at the cell's load, the same check. Its result line
must read ``correct: false``, and the numbers it prints are the upper
readings the limits in ``PERF.md`` were set from. The benchmark's own runs
never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

_ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]


def unstable_sort(keys: np.ndarray):
    k = np.asarray(keys).reshape(-1)
    order = np.argsort(k, kind="quicksort")
    return k[order], order


class ControlTarget:
    """A bulk target whose sort is numpy's, unstable, on the host."""

    def __init__(self, target) -> None:
        self.inner = target
        self.p, self.n, self.regions = target.p, target.n, ()

    def put(self, keys):
        return self.inner.put(keys)

    def payload(self):
        return self.inner.payload()

    def launch(self, x, v, call):
        keys, order = unstable_sort(np.asarray(x))
        return keys, np.asarray(v).reshape(-1)[order]

    def wait(self, handle) -> None:
        pass

    def tier(self, handle) -> str:
        return "control"

    def host_outputs(self, handle):
        return handle

    def warm(self, x, v) -> None:
        pass

    def after_window(self, x, v, ctx) -> None:
        pass

    def release(self) -> None:
        self.inner.release()


class _Done:
    """A future that resolved at submit."""

    def __init__(self, result) -> None:
        self._result = result

    def done(self) -> bool:
        return True

    def result(self):
        return self._result

    def exception(self):
        return None


class _Result:
    def __init__(self, keys, order) -> None:
        self.keys, self.order = keys, order


class ControlService:
    """A service that sorts each request at submit with numpy's unstable sort."""

    batches_dispatched = 0

    def submit(self, keys):
        return _Done(_Result(*unstable_sort(keys)))

    def flush_ready(self) -> bool:
        return False

    def start_driver(self, interval_s: float = 0.0) -> None:
        pass

    def stop_driver(self) -> None:
        pass


def control_impl(cell, devices, seed: int) -> dict:
    """The ``run_cell`` keyword that puts the control in the program's place."""
    entry = cell.config["entry"]
    if entry == "service_open":
        return {"service": ControlService()}
    if entry == "bulk_vmap":
        from bench.runners.bulk_vmap import VmapTarget

        return {"target": ControlTarget(VmapTarget(cell.config, devices, seed))}
    if entry == "bulk_mesh":
        from bench.runners.bulk_mesh import MeshTarget

        return {"target": ControlTarget(MeshTarget(cell.config, devices, seed))}
    raise ValueError(f"no control for entry {entry!r}")


def main(argv=None) -> int:
    from bench.lib import harness

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print("control: needs the cell's chips", file=sys.stderr)
        return 3
    devices = devices[: cell.chips]
    harness.configure_compile_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        result = harness.run_cell(
            cell, seed, args.seconds, False, devices, time.perf_counter(), **control_impl(cell, devices, seed)
        )
        print(json.dumps({"control_seed": seed, "correct": result["correct"], "checks": result["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
