"""The check must fail the control and every fault a cell can have.

The control is the plain reference, unstable, in the program's place
(``bench/control.py``). The faults break the timed path underneath a real
run: a sort that returns its input unchanged, one that leaves half of the
keys or requests out, one that alters one answer where it is made, and, on
the mesh, one whose chips never exchange keys. Each run here is a whole run
of the harness past its TPU check, and each must read ``correct: false``.
"""
import dataclasses
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from bench import control
from bench.lib import harness
from bench.runners import bulk_vmap, service_open

from conftest import ROOT, edit_json


def run(root, name, seed=3, seconds=0.5, **impl):
    cell = harness.find_cell(name, root)
    return harness.run_cell(cell, seed, seconds, False, jax.devices()[: cell.chips], time.perf_counter(), **impl)


# ------------------------------------------------------------------ bulk
class Faulty(bulk_vmap.VmapTarget):
    """The real vmap target, its outputs broken by ``fault``."""

    def __init__(self, cell, fault):
        super().__init__(cell.config, jax.devices()[:1], 3)
        self.fault = fault

    def host_outputs(self, handle):
        keys, payload = super().host_outputs(handle)
        if self.fault == "unchanged":  # the sort hands back its input
            return np.asarray(self.last_input).reshape(-1), np.arange(keys.size)
        if self.fault == "half":  # half of the batch left out
            return keys[: keys.size // 2], payload[: payload.size // 2]
        if self.fault == "altered":  # one answer altered where it is made
            keys = keys.copy()
            keys[keys.size // 3] += 1
            return keys, payload
        if self.fault == "no_exchange":  # each processor sorts only its own keys
            x = np.asarray(self.last_input)
            order = np.argsort(x, axis=1, kind="stable")
            return np.take_along_axis(x, order, 1).reshape(-1), (order + np.arange(x.shape[0])[:, None] * x.shape[1]).reshape(-1)
        raise ValueError(self.fault)

    def launch(self, x, v, call):
        self.last_input = x
        return super().launch(x, v, call)


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered", "no_exchange"])
def test_bulk_faults_read_incorrect(tiny_root, fault):
    cell = harness.find_cell("bulk.uniform.1chip", tiny_root)
    edit_json(tiny_root / "bench" / "traffic" / "bulk_uniform.json", inputs=1, checked_calls=1)
    result = run(tiny_root, "bulk.uniform.1chip", target=Faulty(cell, fault))
    assert result["correct"] is False
    assert result["checks"]["keys_wrong"]["value"] + result["checks"]["payload_wrong"]["value"] > 0


def test_bulk_control_reads_incorrect(tiny_root):
    # 2^18 uniform keys in [0, 2^31) hold some equal pairs, which the unstable
    # sort may swap; so does every 2^26-key input of the cell
    edit_json(tiny_root / "bench" / "configs" / "bulk-int32-1chip.json", n=1 << 18)
    cell = harness.find_cell("bulk.uniform.1chip", tiny_root)
    result = run(tiny_root, "bulk.uniform.1chip", seconds=0.2, **control.control_impl(cell, jax.devices()[:1], 3))
    assert result["correct"] is False
    assert result["checks"]["keys_wrong"]["value"] == 0
    assert result["checks"]["payload_wrong"]["value"] > 0


# --------------------------------------------------------------- service
class FaultyService:
    """A real service whose answers ``fault`` breaks."""

    def __init__(self, cell, fault):
        self.svc = service_open.make_service(cell.config)
        self.fault = fault
        self.n = 0

    def __getattr__(self, name):
        return getattr(self.svc, name)

    def submit(self, keys):
        self.n += 1
        fut = self.svc.submit(keys)
        if self.fault == "half" and self.n % 2:
            return _Never()  # half of the requests never come back
        return _Broken(fut, keys, self.fault)


class _Never:
    def done(self):
        return False


@dataclasses.dataclass
class _Res:
    keys: np.ndarray
    order: np.ndarray


class _Broken:
    def __init__(self, fut, keys, fault):
        self.fut, self.keys, self.fault = fut, keys, fault

    def done(self):
        return self.fut.done()

    def exception(self):
        return self.fut.exception()

    def result(self):
        r = self.fut.result()
        if self.fault == "unchanged":
            return _Res(self.keys, np.arange(self.keys.size))
        if self.fault == "altered" and r.keys.size:
            keys = r.keys.copy()
            keys[-1] -= 1
            return _Res(keys, r.order)
        return r


@pytest.mark.parametrize("fault", ["unchanged", "half", "altered"])
def test_service_faults_read_incorrect(tiny_root, fault):
    cell = harness.find_cell("service.zipf.open", tiny_root)
    result = run(tiny_root, "service.zipf.open", service=FaultyService(cell, fault))
    assert result["correct"] is False
    checks = result["checks"]
    assert checks["requests_wrong"]["value"] + checks["requests_missing"]["value"] > 0
    if fault == "half":
        assert result["failed"] == checks["requests_missing"]["value"] == result["attempted"] // 2


def test_service_control_reads_incorrect(tiny_root):
    cell = harness.find_cell("service.zipf.open", tiny_root)
    result = run(tiny_root, "service.zipf.open", **control.control_impl(cell, jax.devices()[:1], 3))
    assert result["correct"] is False
    assert result["checks"]["requests_wrong"]["value"] > 0
    assert result["checks"]["requests_missing"]["value"] == 0


MESH_NO_EXCHANGE = """
import json, sys, time
sys.path[:0] = {paths!r}
import jax, numpy as np
from bench.lib import harness
from bench.runners.bulk_mesh import MeshTarget

class NoExchange(MeshTarget):
    # each chip sorts only the keys it holds: the exchange between chips left out
    def launch(self, x, v, call):
        return np.asarray(x), np.asarray(v)
    def wait(self, handle):
        pass
    def tier(self, handle):
        return "no_exchange"
    def host_outputs(self, handle):
        x, v = handle
        order = np.argsort(x, axis=1, kind="stable")
        return np.take_along_axis(x, order, 1).reshape(-1), np.take_along_axis(v, order, 1).reshape(-1)

root = harness.Path({root!r})
harness.TRACE_DIR = root / "bench_out" / "trace"
cell = harness.find_cell("mesh.uniform.4chip", root)
devices = jax.devices()[:4]
target = NoExchange(cell.config, devices, 7)
print(json.dumps(harness.run_cell(cell, 7, 0.3, False, devices, time.perf_counter(), target=target)))
"""


def test_mesh_without_the_exchange_reads_incorrect(tiny_root):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    script = MESH_NO_EXCHANGE.format(paths=[str(ROOT), str(ROOT / "src")], root=str(tiny_root))
    proc = subprocess.run([sys.executable, "-c", script], env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.splitlines()[-1])
    assert result["correct"] is False and result["device"]["count"] == 4
    assert result["checks"]["keys_wrong"]["value"] > 0
