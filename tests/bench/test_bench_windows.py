"""Each entry module's window, at tiny sizes on the CPU.

The harness refuses to run without a TPU; these tests call the part after
that refusal (``harness.run_cell``) with the CPU's devices, on a copy of the
benchmark whose configurations are cut to a few thousand keys.
"""
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from bench.lib import gen, harness

from conftest import ROOT


def run(root, name, trace=False, seconds=0.6, seed=2**31 + 11):
    cell = harness.find_cell(name, root)
    return harness.run_cell(cell, seed, seconds, trace, jax.devices()[: cell.chips], time.perf_counter())


def assert_result_line(result, metric_names):
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(result)[-1] == "checks"
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == set(metric_names)
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    dev = result["device"]
    assert dev["platform"] == jax.devices()[0].platform and dev["count"] >= 1
    json.dumps(result)


def test_bulk_vmap_window(tiny_root, capsys):
    result = run(tiny_root, "bulk.uniform.1chip")
    assert_result_line(result, {"setup_s", "sort_keys_per_s"})
    assert result["checks"] == {"keys_wrong": {"value": 0, "limit": 0}, "payload_wrong": {"value": 0, "limit": 0}}
    info = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert {i["info"] for i in info} >= {"memory", "loop", "checked", "window"}


def test_bulk_vmap_traced_window_reads_the_trace(tiny_root, capsys):
    result = run(tiny_root, "bulk.uniform.1chip", trace=True)
    assert result["correct"]
    # the CPU trace has no TPU plane: the device readers find nothing to read
    assert result["metrics"] == {}
    assert result["device"]["window_s"] > 0 and result["device"]["busy_s"] is None
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert (tiny_root / "bench_out" / "trace").is_dir()
    info = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert any(i["info"] == "region" and i["name"] == "lax_sort" for i in info)


def test_service_window(tiny_root, capsys):
    result = run(tiny_root, "service.zipf.open", seconds=1.0)
    assert_result_line(result, {"setup_s", "req_latency_p50_ms", "req_latency_p95_ms", "served_keys_per_s"})
    assert result["attempted"] == 20
    info = {i["info"]: i for i in map(json.loads, capsys.readouterr().out.splitlines())}
    assert info["window"]["compiles_in_window"] == 0  # set-up warmed every program
    assert info["generator"]["requests"] == 20


def test_service_traced_window_reads_spans_and_compiles(tiny_root):
    result = run(tiny_root, "service.zipf.open", trace=True, seconds=1.0)
    assert result["correct"]
    assert result["metrics"]["window_compiles.service"] == {"value": 0.0, "unit": "count"}
    assert result["metrics"]["frontend_ms_per_batch.service"]["value"] > 0


MESH_SCRIPT = """
import json, sys, time
sys.path[:0] = {paths!r}
import jax
from bench.lib import harness
harness.TRACE_DIR = harness.Path({root!r}) / "bench_out" / "trace"
cell = harness.find_cell("mesh.uniform.4chip", harness.Path({root!r}))
print(json.dumps(harness.run_cell(cell, 7, 0.5, False, jax.devices()[:4], time.perf_counter())))
"""


def test_bulk_mesh_window_on_four_host_devices(tiny_root):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    script = MESH_SCRIPT.format(paths=[str(ROOT), str(ROOT / "src")], root=str(tiny_root))
    proc = subprocess.run([sys.executable, "-c", script], env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.splitlines()[-1])
    assert_result_line(result, {"setup_s", "sort_keys_per_s"})
    assert result["device"]["count"] == 4


@pytest.mark.parametrize("seed", [0, 12345, 2**31 + 5, 2**40 + 3])
def test_every_seed_offers_the_same_work(seed):
    mix = json.loads((ROOT / "bench" / "traffic" / "service_zipf_poisson.json").read_text())
    mix = {**mix, "block_keys": 1 << 14, "block_requests": 16}
    base = gen.open_loop_requests(mix, 1, 4.0)
    reqs = gen.open_loop_requests(mix, seed, 4.0)
    assert len(reqs) == len(base) == round(mix["rate_per_s"] * 4.0)
    assert sorted(k.size for _, k in reqs) == sorted(k.size for _, k in base)
    assert reqs[0][0] == 0.0 and all(0 <= d < 4.0 for d, _ in reqs)
    gaps = gen.poisson_gaps(mix["rate_per_s"], len(reqs), np.random.default_rng(seed))
    assert sorted(gaps) == sorted(gen.poisson_gaps(mix["rate_per_s"], len(reqs), np.random.default_rng(1)))
    assert all(k.dtype == "int32" for _, k in reqs)
    again = gen.open_loop_requests(mix, seed, 4.0)
    assert all((a[1] == b[1]).all() and a[0] == b[0] for a, b in zip(reqs, again))


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_a_schedule_seed_fixes_arrivals_and_sizes(seed):
    mix = json.loads((ROOT / "bench" / "traffic" / "service_zipf_poisson.json").read_text())
    mix = {**mix, "block_keys": 1 << 14, "block_requests": 16}
    base = gen.open_loop_requests(mix, 1, 4.0)
    reqs = gen.open_loop_requests(mix, seed, 4.0)
    assert [(d, k.size) for d, k in reqs] == [(d, k.size) for d, k in base]
    assert any((a[1] != b[1]).any() for a, b in zip(reqs, base))  # the seed draws the keys
    other = gen.open_loop_requests({**mix, "schedule_seed": mix["schedule_seed"] + 1}, seed, 4.0)
    assert [k.size for _, k in other] != [k.size for _, k in base]
