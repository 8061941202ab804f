"""BENCHMARK.json against the benchmark's contract, and finding cells by name."""
import json
import os
import re
import shutil
import subprocess
import sys
import time

import jax
import pytest

from bench.lib import harness

from conftest import ROOT, edit_json

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source", "workloads"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves", "workloads"},
}


def text_ok(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark(ROOT)


def test_top_level_and_paths(bench):
    assert set(bench) == KEYS["top"]
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./\-]{1,200}", p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    assert 1 <= len(bench["command"]) <= 32 and all(text_ok(w) for w in bench["command"])
    script = bench["command"][1]
    assert any(script.startswith(p + "/") for p in bench["paths"])
    rs = bench["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_entries_keys_names_and_units(bench):
    names = []
    for kind, key in (("configs", "config"), ("workloads", "workload"), ("end_to_end", "end_to_end"), ("per_layer", "per_layer")):
        assert 1 <= len(bench[kind])
        for e in bench[kind]:
            assert set(e) <= KEYS[key], e
            assert NAME.match(e["name"]), e["name"]
            names.append((kind, e["name"]))
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
            for k in ("why", "layer", "source"):
                if k in e:
                    assert text_ok(e[k]), (e["name"], k)
    for kind in ("configs", "workloads"):
        got = [n for k, n in names if k == kind]
        assert len(got) == len(set(got))
    metrics = [n for k, n in names if k in ("end_to_end", "per_layer")]
    assert len(metrics) == len(set(metrics))


def test_configs_are_files_under_paths(bench):
    used = {w["config"] for w in bench["workloads"]}
    files = set()
    for c in bench["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"] and data["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        assert c["source"] == data["source"]
        assert data["chips"] in (1, 4) and "guarantees" in data
        files.add(c["file"])
    assert len(files) == len(bench["configs"])


def test_workloads_cells_and_chips(bench):
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in bench["workloads"])
    assert len(four) <= max(1, len(bench["workloads"]) // 2)
    for w in bench["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
        assert json.loads((ROOT / cfg["file"]).read_text())["chips"] == w["chips"]


def test_metrics_bounds_and_who_reports_what(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    layers = {}
    for m in bench["per_layer"]:
        layers.setdefault(m["layer"], m["layer"])
    for w in bench["workloads"]:
        cell = harness.find_cell(w["name"], ROOT)
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2, w["name"]
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in reported, (w["name"], m["name"])


def test_metric_files_declare_what_benchmark_json_says(bench):
    for m in bench["per_layer"]:
        mod = harness.metric_reader(m["name"], ROOT)
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (m["layer"], m["unit"], m["source"], m["moves"])
        assert callable(mod.read)


def test_each_cell_finds_its_files(bench):
    for w in bench["workloads"]:
        cell = harness.find_cell(w["name"], ROOT)
        assert cell.config_name == w["config"] and cell.traffic_name == w["traffic"]
        assert cell.chips == w["chips"]
        assert harness.runner(cell).run
    with pytest.raises(LookupError):
        harness.find_cell("no.such.cell", ROOT)


def test_a_new_cell_is_found_and_run_from_added_files(tiny_root):
    (tiny_root / "bench" / "traffic" / "bulk_gaussian.json").write_text(
        json.dumps({"kind": "closed_loop", "keys": "G", "inputs": 1, "checked_calls": 1, "why": "[G] keys"})
    )
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench["workloads"].append(
        {"name": "bulk.gaussian.1chip", "config": "bulk-int32-1chip", "traffic": "bulk_gaussian", "chips": 1, "why": "[G]"}
    )
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "bulk.uniform.1chip" in m.get("workloads", []):
            m["workloads"].append("bulk.gaussian.1chip")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.find_cell("bulk.gaussian.1chip", tiny_root)
    assert cell.traffic["keys"] == "G"
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "sort_keys_per_s"}
    assert {m["name"] for m in cell.per_layer} == {"device_idle_share.bulk", "xla_sort_ms_per_call.bulk"}
    result = harness.run_cell(cell, 5, 0.5, False, jax.devices()[:1], time.perf_counter())
    assert result["correct"] and set(result["metrics"]) == {"setup_s", "sort_keys_per_s"}


def _run_py(cwd, *args):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, env=env, capture_output=True, text=True, timeout=120
    )


def test_refuses_without_a_tpu_and_prints_no_result():
    proc = _run_py(ROOT, "--workload", "bulk.uniform.1chip", "--seed", "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0 and proc.stdout == ""
    assert "no TPU" in proc.stderr


def test_fails_in_a_checkout_of_only_the_benchmark(tmp_path):
    bench = harness.load_benchmark(ROOT)
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(tmp_path, "--workload", "bulk.uniform.1chip", "--seed", "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert not any(line.startswith('{"correct"') for line in proc.stdout.splitlines())


def test_mix_edit_reaches_the_generator(tiny_root):
    edit_json(tiny_root / "bench" / "traffic" / "service_zipf_poisson.json", rate_per_s=3)
    cell = harness.find_cell("service.zipf.open", tiny_root)
    assert cell.traffic["rate_per_s"] == 3
