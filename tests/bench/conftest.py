"""Fixtures for the benchmark's tests: CPU only, tiny sizes, no chip.

``tiny_root`` is a copy of the benchmark (``BENCHMARK.json`` and ``bench/``)
whose configurations and mixes are cut to sizes a test can sort in a
second. The runners and the harness are the real ones, imported from the
repository; only the files they find by name are the copy's. Cells whose
files are in ``bench/`` but whose entries wait in ``pending_cells.json``
(not yet proven on the chip) are added to the copy's ``BENCHMARK.json``, as
a later change would add them.
"""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {
    "bench/configs/bulk-int32-1chip.json": {"n": 1 << 14},
    "bench/configs/bulk-int32-mesh4.json": {"n_per_chip": 1 << 12},
    "bench/configs/service-zipf-1chip.json": {"max_batch_keys": 1 << 13, "drain_s": 10, "warm_threads": 2},
    "bench/traffic/service_zipf_poisson.json": {"block_keys": 1 << 14, "block_requests": 16, "rate_per_s": 20},
}


def edit_json(path: Path, **changes) -> None:
    data = json.loads(path.read_text())
    data.update(changes)
    path.write_text(json.dumps(data))


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    shutil.copytree(ROOT / "bench", tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    pending = Path(__file__).with_name("pending_cells.json")
    if pending.exists():
        for kind, entries in json.loads(pending.read_text()).items():
            have = {e["name"]: e for e in bench[kind]}
            for e in entries:
                if e["name"] in have:  # a cell's name joins a metric's list
                    lists = have[e["name"]].get("workloads")
                    if lists is not None:
                        lists += [w for w in e.get("workloads", []) if w not in lists]
                else:
                    bench[kind].append(e)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for rel, changes in TINY.items():
        edit_json(tmp_path / rel, **changes)
    from bench.lib import harness

    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path / "bench_out" / "trace")
    return tmp_path
