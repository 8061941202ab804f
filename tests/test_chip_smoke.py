"""``chip_smoke.py --rehearse``: every phase, in this process, at tiny sizes.

The chip run itself needs a TPU; this rehearsal runs the same phases on the
CPU (Pallas kernels in interpret mode) and holds them to the same checks:
each phase line reports a numpy match, and the last line is the JSON the
chip contract names, with the platform it really ran on.
"""
import importlib.util
import json
from pathlib import Path

import jax
import pytest

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_rehearsal_phases_match_numpy(capsys):
    assert _load().main(["--rehearse"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    rows = [json.loads(line) for line in lines]
    phases = rows[:-1]
    assert phases and all(r["match"] for r in phases), phases
    assert {r["phase"].split(".")[0] for r in phases} == {"a", "b", "c", "d"}
    retried = next(r for r in phases if r["phase"] == "b.retried")
    assert retried["retries"] >= 1
    dev = jax.devices()[0]
    assert rows[-1] == {
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }


@pytest.mark.skipif(
    jax.default_backend() == "tpu", reason="the refusal needs a machine without a TPU"
)
def test_refuses_without_a_tpu(capsys):
    assert _load().main([]) == 2
    assert capsys.readouterr().out == ""


def test_compile_cache_defers_to_environment(monkeypatch):
    from repro.compile_cache import DEFAULT_DIR, enable_compile_cache

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
