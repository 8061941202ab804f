"""Run a snippet on forced host devices, in a subprocess, so the pytest
process keeps seeing exactly one CPU device."""
import os
import subprocess
import sys
import textwrap

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_on_host_devices(src: str, *args: str, devices: int = 8, timeout: float = 560) -> str:
    """Standard output of ``python -c src args...`` on ``devices`` CPU devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(_ROOT, "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(src), *args],
        capture_output=True,
        text=True,
        env=env,
        timeout=timeout,
    )
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    return r.stdout
