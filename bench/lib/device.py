"""What the benchmark knows about the device: its peaks, memory and compiles.

``PEAKS`` is the one table of a chip's published numbers, keyed by JAX's
``device_kind``. A kind that is not in it is an error, never a default.
"""
from __future__ import annotations

import threading
from typing import Dict, Sequence

import jax

#: TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture page):
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "ici_bits_per_s": 1600e9,
    },
}


def peaks(kind: str) -> Dict[str, float]:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; add them to PEAKS") from None


def peak_bytes_in_use(devices: Sequence) -> int:
    """``peak_bytes_in_use`` of the fullest of ``devices`` (0 if unreported)."""
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks_, default=0))


COMPILE_EVENT_PREFIX = "/jax/core/compile/"
#: fires once per executable JAX builds, fresh or loaded from the persistent cache
BACKEND_COMPILE_EVENT = COMPILE_EVENT_PREFIX + "backend_compile_duration"


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling while open, and the
    executables it built (fresh or from the persistent cache).

    A copy of ``chip_smoke.CompileClock``.
    """

    def __init__(self) -> None:
        self.seconds = 0.0
        self.executables = 0
        self._lock = threading.Lock()  # compiles may report from threads

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith(COMPILE_EVENT_PREFIX):
            with self._lock:
                self.seconds += duration
                self.executables += event == BACKEND_COMPILE_EVENT

    def __enter__(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on_event)
