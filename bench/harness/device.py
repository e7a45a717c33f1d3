"""The chip: who holds it, how many there are, what it compiled, its peaks."""
from __future__ import annotations

import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
CACHE_DIR = ROOT / ".jax_cache"      # fixed: the path is part of the key


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_chips(n: int) -> list:
    """The local TPU devices; raises when there is no TPU or too few."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < n:
        raise NoAccelerator(f"needs {n} chips; JAX found {len(devices)}")
    return devices


def enable_compile_cache() -> Path:
    """JAX's persistent cache inside the checkout, for every program size:
    the circuit kernels compile in well under JAX's default one-second
    floor, and would otherwise never be cached."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return CACHE_DIR


class CompileClock:
    """Seconds and count of JAX compilations, from JAX's own events."""

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.compiles = 0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_) -> None:
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs
            self.compiles += name.endswith("backend_compile_duration")


def device_record(devices: list) -> dict:
    """The result line's `device`: as JAX reports it, with the peak memory
    in use on the fullest chip."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of `device_kind`; unknown kinds stop the
    run rather than borrow another chip's numbers."""
    table = json.loads((BENCH_DIR / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in bench/peaks.json")
    return table["devices"][device_kind]
