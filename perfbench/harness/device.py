"""The device a run is on: the table of peaks, the look for the chips a
cell asks for, memory, and JAX's own compile and cache events."""
from __future__ import annotations

import collections
import threading

# Published peaks of one chip, keyed by `device_kind`. A kind that is not
# here is an error, never a default.
# Source: Google Cloud documentation, "TPU v5e" system architecture:
# 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


class NoChipError(Exception):
    """JAX has no accelerator here, or fewer chips than the cell needs."""


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device_kind {device_kind!r}: add it, "
                       "with its source, to perfbench/harness/device.py")
    return PEAKS[device_kind]


def describe() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(n: int) -> dict:
    """The device as JAX reports it, or `NoChipError`."""
    dev = describe()
    if dev["platform"] != "tpu":
        raise NoChipError(f"JAX's first device is {dev['platform']}:"
                          f"{dev['kind']}; the benchmark measures only on "
                          "a TPU")
    if dev["count"] < n:
        raise NoChipError(f"the cell asks for {n} chips; JAX sees "
                          f"{dev['count']}")
    peaks(dev["kind"])
    return dev


def memory_peak_bytes(n_chips: int = 1) -> int:
    """Peak bytes in use on the fullest of the first `n_chips`."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:n_chips])


class CompileMeter:
    """Programs this process compiled or loaded from the persistent
    cache, from JAX's monitoring events (as `chip_smoke.CompileMeter`
    counts them)."""

    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self._n = collections.Counter()  # guarded by: _lock
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event: str, **_kw) -> None:
        if event.startswith("/jax/compilation_cache/cache_"):
            with self._lock:
                self._n[event.rsplit("/", 1)[1]] += 1

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self._n["compile_seconds"] += secs
                self._n["compiles"] += 1

    def read(self) -> dict:
        with self._lock:
            return {"compile_seconds": float(self._n["compile_seconds"]),
                    "compiles": int(self._n["compiles"]),
                    "cache_hits": int(self._n["cache_hits"]),
                    "cache_misses": int(self._n["cache_misses"])}

    @staticmethod
    def programs(after: dict, before: dict) -> int:
        """Programs compiled or loaded between two readings."""
        return max(after["compiles"] - before["compiles"],
                   after["cache_hits"] + after["cache_misses"]
                   - before["cache_hits"] - before["cache_misses"])
