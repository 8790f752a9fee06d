"""The device rule, the peaks table and the device's own numbers."""

from __future__ import annotations

import json
import os
import sys

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class NoChip(SystemExit):
    """Raised (exit code 4) where a cell cannot run on the chips it asks for."""

    def __init__(self, message: str):
        print(f"benchmarks: {message}; refusing to measure", file=sys.stderr)
        super().__init__(4)


def require_chips(chips: int) -> dict:
    """Run on exactly ``chips`` TPU chips or exit nonzero. There is no CPU
    run of a measuring command: ``ASYNCRL_FORCE_CPU`` is not consulted.
    Returns the device as JAX reports it, and turns on the program's
    persistent compile cache (fixed path inside the checkout, or
    ``JAX_COMPILATION_CACHE_DIR`` where that is set)."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"no TPU ({e})") from None
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"no TPU (jax reports platform={platform!r})")
    if len(devices) != chips:
        raise NoChip(f"the cell asks for {chips} chip(s), jax has {len(devices)}")
    from asyncrl_tpu.utils import runtime

    cache_dir = runtime.enable_compile_cache()
    return {
        "platform": platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "cache_dir": cache_dir,
    }


def peaks(kind: str) -> dict:
    """Peaks of one chip by exact ``device_kind``; any other kind is an
    error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(
            f"no peaks for device kind {kind!r} in {PEAKS_FILE}; "
            f"known: {sorted(table)}"
        )
    return table[kind]


def memory_peak_bytes() -> int:
    """Peak bytes on the fullest chip. On the v5e runtime
    ``peak_bytes_in_use`` counts live buffers only; a program's scratch is
    held as a reservation (``peak_bytes_reserved``: 7.7 GB for the
    ``atari_impala`` step whose ``memory_analysis()`` reports 7.9 GB of
    temp), so the peak is the sum. Backends without the stat give 0."""
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(
            peak,
            int(stats.get("peak_bytes_in_use", 0))
            + int(stats.get("peak_bytes_reserved", 0)),
        )
    return peak


def cache_entries(cache_dir: str | None) -> int:
    from asyncrl_tpu.utils import runtime

    return runtime.cache_entries(cache_dir) if cache_dir else 0


class CompileLog:
    """Times at which JAX asked the backend for a program (a compile or a
    load from the persistent cache: either stalls the caller), from a
    ``jax.monitoring`` listener of the benchmark's own."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import time

        import jax.monitoring

        self.stamps: list[float] = []
        self._clock = time.perf_counter
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if event == self.EVENT:
            self.stamps.append(self._clock())

    def between(self, lo: float, hi: float) -> int:
        return sum(1 for t in self.stamps if lo <= t < hi)
