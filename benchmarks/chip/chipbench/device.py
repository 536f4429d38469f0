"""The device check, the compile counters and the table of peaks."""

from __future__ import annotations

import json
from collections import Counter
from pathlib import Path
from typing import Dict, List

import jax

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


class NoChip(RuntimeError):
    """The run found no TPU, too few chips, or the Pallas kernels interpreted."""


def chips_or_fail(chips: int) -> List[jax.Device]:
    """The first ``chips`` TPU devices; raises :class:`NoChip` otherwise.

    There is no fallback: a CPU run measures XLA's CPU backend, which nobody
    deploys.
    """
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    from repro.kernels import default_interpret

    if default_interpret():
        raise NoChip("Pallas kernels would run interpreted on this device")
    return devices[:chips]


def describe(devices: List[jax.Device]) -> Dict:
    """The ``device`` object of the result line, as JAX reports it."""
    peak = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }


def peak_of(kind: str) -> Dict:
    """The published peaks of one chip of ``kind``; an unknown kind raises."""
    table = json.loads(PEAKS.read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS.name}")
    return table[kind]


def compile_cache() -> str:
    """JAX's persistent compilation cache, as every run of the benchmark
    keeps it: at the directory ``JAX_COMPILATION_CACHE_DIR`` names (the
    entry points point it into the checkout), every program however quick
    its compile, and no eviction, so set-up does the same work on every run
    after the first."""
    from repro.utils.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return enable_compile_cache()


class CompileClock:
    """Counts XLA compilations and persistent-cache hits, and sums compile
    seconds, as JAX reports them.

    JAX reports ``backend_compile_duration`` around every executable it
    obtains, whether compiled or read from the persistent cache; a cache read
    also reports ``cache_hits``.
    """

    def __init__(self):
        self.seconds = 0.0
        self.counts: Counter = Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.seconds += duration
            self.counts["executables"] += 1

    def _on_event(self, event, **_):
        if event == CACHE_HIT:
            self.counts["cache_hits"] += 1

    def snapshot(self) -> Dict:
        return {"compile_s": self.seconds, **self.counts}

    @staticmethod
    def since(before: Dict, after: Dict) -> Dict:
        keys = set(before) | set(after)
        return {k: after.get(k, 0) - before.get(k, 0) for k in sorted(keys)}
