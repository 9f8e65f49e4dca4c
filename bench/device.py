"""The chip the run measures, its compile cache and compile counter."""

from __future__ import annotations

import json
import pathlib
import sys

import jax

from bench.cells import BENCH_DIR

# Lowering and XLA/Mosaic compile, once per program (tracing is left out:
# its events nest, one per inner jit).  Copied from chip_smoke.CompileClock.
COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class NoChip(RuntimeError):
    """The run found no accelerator, or fewer chips than the cell asks."""


def require_chips(chips: int):
    """JAX's devices when the first is a TPU and there are at least
    ``chips`` of them; raise NoChip otherwise (no CPU fallback)."""
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {dev.platform} ({dev.device_kind})")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind`` from ``bench/peaks.json``; a kind
    that is not in the table is an error, not a default."""
    with open(BENCH_DIR / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def enable_cache(root: pathlib.Path) -> str:
    """JAX's persistent compilation cache in ``<checkout>/.jax_cache/``
    (or ``$JAX_COMPILATION_CACHE_DIR``), every program kept so a second
    run of a cell compiles nothing."""
    import os

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        pathlib.Path(root) / ".jax_cache"
    )
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class CompileCounter:
    """Programs lowered and compiled, and their seconds, since ``reset``."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENTS[1]:
            self.count += 1
        if event in COMPILE_EVENTS:
            self.seconds += duration

    def reset(self) -> None:
        self.count, self.seconds = 0, 0.0


def describe(devices) -> dict:
    dev = devices[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak(devices) -> int | None:
    peaks_ = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks_.append(int(stats["peak_bytes_in_use"]))
    return max(peaks_) if peaks_ else None


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)
