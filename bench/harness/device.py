"""The chip this run measures: refusal off the TPU, compile accounting, cache.

Everything here runs before the program under test is imported, so that the
refusal costs nothing and the persistent compilation cache is in place
before the first compile.
"""
from __future__ import annotations

import gc
import os
import time
from collections import defaultdict
from pathlib import Path
from typing import List, Optional, Tuple

# bench/harness/device.py -> the checkout root
ROOT = Path(__file__).resolve().parents[2]
CACHE_DIR = ROOT / "bench" / ".cache"
JAX_CACHE_DIR = CACHE_DIR / "jax"

# fires for every program JAX builds, whether compiled or read from the
# persistent cache; the two cache events below tell the reads apart
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_READ_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class Refused(SystemExit):
    """The run cannot measure anything here (exit code 2, no result line)."""

    def __init__(self, reason: str):
        super().__init__(2)
        self.reason = reason


def check_program_present() -> None:
    """Refuse in a directory that holds only the benchmark."""
    if not (ROOT / "src" / "repro" / "__init__.py").is_file():
        raise Refused(f"the program under test (src/repro) is not in {ROOT}")


def check_chip(jax, chips: int) -> list:
    """The devices of a TPU with at least ``chips`` chips, or a refusal."""
    if os.environ.get("REPRO_FORCE_REF", "0") != "0":
        raise Refused("REPRO_FORCE_REF is set, which routes every kernel to "
                      "its jnp oracle; unset it to measure the Pallas kernels")
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {platform!r}); this "
                      f"benchmark measures only the chip")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees "
                      f"{len(devices)}")
    return devices[:chips]


def use_compile_cache(jax) -> str:
    """JAX's persistent cache at a fixed path in the checkout, for every
    program whatever its compile time or size."""
    JAX_CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(JAX_CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(JAX_CACHE_DIR)


class CompileCounter:
    """Counts the programs JAX builds and their seconds: ``count`` and
    ``seconds`` over all of them, ``hits`` and ``hit_seconds`` for those read
    from the persistent cache, so ``count - hits`` were compiled."""

    def __init__(self, jax):
        self.count = 0
        self.seconds = 0.0
        self.hits = 0
        self.hit_seconds = 0.0
        self.by_program = defaultdict(float)

        def on_duration(event, duration, fun_name="?", **_):
            if event == COMPILE_EVENT:
                self.count += 1
                self.seconds += duration
                self.by_program[fun_name] += duration
            elif event == CACHE_READ_EVENT:
                self.hit_seconds += duration

        def on_event(event, **_):
            if event == CACHE_HIT_EVENT:
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def describe(self) -> str:
        slow = sorted(self.by_program.items(), key=lambda kv: -kv[1])[:3]
        return (f"{self.count} programs in {self.seconds:.3f} s: "
                f"{self.hits} read from the persistent cache "
                f"({self.hit_seconds:.3f} s), {self.count - self.hits} "
                f"compiled; slowest "
                + ", ".join(f"{name} {secs:.3f} s" for name, secs in slow))


class WindowGc:
    """The garbage collector around a measured window. ``open()``, just
    before the window, collects and freezes what survives: the objects
    set-up made (traced programs, warm-up arrays) then stay out of the
    collector's full passes, which would otherwise stop every thread of the
    window for a tenth of a second or more. Until ``close()`` it records
    each pass as ``(generation, seconds)``, so a stall of the host can be
    told apart."""

    def __init__(self):
        self.passes: List[Tuple[int, float]] = []
        self._start = 0.0
        self._open = False

    def _on_gc(self, phase, info):
        if phase == "start":
            self._start = time.perf_counter()
        else:
            self.passes.append((info["generation"],
                                time.perf_counter() - self._start))

    def open(self) -> None:
        gc.collect()
        gc.freeze()
        gc.callbacks.append(self._on_gc)
        self._open = True

    def close(self) -> None:
        if self._open:
            gc.callbacks.remove(self._on_gc)
            gc.unfreeze()
            self._open = False

    def describe(self) -> str:
        if not self.passes:
            return "no garbage collection in the window"
        gen, secs = max(self.passes, key=lambda p: p[1])
        full = sum(1 for g, _ in self.passes if g == 2)
        return (f"{len(self.passes)} garbage collections in the window "
                f"({full} full), longest {1e3 * secs:.3f} ms "
                f"(generation {gen})")


def memory_peak_bytes(devices) -> Optional[int]:
    """Peak bytes in use on the fullest chip, where the backend reports it."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def describe(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
