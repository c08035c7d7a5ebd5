"""The chip: the check that it is there, its peaks, its memory, the
compile cache and the count of compiles.

The harness refuses anything but a TPU.  Peaks come from
``benchmarks/peaks.json``, keyed by ``device_kind``; a kind that is not
there is an error, never a default.
"""
from __future__ import annotations

import json
import os
import threading


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_tpu(chips: int):
    """The first ``chips`` TPU devices, or ``NoChip``."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no devices: {e}") from e
    if not devs or devs[0].platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {devs[0].platform if devs else 'nothing'}"
                     f" ({devs[0] if devs else ''}); no CPU fallback")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX sees {len(devs)}")
    return devs[:chips]


def peaks(root: str, kind: str) -> dict:
    with open(os.path.join(root, "benchmarks", "peaks.json")) as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in benchmarks/peaks.json")
    return table["devices"][kind]


def cache_dir(root: str) -> str:
    """Where JAX's persistent compilation cache lives: the
    ``JAX_COMPILATION_CACHE_DIR`` given, where it lies under this run's
    own HOME, XDG_CACHE_HOME or TMPDIR or inside the checkout; else the
    fixed ``<checkout>/.bench_cache/jax``."""
    given = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if given:
        given = os.path.realpath(given)
        for base in (os.environ.get("HOME"), os.environ.get("XDG_CACHE_HOME"),
                     os.environ.get("TMPDIR"), root):
            if base and given.startswith(os.path.realpath(base) + os.sep):
                return given
    return os.path.join(root, ".bench_cache", "jax")


def enable_cache(root: str) -> str:
    """Turn the persistent compilation cache on for every program, and
    hand the same directory to the program through the environment."""
    import jax
    d = cache_dir(root)
    os.makedirs(d, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = d
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


class CompileCounter:
    """XLA backend compiles, counted from ``jax.monitoring`` events while
    ``counting`` is set."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        self.counting = False
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT and self.counting:
            with self._lock:
                self.n += 1


def memory_peak(devices) -> int:
    """The peak bytes in use on the fullest chip."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
