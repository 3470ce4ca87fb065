"""The chip a run holds: the check that it is there, its description, its
memory peak, JAX's compile cache in the checkout, and a count of compiles."""
from __future__ import annotations

import json
import os
import tempfile
from typing import List

from bench.spec import BENCH_DIR, ROOT

#: JAX's persistent compilation cache: a fixed directory in the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: the monitoring event JAX records for each program it compiles or
#: loads from the persistent cache, and the one it records for a load
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


def use_compile_cache() -> None:
    """Point JAX's compile cache at the checkout, unless the environment
    already names one, and cache every program however quick; keep the
    TPU runtime's logs under ``TMPDIR``. Call before JAX is imported."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    import jax
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def chips(n: int) -> List:
    """The first ``n`` TPU devices; raises :class:`NoChip` otherwise."""
    import jax
    try:
        backend = jax.default_backend()
    except RuntimeError as e:   # no backend could be initialised
        raise NoChip(f"JAX found no accelerator: {e}") from None
    if backend != "tpu":
        raise NoChip(f"JAX found {backend!r}, not a TPU")
    devs = jax.devices()
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devs)}")
    return devs[:n]


def describe(devs) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "device_kind": d.device_kind, "count": len(devs)}


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest of ``devs`` (0 where the backend
    keeps no statistics)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks, default=0))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown chip is an error."""
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return table[device_kind]


class CompileCounter:
    """Counts, from the moment it is made, the programs JAX compiled
    (``compiled``) and those it loaded from the persistent cache
    (``loaded``): each is the first call of a new shape."""

    def __init__(self):
        from jax import monitoring
        self.programs = 0
        self.loaded = 0

        def on_program(event: str, duration: float, **kw) -> None:
            if event == COMPILE_EVENT:
                self.programs += 1

        def on_hit(event: str, **kw) -> None:
            if event == CACHE_HIT_EVENT:
                self.loaded += 1

        monitoring.register_event_duration_secs_listener(on_program)
        monitoring.register_event_listener(on_hit)

    @property
    def compiled(self) -> int:
        return self.programs - self.loaded

    def since(self, mark: tuple) -> dict:
        """Programs compiled and loaded since ``mark = counter.mark()``."""
        return {"compiled": self.compiled - mark[0],
                "loaded": self.loaded - mark[1]}

    def mark(self) -> tuple:
        return self.compiled, self.loaded
