"""The measured window: a host span, the wall clock, and, in a traced run,
the profiler around it."""
from __future__ import annotations

import shutil
import time
from typing import Optional

from bench.trace import WINDOW_SPAN


class Window:
    """``with window:`` brackets the measured loop. ``trace_dir`` set:
    the profiler records it (host spans and device operations, no Python
    call tracing). ``t0``/``t1`` are the host clock at its ends."""

    def __init__(self, trace_dir: Optional[str] = None):
        self.trace_dir = trace_dir
        self.t0 = self.t1 = 0.0
        self._span = None

    def __enter__(self) -> "Window":
        from jax import profiler
        if self.trace_dir:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._span = profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()
        self.t0 = time.perf_counter()
        return self

    def close(self) -> None:
        """End the window at the host clock now (the loop's end)."""
        if self.t1 == 0.0:
            self.t1 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        self.close()
        self._span.__exit__(None, None, None)
        if self.trace_dir:
            from jax import profiler
            profiler.stop_trace()

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def span(name: str, **kw):
    """A host span ``bench.<name>`` in the profiler's trace."""
    from jax import profiler
    return profiler.TraceAnnotation(f"bench.{name}", **kw)
