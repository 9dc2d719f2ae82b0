"""What every driver needs around its timed window.

* :class:`CompileCounter` counts programs traced and compiled (or
  loaded from the persistent cache) between two points, from JAX's own
  monitoring events: a compile inside the measured window is a warm-up
  that missed a shape.
* :class:`Tracer` records a device trace of part of the window into a
  temporary directory and reduces it with ``bench/trace.py``.
* :func:`memory_peak` reads the fullest chip's peak device memory.
"""
from __future__ import annotations

import shutil
import tempfile
import time

import jax
import jax.numpy as jnp

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class CompileCounter:
    """Counts JAX traces, compiles and persistent-cache misses."""

    _listening = None

    def __init__(self):
        self.counts = {"traced": 0, "compiled": 0, "cache_misses": 0}
        CompileCounter._listening = self
        if not getattr(CompileCounter, "_registered", False):
            jax.monitoring.register_event_duration_secs_listener(
                CompileCounter._on_duration)
            jax.monitoring.register_event_listener(CompileCounter._on_event)
            CompileCounter._registered = True

    @staticmethod
    def _on_duration(event, duration, **kw):
        c = CompileCounter._listening
        if c is None:
            return
        if event == _TRACE_EVENT:
            c.counts["traced"] += 1
        elif event == _COMPILE_EVENT:
            c.counts["compiled"] += 1

    @staticmethod
    def _on_event(event, **kw):
        c = CompileCounter._listening
        if c is not None and event == _MISS_EVENT:
            c.counts["cache_misses"] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)


@jax.jit
def _barrier():
    return jnp.zeros((), jnp.int32)


def drain_device() -> None:
    """Wait until the device has run every program dispatched so far (a
    program runs after those queued before it)."""
    jax.block_until_ready(_barrier())


class Tracer:
    """Device trace of ``[start_at, start_at + seconds)`` (host
    monotonic clock), started and stopped only at step boundaries, each
    time once the device has run what was dispatched before: a program
    lies wholly inside the trace or wholly outside it, as the step that
    dispatched it does."""

    def __init__(self, start_at: float, seconds: float):
        self.start_at = start_at
        self.stop_at = start_at + seconds
        self.dir = None
        self.t_start = self.t_stop = None
        drain_device()                 # compiled here, not in the window

    @property
    def active(self) -> bool:
        return self.t_start is not None and self.t_stop is None

    @property
    def next_event(self):
        """When the trace is next due to start or stop (None: done)."""
        if self.t_start is None:
            return self.start_at
        return self.stop_at if self.t_stop is None else None

    def poll(self, now: float) -> None:
        """Start or stop at a step boundary once its time has come."""
        if self.t_start is None and now >= self.start_at:
            drain_device()
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0       # host spans stay
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.t_start = time.monotonic()
        elif self.active and now >= self.stop_at:
            self.stop()

    def stop(self) -> None:
        if self.active:
            drain_device()
            self.t_stop = time.monotonic()
            jax.profiler.stop_trace()

    def reduce(self, rec: dict) -> dict:
        """The reduced trace (``bench/trace.py``); the files are removed."""
        from bench import trace
        try:
            path = trace.find_xplane(self.dir)
            out = trace.reduce(path, self.t_start, self.t_stop)
            out["device_kind"] = jax.devices()[0].device_kind
            return out
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def memory_peak() -> int:
    """Peak bytes in use on the fullest local device."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


def bytes_in_use() -> int:
    """Bytes in use now on the fullest local device."""
    return int(max((d.memory_stats() or {}).get("bytes_in_use", 0)
                   for d in jax.local_devices()))


def span(name: str):
    """A host span in the device trace (a no-op when not tracing)."""
    return jax.profiler.TraceAnnotation(name)


