"""Device/transfer accounting: the sanctioned device->host fetch point and
jit compile counters.

Two costs a wall clock cannot attribute become metrics here:

- Every value the host reads from the device is a synchronization: the
  host waits for the producing program, then for the copy.
  :func:`sync_fetch` is the one place the library crosses that boundary:
  it counts fetches, bytes, and blocking seconds, and stamps a
  ``device_fetch`` event on the open span (``tools/check.py`` L007 points
  bare ``block_until_ready()`` calls here, so every wait is accounted).
- Silent recompiles dominated the 20M north-star run (FE 1501 s
  "upload+compile dominated"). :func:`install_compile_hooks` subscribes to
  ``jax.monitoring``: every backend compile increments ``jit_compiles``,
  feeds the ``jit_compile_seconds`` histogram, and shows up as a named
  ``compile`` event on whatever span was open; every program served from
  or written to the persistent compilation cache increments
  ``jit_cache_hits`` / ``jit_cache_writes``. A compile that fires while
  no ``instrumented_jit`` compile is in flight on its thread is an
  unaccounted program — an eager one-op dispatch, a bare ``jax.jit`` — and
  also counts into ``jit_compiles_eager`` / ``jit_compile_seconds_eager``.
- Host->device placement of a design is the other crossing:
  :func:`accounted_upload` runs it under an ``upload`` span that ends on a
  one-element fetch of the last array placed, and counts ``upload.bytes``
  (a validation design's: ``validation_upload``, on its first scoring).

Metric names emitted:

- ``device_fetches`` / ``device_fetch_bytes`` / ``device_fetch_seconds``
  (counters) and ``device_fetch_seconds`` (histogram)
- ``jit_compiles`` / ``jit_compile_seconds`` (counter) and
  ``jit_compile_seconds`` (histogram)
- ``jit_compiles_eager`` / ``jit_compile_seconds_eager`` (counters)
- ``jit_cache_hits`` / ``jit_cache_writes`` (counters)
- ``upload.bytes`` / ``validation_upload.bytes`` (counters)
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Any, Callable, Iterator, Optional, TypeVar

import numpy as np

from photon_ml_tpu.telemetry import metrics, trace

__all__ = [
    "sync_fetch",
    "accounted_upload",
    "accounted_compile",
    "install_compile_hooks",
]

T = TypeVar("T")

# jax.monitoring duration events counted as compiles: the backend (XLA)
# compile is the expensive one; trace/lowering durations are recorded
# under their own short names for completeness.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# persistent compilation cache: a program loaded from it / written to it
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "jit_cache_hits",
    "/jax/compilation_cache/cache_misses": "jit_cache_writes",
}

_hooks_lock = threading.Lock()
_hooks_installed = False

# depth of instrumented_jit compiles in flight on this thread: a backend
# compile that fires at depth 0 belongs to no accounted executable
_accounted = threading.local()


@contextmanager
def accounted_compile() -> Iterator[None]:
    """Marks this thread's backend compiles as an ``instrumented_jit``'s own
    (``telemetry.xla`` wraps its lower+compile in it), so the compile hook
    can tell them from eager one-op programs."""
    _accounted.depth = getattr(_accounted, "depth", 0) + 1
    try:
        yield
    finally:
        _accounted.depth -= 1


def sync_fetch(x: Any, label: Optional[str] = None) -> np.ndarray:
    """Fetch a device array to the host — the ONE sanctioned sync point.

    Returns ``np.asarray(x)`` (a device->host copy, which waits for the
    program that produces ``x``) while accounting for the crossing:
    counters ``device_fetches``, ``device_fetch_bytes``,
    ``device_fetch_seconds``, a blocking-time histogram, and a
    ``device_fetch`` event on the current span.

    Use it for every result the host must observe (convergence scalars,
    tracker vectors, timing syncs); batch values into one array first —
    each call is one more host wait.
    """
    t0 = time.monotonic()
    out = np.asarray(x)
    dt = time.monotonic() - t0
    metrics.counter("device_fetches").inc()
    metrics.counter("device_fetch_bytes").inc(out.nbytes)
    metrics.counter("device_fetch_seconds").inc(dt)
    metrics.histogram("device_fetch_seconds").observe(dt)
    trace.add_event(
        "device_fetch",
        label=label,
        bytes=out.nbytes,
        seconds=round(dt, 6),
    )
    return out


def accounted_upload(place: Callable[[], T], name: str = "upload") -> T:
    """Run ``place()`` — a host->device placement returning a pytree of
    device arrays — under a span ``name`` (a training design's is
    ``upload``). The span closes on a one-element :func:`sync_fetch` of the
    last non-empty array placed (transfers queue in order), and counter
    ``<name>.bytes`` rises by the bytes placed. Host code only: never call
    it inside a traced function."""
    import jax

    with trace.span(name) as sp:
        out = place()
        leaves = [
            x for x in jax.tree.leaves(out)
            if isinstance(x, jax.Array) and x.size
        ]
        nbytes = sum(int(x.nbytes) for x in leaves)
        metrics.counter(f"{name}.bytes").inc(nbytes)
        sp.set_attr(bytes=nbytes)
        if leaves:
            last = leaves[-1]
            sync_fetch(last[(0,) * last.ndim], label=name)
    return out


def install_compile_hooks() -> bool:
    """Subscribe the compile and cache counters to ``jax.monitoring``
    (idempotent; returns True).

    Registered once per process; jax offers no unregister, so the
    listeners guard themselves against a reset registry and never raise
    into the compiler.
    """
    global _hooks_installed
    from jax import monitoring

    with _hooks_lock:
        if _hooks_installed:
            return True

        def _on_duration(event: str, duration: float, **_kw: Any) -> None:
            try:
                if event != _COMPILE_EVENT:
                    return
                metrics.counter("jit_compiles").inc()
                metrics.counter("jit_compile_seconds").inc(duration)
                metrics.histogram("jit_compile_seconds").observe(duration)
                eager = not getattr(_accounted, "depth", 0)
                if eager:
                    metrics.counter("jit_compiles_eager").inc()
                    metrics.counter("jit_compile_seconds_eager").inc(duration)
                trace.add_event(
                    "compile", seconds=round(duration, 6), eager=eager)
            except Exception:  # noqa: BLE001 — never fail a compile
                pass

        def _on_event(event: str, **_kw: Any) -> None:
            try:
                name = _CACHE_EVENTS.get(event)
                if name is not None:
                    metrics.counter(name).inc()
            except Exception:  # noqa: BLE001 — never fail a compile
                pass

        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _hooks_installed = True
        return True
