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
  ``jax.monitoring`` and totals the compile pipeline's phases over the
  process, one counter each: ``jit_trace_seconds`` (tracing to a jaxpr),
  ``jit_lower_seconds`` (the jaxpr to an MLIR module),
  ``jit_cache_load_seconds`` (an executable read back from the persistent
  compilation cache) and ``jit_compile_seconds`` (jax's backend-compile
  event, which wraps ``compile_or_get_cached``: on a persistent-cache hit
  it INCLUDES the cache load, so the backend's own compiling is
  ``jit_compile_seconds - jit_cache_load_seconds``). jax nests phases (a
  jitted callee is traced inside its caller's trace, an eager op compiles
  inside a trace, the load sits inside the backend event): the trace,
  lowering and load counters take each phase's own seconds, less the
  phases inside it, so they add up to wall time. Every backend compile
  also increments ``jit_compiles`` and feeds the ``jit_compile_seconds``
  histogram. Each phase is an event on whatever span was open
  (``jaxpr_trace`` / ``lowering`` / ``cache_load`` / ``compile``, with
  jax's ``fun_name`` where jax gives one, and its seconds; one event for
  the outermost phase of a program, one for every backend compile as
  before), so a span tree or a Perfetto export shows it inside the span
  that caused it. Inside an
  ``instrumented_jit`` compile (:func:`accounted_compile`) the phases are
  also added to that executable's record (``telemetry.xla``). A compile
  that fires while no ``instrumented_jit`` compile is in flight on its
  thread is an unaccounted program — an eager one-op dispatch, a bare
  ``jax.jit`` — and also counts into ``jit_compiles_eager`` /
  ``jit_compile_seconds_eager``. The cache's events count
  ``jit_cache_hits`` (``/jax/compilation_cache/cache_hits``: a program
  served from it) and ``jit_cache_writes`` (``.../cache_misses``, which
  jax records where it WRITES a program to the cache, not on every miss).
- Host->device placement of a design is the other crossing:
  :func:`accounted_upload` runs it under an ``upload`` span that ends on a
  one-element fetch of the last array placed, and counts ``upload.bytes``
  (a validation design's: ``validation_upload``, on its first scoring).

Metric names emitted:

- ``device_fetches`` / ``device_fetch_bytes`` / ``device_fetch_seconds``
  (counters) and ``device_fetch_seconds`` (histogram)
- ``jit_compiles`` / ``jit_compile_seconds`` (counter; the seconds
  include cache loads) and ``jit_compile_seconds`` (histogram)
- ``jit_trace_seconds`` / ``jit_lower_seconds`` /
  ``jit_cache_load_seconds`` (counters, present from the hooks'
  installation on, 0 until a phase fires)
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

# jax.monitoring duration events: the compile pipeline's phases, each a
# process counter, a span event and a field of the executable being compiled
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_PHASE_EVENTS = {
    # event: (phase = ExecutableRecord field prefix, counter, span event)
    "/jax/core/compile/jaxpr_trace_duration": (
        "trace", "jit_trace_seconds", "jaxpr_trace"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": (
        "lower", "jit_lower_seconds", "lowering"),
    "/jax/compilation_cache/cache_retrieval_time_sec": (
        "cache_load", "jit_cache_load_seconds", "cache_load"),
    _COMPILE_EVENT: ("backend", "jit_compile_seconds", "compile"),
}
#: the phase counters, declared at 0 when the hooks are installed (and by
#: ``telemetry.reset()``), so that a run with no cache hit still reports
#: ``jit_cache_load_seconds``
PHASE_COUNTERS = tuple(c for _, c, _ in _PHASE_EVENTS.values())
# persistent compilation cache: a program loaded from it / written to it
# (jax records cache_misses where it writes a compiled program)
_CACHE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "jit_cache_hits",
    "/jax/compilation_cache/cache_misses": "jit_cache_writes",
}

_hooks_lock = threading.Lock()
_hooks_installed = False

# the instrumented_jit compiles in flight on this thread, innermost last:
# each (executable name, its phases' seconds); a backend compile that fires
# with none in flight belongs to no accounted executable
_accounted = threading.local()


def _open_phases() -> list:
    """This thread's timed phases jax has begun and not yet ended, innermost
    last: ``[event, seconds of the phases ended inside it]``."""
    opened = getattr(_accounted, "opened", None)
    if opened is None:
        opened = _accounted.opened = []
    return opened


def _in_flight() -> list:
    frames = getattr(_accounted, "frames", None)
    if frames is None:
        frames = _accounted.frames = []
    return frames


@contextmanager
def accounted_compile(name: str = "") -> Iterator[dict[str, float]]:
    """Marks this thread's compiles as the ``instrumented_jit`` executable
    ``name``'s own (``telemetry.xla`` wraps its lower+compile in it), so
    the compile hook can tell them from eager one-op programs. Yields the
    seconds of each phase jax reports meanwhile (``trace``, ``lower``,
    ``cache_load``, ``backend``), for the executable's record."""
    phases = {phase: 0.0 for phase, _, _ in _PHASE_EVENTS.values()}
    frames = _in_flight()
    frames.append((name, phases))
    try:
        yield phases
    finally:
        frames.pop()


def declare_phase_counters() -> None:
    """Register the phase counters (at 0 where absent)."""
    for name in PHASE_COUNTERS:
        metrics.counter(name)


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
    """Subscribe the compile-phase and cache counters to ``jax.monitoring``
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

        def _on_scalar(event: str, value: float, **_kw: Any) -> None:
            # jax opens a timed phase with a scalar of its start time
            try:
                if event in _PHASE_EVENTS:
                    _open_phases().append([event, 0.0])
            except Exception:  # noqa: BLE001 — never fail a compile
                pass

        def _on_duration(event: str, duration: float, **kw: Any) -> None:
            try:
                phase = _PHASE_EVENTS.get(event)
                if phase is None:
                    return
                field, counter, label = phase
                # a phase's own seconds: less the phases jax ran inside it
                # (a jit traced inside another's tracing, an eager compile
                # inside a trace, the cache load inside the backend event)
                nested = 0.0
                opened = _open_phases()
                for i in range(len(opened) - 1, -1, -1):
                    if opened[i][0] == event:
                        nested = opened[i][1]
                        del opened[i:]
                        break
                if opened:
                    opened[-1][1] += duration
                own = max(duration - nested, 0.0)
                # jit_compile_seconds is the backend event whole (with the
                # load on a cache hit); the others count their own seconds
                metrics.counter(counter).inc(
                    duration if event == _COMPILE_EVENT else own)
                frames = _in_flight()
                attrs = {"seconds": round(duration, 6)}
                if kw.get("fun_name"):
                    attrs["fun_name"] = kw["fun_name"]
                if frames:
                    executable, phases = frames[-1]
                    phases[field] += own
                    attrs["executable"] = executable
                if event == _COMPILE_EVENT:
                    metrics.counter("jit_compiles").inc()
                    metrics.histogram("jit_compile_seconds").observe(duration)
                    attrs["eager"] = not frames
                    if not frames:
                        metrics.counter("jit_compiles_eager").inc()
                        metrics.counter("jit_compile_seconds_eager").inc(
                            duration)
                if event == _COMPILE_EVENT or not opened:
                    # a phase inside another is in that one's seconds: one
                    # event a phase of a program, every compile as before
                    trace.add_event(label, **attrs)
            except Exception:  # noqa: BLE001 — never fail a compile
                pass

        def _on_event(event: str, **_kw: Any) -> None:
            try:
                name = _CACHE_EVENTS.get(event)
                if name is not None:
                    metrics.counter(name).inc()
            except Exception:  # noqa: BLE001 — never fail a compile
                pass

        monitoring.register_scalar_listener(_on_scalar)
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        declare_phase_counters()
        _hooks_installed = True
        return True
