"""Structured telemetry: tracing spans, a metrics registry, device/memory
accounting, live progress heartbeats, and run reports.

Five layers (ISSUE 1 gave emission; ISSUE 3 the interpretation):

- :mod:`photon_ml_tpu.telemetry.trace` — ``span(name, **attrs)`` opens a
  node of a thread-safe hierarchical span tree with a JSONL sink and a
  Chrome-trace/Perfetto exporter. ``utils.timing.timed()`` is a thin
  wrapper over it, so every driver phase is already a span.
- :mod:`photon_ml_tpu.telemetry.metrics` — process-global counters /
  gauges / histograms with a ``snapshot()`` dict and a JSONL flush;
  attached to the final ``TrainingFinishEvent``; the benchmark's
  counter readers (``benchmark/readers``) read the same snapshot.
- :mod:`photon_ml_tpu.telemetry.device` — ``sync_fetch()``, the one
  sanctioned device->host fetch point (fetches / bytes / blocking
  seconds), plus per-compile counters via ``jax.monitoring``.
- :mod:`photon_ml_tpu.telemetry.memory` — HBM accounting over
  ``device.memory_stats()``: per-phase peak gauges, table-size estimates,
  and a headroom check that warns BEFORE a predicted allocation OOMs.
- :mod:`photon_ml_tpu.telemetry.progress` / ``.report`` — a heartbeat
  daemon that keeps long fits audible, and :class:`RunReport`, which
  merges trace + metrics + checkpoint manifests into one markdown/JSON
  report with a regression ``compare()`` (the ``cli report`` perf gate).
- :mod:`photon_ml_tpu.telemetry.xla` — device-level cost accounting:
  ``instrumented_jit`` records compile time, cost/memory analysis, and
  recompile attribution per executable; roofline peaks for MFU and
  bandwidth utilization; ``comms.*`` collective-bytes estimates (the
  run report's "Device utilization" section).
- :mod:`photon_ml_tpu.telemetry.profile` — the executable layer
  (ISSUE 16): every ``instrumented_jit`` dispatch is counted and every
  Nth honestly timed (fetch-synchronized through ``sync_fetch``),
  yielding per-executable exclusive seconds, MFU, arithmetic intensity,
  and a roofline bound class — the run report's "Hot executables" table
  and the heartbeat's ``hot_exec`` field. Armed at import; sampled (one
  dispatch in 64), so steady state pays the fetch rarely.
- :mod:`photon_ml_tpu.telemetry.identity` / ``.fleet_report`` — fleet
  observability (ISSUE 13): per-member artifact suffixing
  (``trace.proc-0.jsonl``), process identity + epoch anchors in every
  stream, and :class:`FleetReport`, which merges a fleet directory of
  member streams into one report with collective-wait/straggler
  attribution (``cli report --fleet``).

Typical use::

    from photon_ml_tpu import telemetry

    telemetry.configure(trace_out="run.trace.jsonl")
    with telemetry.Heartbeat(interval=30, jsonl_path="run.metrics.jsonl"):
        with telemetry.span("fit", task="logistic"):
            ...
            value = float(telemetry.sync_fetch(result.value, label="loss"))
    telemetry.flush_metrics("run.metrics.jsonl")
    telemetry.export_chrome_trace("run.trace.jsonl", "run.perfetto.json")

Importing this package installs the jit compile hooks (idempotent, and a
no-op without jax.monitoring), so recompiles are counted from the first
traced program onward, and publishes the package's own import seconds as
the counter ``import.seconds`` (``photon_ml_tpu/_import_clock.py``), which
``reset()`` leaves alone.
"""

from __future__ import annotations

import os
from typing import Optional

from photon_ml_tpu import _import_clock

from photon_ml_tpu.telemetry import (  # noqa: F401
    identity,
    memory,
    metrics,
    profile,
    trace,
    xla,
)
from photon_ml_tpu.telemetry import requests  # noqa: F401  (needs trace)
from photon_ml_tpu.telemetry.identity import member_artifact_path  # noqa: F401
from photon_ml_tpu.telemetry.device import (  # noqa: F401
    declare_phase_counters,
    install_compile_hooks,
    sync_fetch,
)
from photon_ml_tpu.telemetry.xla import (  # noqa: F401
    XLA_REGISTRY,
    instrumented_jit,
    record_collective,
)
from photon_ml_tpu.telemetry.metrics import (  # noqa: F401
    counter,
    gauge,
    histogram,
    register_snapshot_provider,
    snapshot,
)
from photon_ml_tpu.telemetry.progress import Heartbeat  # noqa: F401
from photon_ml_tpu.telemetry.trace import (  # noqa: F401
    active_span_path,
    add_event,
    current_span,
    export_chrome_trace,
    finished_spans,
    perfetto_path,
    span,
    to_chrome_trace,
)

__all__ = [
    "span",
    "current_span",
    "add_event",
    "active_span_path",
    "finished_spans",
    "counter",
    "gauge",
    "histogram",
    "snapshot",
    "register_snapshot_provider",
    "flush_metrics",
    "sync_fetch",
    "install_compile_hooks",
    "to_chrome_trace",
    "export_chrome_trace",
    "perfetto_path",
    "Heartbeat",
    "memory",
    "identity",
    "member_artifact_path",
    "xla",
    "profile",
    "requests",
    "instrumented_jit",
    "record_collective",
    "XLA_REGISTRY",
    "configure",
    "configure_from_env",
    "reset",
]

# configure_from_env side effects, remembered so reset() can undo them —
# without this, test ordering decides whether a leaked atexit flush or
# env-pointed sink survives into later tests (ISSUE 3 satellite).
_env_state: dict[str, object] = {"atexit_flush": None}


def configure(
    trace_out: Optional[str] = None,
    buffer_limit: Optional[int] = None,
) -> None:
    """Point the span JSONL sink at ``trace_out`` (None = leave as-is)."""
    trace.configure(jsonl_path=trace_out, buffer_limit=buffer_limit)


def flush_metrics(path: str) -> dict:
    """Append the metrics snapshot to ``path`` (``metrics.flush_jsonl``),
    after flushing the executable profiler's lazily-published derived
    gauges (MFU, bound class, ...) so offline report loads rebuild the
    Hot-executables table from the JSONL alone, and after one last
    per-device memory probe."""
    profile.publish_metrics()
    memory.record_device_memory()  # end-of-run per-device HBM gauges
    return metrics.flush_jsonl(path)


def configure_from_env() -> None:
    """Honor ``PHOTON_TRACE_OUT`` / ``PHOTON_TELEMETRY_OUT`` env vars: the
    span sink opens immediately; the metrics snapshot flushes at process
    exit. Lets benchmarks and ad-hoc scripts opt in without new flags.
    ``reset()`` fully undoes both (including the atexit hook).

    In a fleet (``PHOTON_PROC_ID`` set by the supervisor, or an
    already-initialized multi-process jax) both paths are suffixed per
    member (``trace.jsonl`` -> ``trace.proc-0.jsonl``) so N processes
    pointed at the same env value write N artifact streams instead of
    clobbering one file — the naming contract ``cli report --fleet``
    globs (telemetry.identity / telemetry.fleet_report)."""
    trace_out = os.environ.get("PHOTON_TRACE_OUT")
    if trace_out:
        configure(trace_out=identity.member_artifact_path(trace_out))
    metrics_out = os.environ.get("PHOTON_TELEMETRY_OUT")
    if metrics_out:
        import atexit
        import functools

        metrics_out = identity.member_artifact_path(metrics_out)
        old = _env_state["atexit_flush"]
        if old is not None:
            atexit.unregister(old)
        flush = functools.partial(flush_metrics, metrics_out)
        atexit.register(flush)
        _env_state["atexit_flush"] = flush


def reset() -> None:
    """Restore telemetry to import-time defaults (test isolation): clear
    spans and metrics, close the trace sink, restore the default buffer
    limit, drop any injected memory-stats provider, and unregister the
    ``configure_from_env`` atexit flush."""
    trace.reset()
    metrics.reset()
    declare_phase_counters()
    memory.reset()
    xla.reset()
    profile.reset()
    requests.reset()
    flush = _env_state["atexit_flush"]
    if flush is not None:
        import atexit

        atexit.unregister(flush)
        _env_state["atexit_flush"] = None


install_compile_hooks()
# the package's own imports (photon_ml_tpu/_import_clock.py), kept outside
# the registry so that reset() cannot erase them
metrics.register_counter_provider("import.seconds", _import_clock.seconds)
# arm the executable-level dispatch sampler (idempotent; profile.reset()
# re-arms, so test isolation never leaves profiling dark)
profile.install()
