"""HBM accounting: device memory stats as metrics, per-phase peak gauges,
table-size estimates, and a pre-flight headroom check.

The north-star fits hold multi-GB device state (HBM-resident coefficient
tables, tiled design matrices) for hours; today the first memory signal is
an XLA OOM that kills the run. This module turns ``device.memory_stats()``
— populated on TPU/GPU backends, absent on CPU — into:

- :func:`hbm_stats` / :func:`record_device_memory`: raw per-device
  bytes-in-use/limit, published as ``memory.*`` gauges;
- :func:`record_phase_memory`: per-phase ``memory.phase.<name>.bytes_in_use``
  gauges plus a max-tracked ``memory.phase.<name>.peak_bytes`` (the HBM
  profile of ``fit > cd_iteration > coordinate:<name>`` in the run report);
- :func:`estimate_table_bytes` / :func:`estimate_batch_bytes`: predicted
  residency of a coefficient table / a pytree batch before it is uploaded;
- :func:`check_headroom`: warn (log + ``memory.headroom_warnings`` counter)
  BEFORE a predicted allocation exceeds free HBM, instead of OOMing a
  coordinate mid-fit.

Backends without memory stats (the CPU test mesh) degrade gracefully:
every probe returns None and the headroom check passes as "unknown".
Tests inject deterministic stats via :func:`set_stats_provider`.
"""

from __future__ import annotations

import logging
from typing import Any, Callable, Mapping, Optional, Sequence

from photon_ml_tpu.telemetry import metrics

__all__ = [
    "hbm_stats",
    "set_stats_provider",
    "record_device_memory",
    "record_device_watermarks",
    "device_spread_bytes",
    "record_phase_memory",
    "estimate_table_bytes",
    "estimate_batch_bytes",
    "check_headroom",
    "reset",
]

logger = logging.getLogger("photon_ml_tpu.telemetry.memory")

#: Fraction of the device's byte limit treated as usable by the headroom
#: check — XLA needs workspace beyond the caller's own arrays.
DEFAULT_SAFETY_FRACTION = 0.92

# test/injection hook: zero-arg callable returning a memory_stats()-shaped
# mapping (or None); overrides the real device probe when set
_stats_provider: Optional[Callable[[], Optional[Mapping[str, Any]]]] = None


def set_stats_provider(
    provider: Optional[Callable[[], Optional[Mapping[str, Any]]]]
) -> None:
    """Override the device probe (deterministic tests / simulations).

    ``None`` restores the real ``device.memory_stats()`` probe."""
    global _stats_provider
    _stats_provider = provider


def hbm_stats(device=None) -> Optional[dict[str, int]]:
    """``{"bytes_in_use", "bytes_limit", ...}`` for ``device`` (default:
    the first device), or None when the backend publishes no memory stats
    (CPU) — callers must treat None as "unknown", not "zero"."""
    if _stats_provider is not None and device is None:
        raw = _stats_provider()
        return dict(raw) if raw else None
    try:
        import jax

        if device is None:
            device = jax.devices()[0]
    except Exception:  # noqa: BLE001 — accounting must never fail a caller
        return None
    probe = getattr(device, "memory_stats", None)
    if probe is None:
        return None
    try:
        raw = probe()
    except Exception:  # noqa: BLE001 — some backends raise NotImplemented
        return None
    return dict(raw) if raw else None


def record_device_memory(devices: Optional[Sequence] = None) -> dict[str, int]:
    """Publish ``memory.device.<id>.bytes_in_use`` / ``.bytes_limit`` /
    ``.peak_bytes_in_use`` gauges for every device that exposes stats;
    returns the total in-use bytes per device id (empty on statless
    backends)."""
    if devices is None:
        try:
            import jax

            devices = jax.devices()
        except Exception:  # noqa: BLE001
            return {}
    out: dict[str, int] = {}
    for d in devices:
        stats = hbm_stats(d)
        if not stats:
            continue
        did = getattr(d, "id", len(out))
        in_use = int(stats.get("bytes_in_use", 0))
        metrics.gauge(f"memory.device.{did}.bytes_in_use").set(in_use)
        if "bytes_limit" in stats:
            metrics.gauge(f"memory.device.{did}.bytes_limit").set(
                int(stats["bytes_limit"])
            )
        if "peak_bytes_in_use" in stats:
            # the allocator's own high-watermark, not a sampled one
            metrics.gauge(f"memory.device.{did}.peak_bytes_in_use").set(
                int(stats["peak_bytes_in_use"])
            )
        out[str(did)] = in_use
    return out


def record_device_watermarks(
    devices: Optional[Sequence] = None, phase: Optional[str] = None
) -> dict[str, int]:
    """Sample per-device HBM in-use and max-track high-watermark gauges.

    The executable profiler calls this on its sampling cadence, so the
    peaks are LIVE — they catch the transient allocation spike mid-solve
    that the end-of-phase ``record_phase_memory`` probe sleeps through.
    Gauges: ``memory.device.<id>.peak_bytes`` (per-run high-watermark)
    and, when ``phase`` is given, ``memory.phase.<phase>.device.<id>
    .peak_bytes``. Returns the sampled in-use bytes per device id (empty
    on statless backends — absence stays unknown, never zero)."""
    per_device = record_device_memory(devices)
    for did, in_use in per_device.items():
        peak = metrics.gauge(f"memory.device.{did}.peak_bytes")
        if peak.value is None or in_use > peak.value:
            peak.set(in_use)
        if phase:
            phase_peak = metrics.gauge(
                f"memory.phase.{phase}.device.{did}.peak_bytes"
            )
            if phase_peak.value is None or in_use > phase_peak.value:
                phase_peak.set(in_use)
    return per_device


def device_spread_bytes() -> Optional[int]:
    """Per-device HBM in-use spread (max - min bytes across all devices
    that expose stats), or None with fewer than two reporting devices.

    ``make_mesh`` publishes the per-device ``memory.device.<id>.*`` gauges
    at mesh build; this refreshes them from the live probe, falls back to
    the already-published gauges (statless probes, offline tests), and
    reduces to the ONE number that makes shard imbalance visible (a
    balanced entity sharding keeps it near zero). Also published as the
    ``memory.device_spread_bytes`` gauge so run reports loaded from a
    metrics JSONL can render it."""
    per_device = record_device_memory()
    if len(per_device) < 2:
        prefix, suffix = "memory.device.", ".bytes_in_use"
        per_device = {
            name[len(prefix):-len(suffix)]: value
            for name, value in metrics.snapshot()["gauges"].items()
            if name.startswith(prefix) and name.endswith(suffix)
            and value is not None
        }
    if len(per_device) < 2:
        return None
    spread = max(per_device.values()) - min(per_device.values())
    metrics.gauge("memory.device_spread_bytes").set(spread)
    return int(spread)


def record_phase_memory(phase: str, device=None) -> Optional[int]:
    """Sample HBM in-use under ``phase`` and max-track its peak gauge.

    Gauges: ``memory.phase.<phase>.bytes_in_use`` (last sample) and
    ``memory.phase.<phase>.peak_bytes`` (max over the run). Returns the
    sampled bytes, or None when the backend has no stats."""
    stats = hbm_stats(device)
    if not stats or "bytes_in_use" not in stats:
        return None
    in_use = int(stats["bytes_in_use"])
    metrics.gauge(f"memory.phase.{phase}.bytes_in_use").set(in_use)
    peak = metrics.gauge(f"memory.phase.{phase}.peak_bytes")
    if peak.value is None or in_use > peak.value:
        peak.set(in_use)
    metrics.gauge("memory.bytes_in_use").set(in_use)
    if "bytes_limit" in stats:
        metrics.gauge("memory.bytes_limit").set(int(stats["bytes_limit"]))
    return in_use


def estimate_table_bytes(
    num_entities: int, dim: int, itemsize: int = 4
) -> int:
    """Predicted HBM residency of an [num_entities, dim] coefficient
    table (the ShardedCoefficientTable / RE-bucket model envelope)."""
    return int(num_entities) * int(dim) * int(itemsize)


def estimate_batch_bytes(batch: Any) -> int:
    """Predicted device residency of a pytree batch: the sum of its array
    leaves' ``nbytes`` (host numpy leaves report what the upload will
    cost; device leaves report what is already resident)."""
    try:
        import jax

        leaves = jax.tree.leaves(batch)
    except Exception:  # noqa: BLE001 — accounting only
        leaves = [batch]
    return int(sum(getattr(x, "nbytes", 0) for x in leaves))


def check_headroom(
    predicted_bytes: int,
    label: str = "",
    device=None,
    safety_fraction: float = DEFAULT_SAFETY_FRACTION,
) -> Optional[bool]:
    """Will ``predicted_bytes`` more fit in free HBM?

    Returns True (fits), False (predicted to exceed — a warning is logged
    and ``memory.headroom_warnings`` incremented BEFORE the OOM would
    happen), or None (backend has no stats; nothing to check). Publishes
    ``memory.free_bytes`` either way stats exist.
    """
    stats = hbm_stats(device)
    if not stats or "bytes_limit" not in stats:
        return None
    in_use = int(stats.get("bytes_in_use", 0))
    limit = int(stats["bytes_limit"])
    free = int(limit * safety_fraction) - in_use
    metrics.gauge("memory.free_bytes").set(max(free, 0))
    if predicted_bytes <= free:
        return True
    metrics.counter("memory.headroom_warnings").inc()
    logger.warning(
        "HBM headroom: %s predicts %.2f GB but only %.2f GB free "
        "(%.2f/%.2f GB in use; safety %.0f%%) — expect an OOM or spill",
        label or "allocation",
        predicted_bytes / 2**30,
        max(free, 0) / 2**30,
        in_use / 2**30,
        limit / 2**30,
        safety_fraction * 100,
    )
    return False


def reset() -> None:
    """Restore defaults (test isolation): drop any injected stats
    provider. Gauges/counters live in the metrics registry and are cleared
    by ``metrics.reset()``."""
    set_stats_provider(None)
