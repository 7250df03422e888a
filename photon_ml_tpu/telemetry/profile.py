"""Executable-level roofline profiler: sampled HONEST timing per compiled
executable, bound-class attribution, and HBM high-watermarks.

The fourth observability layer (span -> phase -> fleet -> executable):
PR 1/3 measure wall time and HBM occupancy, PR 4 (telemetry.xla) accounts
compiles and static cost, but nothing attributed *device time* to an
individual executable — and the obvious attempt lies: dispatch is
asynchronous, so a naive ``time.monotonic()`` bracket around it measures
only the enqueue (0.2 ms for a 108 ms kernel on a v5e; chip_smoke.py's
``transport`` line, PR 21). The clock must stop on a wait for the result;
the one wait this library sanctions and accounts is a device->host fetch
through :func:`telemetry.device.sync_fetch`.

So this module hooks every ``instrumented_jit`` dispatch (the
``xla.set_dispatch_profiler`` hook, armed at ``telemetry`` import) and:

- counts every dispatch per ``(name, signature)`` dispatch key — the same
  key the executable registry uses, so shardings stay distinct entries
  and merge per NAME for reporting;
- every Nth dispatch per entry (``PHOTON_PROFILE_SAMPLE_EVERY``, default
  :data:`DEFAULT_SAMPLE_EVERY`; the FIRST dispatch of every entry is
  always sampled so short runs still profile), takes one honest
  measurement: clock the dispatch, then fetch one output leaf through
  ``sync_fetch`` so the clock stops only when the device is actually
  done. Sampling keeps the steady-state cost to one fetch in N
  dispatches (tests assert the counts; the ``profile.overhead_seconds``
  counter records what it cost, for a chip run to judge);
- subtracts nested sampled dispatches (tracing an outer executable can
  dispatch inner ones) via a thread-local measurement stack, yielding
  per-executable EXCLUSIVE seconds;
- derives, against :func:`telemetry.xla.device_peaks`: MFU, arithmetic
  intensity (FLOPs / byte), and a roofline **bound class** —
  MXU-bound / VPU-bound / HBM-bound / dispatch-bound (see
  :func:`bound_class`);
- cross-checks the timing honesty itself: a measured rate above the
  resolved device peak is physically impossible, so it flags
  ``timing_suspect`` instead of reporting a fake number;
- samples per-device HBM high-watermarks (``memory.
  record_device_watermarks``) on the same cadence, attributed to the
  open span's phase;
- optionally arms a ``jax.profiler`` capture window around the Kth
  dispatch (:func:`configure_xprof`; ``cli train --xprof-dir``),
  CPU-guarded so the capture machinery cannot wedge test runs.

Everything is published as ``profile.exec.<name>.<field>`` metrics so run
reports rebuilt from a metrics JSONL can render the Hot-executables table
offline, mirroring the ``xla.exec.*`` convention (names may contain dots;
field names never do).
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading
from time import monotonic as _monotonic
from typing import Any, Callable, Optional

from photon_ml_tpu.telemetry import device, memory, metrics, trace, xla

__all__ = [
    "ProfileEntry",
    "ProfileRegistry",
    "PROFILE_REGISTRY",
    "DEFAULT_SAMPLE_EVERY",
    "BOUND_UNKNOWN",
    "BOUND_MXU",
    "BOUND_VPU",
    "BOUND_HBM",
    "BOUND_DISPATCH",
    "BOUND_CLASS_NAMES",
    "bound_class",
    "bound_class_name",
    "profile_dispatch",
    "install",
    "publish_metrics",
    "merged_profiles",
    "exclusive_seconds_by_name",
    "set_sample_every",
    "set_clock",
    "configure_xprof",
    "stop_xprof",
    "set_xprof_hooks",
    "reset",
]

logger = logging.getLogger("photon_ml_tpu.telemetry.profile")

#: Sample one honest (fetch-synchronized) timing every this many
#: dispatches of one (name, signature) entry. 1/64 sampling bounds the
#: worst case — a sampled dispatch that costs as much again in sync —
#: at ~1.6%, inside the 2% overhead budget the tests assert.
DEFAULT_SAMPLE_EVERY = 64

#: Roofline bound classes (numeric codes so they survive a metrics
#: round trip as gauges; 0 must stay "unknown" — absence of evidence).
BOUND_UNKNOWN = 0
BOUND_MXU = 1
BOUND_VPU = 2
BOUND_HBM = 3
BOUND_DISPATCH = 4

BOUND_CLASS_NAMES = {
    BOUND_UNKNOWN: "unknown",
    BOUND_MXU: "MXU-bound",
    BOUND_VPU: "VPU-bound",
    BOUND_HBM: "HBM-bound",
    BOUND_DISPATCH: "dispatch-bound",
}

#: An executable whose roofline-predicted time is under this fraction of
#: its MEASURED time is dominated by dispatch/launch overhead, not by the
#: device — "make the kernel faster" would be the wrong fix.
DISPATCH_BOUND_RATIO = 0.1

#: Compute-side executables below this MFU are classed VPU-bound: the
#: MXU is idle and throughput tracks the vector unit (masking, scatter,
#: elementwise) — the paper's "VPU-mask-bound" claim, as a threshold.
VPU_MFU_THRESHOLD = 0.05

# test/override hooks (cleared by reset(); plain attribute swaps, same
# discipline as xla._analysis_provider: torn reads see old-or-new, both
# valid)
_clock: Callable[[], float] = _monotonic
_sample_every: Optional[int] = None
_sample_every_env_cache: Optional[int] = None


def set_clock(clock: Optional[Callable[[], float]]) -> None:
    """Override the sampler's clock (forged-clock honesty tests). ``None``
    restores ``time.monotonic``. The ``sync_fetch`` crossing keeps its own
    real clock either way — only the per-dispatch measurement is forged."""
    global _clock
    _clock = _monotonic if clock is None else clock


def set_sample_every(n: Optional[int]) -> None:
    """Override the sampling period (tests / unusual runs). ``None``
    restores the ``PHOTON_PROFILE_SAMPLE_EVERY`` env / default chain."""
    global _sample_every
    _sample_every = None if n is None else max(1, int(n))


def _resolve_sample_every() -> int:
    if _sample_every is not None:
        return _sample_every
    global _sample_every_env_cache
    if _sample_every_env_cache is None:
        n = DEFAULT_SAMPLE_EVERY
        raw = os.environ.get("PHOTON_PROFILE_SAMPLE_EVERY")
        if raw:
            try:
                n = max(1, int(raw))
            except ValueError:
                logger.warning(
                    "ignoring malformed PHOTON_PROFILE_SAMPLE_EVERY=%r", raw
                )
        _sample_every_env_cache = n
    return _sample_every_env_cache


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ProfileEntry:
    """Profiled state of one (name, signature) dispatch-key entry.

    ``sampled_seconds`` are honest (fetch-synchronized) inclusive wall
    seconds over the SAMPLED dispatches only; ``est_exclusive_seconds``
    extrapolates to all dispatches. ``flops`` / ``bytes_accessed`` are the
    per-dispatch cost-analysis estimates copied from the executable
    record; ``None`` means the backend offers none ("unknown"), never
    zero."""

    name: str
    signature: tuple
    dispatches: int = 0
    sampled: int = 0
    sampled_seconds: float = 0.0
    sampled_exclusive_seconds: float = 0.0
    fetch_seconds: float = 0.0
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None

    @property
    def est_exclusive_seconds(self) -> float:
        if self.sampled <= 0:
            return 0.0
        return (
            self.sampled_exclusive_seconds / self.sampled * self.dispatches
        )

    def to_dict(self) -> dict[str, Any]:
        d = dataclasses.asdict(self)
        d["signature"] = list(self.signature)
        d["est_exclusive_seconds"] = self.est_exclusive_seconds
        return d


class ProfileRegistry:
    """Process-global per-executable profile store, keyed like the
    executable registry by ``(name, signature)`` — distinct shardings of
    one name stay distinct entries and merge per name for reporting."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: dict[tuple[str, tuple], ProfileEntry] = {}
        self._suspect_warned: set[str] = set()
        self.total_dispatches = 0

    def count_dispatch(
        self, name: str, signature: tuple, every: int
    ) -> bool:
        """Account one dispatch; True when it is this entry's Nth (the
        sampling decision is a deterministic per-entry counter, so tests
        and replays sample identically)."""
        with self._lock:
            key = (name, signature)
            e = self._entries.get(key)
            if e is None:
                e = self._entries[key] = ProfileEntry(name, signature)
            e.dispatches += 1
            self.total_dispatches += 1
            return (e.dispatches - 1) % every == 0

    def record_sample(
        self,
        name: str,
        signature: tuple,
        seconds: float,
        exclusive_seconds: float,
        fetch_seconds: float,
        flops: Optional[float],
        bytes_accessed: Optional[float],
    ) -> None:
        with self._lock:
            key = (name, signature)
            e = self._entries.get(key)
            if e is None:  # reset() raced the dispatch; re-attach
                e = self._entries[key] = ProfileEntry(
                    name, signature, dispatches=1
                )
            e.sampled += 1
            e.sampled_seconds += seconds
            e.sampled_exclusive_seconds += exclusive_seconds
            e.fetch_seconds += fetch_seconds
            if flops is not None:
                e.flops = flops
            if bytes_accessed is not None:
                e.bytes_accessed = bytes_accessed

    def entries(self, name: Optional[str] = None) -> list[ProfileEntry]:
        with self._lock:
            out = list(self._entries.values())
        if name is not None:
            out = [e for e in out if e.name == name]
        return out

    def first_suspect_warning(self, name: str) -> bool:
        """True exactly once per name — the warn-once latch."""
        with self._lock:
            if name in self._suspect_warned:
                return False
            self._suspect_warned.add(name)
            return True

    def snapshot(self) -> list[dict[str, Any]]:
        """JSON-safe entry list, most estimated-exclusive-time first."""
        return [
            e.to_dict()
            for e in sorted(
                self.entries(),
                key=lambda e: e.est_exclusive_seconds,
                reverse=True,
            )
        ]

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()
            self._suspect_warned.clear()
            self.total_dispatches = 0


#: Process-global profile registry.
PROFILE_REGISTRY = ProfileRegistry()


# ---------------------------------------------------------------------------
# derived roofline numbers
# ---------------------------------------------------------------------------


def bound_class(
    mean_dispatch_seconds: Optional[float],
    flops: Optional[float],
    bytes_accessed: Optional[float],
    peak_flops: Optional[float],
    peak_bw: Optional[float],
    mfu: Optional[float],
) -> int:
    """Roofline bound class for one executable.

    - ``dispatch-bound``: the roofline-predicted device time (max of the
      compute and memory legs) is under :data:`DISPATCH_BOUND_RATIO` of
      the measured time — launch/dispatch overhead dominates.
    - ``HBM-bound``: arithmetic intensity below the device balance point
      (``peak_flops / peak_bw``) — the memory leg of the roofline binds.
    - ``MXU-bound`` vs ``VPU-bound``: compute-side split on
      :data:`VPU_MFU_THRESHOLD` MFU — a compute-limited executable that
      barely touches the MXU is living on the vector unit.
    - ``unknown`` whenever the cost analysis or the peaks are missing —
      absence of evidence is never a class."""
    if (
        mean_dispatch_seconds is None
        or mean_dispatch_seconds <= 0
        or flops is None
        or bytes_accessed is None
        or not bytes_accessed
        or peak_flops is None
        or peak_bw is None
        or not peak_flops
        or not peak_bw
    ):
        return BOUND_UNKNOWN
    roofline_seconds = max(flops / peak_flops, bytes_accessed / peak_bw)
    if roofline_seconds < DISPATCH_BOUND_RATIO * mean_dispatch_seconds:
        return BOUND_DISPATCH
    if flops / bytes_accessed < peak_flops / peak_bw:
        return BOUND_HBM
    if mfu is not None and mfu < VPU_MFU_THRESHOLD:
        return BOUND_VPU
    return BOUND_MXU


def bound_class_name(code: Any) -> str:
    try:
        return BOUND_CLASS_NAMES[int(code)]
    except (KeyError, TypeError, ValueError):
        return "unknown"


def merged_profiles(
    names: Optional[Any] = None,
) -> dict[str, dict[str, Any]]:
    """Per-NAME merge of the profile entries (shardings collapse here)
    with the derived roofline numbers computed against the resolved
    device peaks. Keys of each value: dispatches, sampled,
    sampled_seconds, est_exclusive_seconds, mean_dispatch_seconds,
    flops_per_dispatch, bytes_per_dispatch, mfu, intensity, bound_code,
    timing_suspect. Derived fields are ``None`` when unknown."""
    peak_flops, peak_bw = xla.device_peaks()
    by_name: dict[str, list[ProfileEntry]] = {}
    for e in PROFILE_REGISTRY.entries():
        if names is not None and e.name not in names:
            continue
        by_name.setdefault(e.name, []).append(e)
    out: dict[str, dict[str, Any]] = {}
    for name, entries in by_name.items():
        dispatches = sum(e.dispatches for e in entries)
        sampled = sum(e.sampled for e in entries)
        sampled_seconds = sum(e.sampled_seconds for e in entries)
        est_exclusive = sum(e.est_exclusive_seconds for e in entries)
        mean = sampled_seconds / sampled if sampled else None
        # per-dispatch cost, weighted by each entry's sample count so a
        # rarely-run sharding does not skew the merged intensity
        fl_known = [e for e in entries if e.flops is not None and e.sampled]
        by_known = [
            e for e in entries
            if e.bytes_accessed is not None and e.sampled
        ]
        flops = None
        if fl_known:
            w = sum(e.sampled for e in fl_known)
            flops = sum(e.flops * e.sampled for e in fl_known) / w
        nbytes = None
        if by_known:
            w = sum(e.sampled for e in by_known)
            nbytes = (
                sum(e.bytes_accessed * e.sampled for e in by_known) / w
            )
        mfu = intensity = None
        suspect = False
        if flops is not None and nbytes:
            intensity = flops / nbytes
        if mean is not None and mean > 0:
            if flops is not None and peak_flops:
                mfu = flops / mean / peak_flops
                suspect = suspect or flops / mean > peak_flops
            if nbytes is not None and peak_bw:
                suspect = suspect or nbytes / mean > peak_bw
        elif sampled and mean == 0 and (peak_flops or peak_bw):
            # zero measured seconds with work attributed: the clock is
            # lying outright (it stopped on the enqueue, not the result)
            suspect = flops is not None or nbytes is not None
        out[name] = {
            "dispatches": dispatches,
            "sampled": sampled,
            "sampled_seconds": sampled_seconds,
            "est_exclusive_seconds": est_exclusive,
            "mean_dispatch_seconds": mean,
            "flops_per_dispatch": flops,
            "bytes_per_dispatch": nbytes,
            "mfu": mfu,
            "intensity": intensity,
            "bound_code": bound_class(
                mean, flops, nbytes, peak_flops, peak_bw, mfu
            ),
            "timing_suspect": suspect,
        }
    return out


def exclusive_seconds_by_name() -> dict[str, float]:
    """``{name: estimated exclusive seconds}`` — the heartbeat's hot_exec
    input. Pure registry read: registers no metrics (absence stays
    unknown)."""
    out: dict[str, float] = {}
    for e in PROFILE_REGISTRY.entries():
        out[e.name] = out.get(e.name, 0.0) + e.est_exclusive_seconds
    return out


def publish_metrics(names: Optional[Any] = None) -> None:
    """Publish ``profile.exec.<name>.<field>`` gauges for every profiled
    name (or just ``names``) so offline report loads can rebuild the
    Hot-executables table from a metrics JSONL. Runs at report build and
    metrics flush — NOT per sample, keeping the dispatch path cheap."""
    for name, m in merged_profiles(names).items():
        prefix = f"profile.exec.{name}"
        metrics.gauge(f"{prefix}.dispatches").set(m["dispatches"])
        metrics.gauge(f"{prefix}.sampled").set(m["sampled"])
        metrics.gauge(f"{prefix}.sampled_seconds").set(m["sampled_seconds"])
        metrics.gauge(f"{prefix}.est_exclusive_seconds").set(
            m["est_exclusive_seconds"]
        )
        if m["mean_dispatch_seconds"] is not None:
            metrics.gauge(f"{prefix}.mean_dispatch_seconds").set(
                m["mean_dispatch_seconds"]
            )
        if m["mfu"] is not None:
            metrics.gauge(f"{prefix}.mfu").set(m["mfu"])
        if m["intensity"] is not None:
            metrics.gauge(f"{prefix}.intensity").set(m["intensity"])
        metrics.gauge(f"{prefix}.bound_code").set(m["bound_code"])
        if m["timing_suspect"]:
            metrics.gauge(f"{prefix}.timing_suspect").set(1)
            metrics.counter("profile.timing_suspect_total").inc()
            if PROFILE_REGISTRY.first_suspect_warning(name):
                logger.warning(
                    "timing suspect: executable '%s' measures above the "
                    "resolved device peak — the clock is not seeing the "
                    "device (only a device->host fetch truly syncs); "
                    "treat its rates as fake until the "
                    "measurement path is fixed",
                    name,
                )


# ---------------------------------------------------------------------------
# the dispatch sampler (the xla.set_dispatch_profiler hook)
# ---------------------------------------------------------------------------


class _Frame:
    """One in-flight sampled measurement on the thread-local stack."""

    __slots__ = ("child_seconds",)

    def __init__(self):
        self.child_seconds = 0.0


_tls = threading.local()


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


def _first_array_leaf(out: Any) -> Optional[Any]:
    """The first array-shaped output leaf — the fetch target that makes
    the measurement honest. None for array-free outputs (nothing to
    synchronize on; the timing is then best-effort)."""
    import jax

    for leaf in jax.tree.leaves(out):
        if (
            getattr(leaf, "shape", None) is not None
            and getattr(leaf, "dtype", None) is not None
        ):
            return leaf
    return None


def profile_dispatch(rec, target, args, kwargs):
    """Route one ``instrumented_jit`` dispatch: count it, and every Nth
    per entry take one honest timing — clock the dispatch, then fetch one
    output leaf through the sanctioned ``sync_fetch`` crossing so the
    clock stops only when the device is actually done (L013 enforces
    that this function and everything it reaches never syncs another
    way). Target exceptions propagate unmodified — the AOT
    TypeError/ValueError fallback in ``xla`` depends on seeing them."""
    sampled = PROFILE_REGISTRY.count_dispatch(
        rec.name, rec.signature, _resolve_sample_every()
    )
    if _xprof_config is not None:
        _xprof_tick()
    if not sampled:
        return target(*args, **kwargs)
    clock = _clock
    stack = _stack()
    frame = _Frame()
    stack.append(frame)
    t0 = clock()
    try:
        out = target(*args, **kwargs)
    except BaseException:
        # no sample: a dispatch that never produced a result has no
        # honest duration (xla may retry it through plain jit next)
        stack.pop()
        raise
    t_exec = clock()
    fetch_seconds = 0.0
    leaf = _first_array_leaf(out)
    if leaf is not None:
        try:
            device.sync_fetch(leaf, label=f"profile:{rec.name}")
        except Exception:  # noqa: BLE001 — never fail a dispatch over
            # accounting; the sample is still recorded, just unsynced
            metrics.counter("profile.fetch_errors").inc()
        fetch_seconds = clock() - t_exec
    dt = clock() - t0
    stack.pop()
    exclusive = dt - frame.child_seconds
    if exclusive < 0.0:
        exclusive = 0.0
    if stack:
        stack[-1].child_seconds += dt
    PROFILE_REGISTRY.record_sample(
        rec.name,
        rec.signature,
        dt,
        exclusive,
        fetch_seconds,
        rec.flops,
        rec.bytes_accessed,
    )
    t_book = clock()
    # HBM high-watermark on the sampling cadence, attributed to the open
    # span's phase (cheap: one memory_stats() probe per local device).
    # Derived gauges (MFU, bound class, ...) are NOT published here —
    # publish_metrics() runs at report/flush time, off the hot path.
    span = trace.current_span()
    memory.record_device_watermarks(
        phase=None if span is None else span.name
    )
    metrics.counter("profile.sampled").inc()
    # overhead = everything a non-profiled run would not have paid: the
    # synchronizing fetch plus the bookkeeping after it
    metrics.counter("profile.overhead_seconds").inc(
        fetch_seconds + (clock() - t_book)
    )
    return out


def install() -> None:
    """Arm the sampler on every ``instrumented_jit`` dispatch
    (idempotent; done at ``telemetry`` import and re-done by
    :func:`reset` so test isolation never leaves profiling disarmed)."""
    xla.set_dispatch_profiler(profile_dispatch)


# ---------------------------------------------------------------------------
# optional jax.profiler capture window
# ---------------------------------------------------------------------------

_xprof_lock = threading.Lock()
_xprof_config: Optional[dict[str, Any]] = None
_xprof_active = False
_xprof_start_hook: Optional[Callable[[str], None]] = None
_xprof_stop_hook: Optional[Callable[[], None]] = None


def set_xprof_hooks(
    start: Optional[Callable[[str], None]],
    stop: Optional[Callable[[], None]],
) -> None:
    """Inject the capture start/stop (tests). ``None`` restores the real
    ``jax.profiler.start_trace`` / ``stop_trace``."""
    global _xprof_start_hook, _xprof_stop_hook
    _xprof_start_hook = start
    _xprof_stop_hook = stop


def _default_backend() -> str:
    try:
        import jax

        return str(jax.default_backend())
    except Exception:  # noqa: BLE001 — no jax, no capture
        return "unknown"


def configure_xprof(
    out_dir: str,
    arm_at: int = 20,
    capture: int = 8,
    force: bool = False,
) -> bool:
    """Arm a ``jax.profiler`` capture window: start when the global
    profiled dispatch count reaches ``arm_at`` (past warmup/compile —
    "around the Kth CD iteration"), stop ``capture`` dispatches later.

    CPU-guarded: on a CPU backend the capture is skipped (returns False,
    logged) unless ``force=True`` or ``PHOTON_XPROF_FORCE=1`` — the
    capture machinery has wedged CPU-only CI runs and a CPU trace answers
    no roofline question anyway. A window still open at :func:`reset`
    (run teardown) is stopped there."""
    backend = _default_backend()
    if (
        backend == "cpu"
        and not force
        and os.environ.get("PHOTON_XPROF_FORCE") != "1"
    ):
        logger.info(
            "xprof capture skipped on the cpu backend (force=True or "
            "PHOTON_XPROF_FORCE=1 to override)"
        )
        return False
    global _xprof_config
    with _xprof_lock:
        _xprof_config = {
            "dir": out_dir,
            "arm_at": max(int(arm_at), 0),
            "stop_at": max(int(arm_at), 0) + max(int(capture), 1),
        }
    logger.info(
        "xprof capture armed: dir=%s dispatches [%d, %d)",
        out_dir,
        _xprof_config["arm_at"],
        _xprof_config["stop_at"],
    )
    metrics.gauge("profile.xprof_armed").set(1)
    return True


def _xprof_start(out_dir: str) -> None:
    if _xprof_start_hook is not None:
        _xprof_start_hook(out_dir)
        return
    import jax

    jax.profiler.start_trace(out_dir)


def _xprof_stop() -> None:
    if _xprof_stop_hook is not None:
        _xprof_stop_hook()
        return
    import jax

    jax.profiler.stop_trace()


def _xprof_tick() -> None:
    """Advance the capture window from the dispatch stream (cheap: the
    caller already checked a config exists). Capture failures log and
    disarm — profiling must never take the run down."""
    global _xprof_config, _xprof_active
    with _xprof_lock:
        cfg = _xprof_config
        if cfg is None:
            return
        n = PROFILE_REGISTRY.total_dispatches
        start = not _xprof_active and n >= cfg["arm_at"]
        stop = _xprof_active and n >= cfg["stop_at"]
    if start:
        try:
            _xprof_start(cfg["dir"])
        except Exception:  # noqa: BLE001
            logger.warning(
                "xprof capture failed to start; disarmed", exc_info=True
            )
            with _xprof_lock:
                _xprof_config = None
            return
        with _xprof_lock:
            _xprof_active = True
        trace.add_event("xprof_start", dir=cfg["dir"])
        logger.info("xprof capture started -> %s", cfg["dir"])
    elif stop:
        stop_xprof()


def stop_xprof() -> None:
    """Stop an open capture window and disarm (idempotent)."""
    global _xprof_config, _xprof_active
    with _xprof_lock:
        was_active = _xprof_active
        _xprof_active = False
        cfg = _xprof_config
        _xprof_config = None
    if not was_active:
        return
    try:
        _xprof_stop()
    except Exception:  # noqa: BLE001
        logger.warning("xprof capture failed to stop", exc_info=True)
        return
    trace.add_event(
        "xprof_stop", dir=None if cfg is None else cfg.get("dir")
    )
    logger.info("xprof capture stopped")


def reset() -> None:
    """Restore import-time defaults (test isolation): stop any capture,
    clear the registry and the clock/sampling overrides — and RE-ARM the
    sampler, so a reset never silently disarms profiling."""
    global _sample_every, _sample_every_env_cache, _clock
    stop_xprof()
    set_xprof_hooks(None, None)
    PROFILE_REGISTRY.reset()
    _sample_every = None
    _sample_every_env_cache = None
    _clock = _monotonic
    install()
