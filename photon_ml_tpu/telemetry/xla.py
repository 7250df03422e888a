"""Device-level performance accounting: every jitted hot path becomes an
accounted executable.

PRs 1/3 measure *wall time and HBM occupancy*; this layer answers the
questions that decide the next perf PR — is the per-entity vmap solve
compute-bound or bandwidth-bound? what fraction of a distributed solve is
psum traffic? which argument-shape change triggered that recompile storm?

Three pieces:

- :func:`instrumented_jit` — a drop-in ``jax.jit`` replacement (lint L011
  enforces it in hot-path library modules). The first call per argument
  shape-signature goes through ``lowered.compile()`` with the compile wall
  time, ``cost_analysis()`` FLOPs / bytes-accessed, and
  ``memory_analysis()`` temp/arg/output bytes recorded in the process-
  global :data:`XLA_REGISTRY`, keyed by ``(name, signature)``, beside
  the compile's phases as jax's monitoring events report them (tracing,
  lowering, a persistent-cache load, the backend compile; counters
  ``xla.exec.<name>.<phase>_seconds``); the two analyses' own seconds are
  counter ``xla.analysis_seconds``. Subsequent
  same-signature calls dispatch to the cached compiled executable and
  accumulate per-call FLOPs/bytes onto the open telemetry span (so the
  run report can compute per-phase roofline numbers from span wall time).
  A NEW signature for a known name is a **recompile**: it is attributed to
  the exact per-argument delta that caused it, counted
  (``xla.recompiles``), stamped as a span event, and escalated to a
  structured warning at ``RECOMPILE_WARN_THRESHOLD`` distinct signatures
  — the recompile-storm detector.
- roofline peaks — :func:`device_peaks` resolves the device's peak FLOP/s
  and HBM bandwidth from one table keyed by the exact ``device_kind``
  (:func:`set_peaks` for tests; a kind the table does not know is
  "unknown", never a neighbour's numbers) and publishes them as
  ``device.peak_*`` gauges so reports loaded from a metrics JSONL can
  compute MFU offline.
- collective estimates — :func:`record_collective` turns mesh sharding
  specs into estimated wire bytes (ring psum moves ``2(n-1)/n`` of the
  payload per device; all-gather ``(n-1)/n``), exposed as ``comms.*``
  counters/gauges and accumulated onto the open span, so MULTICHIP_r*
  results carry a comms fraction.

Everything degrades gracefully: backends without cost/memory analysis
leave those record fields ``None`` (rendered "unknown"), an executable
that cannot be AOT-compiled falls back to plain ``jax.jit`` dispatch —
logged as a WARNING with the compiler's text and counted in
``xla.fallback_calls``, so a run can assert it never happened — and
analysis is injectable for deterministic tests via
:func:`set_analysis_provider`.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import threading
import time
from typing import Any, Callable, Mapping, Optional, Sequence

from photon_ml_tpu.telemetry import metrics, trace
from photon_ml_tpu.telemetry.device import accounted_compile

__all__ = [
    "ExecutableRecord",
    "ExecutableRegistry",
    "XLA_REGISTRY",
    "instrumented_jit",
    "shape_signature",
    "set_analysis_provider",
    "set_dispatch_profiler",
    "set_peaks",
    "device_peaks",
    "collective_bytes",
    "record_collective",
    "RECOMPILE_WARN_THRESHOLD",
    "reset",
]

logger = logging.getLogger("photon_ml_tpu.telemetry.xla")

#: Distinct signatures of ONE executable name at which the recompile
#: counter escalates to a structured warning (the recompile-storm
#: signal).
RECOMPILE_WARN_THRESHOLD = 3

# Peak per-chip dense-matmul FLOP/s (bf16) and HBM bandwidth (bytes/s) by
# the EXACT ``device_kind`` jax reports (both spellings jax itself knows
# per generation, jax/_src/pallas/mosaic/tpu_info.py). Denominators of MFU
# and bandwidth utilization; a kind that is not a key is "unknown" (None).
# Source: Google Cloud TPU documentation, system architecture per version.
_V5E = (197e12, 819e9)
_V5P = (459e12, 2765e9)
_V6E = (918e12, 1640e9)
_PEAK_TABLE: dict[str, tuple[float, float]] = {
    "TPU v2": (45e12, 700e9),
    "TPU v3": (123e12, 900e9),
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
    "TPU v5": _V5P,
    "TPU v5p": _V5P,
    "TPU v6 lite": _V6E,
    "TPU v6e": _V6E,
}

# test/override hooks (cleared by reset(); plain attribute swaps — set
# from the main/test thread, read racily by design: a torn read returns
# either the old or the new hook, both valid)
_peaks_override: Optional[tuple[Optional[float], Optional[float]]] = None
_analysis_provider: Optional[Callable] = None

# the executable-level profiler hook (telemetry.profile installs its
# sampler here at import). NOT cleared by reset() — disarming profiling
# is an explicit set_dispatch_profiler(None), never a side effect of
# test isolation.
_dispatch_profiler: Optional[Callable] = None


def set_dispatch_profiler(hook: Optional[Callable]) -> None:
    """Install the per-dispatch profiler hook. When set, every
    ``InstrumentedFunction`` invocation routes through
    ``hook(record, target, args, kwargs)`` — the hook must call
    ``target(*args, **kwargs)`` exactly once, return its result, and let
    target exceptions propagate unmodified (the AOT TypeError/ValueError
    fallback depends on seeing them). ``None`` disarms."""
    global _dispatch_profiler
    _dispatch_profiler = hook


# ---------------------------------------------------------------------------
# roofline peaks
# ---------------------------------------------------------------------------


def set_peaks(
    peak_flops: Optional[float], peak_hbm_bytes_per_sec: Optional[float]
) -> None:
    """Override the device peak numbers (deterministic tests / devices the
    table does not know). ``set_peaks(None, None)`` does NOT clear the
    override — it pins "unknown"; call :func:`reset` to restore probing."""
    global _peaks_override
    _peaks_override = (peak_flops, peak_hbm_bytes_per_sec)
    _publish_peaks(peak_flops, peak_hbm_bytes_per_sec)


def _publish_peaks(
    peak_flops: Optional[float], peak_bw: Optional[float]
) -> None:
    if peak_flops is not None:
        metrics.gauge("device.peak_flops").set(peak_flops)
    if peak_bw is not None:
        metrics.gauge("device.peak_hbm_bytes_per_sec").set(peak_bw)


def device_peaks() -> tuple[Optional[float], Optional[float]]:
    """``(peak_flops, peak_hbm_bytes_per_sec)`` for device 0, or ``None``s
    when its ``device_kind`` is not a key of the table (CPU, a generation
    nobody entered). An injected :func:`set_peaks` override wins.
    Publishes ``device.peak_*`` gauges when known so offline report loads
    can compute MFU from the metrics JSONL."""
    if _peaks_override is not None:
        return _peaks_override
    import jax

    flops, bw = _PEAK_TABLE.get(
        str(jax.devices()[0].device_kind), (None, None))
    _publish_peaks(flops, bw)
    return flops, bw


# ---------------------------------------------------------------------------
# analysis (cost / memory) with injection
# ---------------------------------------------------------------------------


def set_analysis_provider(provider: Optional[Callable]) -> None:
    """Override executable analysis for tests: ``provider(compiled)`` must
    return ``(cost, mem)`` where ``cost`` is a ``cost_analysis()``-shaped
    mapping (``{"flops": ..., "bytes accessed": ...}``) or None, and
    ``mem`` a ``memory_analysis()``-shaped object/mapping or None.
    ``None`` restores the real XLA analysis."""
    global _analysis_provider
    _analysis_provider = provider


def _cost_mapping(raw: Any) -> Optional[Mapping[str, float]]:
    """``cost_analysis()`` output as a mapping, else None."""
    return raw if isinstance(raw, Mapping) else None


def _mem_field(mem: Any, field: str) -> Optional[int]:
    if mem is None:
        return None
    if isinstance(mem, Mapping):
        v = mem.get(field)
    else:
        v = getattr(mem, field, None)
    return None if v is None else int(v)


def _analyze(compiled: Any) -> tuple[Optional[Mapping], Any]:
    """(cost mapping, memory stats) for a compiled executable; ``(None,
    None)`` on backends where the analyses are unavailable — never
    raises."""
    if _analysis_provider is not None:
        try:
            cost, mem = _analysis_provider(compiled)
            return _cost_mapping(cost), mem
        except Exception:  # noqa: BLE001 — a broken injected provider
            logger.debug("injected analysis provider failed", exc_info=True)
            return None, None
    cost = mem = None
    try:
        cost = _cost_mapping(compiled.cost_analysis())
    except Exception:  # noqa: BLE001 — unimplemented on some backends
        cost = None
    try:
        mem = compiled.memory_analysis()
    except Exception:  # noqa: BLE001
        mem = None
    return cost, mem


# ---------------------------------------------------------------------------
# shape signatures
# ---------------------------------------------------------------------------

_DTYPE_SHORT = {
    "float32": "f32", "float64": "f64", "float16": "f16",
    "bfloat16": "bf16", "int32": "i32", "int64": "i64", "int16": "i16",
    "int8": "i8", "uint32": "u32", "uint8": "u8", "bool": "b1",
}


_named_sharding_cls: Optional[type] = None


def _mesh_sharding(leaf: Any):
    """The leaf's NamedSharding when it is committed to a multi-device
    mesh, else None. Mesh placement is part of the compiled program (GSPMD
    partitions differently per sharding), so it must be part of both the
    dispatch key and the recompile-attribution signature; single-device
    and host leaves stay sharding-free so existing signatures are
    unchanged. jax is resolved lazily — a leaf carrying ``.sharding``
    proves it is already imported."""
    global _named_sharding_cls
    sh = getattr(leaf, "sharding", None)
    if sh is None:
        return None
    if _named_sharding_cls is None:
        from jax.sharding import NamedSharding

        _named_sharding_cls = NamedSharding
    if isinstance(sh, _named_sharding_cls) and sh.mesh.devices.size > 1:
        return sh
    return None


def _leaf_sig(leaf: Any) -> str:
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        dt = _DTYPE_SHORT.get(str(dtype), str(dtype))
        weak = "*" if getattr(getattr(leaf, "aval", None), "weak_type", False) else ""
        sh = _mesh_sharding(leaf)
        mesh_sig = "" if sh is None else f"@{sh.spec}"
        return f"{dt}{weak}[{','.join(str(int(d)) for d in shape)}]{mesh_sig}"
    if isinstance(leaf, bool):
        return "pybool"
    if isinstance(leaf, int):
        return "pyint"
    if isinstance(leaf, float):
        return "pyfloat"
    if isinstance(leaf, complex):
        return "pycomplex"
    # structure-affecting leaves (strings, None never reaches here — it is
    # part of the treedef): keyed by value, they ARE the trace key
    return f"={leaf!r}"


def shape_signature(tree: Any) -> tuple[str, tuple[str, ...]]:
    """``(structure_key, per_leaf_shapes)`` for an argument pytree — the
    executable-registry key. Array leaves contribute ``dtype[shape]``
    (weak types marked ``*``); python scalars contribute their type only
    (values are traced, not trace keys); other leaves their repr."""
    import jax

    leaves, treedef = jax.tree.flatten(tree)
    return str(treedef), tuple(_leaf_sig(x) for x in leaves)


def _leaf_key(leaf: Any):
    """Cheap hashable dispatch key for one leaf — no string formatting on
    the hot path (the pretty ``_leaf_sig`` strings are built only when a
    signature is first compiled)."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is not None and dtype is not None:
        weak = getattr(getattr(leaf, "aval", None), "weak_type", False)
        return (dtype, tuple(shape), weak, _mesh_sharding(leaf))
    if isinstance(leaf, (bool, int, float, complex)):
        return type(leaf)
    return ("repr", repr(leaf))


def _signature_delta(
    old: Sequence[str], new: Sequence[str]
) -> str:
    """Human-readable per-leaf diff between two signatures — the exact
    argument change a recompile is attributed to."""
    changes = []
    n = max(len(old), len(new))
    for i in range(n):
        a = old[i] if i < len(old) else "<absent>"
        b = new[i] if i < len(new) else "<absent>"
        if a != b:
            changes.append(f"leaf[{i}]: {a} -> {b}")
    if not changes:
        return "argument structure changed (same leaf shapes)"
    head = "; ".join(changes[:4])
    if len(changes) > 4:
        head += f"; ... {len(changes) - 4} more leaves"
    return head


# ---------------------------------------------------------------------------
# executable registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ExecutableRecord:
    """One compiled (name, signature) executable's accounted state.

    ``flops`` / ``bytes_accessed`` are per-call estimates from XLA's cost
    analysis; ``None`` means the backend offers no analysis ("unknown"),
    never zero."""

    name: str
    signature: tuple[str, ...]
    structure: str = ""
    compile_seconds: float = 0.0
    # what jax's monitoring events said of the compile, by phase (telemetry
    # .device's hooks), each its own seconds: tracing, lowering to MLIR, a
    # persistent-cache load, the backend-compile event less that load.
    # compile_seconds less the four is what no phase names.
    trace_seconds: float = 0.0
    lower_seconds: float = 0.0
    cache_load_seconds: float = 0.0
    backend_seconds: float = 0.0
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    temp_bytes: Optional[int] = None
    argument_bytes: Optional[int] = None
    output_bytes: Optional[int] = None
    generated_code_bytes: Optional[int] = None
    calls: int = 0

    def to_dict(self) -> dict[str, Any]:
        d = dataclasses.asdict(self)
        d["signature"] = list(self.signature)
        return d


#: an executable's compile phases, as ExecutableRecord fields
#: ``<phase>_seconds`` and counters ``xla.exec.<name>.<phase>_seconds``
_PHASES = ("trace", "lower", "cache_load", "backend")


class ExecutableRegistry:
    """Process-global registry of accounted executables keyed by
    ``(name, shape-signature)``, with per-name signature history for
    recompile attribution."""

    def __init__(self):
        self._lock = threading.Lock()
        self._records: dict[tuple[str, tuple], ExecutableRecord] = {}
        # name -> signatures in arrival order (recompile attribution)
        self._history: dict[str, list[tuple[str, ...]]] = {}
        self._warned: set[str] = set()

    def record_compile(
        self,
        name: str,
        signature: tuple[str, ...],
        structure: str,
        compile_seconds: float,
        cost: Optional[Mapping],
        mem: Any,
        multi_shape: bool = False,
        phases: Optional[Mapping[str, float]] = None,
    ) -> ExecutableRecord:
        """Insert (or refresh) the record for a freshly compiled
        executable, publish its compile metrics, and attribute a
        recompile when ``name`` already had a different signature.

        ``multi_shape`` marks an executable whose signature SET is by
        design (the serving engine's padded batch buckets, per-bucket
        entity counts): new signatures still register and publish compile
        metrics, but are not counted as recompiles and never trip the
        storm warning — the gate metric must not flag healthy warmups.
        ``phases``: the compile's seconds by phase (``trace``, ``lower``,
        ``cache_load``, ``backend``; ``telemetry.device.accounted_compile``).
        """
        phases = phases or {}
        rec = ExecutableRecord(
            name=name,
            signature=signature,
            structure=structure,
            compile_seconds=float(compile_seconds),
            trace_seconds=float(phases.get("trace", 0.0)),
            lower_seconds=float(phases.get("lower", 0.0)),
            cache_load_seconds=float(phases.get("cache_load", 0.0)),
            backend_seconds=float(phases.get("backend", 0.0)),
            flops=None if cost is None else _maybe_float(cost.get("flops")),
            bytes_accessed=(
                None if cost is None
                else _maybe_float(cost.get("bytes accessed"))
            ),
            temp_bytes=_mem_field(mem, "temp_size_in_bytes"),
            argument_bytes=_mem_field(mem, "argument_size_in_bytes"),
            output_bytes=_mem_field(mem, "output_size_in_bytes"),
            generated_code_bytes=_mem_field(
                mem, "generated_code_size_in_bytes"
            ),
        )
        with self._lock:
            self._records[(name, signature)] = rec
            history = self._history.setdefault(name, [])
            prior = list(history)
            history.append(signature)
            n_sigs = len(history)
            warn = (
                not multi_shape
                and n_sigs >= RECOMPILE_WARN_THRESHOLD
                and name not in self._warned
            )
            if warn:
                self._warned.add(name)
        metrics.counter("xla.compiles").inc()
        metrics.counter("xla.compile_seconds").inc(rec.compile_seconds)
        metrics.counter(f"xla.exec.{name}.compiles").inc()
        metrics.counter(f"xla.exec.{name}.compile_seconds").inc(
            rec.compile_seconds
        )
        for phase in _PHASES:
            metrics.counter(f"xla.exec.{name}.{phase}_seconds").inc(
                getattr(rec, f"{phase}_seconds")
            )
        if rec.flops is not None:
            metrics.gauge(f"xla.exec.{name}.flops_per_call").set(rec.flops)
        if rec.bytes_accessed is not None:
            metrics.gauge(f"xla.exec.{name}.bytes_per_call").set(
                rec.bytes_accessed
            )
        if rec.temp_bytes is not None:
            metrics.gauge(f"xla.exec.{name}.temp_bytes").set(rec.temp_bytes)
        if prior and multi_shape:
            # expected shape set: registered and accounted, not a storm
            logger.info(
                "executable '%s': signature #%d of its expected shape set "
                "(%s)",
                name,
                n_sigs,
                _signature_delta(prior[-1], signature),
            )
        elif prior:
            delta = _signature_delta(prior[-1], signature)
            metrics.counter("xla.recompiles").inc()
            metrics.counter(f"xla.exec.{name}.recompiles").inc()
            trace.add_event(
                "recompile",
                executable=name,
                delta=delta,
                distinct_signatures=n_sigs,
            )
            if warn:
                logger.warning(
                    "recompile storm: executable '%s' compiled %d distinct "
                    "signatures; last delta: %s — stabilize the argument "
                    "shapes (pad to buckets) or split the executable",
                    name,
                    n_sigs,
                    delta,
                )
            else:
                logger.info(
                    "recompile: '%s' signature #%d (%s)", name, n_sigs, delta
                )
        return rec

    def record_call(self, rec: ExecutableRecord) -> None:
        """Account one dispatch of ``rec``: global + per-executable call
        counters, FLOP/byte totals, and span-local accumulation for
        per-phase roofline numbers."""
        with self._lock:
            rec.calls += 1
            # re-attach records orphaned by a reset() (long-lived cached
            # solvers outlive test-isolation resets)
            self._records.setdefault((rec.name, rec.signature), rec)
            self._history.setdefault(rec.name, [rec.signature])
        metrics.counter("xla.calls").inc()
        metrics.counter(f"xla.exec.{rec.name}.calls").inc()
        if rec.flops is not None:
            metrics.counter("xla.flops_total").inc(rec.flops)
            metrics.counter(f"xla.exec.{rec.name}.flops_total").inc(rec.flops)
        if rec.bytes_accessed is not None:
            metrics.counter("xla.bytes_total").inc(rec.bytes_accessed)
            metrics.counter(f"xla.exec.{rec.name}.bytes_total").inc(
                rec.bytes_accessed
            )
        _accumulate_span_attr("xla_flops", rec.flops)
        _accumulate_span_attr("xla_bytes", rec.bytes_accessed)

    def executables(self, name: Optional[str] = None) -> list[ExecutableRecord]:
        with self._lock:
            recs = list(self._records.values())
        if name is not None:
            recs = [r for r in recs if r.name == name]
        return recs

    def signature_history(self, name: str) -> list[tuple[str, ...]]:
        with self._lock:
            return list(self._history.get(name, ()))

    def snapshot(self) -> list[dict[str, Any]]:
        """JSON-safe record list, most total-cost first (cost = per-call
        flops x calls when known, else compile seconds)."""

        def rank(r: ExecutableRecord) -> float:
            if r.flops is not None:
                return r.flops * max(r.calls, 1)
            return r.compile_seconds

        return [
            r.to_dict()
            for r in sorted(self.executables(), key=rank, reverse=True)
        ]

    def reset(self) -> None:
        with self._lock:
            self._records.clear()
            self._history.clear()
            self._warned.clear()


def _maybe_float(v: Any) -> Optional[float]:
    try:
        f = float(v)
    except (TypeError, ValueError):
        return None
    return f if f >= 0 else None


def _accumulate_span_attr(key: str, value: Optional[float]) -> None:
    if value is None:
        return
    cur = trace.current_span()
    if cur is not None:
        cur.attrs[key] = float(cur.attrs.get(key, 0.0)) + float(value)


#: Process-global executable registry.
XLA_REGISTRY = ExecutableRegistry()


# ---------------------------------------------------------------------------
# instrumented_jit
# ---------------------------------------------------------------------------


class InstrumentedFunction:
    """``jax.jit`` with an accounted compile path (see module docstring).

    Thread-safe; per-signature compiled executables are cached on the
    instance. Two instances MAY share a ``name`` (e.g. one lru-cached
    solver factory per optimizer config): each instance's first compile
    of a signature is a distinct registry entry (suffix ``#<k>``), so a
    same-shape recompile caused by a new static configuration is still
    attributed instead of silently merged."""

    def __init__(
        self,
        fn: Callable,
        name: str,
        jit_kwargs: dict,
        multi_shape: bool = False,
    ):
        import jax

        self._fn = fn
        self.name = name
        # the compiled module is called jit_<name> (a profiler trace's
        # `XLA Modules` line), not after whatever `fn` happens to be called
        self._jit = jax.jit(_named(fn, name), **jit_kwargs)
        self._instance = _next_instance(name)
        self._multi_shape = multi_shape
        self._compiled: dict[tuple, tuple[Any, ExecutableRecord]] = {}
        self._lock = threading.Lock()
        self.__wrapped__ = fn

    # jax.jit API passthroughs used by callers/tests
    def lower(self, *args, **kwargs):
        return self._jit.lower(*args, **kwargs)

    def _dispatch_key(self, args, kwargs):
        """Hashable per-call key: pytree structure + cheap leaf keys (no
        string building — serving/solve hot paths dispatch through
        here)."""
        import jax

        leaves, treedef = jax.tree.flatten((args, kwargs))
        return (treedef, tuple(_leaf_key(x) for x in leaves)), leaves

    def record_for(self, *args, **kwargs) -> Optional[ExecutableRecord]:
        """The registry record this instance compiled for these arguments'
        signature, or None when that signature has not been compiled yet
        (no compile is triggered). Lets owners of per-shape executables
        (the serving engine's batch buckets) surface compile state."""
        key, _leaves = self._dispatch_key(args, kwargs)
        entry = self._compiled.get(key)
        return None if entry is None else entry[1]

    def __call__(self, *args, **kwargs):
        key, leaves = self._dispatch_key(args, kwargs)
        entry = self._compiled.get(key)
        if entry is None:
            with self._lock:
                entry = self._compiled.get(key)
                if entry is None:
                    leaf_sig = tuple(_leaf_sig(x) for x in leaves)
                    if self._instance:
                        leaf_sig = (
                            f"static-config#{self._instance}",
                        ) + leaf_sig
                    entry = self._compile(
                        str(key[0]), leaf_sig, args, kwargs
                    )
                    self._compiled[key] = entry
        compiled, rec = entry
        XLA_REGISTRY.record_call(rec)
        prof = _dispatch_profiler
        if compiled is None:
            if prof is not None:
                return prof(rec, self._jit, args, kwargs)
            return self._jit(*args, **kwargs)
        try:
            if prof is not None:
                return prof(rec, compiled, args, kwargs)
            return compiled(*args, **kwargs)
        except (TypeError, ValueError):
            # AOT argument-processing mismatch inside one key bucket
            # (weak-type / sharding variants): these raise BEFORE the
            # executable runs, so re-dispatching through plain jit is
            # safe even with donated arguments. Runtime errors (OOM,
            # XlaRuntimeError) propagate — re-executing after a partial
            # run could read already-donated buffers.
            logger.warning(
                "AOT dispatch of '%s' failed; falling back to jax.jit",
                self.name,
                exc_info=True,
            )
            metrics.counter("xla.fallback_calls").inc()
            self._compiled[key] = (None, rec)
            if prof is not None:
                return prof(rec, self._jit, args, kwargs)
            return self._jit(*args, **kwargs)

    def _compile(self, structure, leaf_sig, args, kwargs):
        t0 = time.monotonic()
        compiled = None
        cost = mem = None
        phases = None
        try:
            with accounted_compile(self.name) as phases:
                lowered = self._jit.lower(*args, **kwargs)
                compiled = lowered.compile()
        except Exception as e:  # noqa: BLE001 — backends/args AOT cannot handle
            # a compiler refusal (e.g. Mosaic) lands here too: say so NOW,
            # with its text — the jit re-dispatch will raise it again from
            # somewhere less obvious
            logger.warning(
                "AOT compile of '%s' failed (%s: %s); using jax.jit dispatch",
                self.name,
                type(e).__name__,
                e,
            )
            metrics.counter("xla.fallback_calls").inc()
        dt = time.monotonic() - t0
        if compiled is not None:
            # telemetry's own set-up cost, after every compile
            t1 = time.monotonic()
            cost, mem = _analyze(compiled)
            metrics.counter("xla.analysis_seconds").inc(time.monotonic() - t1)
        rec = XLA_REGISTRY.record_compile(
            self.name, leaf_sig, structure, dt, cost, mem,
            multi_shape=self._multi_shape, phases=phases,
        )
        trace.add_event(
            "xla_compile",
            executable=self.name,
            seconds=round(dt, 6),
            flops=rec.flops,
        )
        return compiled, rec


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under the ``__name__`` ``name`` and inside
    ``jax.named_scope(name)``: jax names a jitted function's module
    ``jit_<__name__>``, and the scope groups its ops in xprof's op view.
    ``functools.wraps`` keeps the signature jax resolves
    ``static_argnames`` / ``donate_argnames`` against."""
    import jax

    @functools.wraps(fn)
    def named(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)

    named.__name__ = named.__qualname__ = name
    return named


_instance_lock = threading.Lock()
_instance_counts: dict[str, int] = {}


def _next_instance(name: str) -> int:
    with _instance_lock:
        n = _instance_counts.get(name, 0)
        _instance_counts[name] = n + 1
        return n


def instrumented_jit(
    fn: Optional[Callable] = None,
    *,
    name: Optional[str] = None,
    multi_shape: bool = False,
    **jit_kwargs: Any,
) -> Any:
    """Accounted ``jax.jit``: usable as ``instrumented_jit(f, name=...)``
    or as a decorator ``@instrumented_jit(name=...)``. All ``jax.jit``
    keyword arguments (``donate_argnums``, ``out_shardings``, ...) pass
    through. ``multi_shape=True`` declares that this executable compiles
    a SET of signatures by design (padded batch buckets, per-bucket
    entity counts): its compiles register and publish cost normally but
    are never counted as recompiles or escalated to a storm warning."""
    if fn is None:
        return lambda f: instrumented_jit(
            f, name=name, multi_shape=multi_shape, **jit_kwargs
        )
    return InstrumentedFunction(
        fn,
        name or getattr(fn, "__name__", "jit_fn"),
        jit_kwargs,
        multi_shape=multi_shape,
    )


# ---------------------------------------------------------------------------
# collective-communication estimates
# ---------------------------------------------------------------------------


def collective_bytes(
    op: str, n_devices: int, payload_bytes: int
) -> int:
    """Estimated per-device wire bytes for one collective over an
    ``n_devices`` mesh axis: ring ``psum`` (all-reduce) moves
    ``2(n-1)/n`` of the payload; ``all_gather``/``reduce_scatter`` move
    ``(n-1)/n``. Zero on a 1-device axis (XLA elides the collective)."""
    n = int(n_devices)
    if n <= 1 or payload_bytes <= 0:
        return 0
    if op == "psum":
        frac = 2.0 * (n - 1) / n
    elif op in ("all_gather", "reduce_scatter"):
        frac = (n - 1) / n
    else:
        raise ValueError(f"unknown collective op '{op}'")
    return int(frac * payload_bytes)


def record_collective(
    label: str,
    op: str,
    n_devices: int,
    payload_bytes: int,
    count: int = 1,
) -> int:
    """Account ``count`` collectives of ``payload_bytes`` each under
    ``label``: ``comms.bytes_total`` / ``comms.<label>.bytes`` counters, a
    per-call gauge, and span-local ``comms_bytes`` accumulation (the run
    report's comms-fraction input). Returns the estimated bytes. This is
    a STATIC estimate from sharding specs — see README for its limits."""
    per_call = collective_bytes(op, n_devices, payload_bytes)
    total = per_call * max(int(count), 0)
    if total <= 0:
        return 0
    metrics.counter("comms.bytes_total").inc(total)
    metrics.counter(f"comms.{label}.bytes").inc(total)
    metrics.gauge(f"comms.{label}.bytes_per_call").set(per_call)
    _accumulate_span_attr("comms_bytes", total)
    return total


def reset() -> None:
    """Restore import-time defaults (test isolation): clear the registry,
    the injected analysis provider, and the peaks override. Compiled-
    executable caches inside live ``InstrumentedFunction`` instances
    survive (re-attached to the registry on their next call)."""
    global _peaks_override
    XLA_REGISTRY.reset()
    set_analysis_provider(None)
    _peaks_override = None
