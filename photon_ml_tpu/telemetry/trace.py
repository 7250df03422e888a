"""Hierarchical tracing spans with a JSONL sink and a Chrome-trace exporter.

The photon-ml driver wraps every phase in named ``Timed`` blocks
(util/Timed.scala) but only ever logs flat durations. Here every phase is a
*span* in a thread-safe tree: ``with trace.span("fit"):`` nests under
whatever span is open on the current thread, records monotonic wall time,
arbitrary attributes, and point-in-time events (device fetches, jit
compiles). Completed spans stream to a JSONL file (one object per line) and
convert to the Chrome trace-event format, so a full GAME fit opens as a
flame chart in Perfetto (https://ui.perfetto.dev).

Durations use ``time.monotonic()`` exclusively — wall-clock steps (NTP,
DST) corrupt phase timings. The one
wall-clock anchor, recorded at configure time for human correlation, comes
from ``datetime`` so the ``time.time()`` lint stays meaningful.

Span JSONL schema (one line per completed span)::

    {"type": "span", "id": 7, "parent": 3, "name": "coordinate:fixed",
     "ts": 1.042, "dur": 0.381, "thread": "MainThread",
     "attrs": {"iteration": 0},
     "events": [{"name": "device_fetch", "ts": 1.401,
                 "attrs": {"bytes": 4, "seconds": 0.1}}]}

``ts`` is seconds since the tracer's monotonic anchor; ``events[].ts``
shares the same timebase.

Every span is also entered as a ``jax.profiler.TraceAnnotation`` named
``photon:<span name>`` (a flag check while no profiler capture runs), so a
capture started by anyone — ``cli profile``, ``cli train --xprof-dir``, a
benchmark's ``start_trace`` — holds the span tree on its host plane, beside
the device planes (whose stamps ran 1-3 ms ahead of the host's in the v5e
traces on record: ``benchmark/program_trace.py`` aligns them, PERF.md).
"""

from __future__ import annotations

import datetime
import itertools
import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Any, Iterable, Iterator, Optional

from photon_ml_tpu.telemetry import identity

__all__ = [
    "Span",
    "Tracer",
    "TRACER",
    "span",
    "current_span",
    "add_event",
    "active_span_path",
    "configure",
    "reset",
    "finished_spans",
    "to_chrome_trace",
    "export_chrome_trace",
    "perfetto_path",
]

DEFAULT_BUFFER_LIMIT = 50_000

#: prefix of a span's mirror in a profiler capture
ANNOTATION_PREFIX = "photon:"

_trace_annotation = None  # jax.profiler.TraceAnnotation; False = no jax


def _annotation(name: str):
    """The span's mirror in the profiler's trace, or None without jax.
    jax is resolved at the first span, not at import: this module stays
    importable (and the tracer usable) where jax is not."""
    global _trace_annotation
    if _trace_annotation is None:
        try:
            from jax.profiler import TraceAnnotation

            _trace_annotation = TraceAnnotation
        except Exception:  # noqa: BLE001 — tracing must never fail a run
            _trace_annotation = False
    if not _trace_annotation:
        return None
    return _trace_annotation(ANNOTATION_PREFIX + name)


class Span:
    """One timed phase: a node of the per-thread span tree."""

    __slots__ = (
        "name", "span_id", "parent_id", "ts", "dur", "attrs", "events",
        "thread",
    )

    def __init__(
        self,
        name: str,
        span_id: int,
        parent_id: Optional[int],
        ts: float,
        thread: str,
        attrs: dict[str, Any],
    ):
        self.name = name
        self.span_id = span_id
        self.parent_id = parent_id
        self.ts = ts
        self.dur: Optional[float] = None  # set when the span closes
        self.attrs = attrs
        self.events: list[dict[str, Any]] = []
        self.thread = thread

    def set_attr(self, **attrs: Any) -> "Span":
        self.attrs.update(attrs)
        return self

    def add_event(self, name: str, ts: float, **attrs: Any) -> None:
        self.events.append({"name": name, "ts": ts, "attrs": attrs})

    def to_dict(self) -> dict[str, Any]:
        return {
            "type": "span",
            "id": self.span_id,
            "parent": self.parent_id,
            "name": self.name,
            "ts": round(self.ts, 6),
            "dur": None if self.dur is None else round(self.dur, 6),
            "thread": self.thread,
            "attrs": self.attrs,
            "events": self.events,
        }


class Tracer:
    """Thread-safe span collector: per-thread open-span stacks, a shared
    bounded buffer of completed spans, and an optional JSONL sink.

    Tracing must never fail training: sink write errors are swallowed after
    disabling the sink, and attribute values that are not JSON-serializable
    are stringified.
    """

    def __init__(self, buffer_limit: int = DEFAULT_BUFFER_LIMIT):
        self._local = threading.local()
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._anchor = time.monotonic()
        self._finished: list[Span] = []
        # every thread's open-span stack, so reset() can clear them ALL
        # (threading.local is only visible from its own thread)
        self._all_stacks: list[list[Span]] = []
        self._default_buffer_limit = buffer_limit
        self._buffer_limit = buffer_limit
        self.dropped_spans = 0
        self._sink_path: Optional[str] = None
        self._sink_fh = None
        self._wall_anchor: Optional[str] = None

    # -- configuration -------------------------------------------------------

    def configure(
        self,
        jsonl_path: Optional[str] = None,
        buffer_limit: Optional[int] = None,
    ) -> None:
        """Set (or replace) the JSONL sink and/or the in-memory buffer cap."""
        with self._lock:
            if buffer_limit is not None:
                self._buffer_limit = int(buffer_limit)
            if jsonl_path is not None and jsonl_path != self._sink_path:
                self._close_sink_locked()
                self._sink_path = jsonl_path
                # truncate: one session per file — appending a rerun would
                # mix incompatible monotonic timebases (and a second
                # mid-file trace_header) into one Perfetto export
                self._sink_fh = open(jsonl_path, "w", encoding="utf-8")
                wall = datetime.datetime.now(datetime.timezone.utc)
                self._wall_anchor = wall.isoformat()
                header = {
                    "type": "trace_header",
                    "wall_time": self._wall_anchor,
                    "monotonic_anchor": round(time.monotonic() - self._anchor, 6),
                    # the monotonic<->epoch anchor pair: a span at tracer
                    # time `ts` happened at absolute epoch second
                    # `anchor_unix_s + (ts - monotonic_anchor)` — the
                    # alignment FleetReport merges member timelines on
                    "anchor_unix_s": round(wall.timestamp(), 6),
                    "hostname": identity.hostname(),
                }
                proc = identity.fleet_process_index()
                if proc is not None:
                    header["process_index"] = proc
                    nproc = identity.fleet_process_count()
                    if nproc is not None:
                        header["num_processes"] = nproc
                self._sink_fh.write(json.dumps(header) + "\n")
                self._sink_fh.flush()

    def _close_sink_locked(self) -> None:
        if self._sink_fh is not None:
            try:
                self._sink_fh.close()
            except OSError:
                pass
        self._sink_fh = None
        self._sink_path = None

    def reset(self) -> None:
        """Drop all finished spans, close the sink, clear EVERY thread's
        open-span stack (test isolation; a span left open on a worker
        thread must not parent post-reset spans), and restore the
        constructor-default buffer limit and drop accounting."""
        with self._lock:
            self._finished.clear()
            self._close_sink_locked()
            for stack in self._all_stacks:
                stack.clear()
            self._buffer_limit = self._default_buffer_limit
            self.dropped_spans = 0

    # -- span lifecycle ------------------------------------------------------

    def _stack(self) -> list[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
            with self._lock:
                self._all_stacks.append(stack)
        return stack

    def current(self) -> Optional[Span]:
        stack = self._stack()
        return stack[-1] if stack else None

    def open_spans(self) -> list[Span]:
        """The deepest currently-open span path ACROSS threads, outermost
        first — the stack whose innermost span started most recently wins.
        Safe to call from a monitor thread (the heartbeat): stacks are
        copied under the GIL; a span closing mid-copy at worst drops one
        path element."""
        with self._lock:
            stacks = [list(s) for s in self._all_stacks]
        stacks = [s for s in stacks if s]
        if not stacks:
            return []
        return max(stacks, key=lambda s: s[-1].ts)

    def active_span_path(self, sep: str = " > ") -> str:
        """``"fit > coordinate_descent > cd_iteration > coordinate:fixed >
        update"`` for the deepest open span path, or ``""`` when nothing
        is open."""
        return sep.join(s.name for s in self.open_spans())

    def now(self) -> float:
        """Seconds on the tracer's monotonic timebase."""
        return time.monotonic() - self._anchor

    @contextmanager
    def span(self, name: str, **attrs: Any) -> Iterator[Span]:
        stack = self._stack()
        parent = stack[-1] if stack else None
        s = Span(
            name=name,
            span_id=next(self._ids),
            parent_id=None if parent is None else parent.span_id,
            ts=self.now(),
            thread=threading.current_thread().name,
            attrs=dict(attrs),
        )
        stack.append(s)
        try:
            annotation = _annotation(name)
            if annotation is not None:
                annotation.__enter__()
        except Exception:  # noqa: BLE001 — mirroring must never fail
            annotation = None
        try:
            yield s
        finally:
            if annotation is not None:
                try:
                    annotation.__exit__(None, None, None)
                except Exception:  # noqa: BLE001
                    pass
            s.dur = self.now() - s.ts
            # close even if exits arrive out of order (a leaked child span)
            while stack and stack[-1] is not s:
                stack.pop()
            if stack:
                stack.pop()
            self._finish(s)

    def add_event(self, name: str, **attrs: Any) -> None:
        """Attach a point-in-time event to the current span (no-op when no
        span is open — telemetry must never fail the caller)."""
        cur = self.current()
        if cur is not None:
            cur.add_event(name, ts=self.now(), **attrs)

    def emit(
        self,
        name: str,
        ts: float,
        dur: float,
        parent: Optional[int] = None,
        **attrs: Any,
    ) -> int:
        """Record an already-measured span retroactively (no context
        manager): the request tracer's tail sampler decides AFTER a
        request finished whether its phases deserve full spans. Returns
        the span id so callers can parent children under it."""
        s = Span(
            name=name,
            span_id=next(self._ids),
            parent_id=parent,
            ts=float(ts),
            thread=threading.current_thread().name,
            attrs=dict(attrs),
        )
        s.dur = max(0.0, float(dur))
        self._finish(s)
        return s.span_id

    def _finish(self, s: Span) -> None:
        dropped = 0
        with self._lock:
            self._finished.append(s)
            if len(self._finished) > self._buffer_limit:
                dropped = len(self._finished) - self._buffer_limit
                del self._finished[:dropped]
                self.dropped_spans += dropped
            if self._sink_fh is not None:
                try:
                    self._sink_fh.write(
                        json.dumps(s.to_dict(), default=str) + "\n"
                    )
                    self._sink_fh.flush()
                except (OSError, ValueError):
                    self._close_sink_locked()  # never fail training
        if dropped:
            # buffer overflow was silent data loss — surface it in the
            # metrics snapshot and the run report (local import: metrics
            # must stay importable without trace)
            from photon_ml_tpu.telemetry import metrics

            metrics.counter("trace.dropped_spans").inc(dropped)

    # -- inspection ----------------------------------------------------------

    def finished_spans(self, name: Optional[str] = None) -> list[Span]:
        with self._lock:
            spans = list(self._finished)
        if name is not None:
            spans = [s for s in spans if s.name == name]
        return spans


#: Process-global tracer; module-level helpers below delegate to it.
TRACER = Tracer()

span = TRACER.span
current_span = TRACER.current
add_event = TRACER.add_event
active_span_path = TRACER.active_span_path
configure = TRACER.configure
reset = TRACER.reset
finished_spans = TRACER.finished_spans


# -- Chrome trace (Perfetto) export ------------------------------------------


def to_chrome_trace(records: Iterable[dict] | str) -> dict:
    """Convert span dicts (``Span.to_dict()`` / JSONL lines) to the Chrome
    trace-event JSON object Perfetto and chrome://tracing load directly.

    Complete spans become ``ph: "X"`` duration events; span events become
    ``ph: "i"`` thread-scoped instants. Timestamps are microseconds on the
    tracer's monotonic timebase.

    ``records`` may instead be a FLEET telemetry directory path: every
    member's ``trace.proc-<i>.jsonl`` stream merges into one file with a
    Perfetto track per process (``proc-<i> (<hostname>)``) and timestamps
    aligned through the PR 13 skew anchors — a request that fanned out
    across members renders as one timeline.
    """
    if isinstance(records, str):
        return _fleet_chrome_trace(records)
    tids: dict[str, int] = {}
    events: list[dict] = []
    meta: list[dict] = []

    def tid(thread: str) -> int:
        if thread not in tids:
            tids[thread] = len(tids) + 1
            meta.append(
                {
                    "name": "thread_name",
                    "ph": "M",
                    "pid": 1,
                    "tid": tids[thread],
                    "args": {"name": thread},
                }
            )
        return tids[thread]

    for rec in records:
        if rec.get("type") != "span":
            continue
        t = tid(rec.get("thread", "main"))
        events.append(
            {
                "name": rec["name"],
                "cat": "span",
                "ph": "X",
                "ts": round(rec["ts"] * 1e6, 3),
                "dur": round((rec.get("dur") or 0.0) * 1e6, 3),
                "pid": 1,
                "tid": t,
                "args": rec.get("attrs", {}),
            }
        )
        for ev in rec.get("events", ()):
            events.append(
                {
                    "name": ev["name"],
                    "cat": "event",
                    "ph": "i",
                    "s": "t",
                    "ts": round(ev["ts"] * 1e6, 3),
                    "pid": 1,
                    "tid": t,
                    "args": ev.get("attrs", {}),
                }
            )
    return {"traceEvents": meta + events, "displayTimeUnit": "ms"}


def _fleet_chrome_trace(fleet_dir: str) -> dict:
    """One Chrome trace for a whole fleet directory: per-process tracks,
    member timelines aligned on FleetReport's absolute (anchor + skew)
    timebase, origin at the earliest anchored span."""
    # local import: fleet_report imports report which imports this module
    from photon_ml_tpu.telemetry.fleet_report import FleetReport

    fleet = FleetReport.load(fleet_dir)
    merged = fleet.merged_spans()
    anchored = [
        r["abs_ts"] for r in merged if isinstance(r.get("abs_ts"), (int, float))
    ]
    t0 = min(anchored) if anchored else 0.0
    hosts = {m.process_index: m.hostname for m in fleet.members}
    events: list[dict] = []
    meta: list[dict] = []
    pids: set[int] = set()
    tids: dict[tuple[int, str], int] = {}

    def pid_of(proc: int) -> int:
        pid = int(proc) + 1  # Perfetto hides pid 0
        if pid not in pids:
            pids.add(pid)
            label = f"proc-{proc}"
            if hosts.get(proc):
                label += f" ({hosts[proc]})"
            meta.append(
                {
                    "name": "process_name",
                    "ph": "M",
                    "pid": pid,
                    "args": {"name": label},
                }
            )
        return pid

    def tid_of(pid: int, thread: str) -> int:
        key = (pid, thread)
        if key not in tids:
            tids[key] = sum(1 for k in tids if k[0] == pid) + 1
            meta.append(
                {
                    "name": "thread_name",
                    "ph": "M",
                    "pid": pid,
                    "tid": tids[key],
                    "args": {"name": thread},
                }
            )
        return tids[key]

    for rec in merged:
        if rec.get("type") != "span":
            continue
        ts = rec.get("ts")
        if not isinstance(ts, (int, float)):
            continue
        pid = pid_of(int(rec.get("process_index") or 0))
        t = tid_of(pid, rec.get("thread", "main"))
        abs_ts = rec.get("abs_ts")
        # the per-record delta from member-local to fleet-absolute time;
        # an unanchored stream keeps its local timebase (better skewed
        # than dropped)
        shift = (abs_ts - t0 - ts) if isinstance(abs_ts, (int, float)) else 0.0
        events.append(
            {
                "name": rec["name"],
                "cat": "span",
                "ph": "X",
                "ts": round((ts + shift) * 1e6, 3),
                "dur": round((rec.get("dur") or 0.0) * 1e6, 3),
                "pid": pid,
                "tid": t,
                "args": rec.get("attrs", {}),
            }
        )
        for ev in rec.get("events", ()):
            if not isinstance(ev.get("ts"), (int, float)):
                continue
            events.append(
                {
                    "name": ev["name"],
                    "cat": "event",
                    "ph": "i",
                    "s": "t",
                    "ts": round((ev["ts"] + shift) * 1e6, 3),
                    "pid": pid,
                    "tid": t,
                    "args": ev.get("attrs", {}),
                }
            )
    return {"traceEvents": meta + events, "displayTimeUnit": "ms"}


def perfetto_path(trace_out: str) -> str:
    """The sibling ``.perfetto.json`` path for a span JSONL path (shared by
    every driver that auto-exports a Chrome trace next to its JSONL)."""
    base = trace_out[:-6] if trace_out.endswith(".jsonl") else trace_out
    return base + ".perfetto.json"


def export_chrome_trace(jsonl_path: str, out_path: str) -> int:
    """Convert a span JSONL file — or a fleet telemetry DIRECTORY of
    ``trace.proc-<i>.jsonl`` streams — to one Chrome/Perfetto trace file.

    Returns the number of trace events written. Unparseable lines are
    skipped (a crashed run leaves a truncated last line)."""
    if os.path.isdir(jsonl_path):
        doc = to_chrome_trace(jsonl_path)
        from photon_ml_tpu.utils.atomic import atomic_write_json

        atomic_write_json(out_path, doc)
        return len(doc["traceEvents"])
    records = []
    with open(jsonl_path, encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    doc = to_chrome_trace(records)
    # atomic write (tools/check.py L008): a crash mid-export must not leave
    # a truncated trace that viewers reject wholesale
    from photon_ml_tpu.utils.atomic import atomic_write_json

    atomic_write_json(out_path, doc)
    return len(doc["traceEvents"])
