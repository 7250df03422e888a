"""The staged, threaded ingest pipeline: ``ChunkStream``.

Stages, each its own thread(s), connected by bounded hand-offs:

  decode workers (N)  -- fill staging buffers from block ranges
        |  deterministic reorder (chunks re-sequence to plan order)
  uploader (1)        -- ``device_put`` of chunk K+1 while chunk K solves
        |  bounded output queue (``prefetch_depth``)
  consumer            -- the training loop, iterating DeviceChunks

Backpressure is structural: decode blocks on the buffer ring, the
uploader blocks on the output queue, and every wait has a stall timeout
that raises a typed :class:`~photon_ml_tpu.ingest.errors.IngestStall`
instead of hanging. Ordering is deterministic — chunks leave the
pipeline in plan order no matter which worker finished first — so a
checkpoint resume (``start_chunk=K``) replays the exact remaining
stream, and the stream-global id-column interning is reproducible.

Telemetry: ``ingest.rows`` / ``ingest.chunks`` / ``ingest.stalls`` /
``ingest.buffer_growths`` counters, ``ingest.queue_depth`` /
``ingest.staging_bytes`` / ``ingest.rows_per_sec`` gauges, an
``ingest.solve_wait_s`` histogram plus ``ingest.solve_waits`` (how often
the SOLVE waited on data after warm-up — the number the RunReport
"Ingestion" section is built around), and per-stage spans.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import queue
import threading
import time
from typing import Mapping, Optional, Sequence

import jax
import numpy as np

from photon_ml_tpu import faults, telemetry
from photon_ml_tpu.ingest.buffers import BufferRing, StagingBuffer
from photon_ml_tpu.ingest.decode import (
    DecodeContext,
    build_decode_context,
    decode_chunk,
)
from photon_ml_tpu.ingest.errors import (
    ChunkDecodeError,
    IngestConfigError,
    IngestStall,
    PipelineClosed,
)
from photon_ml_tpu.ingest.planner import ChunkPlan, plan_chunks
from photon_ml_tpu.ops.sparse import SparseBatch

_END = object()

# Injection seam on the uploader's per-chunk device_put: a firing rule is
# the uploader thread dying mid-stream (the consumer must surface it as a
# typed error, not a silent hang).
_FP_UPLOAD_CHUNK = faults.register_point(
    "ingest.upload.chunk",
    description="uploader device_put of one device-ready chunk",
)


@dataclasses.dataclass(frozen=True)
class IngestSpec:
    """Tuning knobs of one ingest pipeline.

    ``workers=0`` means one decode worker per host core.
    ``prefetch_depth`` bounds how many device-ready chunks may wait ahead
    of the solve (the double-buffer depth). ``ring_slots=0`` sizes the
    staging ring to ``workers + prefetch_depth + 1``.
    ``resident_budget_mb`` caps the HOST-resident staging memory: the
    ring shrinks to fit (never below 2 slots — below that the pipeline
    cannot overlap, and the spec is rejected with the sizing math).
    ``read_retries`` bounds how many times ONE chunk's decode is retried
    after a transient ``OSError`` (flaky network filesystem read) before
    the error propagates and kills the stream; retries back off
    ``retry_backoff_s * 2**attempt`` and are surfaced in
    :class:`IngestStats` / ``ingest.read_retries``.
    """

    workers: int = 0
    prefetch_depth: int = 2
    chunk_rows: int = 65536
    nnz_per_row_hint: int = 32
    ring_slots: int = 0
    resident_budget_mb: Optional[float] = None
    stall_timeout_s: float = 600.0
    read_retries: int = 2
    retry_backoff_s: float = 0.05

    def __post_init__(self):
        if self.workers < 0:
            raise IngestConfigError("ingest workers must be >= 0")
        if self.read_retries < 0:
            raise IngestConfigError("read_retries must be >= 0")
        if self.retry_backoff_s < 0:
            raise IngestConfigError("retry_backoff_s must be >= 0")
        if self.prefetch_depth < 1:
            raise IngestConfigError("prefetch_depth must be >= 1")
        if self.chunk_rows < 1:
            raise IngestConfigError("chunk_rows must be >= 1")
        if self.nnz_per_row_hint < 1:
            raise IngestConfigError("nnz_per_row_hint must be >= 1")
        if self.ring_slots < 0:
            raise IngestConfigError("ring_slots must be >= 0")
        if self.stall_timeout_s <= 0:
            raise IngestConfigError("stall_timeout_s must be > 0")
        if (
            self.resident_budget_mb is not None
            and self.resident_budget_mb <= 0
        ):
            raise IngestConfigError("resident_budget_mb must be > 0")

    def resolved_workers(self) -> int:
        return self.workers or max(os.cpu_count() or 1, 1)

    @staticmethod
    def from_config(obj) -> "IngestSpec":
        """Config value -> spec: ``true`` means defaults, a dict overrides
        fields; unknown keys are a typed error (a silently ignored knob
        is worse than a refusal)."""
        if obj is True:
            return IngestSpec()
        if not isinstance(obj, Mapping):
            raise IngestConfigError(
                f"ingest config must be true or an object, got {obj!r}"
            )
        fields = {f.name for f in dataclasses.fields(IngestSpec)}
        unknown = set(obj) - fields
        if unknown:
            raise IngestConfigError(
                f"unknown ingest config keys: {sorted(unknown)} "
                f"(known: {sorted(fields)})"
            )
        return IngestSpec(**obj)


@dataclasses.dataclass
class DeviceChunk:
    """One device-ready chunk, in deterministic stream order.

    ``shards`` hold padded SparseBatches with DEVICE leaves (uniform
    ``rows_cap`` rows; nnz capacity may step up once if the hint was
    low). ``labels``/``offsets``/``weights`` are exact f64 HOST copies of
    the real rows (assemblers and evaluators want unpadded host
    scalars); ``id_codes`` are stream-GLOBAL interned entity codes.
    """

    index: int
    row_start: int
    rows: int
    shards: dict[str, SparseBatch]
    nnz_used: dict[str, int]
    labels: np.ndarray
    offsets: np.ndarray
    weights: np.ndarray
    id_codes: dict[str, np.ndarray]

    @property
    def batch(self) -> SparseBatch:
        """The single-shard convenience view (GLM flows)."""
        if len(self.shards) != 1:
            raise ValueError(
                f"chunk has {len(self.shards)} shards; name one explicitly"
            )
        return next(iter(self.shards.values()))


@dataclasses.dataclass
class IngestStats:
    rows: int = 0
    chunks: int = 0
    stalls: int = 0
    solve_waits: int = 0
    solve_wait_s: float = 0.0
    buffer_growths: int = 0
    staging_bytes: int = 0
    rows_per_sec: float = 0.0
    #: transient-read retries that succeeded on a later attempt — a
    #: nonzero value means the storage layer flaked and the bounded
    #: retry absorbed it (RunReport "Ingestion" surfaces this)
    read_retries: int = 0


class ChunkStream:
    """Iterator of :class:`DeviceChunk`, fed by the threaded pipeline.

    Use as an iterator or a context manager; ``close()`` tears the
    threads down early (abandoning a stream mid-run is legal — resume
    later with ``start_chunk``).
    """

    def __init__(
        self,
        paths: Sequence[str],
        feature_shards: Optional[Mapping[str, Sequence[str]]] = None,
        index_maps: Optional[Mapping] = None,
        id_columns: Sequence[str] = (),
        add_intercept: bool = True,
        is_response_required: bool = True,
        spec: Optional[IngestSpec] = None,
        placement=None,
        start_chunk: int = 0,
        id_vocabularies: Optional[Mapping[str, Sequence]] = None,
    ):
        from photon_ml_tpu.data.avro import _as_paths

        if index_maps is None:
            raise IngestConfigError(
                "the ingest pipeline needs index_maps up front (build or "
                "load them first — data.avro.build_index_maps_from_avro "
                "does a cheap vocab-only scan); an out-of-core stream "
                "cannot discover the feature space as it goes"
            )
        self.spec = spec or IngestSpec()
        feature_shards = dict(feature_shards or {"features": ("features",)})
        file_list = _as_paths(list(paths))
        self.metas, all_plans = plan_chunks(file_list, self.spec.chunk_rows)
        if start_chunk < 0 or start_chunk > len(all_plans):
            raise IngestConfigError(
                f"start_chunk={start_chunk} out of range for "
                f"{len(all_plans)} planned chunks"
            )
        self.plans = all_plans  # full deterministic plan (for resume math)
        self._todo = all_plans[start_chunk:]
        self.total_rows = sum(p.n_rows for p in all_plans)
        self._ctx: DecodeContext = build_decode_context(
            self.metas, feature_shards, index_maps, id_columns,
            add_intercept, is_response_required,
        )
        self.shard_names = self._ctx.shard_names
        self.num_features = {
            s: len(index_maps[s]) for s in self.shard_names
        }
        self._placement = placement
        self.rows_cap = max((p.n_rows for p in all_plans), default=1)
        self._intercept = any(c >= 0 for c in self._ctx.intercept_cols)

        n_workers = min(self.spec.resolved_workers(),
                        max(len(self._todo), 1))
        ring = self._build_ring(n_workers, len(feature_shards),
                                len(id_columns))
        self._ring = ring
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._stop = threading.Event()
        self._error: Optional[BaseException] = None
        self._work_i = 0
        self._pending: dict[int, StagingBuffer] = {}
        # per-shard stream-global raw-nnz capacity (monotone; workers grow
        # free buffers up to it at acquire time, the uploader normalizes
        # in-flight stragglers, so chunk shapes stay uniform)
        self._raw_caps = [self._init_raw_cap] * len(self.shard_names)
        self._out: "queue.Queue" = queue.Queue(
            maxsize=self.spec.prefetch_depth
        )
        # stream-global id interning. NOTE the resume caveat: interned
        # codes are first-seen IN STREAM ORDER, so a stream started at
        # chunk K assigns different codes than the full stream unless the
        # caller seeds it with the original run's vocabularies
        # (`id_vocabularies`, e.g. persisted next to a checkpoint via
        # `id_vocabulary()`); chunk ordering and array contents are
        # start-chunk-independent either way.
        self._interns: list[dict] = []
        for col in id_columns:
            seed = (id_vocabularies or {}).get(col, ())
            self._interns.append({v: i for i, v in enumerate(seed)})
        self._stats = IngestStats(staging_bytes=ring.nbytes)
        self._t0 = time.monotonic()
        self._got_first = False
        self._done = False
        self._threads = [
            threading.Thread(
                target=self._decode_loop, name=f"ingest-decode-{i}",
                daemon=True,
            )
            for i in range(n_workers)
        ]
        self._threads.append(
            threading.Thread(
                target=self._upload_loop, name="ingest-upload", daemon=True
            )
        )
        for t in self._threads:
            t.start()

    # -- sizing --------------------------------------------------------------

    def _build_ring(
        self, n_workers: int, n_shards: int, n_ids: int
    ) -> BufferRing:
        spec = self.spec
        self._init_raw_cap = max(
            self.rows_cap * spec.nnz_per_row_hint, 1
        )
        probe = StagingBuffer(
            self.rows_cap, self._init_raw_cap, n_shards, n_ids,
            self._intercept,
        )
        slot_bytes = probe.nbytes
        want = spec.ring_slots or (
            n_workers + spec.prefetch_depth + 1
        )
        if spec.resident_budget_mb is not None:
            budget = int(spec.resident_budget_mb * 2**20)
            fit = max(budget // max(slot_bytes, 1), 0)
            if fit < 2:
                raise IngestConfigError(
                    f"resident_budget_mb={spec.resident_budget_mb:g} fits "
                    f"{fit} staging slot(s) of {slot_bytes / 2**20:.1f} MB "
                    f"(rows_cap={self.rows_cap}, nnz_per_row_hint="
                    f"{spec.nnz_per_row_hint}); the pipeline needs >= 2 — "
                    "raise the budget or lower chunk_rows/nnz_per_row_hint"
                )
            want = min(want, fit)
        slots = [probe] + [
            StagingBuffer(
                self.rows_cap, self._init_raw_cap, n_shards, n_ids,
                self._intercept,
            )
            for _ in range(want - 1)
        ]
        return BufferRing(slots, spec.stall_timeout_s)

    # -- worker side ---------------------------------------------------------

    def _grow(
        self, buf: StagingBuffer, si: int, needed: int, preserve: int
    ) -> None:
        with self._lock:
            if needed > self._raw_caps[si]:
                new_cap = max(self._raw_caps[si] * 2, needed)
                self._raw_caps[si] = new_cap
                telemetry.counter("ingest.buffer_growths").inc()
                self._stats.buffer_growths += 1
            target = self._raw_caps[si]
        buf.shards[si].grow(target, self.rows_cap, self._intercept,
                            preserve=preserve)

    def _decode_with_retry(self, plan: ChunkPlan, buf: StagingBuffer) -> None:
        """One chunk's decode, retried past transient ``OSError``s.

        A flaky read from a network filesystem must not kill the whole
        stream on its first occurrence: up to ``spec.read_retries``
        re-reads with exponential backoff, each starting the chunk over
        (``decode_chunk`` re-initializes the buffer, so a partial first
        attempt leaves no residue). Deterministic failures — a
        :class:`ChunkDecodeError` from corrupt bytes or a schema
        violation — propagate immediately: re-reading corrupt data
        produces the same corrupt data."""
        for attempt in range(self.spec.read_retries + 1):
            try:
                decode_chunk(self._ctx, plan, buf, self._grow)
                return
            except ChunkDecodeError:
                raise
            except OSError as e:
                if attempt >= self.spec.read_retries:
                    raise
                telemetry.counter("ingest.read_retries").inc()
                with self._lock:
                    self._stats.read_retries += 1
                delay = self.spec.retry_backoff_s * (2 ** attempt)
                logging.getLogger("photon_ml_tpu.ingest").warning(
                    "transient read failure on chunk %d of %s (attempt "
                    "%d/%d, retrying in %.2fs): %s", plan.index, plan.path,
                    attempt + 1, self.spec.read_retries + 1, delay, e,
                )
                if self._stop.wait(delay):
                    raise PipelineClosed(
                        "stream closed during a read-retry backoff"
                    ) from None

    def _next_plan(self) -> Optional[ChunkPlan]:
        with self._lock:
            if self._work_i >= len(self._todo):
                return None
            plan = self._todo[self._work_i]
            self._work_i += 1
            return plan

    def _decode_loop(self) -> None:
        try:
            while not self._stop.is_set():
                # buffer first, plan second: whoever holds a slot takes the
                # LOWEST undecoded chunk. The other order lets a decoder
                # that holds chunk k wait for a slot while its peers fill
                # every slot with chunks > k, which the in-order uploader
                # never drains (seen as a hang of the out-of-core fit).
                buf = self._ring.acquire()
                plan = self._next_plan()
                if plan is None:
                    self._ring.release(buf)
                    return
                # converge lagging slots to the stream-global capacity
                # while the buffer is provably free
                with self._lock:
                    caps = list(self._raw_caps)
                for si, cap in enumerate(caps):
                    buf.shards[si].grow(cap, self.rows_cap, self._intercept)
                with telemetry.span(
                    "ingest_decode", chunk=plan.index, rows=plan.n_rows,
                    bytes=plan.nbytes,
                ):
                    self._decode_with_retry(plan, buf)
                with self._cv:
                    self._pending[plan.index] = buf
                    self._cv.notify_all()
        except PipelineClosed:
            pass
        except BaseException as e:  # surface worker deaths to the consumer
            self._fail(e)

    # -- uploader ------------------------------------------------------------

    def _normalized_shard_arrays(self, buf: StagingBuffer, si: int):
        """Pad a straggler (pre-growth) slot's final arrays up to the
        stream-global capacity — rare, only right after a growth, and
        it keeps every chunk batch the same shape."""
        st = buf.shards[si]
        with self._lock:
            target_raw = self._raw_caps[si]
        target = target_raw + (self.rows_cap if self._intercept else 0)
        vals, rws, cls = st.values, st.rows, st.cols
        if len(vals) < target:
            extra = target - len(vals)
            vals = np.concatenate(
                [vals, np.zeros(extra, np.float32)]
            )
            rws = np.concatenate(
                [rws, np.full(extra, self.rows_cap - 1, np.int32)]
            )
            cls = np.concatenate([cls, np.zeros(extra, np.int32)])
        return vals, rws, cls

    def _put_out(self, item) -> None:
        deadline = time.monotonic() + self.spec.stall_timeout_s
        while True:
            if self._stop.is_set():
                raise PipelineClosed("stream closed while uploading")
            try:
                self._out.put(item, timeout=0.25)
                telemetry.gauge("ingest.queue_depth").set(
                    self._out.qsize()
                )
                return
            except queue.Full:
                if time.monotonic() > deadline:
                    telemetry.counter("ingest.stalls").inc()
                    with self._lock:
                        self._stats.stalls += 1
                    raise IngestStall(
                        "upload", self.spec.stall_timeout_s,
                        "output queue stayed full (consumer stopped?)",
                    ) from None

    def _upload_one(self, plan: ChunkPlan, buf: StagingBuffer) -> DeviceChunk:
        import jax.numpy as jnp

        placement = self._placement

        def put(x):
            # The copy is load-bearing: device_put MAY zero-copy an
            # aligned host array (measured on CPU even under explicit
            # shardings), silently aliasing the staging buffer this ring
            # is about to recycle. Default path: jnp.array(copy=True) is
            # one guaranteed-copy hop (on TPU the copy IS the H2D
            # transfer). Placement path: commit a FRESH host copy — the
            # buffer may alias that never-mutated temp all it wants, and
            # a host memcpy is cheaper than a post-hoc device reshard.
            if placement is None:
                return jnp.array(x, copy=True)
            return jax.device_put(np.array(x), placement)
        n = plan.n_rows
        shards: dict[str, SparseBatch] = {}
        nnz_used: dict[str, int] = {}
        labels_d = put(buf.labels)
        offsets_d = put(buf.offsets)
        weights_d = put(buf.weights)
        for si, name in enumerate(self.shard_names):
            vals, rws, cls = self._normalized_shard_arrays(buf, si)
            shards[name] = SparseBatch(
                values=put(vals),
                rows=put(rws),
                cols=put(cls),
                labels=labels_d,
                offsets=offsets_d,
                weights=weights_d,
                num_features=self.num_features[name],
            )
            nnz_used[name] = buf.shards[si].nnz_used
        # exact f64 host copies of the real rows (the staging buffer is
        # about to be recycled)
        labels = buf.scratch_labels[:n].copy()
        offsets = buf.scratch_offsets[:n].copy()
        weights = buf.scratch_weights[:n].copy()
        id_codes: dict[str, np.ndarray] = {}
        for ci, col in enumerate(self._ctx.id_columns):
            table = self._interns[ci]
            vocab = buf.id_vocabs[ci]
            remap = np.empty(len(vocab), np.int64)
            for i, key in enumerate(vocab):
                code = table.get(key)
                if code is None:
                    code = len(table)
                    table[key] = code
                remap[i] = code
            local = buf.id_codes[ci][:n]
            id_codes[col] = remap[local] if len(local) else local.copy()
        # wait for the H2D copies before recycling the staging buffer —
        # the transfer source must not be overwritten mid-flight
        leaves = [labels_d, offsets_d, weights_d]
        for b in shards.values():
            leaves += [b.values, b.rows, b.cols]
        leaves = jax.block_until_ready(leaves)
        return DeviceChunk(
            index=plan.index,
            row_start=plan.row_start,
            rows=n,
            shards=shards,
            nnz_used=nnz_used,
            labels=labels,
            offsets=offsets,
            weights=weights,
            id_codes=id_codes,
        )

    def _upload_loop(self) -> None:
        try:
            for plan in self._todo:
                with self._cv:
                    ok = self._cv.wait_for(
                        lambda: plan.index in self._pending
                        or self._stop.is_set(),
                        timeout=self.spec.stall_timeout_s,
                    )
                    if self._stop.is_set():
                        return
                    if not ok:
                        telemetry.counter("ingest.stalls").inc()
                        self._stats.stalls += 1
                        raise IngestStall(
                            "upload", self.spec.stall_timeout_s,
                            f"chunk {plan.index} never arrived from decode",
                        )
                    buf = self._pending.pop(plan.index)
                with telemetry.span(
                    "ingest_upload", chunk=plan.index, rows=plan.n_rows
                ):
                    faults.fault_point(_FP_UPLOAD_CHUNK)
                    chunk = self._upload_one(plan, buf)
                self._ring.release(buf)
                telemetry.counter("ingest.rows").inc(chunk.rows)
                telemetry.counter("ingest.chunks").inc()
                with self._lock:
                    self._stats.rows += chunk.rows
                    self._stats.chunks += 1
                self._put_out(chunk)
            self._put_out(_END)
        except PipelineClosed:
            pass
        except BaseException as e:
            self._fail(e)

    # -- failure / shutdown --------------------------------------------------

    def _fail(self, exc: BaseException) -> None:
        with self._lock:
            if self._error is None:
                self._error = exc
        self._stop.set()
        self._ring.close()
        with self._cv:
            self._cv.notify_all()

    def close(self) -> None:
        """Tear the pipeline down (idempotent)."""
        self._stop.set()
        self._ring.close()
        with self._cv:
            self._cv.notify_all()
        # unblock a put-blocked uploader
        while True:
            try:
                self._out.get_nowait()
            except queue.Empty:
                break
        for t in self._threads:
            t.join(timeout=5.0)

    def __enter__(self) -> "ChunkStream":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- consumer side -------------------------------------------------------

    def __iter__(self) -> "ChunkStream":
        return self

    def __next__(self) -> DeviceChunk:
        if self._done:
            raise StopIteration
        t0 = time.monotonic()
        while True:
            with self._lock:
                if self._error is not None:
                    self._done = True
                    raise self._error
            try:
                item = self._out.get(timeout=0.25)
                break
            except queue.Empty:
                if time.monotonic() - t0 > self.spec.stall_timeout_s:
                    self._done = True
                    telemetry.counter("ingest.stalls").inc()
                    with self._lock:
                        self._stats.stalls += 1
                    raise IngestStall(
                        "consume", self.spec.stall_timeout_s,
                        "no chunk arrived (decode starved or a worker "
                        "died silently)",
                    ) from None
        telemetry.gauge("ingest.queue_depth").set(self._out.qsize())
        if item is _END:
            self._done = True
            elapsed = max(time.monotonic() - self._t0, 1e-9)
            with self._lock:
                self._stats.rows_per_sec = self._stats.rows / elapsed
            if self._stats.rows:
                telemetry.gauge("ingest.rows_per_sec").set(
                    self._stats.rows_per_sec
                )
            raise StopIteration
        waited = time.monotonic() - t0
        if self._got_first:
            # warm-up excluded: the FIRST chunk always waits for the
            # pipeline to fill; steady-state waits mean the solve is
            # ingest-bound (the RunReport "Ingestion" headline)
            telemetry.histogram("ingest.solve_wait_s").observe(waited)
            if waited > 0.002:
                telemetry.counter("ingest.solve_waits").inc()
                with self._lock:
                    self._stats.solve_waits += 1
                    self._stats.solve_wait_s += waited
        self._got_first = True
        return item

    @property
    def using_native_decoder(self) -> bool:
        """Whether chunks decode through the native C++ interpreter (False
        = the pure-Python fallback workers, identical arrays)."""
        return self._ctx.use_native

    def stats(self) -> IngestStats:
        with self._lock:
            return dataclasses.replace(self._stats)

    def id_vocabulary(self, column: str) -> np.ndarray:
        """The stream-global first-seen vocabulary of an id column
        (complete once the stream is exhausted)."""
        ci = self._ctx.id_columns.index(column)
        return np.asarray(list(self._interns[ci]))
