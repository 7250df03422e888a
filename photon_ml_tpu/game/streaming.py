"""Billion-coefficient random-effect training: resident sharded coefficient
tables + streamed entity chunks.

The reference's defining scale claim is "hundreds of billions of
coefficients" across per-entity models (/root/reference/README.md:73;
projection envelope ~1e8 entities x ~1e3 features/entity,
photon-ml projector/README.md:8-12), held as RDD partitions across a Spark
cluster. The TPU-native answer:

  - The COEFFICIENT TABLE [N, K] is HBM-resident for the whole fit (4 GB
    per 1e9 f32 coefficients — one v5e chip holds ~2-3e9 alongside its
    working set; a mesh shards the entity axis so capacity scales linearly
    with devices, the multi-host path to 1e11).
  - The TRAINING DATA does not fit (a dense [N, R, K] stack is R*4 bytes
    per coefficient) and never has to: per-entity problems are
    independent, so entities stream through in CHUNKS. Chunk i+1's data is
    enqueued (host `device_put` or an on-device generator) before chunk
    i's solve is awaited — JAX's async dispatch overlaps the transfer with
    the compute, the streaming analog of Spark pipelining a partition
    fetch behind a partition solve.
  - Each chunk is ONE vmapped optimizer call on the dense local-design
    layout (ops/dense.DenseBatch — pure MXU-batched matmul sweeps, no
    random access); under a mesh the chunk is committed with
    ``parallel.sharding.entity_sharding`` (the reusable P("model")
    primitive shared with the RE bucket solves and, per ROADMAP item 4,
    sharded serving) and GSPMD partitions the vmap lanes — the only
    collective is the one-scalar convergence test per iteration
    (RandomEffectCoordinate.scala:101-130 semantics).

``__graft_entry__.dryrun_multichip`` runs the sharded-table path on the
virtual CPU mesh; not measured on the chip (ROADMAP R7).
"""

from __future__ import annotations

import dataclasses
import logging
from functools import lru_cache, partial
from typing import Callable, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from photon_ml_tpu import faults, telemetry
from photon_ml_tpu.parallel import sharding as psharding
from photon_ml_tpu.telemetry.xla import record_collective
from photon_ml_tpu.telemetry import memory as telemetry_memory
from photon_ml_tpu.ops.dense import DenseBatch
from photon_ml_tpu.ops.objective import make_objective
from photon_ml_tpu.optim.factory import OptimizerConfig
from photon_ml_tpu.optim.guard import GuardSpec, damped_objective, solve_health

Array = jax.Array

logger = logging.getLogger("photon_ml_tpu.game.streaming")

# fault-injection seams (photon_ml_tpu.faults): a chunk solve whose result
# can be NaN-poisoned on demand (drives the guard's damped-retry/rollback
# machinery deterministically) and the chunk boundary where checkpoint +
# stop handling runs (an injected raise here must leave a resumable
# directory behind)
_FP_SOLVE_RESULT = faults.register_point(
    "streaming.solve.result",
    description="chunk solve output (nan action poisons w for the guard)",
)
_FP_CHUNK_BOUNDARY = faults.register_point(
    "streaming.chunk.boundary",
    description="between a chunk solve and its checkpoint/stop handling",
)
# the fleet seam shared with the GSPMD solve dispatch: the last host-side
# instruction before a chunk solve's cross-process collective program
from photon_ml_tpu.parallel.distributed import FP_COLLECTIVE_ENTRY  # noqa: E402
from photon_ml_tpu.parallel.multihost import collective_wait  # noqa: E402


@lru_cache(maxsize=16)
def _chunk_writer(donate: bool):
    def write(table, w, start):
        return jax.lax.dynamic_update_slice(
            table, w.astype(table.dtype), (start, 0)
        )

    # multi_shape: the tail chunk is legitimately smaller than the rest
    return telemetry.instrumented_jit(
        write,
        name="streaming_chunk_write",
        multi_shape=True,
        donate_argnums=(0,) if donate else (),
    )


def _read_chunk(table, start: int, size: int) -> Array:
    return jax.lax.dynamic_slice(table, (start, 0), (size, table.shape[1]))


class ShardedCoefficientTable:
    """HBM-resident [N, K] coefficient table, chunk-updated in place.

    Updates donate the table buffer, so the table is never duplicated in
    HBM. With ``mesh`` the entity axis is sharded (NamedSharding P(axis))
    — per-device residency N*K*4/n_devices bytes. The per-entity SOLVES
    are collective-free (independent problems under shard_map); chunk
    read/write slices may reshard between the chunk's P(axis) layout and
    the table's, which XLA lowers to the minimal ICI exchange.
    """

    def __init__(
        self,
        num_entities: int,
        dim: int,
        mesh: Optional[Mesh] = None,
        axis: Optional[str] = None,
        dtype=jnp.float32,
    ):
        self.num_entities = int(num_entities)
        self.dim = int(dim)
        self.mesh = mesh
        if mesh is None:
            self.axis = axis
            self.sharding = None
            self.coefficients = jnp.zeros((num_entities, dim), dtype)
        else:
            # the ONE entity-sharding definition (parallel.sharding):
            # training tables, bucket solves and sharded serving all place
            # through it, so their shards line up across the mesh
            self.sharding = psharding.entity_sharding(mesh, axis)
            self.axis = self.sharding.spec[0]
            n_dev = psharding.axis_size(mesh, self.axis)
            if num_entities % n_dev:
                raise ValueError(
                    f"num_entities={num_entities} must divide over the "
                    f"{n_dev}-device '{self.axis}' axis (pad the entity "
                    "count)"
                )
            # jit-with-out_shardings materializes the zeros directly in
            # their sharded layout — no host/full-device copy, and it is
            # multi-controller-safe (every process runs the same program
            # and owns only its shards).
            # multi_shape: each table instance is its own executable by
            # design (a fresh closure per table) — not a recompile storm
            self.coefficients = telemetry.instrumented_jit(
                partial(jnp.zeros, (num_entities, dim), dtype),
                name="streaming_table_init",
                multi_shape=True,
                out_shardings=self.sharding,
            )()

    @classmethod
    def from_coefficients(
        cls,
        coefficients: Array,
        mesh: Optional[Mesh] = None,
        axis: Optional[str] = None,
    ) -> "ShardedCoefficientTable":
        """Wrap an ALREADY-PLACED [N, K] device array (e.g. an elastic
        checkpoint restore via
        ``StreamingCheckpointManager.restore_placed``) without the zero
        init + overwrite a construct-then-write resume would pay."""
        table = cls.__new__(cls)
        table.num_entities = int(coefficients.shape[0])
        table.dim = int(coefficients.shape[1])
        table.mesh = mesh
        if mesh is None:
            table.axis = axis
            table.sharding = None
        else:
            table.sharding = psharding.entity_sharding(mesh, axis)
            table.axis = table.sharding.spec[0]
            n_dev = psharding.axis_size(mesh, table.axis)
            if table.num_entities % n_dev:
                raise ValueError(
                    f"num_entities={table.num_entities} must divide over "
                    f"the {n_dev}-device '{table.axis}' axis"
                )
            if coefficients.sharding != table.sharding:
                coefficients = jax.device_put(coefficients, table.sharding)
        table.coefficients = coefficients
        return table

    @property
    def nbytes(self) -> int:
        return self.num_entities * self.dim * self.coefficients.dtype.itemsize

    def _check_bounds(self, start: int, size: int) -> None:
        # dynamic_(update_)slice silently CLAMPS an out-of-range start, which
        # would read/write the wrong entity rows — fail loudly instead.
        if start < 0 or size < 0 or start + size > self.num_entities:
            raise ValueError(
                f"chunk [{start}, {start + size}) out of bounds for table "
                f"of {self.num_entities} entities"
            )

    def write_chunk(self, start: int, w: Array) -> None:
        self._check_bounds(start, int(w.shape[0]))
        self.coefficients = _chunk_writer(True)(
            self.coefficients, w, jnp.int32(start)
        )

    def read_chunk(self, start: int, size: int) -> Array:
        self._check_bounds(start, size)
        return _read_chunk(self.coefficients, jnp.int32(start), size)

    def to_numpy(self) -> np.ndarray:
        """Full table on the host — models/summaries/tests only. At
        scale the table never belongs on the host: checkpointing hands
        ``coefficients`` to ``StreamingCheckpointManager``, which saves
        one addressable shard at a time."""
        from photon_ml_tpu.parallel.multihost import gather_to_host

        return gather_to_host(self.coefficients)


@dataclasses.dataclass
class LocalChunk:
    """A chunk supplied as PROCESS-LOCAL rows in a multi-host fleet.

    Each process passes only the entities it ingested (its
    ``process_slice`` of the chunk's global [start, start+global_size)
    range); the trainer assembles the global sharded batch with
    ``make_array_from_process_local_data`` — no host ever holds the whole
    chunk. This is the executor-local-partition analog
    (RandomEffectDataSet.scala:209-246 reads per-partition on executors).
    """

    batch: DenseBatch  # numpy leaves, leading dim = this process's rows
    global_size: int  # entities in the chunk across ALL processes


@dataclasses.dataclass
class ChunkResult:
    """Per-chunk telemetry, kept ON DEVICE until summarized."""

    start: int
    size: int
    iterations: Array  # i32[E_c]
    values: Array  # f32[E_c]
    reasons: Array  # i32[E_c] convergence reason codes


@dataclasses.dataclass
class StreamingTrainStats:
    total_entities: int
    total_coefficients: int
    num_chunks: int
    mean_iterations: float
    total_final_value: float
    # full per-entity solve telemetry (iterations/reasons/values, one
    # packed host fetch) — the RandomEffectOptimizationTracker the bucket
    # path reports, at streaming scale
    tracker: Optional["RandomEffectOptimizationTracker"] = None


class StreamingRandomEffectTrainer:
    """Drive a :class:`ShardedCoefficientTable` through streamed chunks.

    ``chunks`` yields ``(start, batch_source)`` where ``batch_source`` is
    either a DenseBatch of HOST (numpy) arrays — uploaded with
    ``device_put`` one chunk ahead of the solve — or a zero-arg callable
    returning a device DenseBatch (an on-device generator, for a caller
    whose features are computed rather than stored).
    """

    def __init__(
        self,
        loss_name: str,
        config: OptimizerConfig,
        mesh: Optional[Mesh] = None,
        axis: Optional[str] = None,
        compute_variances: bool = False,
        prefetch: bool = True,
        prefetch_depth: int = 1,
        guard: Optional[GuardSpec] = None,
        feed_retries: int = 2,
    ):
        # the vmapped per-entity solver builder is shared with
        # RandomEffectCoordinate — one lru_cache entry serves both, and
        # the SAME compiled family serves mesh and single-device calls
        # (sharded dispatch signatures are distinct registry entries)
        from photon_ml_tpu.game.coordinates import _re_solver
        from photon_ml_tpu.ops.losses import get_loss

        config.validate(loss_name)
        if compute_variances and not get_loss(loss_name).has_hessian:
            raise ValueError(
                "coefficient variances need a twice-differentiable loss; "
                f"'{loss_name}' is not"
            )
        self.loss_name = loss_name
        self.config = config
        self.mesh = mesh
        self.compute_variances = compute_variances
        # chunk feeding runs through ingest.double_buffered: a background
        # feeder thread prepares (decodes/uploads) up to ``prefetch_depth``
        # chunks ahead of the solve behind a bounded queue — host-side feed
        # work AND the H2D transfer overlap the solve. False = fully
        # synchronous, the control arm for measuring the overlap win
        # (not measured on the chip; ROADMAP S7)
        self.prefetch = prefetch
        if prefetch_depth < 1:
            raise ValueError("prefetch_depth must be >= 1")
        self.prefetch_depth = int(prefetch_depth)
        # per-chunk divergence guard (optim.guard). NOTE: the health check is
        # one scalar fetch per chunk, which serializes the chunk pipeline —
        # enable it for robustness, not for peak-throughput benches.
        self._guard = guard
        # bounded retry around host->device chunk feeding (a flaky
        # storage read should not kill a billion-coefficient run)
        if feed_retries < 0:
            raise ValueError("feed_retries must be >= 0")
        self._feed_retries = feed_retries
        # the streaming table trains DENSE per-entity models: a global box
        # constraint on local dim k applies identically to every entity
        # (the bucket path gathers the same bounds through each entity's
        # projection; here the projection is the identity)
        self._constrained = bool(config.box_constraints)
        constrained_mode = "shared" if self._constrained else False
        if mesh is None:
            self._sharding = None
            self._axis = axis
            self._n_dev = 1
        else:
            self._sharding = psharding.entity_sharding(mesh, axis)
            self._axis = self._sharding.spec[0]
            self._n_dev = psharding.axis_size(mesh, self._axis)
        key_cfg = dataclasses.replace(config, regularization_weight=0.0)
        self._solver = _re_solver(
            key_cfg, loss_name, constrained_mode, compute_variances
        )
        self._obj = make_objective(
            loss_name,
            l2_weight=config.regularization.l2_weight(
                config.regularization_weight
            ),
        )
        self._l1 = jnp.float32(
            config.regularization.l1_weight(config.regularization_weight)
        )

    def _prepare(self, source) -> DenseBatch:
        if callable(source):
            generated = source()
            if self._sharding is None:
                return generated
            # an on-device generator may have produced the chunk on the
            # default device; commit it to the entity sharding so the
            # solver program sees the mesh layout
            return jax.tree.map(
                lambda x: jax.device_put(x, self._sharding), generated
            )
        if isinstance(source, LocalChunk):
            if self._sharding is None:
                return jax.tree.map(jax.device_put, source.batch)
            gsize = int(source.global_size)

            def put_local(x):
                return jax.make_array_from_process_local_data(
                    self._sharding, np.asarray(x),
                    global_shape=(gsize,) + tuple(np.shape(x))[1:],
                )

            return jax.tree.map(put_local, source.batch)
        if isinstance(source, DenseBatch):
            leaves = jax.tree.leaves(source)
            if leaves and isinstance(leaves[0], np.ndarray):
                put = (
                    jax.device_put
                    if self._sharding is None
                    else partial(jax.device_put, device=self._sharding)
                )
                return jax.tree.map(put, source)
            return source
        raise TypeError(f"chunk source {type(source).__name__}")

    # retryable feed failures: storage I/O and runtime transfer
    # errors (jax surfaces device/transfer faults as RuntimeError
    # subclasses). Deterministic programming errors (TypeError/ValueError/
    # KeyError/shape bugs) raise immediately — re-running cannot help.
    _TRANSIENT_FEED_ERRORS = (OSError, RuntimeError, ConnectionError,
                              TimeoutError)

    def _feed(self, source) -> DenseBatch:
        """_prepare with bounded retry: transient host->device feed failures
        (generator or storage I/O) re-attempt up to ``feed_retries``
        times before surfacing; programming errors raise immediately.

        Host-supplied chunks get a pre-upload HBM headroom check: the
        chunk's leaf bytes are known before device_put, so a chunk
        predicted to exceed free HBM warns (log + counter) instead of
        OOMing the run (no-op on statless backends)."""
        if not callable(source):
            predicted = telemetry_memory.estimate_batch_bytes(source)
            if predicted:
                telemetry_memory.check_headroom(
                    predicted, label="streaming chunk upload"
                )
        last_err: Optional[Exception] = None
        for attempt in range(self._feed_retries + 1):
            if attempt:
                telemetry.counter("streaming.feed_retries").inc()
                logger.warning(
                    "chunk feed failed (%s); retry %d/%d",
                    last_err, attempt, self._feed_retries,
                )
            try:
                return self._prepare(source)
            except self._TRANSIENT_FEED_ERRORS as e:
                last_err = e
        assert last_err is not None
        raise last_err

    def _chunk_constraints(self, dim: int):
        """ONE [dim] box shared by every entity (vmap broadcasts it) — the
        [E, K] materialization the bucket path needs for per-entity
        projections would be dim*entities floats at streaming scale."""
        if not self._constrained:
            return None
        from photon_ml_tpu.optim.common import BoxConstraints

        lower, upper = self.config.dense_box_bounds(dim)
        cons = BoxConstraints(
            lower=jnp.asarray(lower), upper=jnp.asarray(upper)
        )
        if self.mesh is not None:
            cons = psharding.place_replicated(cons, self.mesh)
        return cons

    def _solve(
        self,
        table,
        start: int,
        batch: DenseBatch,
        variance_table: Optional[ShardedCoefficientTable] = None,
    ) -> ChunkResult:
        size = batch.labels.shape[0]
        if self.mesh is not None and size % self._n_dev:
            # fail with intent, not a shard-shape error deep inside jax
            raise ValueError(
                f"chunk of {size} entities must divide over the "
                f"{self._n_dev}-device mesh (pad the chunk)"
            )
        w0 = table.read_chunk(start, size)
        if self._sharding is not None:
            # chunk reads slice the sharded table; commit the slice (and
            # the warm-start layout the solver sees) to the entity axis
            w0 = jax.device_put(w0, self._sharding)
            # static comms estimate: per-entity solves are independent —
            # the masked while-loop's one-scalar convergence test is the
            # only collective, once per iteration
            record_collective(
                "streaming_chunk_solve", "psum", self._n_dev, 4,
                count=max(int(self.config.max_iterations), 1),
            )
        cons = self._chunk_constraints(table.dim)
        rolled_back = False
        with telemetry.span("streaming_chunk", start=start, size=int(size)):
            attempt = 0
            while True:
                obj = self._obj
                if attempt:
                    telemetry.counter("solves.retried").inc()
                    obj = damped_objective(
                        obj, self._guard.damping_for(attempt)
                    )
                faults.fault_point(FP_COLLECTIVE_ENTRY)
                # per-member collective-wait attribution (no-op single
                # process): the window the fleet report sums per member
                with collective_wait("streaming_chunk_solve"):
                    res, var = self._solver(obj, batch, w0, self._l1, cons)
                # injection seam: a `nan` rule here poisons the solve
                # result, driving the guard's retry/rollback path on demand
                w = faults.corrupt_array(_FP_SOLVE_RESULT, res.w)
                if self._guard is None:
                    break
                ok = bool(
                    telemetry.sync_fetch(
                        solve_health(res, w), label="streaming_guard"
                    )
                )
                if ok:
                    break
                telemetry.counter("solves.diverged").inc()
                if attempt >= self._guard.max_retries:
                    # rollback: the chunk's table rows keep their pre-solve
                    # coefficients; telemetry values are sanitized so the
                    # run summary stays finite
                    telemetry.counter("solves.rolled_back").inc()
                    logger.warning(
                        "chunk [%d, %d) still diverging after %d damped "
                        "retries; keeping previous coefficients",
                        start, start + size, self._guard.max_retries,
                    )
                    rolled_back = True
                    break
                attempt += 1
            if not rolled_back:
                table.write_chunk(start, w)
        telemetry.counter("streaming_chunks").inc()
        telemetry.counter("streaming_entities").inc(int(size))
        # heartbeat rate sources: streamed example-rows and the chunk's
        # slice of the coefficient table count as processed work
        telemetry.counter("progress.rows").inc(
            int(np.prod(batch.labels.shape))
        )
        telemetry.counter("progress.coeffs").inc(int(size) * table.dim)
        telemetry_memory.record_phase_memory("streaming_chunk")
        if var is not None and not rolled_back:
            if variance_table is None:
                raise ValueError(
                    "compute_variances=True needs a variance_table to "
                    "write into (train(..., variance_table=...))"
                )
            variance_table.write_chunk(start, var)
        values = res.value
        if rolled_back:
            values = jnp.where(jnp.isfinite(values), values, 0.0)
        return ChunkResult(
            start=start,
            size=size,
            iterations=res.iterations,
            values=values,
            reasons=res.reason,
        )

    def _after_chunk(
        self,
        chunk_index: int,
        table: ShardedCoefficientTable,
        variance_table: Optional[ShardedCoefficientTable],
        checkpointer,
        should_stop,
        final: bool,
    ) -> None:
        """Chunk-boundary bookkeeping: periodic checkpoint, and the
        graceful-preemption handshake (save-then-raise on a stop
        request — the deterministic ingest order makes ``next_chunk``
        sufficient resume state).

        Checkpoints receive the LIVE device arrays: the manager saves a
        sharded table one addressable shard at a time, so no chunk
        boundary ever assembles the full table on the host (the old
        ``local_shard()`` gather was a host-OOM time bomb at the
        ``game_10B`` 40 GB-table scale)."""
        faults.fault_point(_FP_CHUNK_BOUNDARY)
        if checkpointer is None:
            if should_stop is not None and should_stop():
                from photon_ml_tpu.game.checkpoint import TrainingInterrupted

                raise TrainingInterrupted(chunk_index, None)
            return
        from photon_ml_tpu.game.checkpoint import (
            StreamCheckpointState,
            TrainingInterrupted,
        )

        stop = should_stop is not None and should_stop()
        path = None
        if stop or (not final and checkpointer.should_save(chunk_index)):
            path = checkpointer.save(
                StreamCheckpointState(
                    next_chunk=chunk_index + 1,
                    coefficients=table.coefficients,
                    variances=(
                        None if variance_table is None
                        else variance_table.coefficients
                    ),
                )
            )
        if stop:
            raise TrainingInterrupted(chunk_index, path)

    def train(
        self,
        table: ShardedCoefficientTable,
        chunks: Iterable[tuple[int, DenseBatch | Callable[[], DenseBatch]]],
        variance_table: Optional[ShardedCoefficientTable] = None,
        with_tracker: bool = False,
        should_stop: Optional[Callable[[], bool]] = None,
        checkpointer=None,
        start_chunk: int = 0,
    ) -> StreamingTrainStats:
        """Solve every chunk into ``table``; feeding (decode + host->device
        upload) runs ``prefetch_depth`` chunks ahead of the solve in a
        background thread (``ingest.double_buffered`` — the trainer is a
        CONSUMER of the pipeline, not an ingestion implementation).

        ``variance_table``: required when ``compute_variances``; receives
        the per-coefficient Hessian-diagonal-inverse variances
        (SingleNodeOptimizationProblem.scala:57-88 at streaming scale).
        ``with_tracker``: also return the full per-entity
        RandomEffectOptimizationTracker (costs one extra packed
        device->host fetch of 3 x total_entities values).

        Fault tolerance: with a ``checkpointer``
        (:class:`~photon_ml_tpu.game.checkpoint.StreamingCheckpointManager`)
        the table is snapshotted every ``every`` chunk boundaries, and a
        ``should_stop`` request (e.g. :class:`GracefulStop` on SIGTERM)
        finishes the current chunk, saves a final checkpoint, and raises
        ``TrainingInterrupted``. Resume by restoring the table and passing
        the restored ``next_chunk`` as ``start_chunk`` — chunk ordering is
        deterministic, so the replayed stream is exactly the remainder.
        """
        if self.compute_variances and variance_table is None:
            raise ValueError(
                "compute_variances=True needs a variance_table"
            )
        if start_chunk < 0:
            raise ValueError("start_chunk must be >= 0")
        results: list[ChunkResult] = []
        chunk_iter = iter(chunks)
        if start_chunk:
            # replay: skip already-solved chunks WITHOUT feeding them
            import itertools

            chunk_iter = itertools.islice(chunk_iter, start_chunk, None)
        index = start_chunk - 1
        if self.prefetch:
            from photon_ml_tpu.ingest.prefetch import double_buffered

            for (start, _source), batch in double_buffered(
                chunk_iter,
                lambda item: self._feed(item[1]),
                depth=self.prefetch_depth,
                name="streaming_chunk",
            ):
                index += 1
                results.append(
                    self._solve(
                        table, start, batch, variance_table=variance_table
                    )
                )
                self._after_chunk(
                    index, table, variance_table, checkpointer,
                    should_stop, final=False,
                )
        else:
            # control arm: serialize transfer and compute completely — a
            # 1-element fetch waits for each chunk's solve (the accounted
            # crossing; tools/check.py L007)
            for start, source in chunk_iter:
                index += 1
                results.append(
                    self._solve(
                        table,
                        start,
                        self._feed(source),
                        variance_table=variance_table,
                    )
                )
                telemetry.sync_fetch(
                    table.coefficients[start, 0], label="streaming_sync"
                )
                self._after_chunk(
                    index, table, variance_table, checkpointer,
                    should_stop, final=False,
                )
        if checkpointer is not None and results:
            # terminal checkpoint: a crash AFTER the stream finishes must
            # not replay the tail chunks (sharded per-shard save — no
            # host gather, same as the boundary saves)
            from photon_ml_tpu.game.checkpoint import StreamCheckpointState

            checkpointer.save(
                StreamCheckpointState(
                    next_chunk=index + 1,
                    coefficients=table.coefficients,
                    variances=(
                        None if variance_table is None
                        else variance_table.coefficients
                    ),
                )
            )
        if not results:
            return StreamingTrainStats(0, 0, 0, 0.0, 0.0)
        # ONE device->host fetch for the scalar summaries
        sums = telemetry.sync_fetch(
            jnp.stack(
                [
                    jnp.sum(
                        jnp.stack(
                            [jnp.sum(r.iterations.astype(jnp.float32))
                             for r in results]
                        )
                    ),
                    jnp.sum(jnp.stack([jnp.sum(r.values) for r in results])),
                ]
            ),
            label="streaming_summary",
        )
        tracker = None
        if with_tracker:
            from photon_ml_tpu.optim.trackers import (
                RandomEffectOptimizationTracker,
            )

            tracker = RandomEffectOptimizationTracker.from_device_parts(
                [r.iterations for r in results],
                [r.reasons for r in results],
                [r.values for r in results],
            )
        total_e = sum(r.size for r in results)
        return StreamingTrainStats(
            total_entities=total_e,
            total_coefficients=total_e * table.dim,
            num_chunks=len(results),
            mean_iterations=float(sums[0]) / max(total_e, 1),
            total_final_value=float(sums[1]),
            tracker=tracker,
        )
