"""GAME coordinates: the per-block training strategies driven by coordinate
descent.

Reference analog: photon-api algorithm/{Coordinate,FixedEffectCoordinate,
RandomEffectCoordinate}.scala (SURVEY.md §2.c). A coordinate owns its data
block and knows how to (re)train its sub-model given residual scores from
the other coordinates and how to produce its scores on the training data.

TPU realization:
  - FixedEffectCoordinate: one (optionally mesh-sharded) GLM solve; the
    residuals enter as extra offsets (addScoresToOffsets analog). Under a
    mesh the FLAT design is committed with
    ``NamedSharding(mesh, P("batch"))`` and the whole optimizer while-loop
    runs in one GSPMD jit (parallel.distributed.gspmd_solve) — no host
    restacking; only the tiled layout's pallas kernels sit in a
    ``shard_map`` over the batch axis (ops.tiled.TiledBatch._run).
  - RandomEffectCoordinate: per geometry bucket, ONE vmapped optimizer call
    solves every entity's independent problem simultaneously; converged
    entities freeze in the masked while-loop. Under a mesh the bucket's
    entity axis is committed with ``entity_sharding(mesh, P("model"))``
    (parallel.sharding) and GSPMD partitions the vmap lanes — no
    cross-device communication during the solve beyond the one-scalar
    convergence test (SURVEY.md §2.f "per-entity model parallelism").
"""

from __future__ import annotations

import dataclasses
import logging
import weakref
from functools import lru_cache
from typing import Optional, Protocol

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from photon_ml_tpu.game.dataset import GameDataset
from photon_ml_tpu.game.models import (
    FixedEffectModel,
    RandomEffectBucketModel,
    RandomEffectModel,
)
from photon_ml_tpu.data.normalization import NormalizationContext
from photon_ml_tpu.game.random_effect_data import RandomEffectDataset
from photon_ml_tpu.ops.objective import make_objective
from photon_ml_tpu.ops.sparse import SparseBatch
from photon_ml_tpu.ops.panels import (
    PanelBatch,
    pack_design,
    pack_rows_like,
    report_layout,
)
from photon_ml_tpu.ops.tiled import ROWS_PER_TILE
from photon_ml_tpu.optim.adapter import glm_adapter
from photon_ml_tpu.optim.common import BoxConstraints
from photon_ml_tpu.optim.factory import (
    OptimizerConfig,
    OptimizerType,
    dispatch_solve,
)
from photon_ml_tpu.optim.guard import damped_objective, solve_health
from photon_ml_tpu.optim.spd_solve import takes_hand_solve
from photon_ml_tpu.parallel.distributed import gspmd_solve
from photon_ml_tpu.parallel import sharding as psharding
from photon_ml_tpu.telemetry.device import accounted_upload
from photon_ml_tpu.telemetry.metrics import counter, gauge
from photon_ml_tpu.telemetry.trace import span
from photon_ml_tpu.telemetry.xla import instrumented_jit, record_collective

Array = jax.Array

logger = logging.getLogger("photon_ml_tpu.game")


class Coordinate(Protocol):
    name: str

    def initialize_model(self): ...

    def update_model(self, model, residual_scores: Array): ...

    def score(self, model) -> Array: ...


# ---------------------------------------------------------------------------
# Fixed effect
# ---------------------------------------------------------------------------

@lru_cache(maxsize=64)
def _fe_solver(config: OptimizerConfig, loss_name: str):
    def run(obj, batch, w0, l1, constraints):
        return dispatch_solve(
            glm_adapter(obj, batch), w0, config, l1, constraints=constraints
        )

    # multi_shape: one lru-shared solver serves every FE coordinate
    # (and dataset) with this config — distinct feature/row shapes are by
    # design, not a storm
    return instrumented_jit(run, name="fe_solve", multi_shape=True)


def _record_placement(label: str, array: Array) -> None:
    """Publish how many devices hold ``array`` (gauge
    ``placement.<label>.devices``): a mesh run's own evidence that a
    design or a coefficient table was spread and does not sit on one."""
    gauge(f"placement.{label}.devices").set(len(array.sharding.device_set))


@lru_cache(maxsize=1)
def _tiled_scorer():
    def score(batch, w):
        return batch.dot_rows(_to_design(batch, w.astype(jnp.float32)))

    return instrumented_jit(score, name="fe_score_tiled", multi_shape=True)


def _fit_rows(z: Array, n_pad: int) -> Array:
    """A design's per-row vector (its rows padded to whole tiles) cut or
    padded to the dataset's own padded row count."""
    if z.shape[0] >= n_pad:
        return z[:n_pad]
    return jnp.pad(z, (0, n_pad - z.shape[0]))


def _to_design(design, vec):
    """A feature-space vector in the order the design's kernels index it: a
    :class:`PanelBatch` numbers its columns by frequency rank, every other
    layout by feature. ``None`` stays None."""
    if vec is None or not isinstance(design, PanelBatch):
        return vec
    return jnp.asarray(vec)[design.order]


@lru_cache(maxsize=1)
def _design_permuter():
    """(to rank order, back to feature order) as named executables, for the
    coefficients that cross a PanelBatch coordinate's edge every update."""
    return (
        instrumented_jit(
            lambda w, order: w[order], name="fe_to_ranks", multi_shape=True),
        instrumented_jit(
            lambda w, rank: w[rank], name="fe_from_ranks", multi_shape=True),
    )


@dataclasses.dataclass
class FixedEffectCoordinate:
    """Global GLM block (the DP strategy; FixedEffectCoordinate.scala:33-167).

    Residual scores arrive as additional offsets; the solve warm-starts from
    the current sub-model. Down-sampling (BinaryClassificationDownSampler
    analog) re-weights kept negatives by 1/rate.
    """

    name: str
    data: GameDataset
    shard_name: str
    loss_name: str
    config: OptimizerConfig
    seed: int = 0
    normalization: Optional[NormalizationContext] = None
    mesh: Optional[Mesh] = None  # mesh with a batch/data axis -> gspmd_solve
    layout: str = "auto"  # "auto" | "tiled" | "coo" training layout

    def __post_init__(self):
        self.config.validate(self.loss_name)
        with span("build.rows"):  # labels, offsets, weights beside the shard
            self._base_batch = self.data.batch_for(self.shard_name)
        # "auto": the tiled one-hot-matmul layouts are the TPU fast path
        # (ops/tiled.py, and ops/panels.py for wide designs: PERF.md has
        # both against COO's gather / scatter-add on the chip); elsewhere
        # pallas falls back to interpret mode, so COO is faster
        if self.layout not in ("auto", "tiled", "coo"):
            raise ValueError(f"unknown layout '{self.layout}'")
        self._use_tiled = self.layout == "tiled" or (
            self.layout == "auto" and jax.default_backend() == "tpu"
        )
        # the design is laid out on the host (span `layout`), then placed
        # (span `upload`): the two never overlap, and the host copy dies
        # with this method
        host_tiled = None
        if self.mesh is not None and psharding.data_axis(self.mesh) is None:
            # an entity-only mesh has no row axis to data-parallel over;
            # the FE block runs single-device (its RE siblings still shard)
            self.mesh = None
        if self._use_tiled:
            with span("layout"):
                # plain tiles or column panels: the design's own width and
                # column histogram decide (ops/panels.py::pack_design)
                host_tiled = pack_design(
                    self._base_batch,
                    shards=1 if self.mesh is None else psharding.axis_size(
                        self.mesh, psharding.data_axis(self.mesh)))
        # fresh sample per update_model (runWithSampling parity: the reference
        # re-samples on every coordinate update, DistributedOptimizationProblem
        # .scala:113-125); counter salts the rng so updates differ
        self._update_count = 0
        # guarded-solve hooks (optim.guard): extra L2 added to the next
        # update's objective (traced leaf -> no recompile), and the device
        # health scalar of the last solve — computed only when the guard
        # flips health_check on (unguarded fits skip the extra reduces)
        self.extra_l2 = 0.0
        self.health_check = False
        self.last_health = None
        key_cfg = dataclasses.replace(self.config, regularization_weight=0.0)
        self._solver = _fe_solver(key_cfg, self.loss_name)
        self._constraints = self.config.build_box_constraints(
            self._base_batch.num_features
        )
        norm = self.normalization
        if self._constraints is not None and norm is not None:
            # bounds are declared in ORIGINAL space; the solve runs in
            # normalized space where w_original = w' * factor, so enforce
            # w' in [lo/factor, hi/factor]. With shifts, the intercept's
            # original value additionally absorbs -w.shift at
            # back-transform time, so an intercept bound cannot be
            # enforced inside the solve — reject it.
            f = norm.factors
            if f is not None:
                self._constraints = type(self._constraints)(
                    lower=self._constraints.lower / f,
                    upper=self._constraints.upper / f,
                )
            if norm.shifts is not None and norm.intercept_index is not None:
                ii = norm.intercept_index
                if np.isfinite(
                    float(self._constraints.lower[ii])
                ) or np.isfinite(float(self._constraints.upper[ii])):
                    raise ValueError(
                        "a box constraint on the intercept cannot be "
                        "enforced under shift normalization (the intercept "
                        "absorbs -w.shift at back-transform)"
                    )
        # a PanelBatch solves in frequency-rank order: bounds and
        # normalization are renumbered here, coefficients in update_model
        # and score, so nothing outside this class sees a rank
        self._factors = _to_design(
            host_tiled, None if norm is None else norm.factors)
        self._shifts = _to_design(
            host_tiled, None if norm is None else norm.shifts)
        if self._constraints is not None:
            self._constraints = type(self._constraints)(
                lower=_to_design(host_tiled, self._constraints.lower),
                upper=_to_design(host_tiled, self._constraints.upper),
            )
        with span("build.objective"):  # its eager one-op programs
            self._obj = make_objective(
                self.loss_name,
                l2_weight=self.config.regularization.l2_weight(
                    self.config.regularization_weight
                ),
                factors=self._factors,
                shifts=self._shifts,
            )
            self._l1 = jnp.float32(self.config.regularization.l1_weight(
                self.config.regularization_weight))
        if self.mesh is not None:
            # GSPMD path: the FLAT design (tiles or COO slots) is committed
            # with NamedSharding(mesh, P(batch)) ONCE; per-update offsets
            # and weights are re-placed with the same row sharding
            # (_place_rows) so residual updates and fresh down-samples
            # never rebuild the nnz arrays
            self._axis = psharding.data_axis(self.mesh)
            self._n_shards = psharding.axis_size(self.mesh, self._axis)
            self._row_sharding = psharding.batch_sharding(self.mesh, self._axis)
            self._solve_batch = accounted_upload(
                lambda: psharding.place_batch(
                    host_tiled if self._use_tiled else self._base_batch,
                    self.mesh,
                    self._axis,
                )
            )
            if self._use_tiled:
                # ONE resident design, placed from the host straight onto
                # its shards; scoring goes through the sharded tiles too
                self._tiled = self._solve_batch
        elif self._use_tiled:
            self._tiled = accounted_upload(host_tiled.device)
        else:
            # single-device COO solve path: upload the design ONCE; per-row
            # updates (offsets/weights) are swapped onto this device copy
            self._solve_batch = accounted_upload(self._base_batch.device)
        design = self._tiled if self._use_tiled else self._solve_batch
        _record_placement(f"{self.name}.design", jax.tree.leaves(design)[0])
        # set where coefficients cross this class's edge in rank order
        self._panels = design if isinstance(design, PanelBatch) else None
        # (weak reference to the dataset, its rows in this design's layout
        # or None) of the last foreign dataset scored: score_dataset
        self._foreign = None

    def _downsampled_weights(self, batch, update_index: int):
        rate = self.config.down_sampling_rate
        if rate >= 1.0:
            return batch.weights
        rng = np.random.default_rng((self.seed, update_index))
        labels = np.asarray(batch.labels)
        weights = np.asarray(batch.weights).copy()
        if "logistic" in self.loss_name or "hinge" in self.loss_name:
            # keep all positives, sample negatives at rate, reweight by 1/rate
            neg = (labels <= 0.5) & (weights > 0)
            drop = neg & (rng.random(len(labels)) >= rate)
            weights[drop] = 0.0
            weights[neg & ~drop] /= rate
        else:
            keep = rng.random(len(labels)) < rate
            weights[~keep] = 0.0
            weights[keep] /= rate
        return jnp.asarray(weights, batch.dtype)

    def _maybe_downsample(self, batch, update_index: int):
        if self.config.down_sampling_rate >= 1.0:
            return batch
        return dataclasses.replace(
            batch, weights=self._downsampled_weights(batch, update_index)
        )

    def _place_rows(self, per_row: Array) -> Array:
        """Pad a global [n_pad] per-row array to the sharded solve batch's
        row count (tiled: into its [T, 1, 128] grid) and commit it with the
        batch-axis sharding, matching the resident design's placement."""
        a = jnp.asarray(per_row, jnp.float32)
        if self._use_tiled:
            tiles = self._solve_batch.num_tiles
            a = jnp.pad(a, (0, tiles * ROWS_PER_TILE - a.shape[0]))
            a = a.reshape(tiles, 1, ROWS_PER_TILE)
        else:
            a = jnp.pad(a, (0, self._solve_batch.num_rows - a.shape[0]))
        return jax.device_put(a, self._row_sharding)

    def _tiled_rows(self, per_row: Array) -> Array:
        """Pad a global [n_pad] per-row array to the tiled row count."""
        a = jnp.asarray(per_row, jnp.float32)
        return jnp.pad(a, (0, self._tiled.num_rows - a.shape[0]))

    def _with_rows(self, batch, field: str, per_row: Array):
        """``batch`` with its per-row ``offsets`` or ``weights`` replaced
        (the tiled layouts keep them as a [T, 1, 128] grid)."""
        if self._use_tiled:
            return getattr(batch, "with_" + field)(per_row)
        return dataclasses.replace(batch, **{field: per_row})

    def initialize_model(self) -> FixedEffectModel:
        d = self._base_batch.num_features
        return FixedEffectModel(
            coefficients=jnp.zeros((d,), self._base_batch.dtype),
            shard_name=self.shard_name,
        )

    def update_model(
        self, model: FixedEffectModel, residual_scores: Optional[Array]
    ) -> FixedEffectModel:
        w0 = model.coefficients
        norm = self.normalization
        if norm is not None:
            # models live in ORIGINAL space; the solve runs in normalized
            # space (createModel analog, GeneralizedLinearOptimizationProblem)
            w0 = norm.inverse_transform_model_coefficients(w0)
        update_index = self._update_count
        self._update_count += 1
        # damped retry (optim.guard): l2_weight is a traced leaf, so the
        # compiled solver is reused unchanged
        obj = damped_objective(self._obj, self.extra_l2)
        if self._panels is not None:
            w0 = _design_permuter()[0](w0, self._panels.order)
        if self.mesh is not None:
            # DP path (FixedEffectCoordinate.scala:136-147): rows committed
            # P(batch), whole while-loop in ONE GSPMD jit, grads psum'd by
            # the compiler. Only changed per-row arrays are re-placed.
            batch = self._solve_batch
            if residual_scores is not None:
                batch = self._with_rows(batch, "offsets", self._place_rows(
                    self._base_batch.offsets + residual_scores))
            if self.config.down_sampling_rate < 1.0:
                batch = self._with_rows(batch, "weights", self._place_rows(
                    self._downsampled_weights(self._base_batch, update_index)))
            res = gspmd_solve(
                self.loss_name,
                batch,
                self.config,
                w0,
                self.mesh,
                axis=self._axis,
                constraints=self._constraints,
                factors=self._factors,
                shifts=self._shifts,
                extra_l2=self.extra_l2,
            )
        elif self._use_tiled:
            batch = self._tiled
            if self.config.down_sampling_rate < 1.0:
                batch = batch.with_weights(self._tiled_rows(
                    self._downsampled_weights(self._base_batch, update_index)))
            if residual_scores is not None:
                batch = batch.with_offsets(self._tiled_rows(
                    self._base_batch.offsets + residual_scores))
            res = self._solver(obj, batch, w0, self._l1, self._constraints)
        else:
            batch = self._solve_batch
            if self.config.down_sampling_rate < 1.0:
                # weights are drawn from the HOST base batch (transfer-free
                # reads); only the fresh [n] weight vector is uploaded
                batch = dataclasses.replace(
                    batch,
                    weights=self._downsampled_weights(
                        self._base_batch, update_index
                    ),
                )
            if residual_scores is not None:
                batch = batch.with_offsets(
                    self._base_batch.offsets + residual_scores
                )
            res = self._solver(obj, batch, w0, self._l1, self._constraints)
        w = res.w
        if self._panels is not None:
            w = _design_permuter()[1](w, self._panels.rank)
        from photon_ml_tpu.optim.trackers import FixedEffectOptimizationTracker

        self.last_tracker = FixedEffectOptimizationTracker.from_result(res)
        if norm is not None:
            w = norm.transform_model_coefficients(w)
        self.last_health = solve_health(res, w) if self.health_check else None
        return dataclasses.replace(model, coefficients=w)

    def score(self, model: FixedEffectModel) -> Array:
        if self._use_tiled:
            # the solve layout already holds the design in HBM — score
            # through it instead of uploading a second (COO) copy
            z = _tiled_scorer()(self._tiled, model.coefficients)
            return _fit_rows(z, self.data.shard(self.shard_name).num_rows)
        return model.score(self.data)

    def score_dataset(self, model: FixedEffectModel, data: GameDataset) -> Array:
        """``model.score(data)`` for rows that are not this coordinate's own
        (validation). Where the training design is tiled, ``data``'s shard
        is laid out the same way the first time it is scored, stays on the
        device for as long as ``data`` is the dataset asked for, and is
        scored by the kernels that score the training rows; XLA's gather
        and segment-sum over the COO batch took 27 and 38 times as long on
        a TPU at the benchmark's sizes (PERF.md, Findings PR 27). A COO
        coordinate scores COO."""
        if data is self.data:
            return self.score(model)
        if not self._use_tiled or model.shard_name != self.shard_name:
            return model.score(data)
        design = self._foreign_design(data)
        if design is None:
            counter("validate.coo_scores").inc()
            return model.score(data)
        z = _tiled_scorer()(design, model.coefficients)
        return _fit_rows(z, data.shard(self.shard_name).num_rows)

    def _foreign_design(self, data: GameDataset):
        """``data``'s shard in the training design's layout, built on first
        use (spans ``validation_layout`` / ``validation_upload``, counters
        ``validate.layout.*``: the training design's ``layout`` / ``upload``
        / ``layout.*`` stay its own); None where only COO can score it."""
        if self._foreign is not None and self._foreign[0]() is data:
            if self._foreign[1] is not None:
                counter("validate.design_hits").inc()
            return self._foreign[1]
        self._foreign = None  # the last dataset's design goes first
        batch = data.shard(self.shard_name)
        design = None
        if batch.num_features != self._tiled.num_features:
            logger.warning(
                "coordinate %s: shard '%s' of the dataset to score has %d "
                "features, the training design %d; scoring it as COO",
                self.name, self.shard_name, batch.num_features,
                self._tiled.num_features,
            )
        else:
            with span("validation_layout"):
                shards = 1 if self.mesh is None else self._n_shards
                host = pack_rows_like(
                    self._tiled, batch, shards=shards).traced_as("validate")
                report_layout(host, "validate.layout", shards=shards)
            design = accounted_upload(
                host.device if self.mesh is None
                else lambda: psharding.place_batch(
                    host, self.mesh, self._axis),
                name="validation_upload",
            )
            counter("validate.design_builds").inc()
        self._foreign = (weakref.ref(data), design)
        return design


# ---------------------------------------------------------------------------
# Random effect
# ---------------------------------------------------------------------------

# DistributedOptimizationProblem.computeVariances adds this to the Hessian
# diagonal before inverting (MathConst.HIGH_PRECISION_TOLERANCE_THRESHOLD)
_VARIANCE_EPS = 1e-12


def _make_solve_one(config: OptimizerConfig, compute_variances: bool):
    """One entity's solve (+optional Hessian-diagonal-inverse variances, the
    computeVariances path of SingleNodeOptimizationProblem.scala:57-88).
    Returns ``(SolveResult, variances-or-None)``."""

    def solve_one(obj, batch, w0, l1, constraints):
        res = dispatch_solve(
            glm_adapter(obj, batch), w0, config, l1, constraints=constraints
        )
        if not compute_variances:
            return res, None
        var = 1.0 / (obj.hessian_diagonal(res.w, batch) + _VARIANCE_EPS)
        return res, var

    return solve_one


def _adapt_solve_one(config, compute_variances: bool, packed: bool,
                     kmajor: bool = False):
    """Per-entity solve body; ``packed`` reassembles a DenseBatch from the
    flat packed design inside jit (_packed_dense_batch): row-major [R*K],
    or with ``kmajor`` feature-major [K*R] (what the factored coordinate's
    projection pass writes)."""
    base_one = _make_solve_one(config, compute_variances)
    if not packed:
        return base_one

    def solve_one(obj, batch, w0, l1, constraints):
        return base_one(
            obj, _packed_dense_batch(batch, w0, kmajor), w0, l1, constraints)

    return solve_one


@lru_cache(maxsize=64)
def _re_solver(
    config: OptimizerConfig,
    loss_name: str,
    constrained: bool | str = False,
    compute_variances: bool = False,
    packed: bool = False,
    kmajor: bool = False,
):
    solve_one = _adapt_solve_one(config, compute_variances, packed, kmajor)
    # obj, l1 broadcast; batch leaves, w0 (and per-entity constraint boxes,
    # when present) map over the entity axis. constrained="shared" keeps one
    # [K] box broadcast to every entity (the streaming table's dense local
    # space) instead of materializing [E, K] bounds.
    c_axis = 0 if constrained is True else None
    # multi_shape: each geometry bucket (entity count, rows, K) is its
    # own signature by construction
    return instrumented_jit(
        jax.vmap(solve_one, in_axes=(None, 0, 0, None, c_axis)),
        name="re_solve_dense" if packed else "re_solve",
        multi_shape=True,
    )


def place_entity_solve(
    mesh: Mesh,
    axis: Optional[str],
    batch,
    w0: Array,
    constraints: Optional[BoxConstraints] = None,
    shared_constraints: bool = False,
):
    """Commit one bucket/chunk solve's inputs for GSPMD entity sharding:
    batch leaves and w0 get ``entity_sharding(mesh, axis)`` on their
    leading [E] dim (already padded to the axis size), constraint boxes
    get the same placement when per-entity ([E, K]) or replication when
    shared ([K], the streaming dense space). The plain vmapped ``_re_solver``
    then runs under one jit with the lanes partitioned by the compiler —
    the EP-like strategy of SURVEY.md §2.f / RandomEffectCoordinate
    .scala:101-130, with no hand-rolled shard_map."""
    eshard = psharding.entity_sharding(mesh, axis)
    batch = jax.tree.map(lambda x: jax.device_put(x, eshard), batch)
    w0 = jax.device_put(w0, eshard)
    if constraints is not None:
        put = (
            psharding.place_replicated(constraints, mesh)
            if shared_constraints
            else jax.tree.map(lambda x: jax.device_put(x, eshard), constraints)
        )
        constraints = put
    return batch, w0, constraints


def record_entity_solve_comms(label: str, mesh: Mesh, axis: str,
                              iterations: int) -> None:
    """Static comms estimate for one entity-sharded vmapped solve: the
    per-entity problems are independent — the only cross-device traffic
    the masked while-loop needs is its one-scalar convergence test
    (all-reduce of the active mask) per iteration."""
    record_collective(
        label, "psum", int(mesh.shape[axis]), 4,
        count=max(int(iterations), 1),
    )


def _pad_entities(batch: SparseBatch, w0: Array, total: int):
    """Pad the leading entity axis to ``total`` with all-zero problems
    (weight 0 everywhere -> the padded solves converge immediately)."""
    n = w0.shape[0]
    if total == n:
        return batch, w0

    def padf(x):
        pad = jnp.zeros((total - n,) + x.shape[1:], x.dtype)
        return jnp.concatenate([x, pad], axis=0)

    return jax.tree.map(padf, batch), padf(w0)


def _pad_constraints(cons: Optional[BoxConstraints], total: int):
    """Pad per-entity constraint boxes to ``total`` entities with unbounded
    rows (padded problems are all-zero; their iterates must stay free)."""
    if cons is None or cons.lower.shape[0] == total:
        return cons

    def padv(x, fill):
        n = x.shape[0]
        pad = jnp.full((total - n,) + x.shape[1:], fill, x.dtype)
        return jnp.concatenate([x, pad], axis=0)

    return BoxConstraints(
        lower=padv(cons.lower, -jnp.inf), upper=padv(cons.upper, jnp.inf)
    )


def _add_bucket_scores(scores: Array, row_index: Array, margins: Array):
    """``scores`` [n] plus a bucket's per-entity margins [E, R], each at its
    example row (``row_index`` [E, R], -1 on padding)."""
    idx = row_index.reshape(-1)
    return scores.at[jnp.maximum(idx, 0)].add(
        jnp.where(idx >= 0, margins.reshape(-1), 0.0)
    )


@lru_cache(maxsize=1)
def _re_scorer():
    def score_bucket(coeffs, bucket_batch, row_index, scores):
        # per-entity margins x.w (no offsets) [E, R], added at their rows
        margins = jax.vmap(lambda w, b: b.dot_rows(w))(coeffs, bucket_batch)
        return _add_bucket_scores(scores, row_index, margins)

    return instrumented_jit(score_bucket, name="re_score", multi_shape=True)


@lru_cache(maxsize=1)
def _re_dense_scorer():
    def score(coeffs, x_flat, row_index, scores):
        E, K = coeffs.shape
        x = x_flat.reshape(E, -1, K)
        margins = jnp.einsum(
            "erk,ek->er", x, coeffs, precision=jax.lax.Precision.HIGHEST
        )
        return _add_bucket_scores(scores, row_index, margins)

    return instrumented_jit(score, name="re_score_dense", multi_shape=True)


@lru_cache(maxsize=1)
def _re_offsets():
    """A bucket's offsets [E, R] plus the other coordinates' scores at its
    rows (``EntityBucket.with_extra_offsets``, as one named program: eagerly
    it was four one-op programs a bucket and update)."""

    def offsets(bucket, per_row):
        return bucket.with_extra_offsets(per_row).offsets

    return instrumented_jit(offsets, name="re_offsets", multi_shape=True)


def _packed_dense_batch(packed, w0, kmajor: bool = False):
    """Reassemble a DenseBatch from the PACKED per-entity design INSIDE
    jit: the design is stored flat [R*K] per entity (TPU pads a resident
    [E, R, K] array's K lanes to 128 — 128/K-fold HBM bloat; the flat
    layout is padding-free and the in-jit reshape is a transient). With
    ``kmajor`` the flat design is [K*R], a feature's rows together: the
    transpose folds into the products' dimension numbers."""
    from photon_ml_tpu.ops.dense import DenseBatch

    x_flat, labels, offsets, weights = packed
    k = w0.shape[0]
    return DenseBatch(
        x=x_flat.reshape(k, -1).T if kmajor else x_flat.reshape(-1, k),
        labels=labels,
        offsets=offsets,
        weights=weights,
    )


# Route a bucket's per-entity solves through the DENSE local-design layout
# ([E, R, K] batched matmuls on the MXU — the layout the 1B streaming path
# uses) when the densified design is at most this factor of the padded-COO
# footprint; the COO gather/scatter path stays for high-dim sparse locals.
_DENSE_BYTES_FACTOR = 3.0


def _bucket_dense_design(b: EntityBucket) -> Optional[np.ndarray]:
    """Host-side densified design for a bucket as PACKED [E, R*K] rows
    (row-major per entity), or None when the COO layout is the better
    trade (K large / very sparse locals). Packed because a resident
    [E, R, K] device array pads its K lanes to 128 (128/K-fold HBM
    bloat); solvers reshape inside jit (_packed_dense_batch)."""
    E, R, K = b.num_entities, b.rows_per_entity, b.num_local_features
    nz = b.values.shape[1]
    dense_bytes = E * R * K * 4
    coo_bytes = E * nz * 12
    if dense_bytes > max(64 << 20, _DENSE_BYTES_FACTOR * coo_bytes):
        return None
    vals = np.asarray(b.values)
    e, slot = np.nonzero(vals)  # padded nnz carry value 0: nothing to add
    flat = (
        e * (R * K)
        + np.asarray(b.rows)[e, slot].astype(np.int64) * K
        + np.asarray(b.cols)[e, slot]
    )
    # scatter-ADD (a row may hold a column twice) straight into float32:
    # numpy's indexed add is several times a float64 bincount over every
    # cell + its cast, which was a third of a coordinate's layout seconds
    x = np.zeros(E * R * K, np.float32)
    np.add.at(x, flat, vals[e, slot].astype(np.float32))
    return x.reshape(E, R * K)


@dataclasses.dataclass
class RandomEffectCoordinate:
    """Per-entity GLM blocks (RandomEffectCoordinate.scala:37-208).

    Each bucket's entities are solved by one vmapped jit-compiled optimizer
    run — the analog of Spark's mapValues-with-local-solver, with identical
    per-entity optimization configs (RandomEffectOptimizationProblem
    semantics). Passive rows are scored through the model's searchsorted
    path.
    """

    name: str
    data: GameDataset
    re_data: RandomEffectDataset
    loss_name: str
    config: OptimizerConfig
    mesh: Optional[Mesh] = None  # mesh with a model/entity axis -> GSPMD
    # entity-sharded bucket solves (place_entity_solve)
    compute_variances: bool = False  # per-coefficient Hessian-diag inverse

    def __post_init__(self):
        from photon_ml_tpu.ops.losses import get_loss

        self.config.validate(self.loss_name)
        if self.compute_variances and not get_loss(self.loss_name).has_hessian:
            raise ValueError(
                "coefficient variances need a twice-differentiable loss; "
                f"'{self.loss_name}' is not"
            )
        # dense [E, R, K] designs for small-K buckets: batched-matmul MXU
        # solves (the streaming-path layout) instead of vmapped COO
        # gather/scatter — measured ~10x on the GLMix RE coordinate; the
        # device bucket copies skip the COO arrays where dense is active
        self._dense_x = self.re_data.dense_designs()
        self._buckets = self.re_data.device_buckets_for_dense()
        # per bucket and entity, its own rows x its own local features: the
        # design cells one pass of its solve has to read (_report_stragglers)
        with span("build.layout_report"):  # host passes over the buckets
            self._entity_cells = [
                np.count_nonzero(hb.row_index >= 0, axis=1)
                * np.count_nonzero(
                    hb.projection < hb.num_global_features, axis=1)
                for hb in self.re_data.buckets
            ]
            self._report_layout()
        # Box constraints are declared against GLOBAL feature ids
        # (OptimizerConfig constraintMap); each entity's local space is an
        # index-map renumbering (local k <-> global projection[e, k]), so the
        # global boxes gather straight through the projection into per-entity
        # [E, K] bounds — the reference threads the same map into every
        # per-entity problem (SingleNodeOptimizationProblem.scala:124-139).
        self._bucket_constraints: list = [None] * len(self.re_data.buckets)
        constrained = bool(self.config.box_constraints)
        if constrained:
            lower_g, upper_g = self.config.dense_box_bounds(
                self.re_data.num_global_features, sentinel=True
            )
            for i, b in enumerate(self.re_data.buckets):
                proj = np.asarray(b.projection)
                self._bucket_constraints[i] = BoxConstraints(
                    lower=jnp.asarray(lower_g[proj]),
                    upper=jnp.asarray(upper_g[proj]),
                )
        key_cfg = dataclasses.replace(self.config, regularization_weight=0.0)
        if self.mesh is not None:
            # GSPMD entity sharding: the same vmapped solvers serve the
            # mesh path, with inputs committed P(model) per bucket
            self._axis = psharding.model_axis(self.mesh)
            if self._axis is None:
                self.mesh = None  # batch-only mesh: no entity axis to use
        self._solver = _re_solver(
            key_cfg, self.loss_name, constrained, self.compute_variances
        )
        self._dense_solver = _re_solver(
            key_cfg, self.loss_name, constrained, self.compute_variances,
            packed=True,
        )
        self._scorer = _re_scorer()
        with span("build.objective"):  # its eager one-op programs
            self._obj = make_objective(
                self.loss_name,
                l2_weight=self.config.regularization.l2_weight(
                    self.config.regularization_weight
                ),
            )
            self._l1 = jnp.float32(self.config.regularization.l1_weight(
                self.config.regularization_weight))
        # guarded-solve hooks (optim.guard); health reduces only when the
        # guard flips health_check on
        self.extra_l2 = 0.0
        self.health_check = False
        self.last_health = None

    def _report_layout(self) -> None:
        """Counters ``re.<name>.*`` (and their sums over the coordinates,
        ``re.*``): what the geometry buckets hold and what their rounding
        costs. ``rows`` / ``nnz`` are the entities' own, ``rows_padded`` the
        [E, R] slots, ``nnz_padded`` the design values as stored (E*R*K of
        a dense-routed bucket, E*NZ of a COO one)."""
        totals = dict.fromkeys(
            ("entities", "rows", "rows_padded", "nnz", "nnz_padded",
             "buckets"), 0)
        for hb, x in zip(self.re_data.buckets, self._dense_x):
            E, R = hb.num_entities, hb.rows_per_entity
            totals["entities"] += E
            totals["rows"] += int(np.count_nonzero(hb.row_index >= 0))
            totals["rows_padded"] += E * R
            totals["nnz"] += int(np.count_nonzero(hb.values))
            totals["nnz_padded"] += (
                E * hb.values.shape[1] if x is None
                else E * R * hb.num_local_features)
            totals["buckets"] += 1
        for key, value in totals.items():
            counter(f"re.{self.name}.{key}").inc(value)
            counter(f"re.{key}").inc(value)

    def initialize_model(self) -> RandomEffectModel:
        # dtype from the HOST buckets: dense-routed device buckets carry
        # f32 placeholder stubs in `values`, not the dataset's dtype
        buckets = tuple(
            RandomEffectBucketModel(
                coefficients=jnp.zeros(
                    (b.num_entities, b.num_local_features), hb.values.dtype
                ),
                projection=b.projection,
                entity_codes=b.entity_codes,
            )
            for b, hb in zip(self._buckets, self.re_data.buckets)
        )
        return RandomEffectModel(
            id_name=self.re_data.id_name,
            shard_name=self.re_data.shard_name,
            buckets=buckets,
            entity_bucket=self.re_data.entity_bucket,
            entity_pos=self.re_data.entity_pos,
            vocab=self.data.id_columns[self.re_data.id_name].vocab,
        )

    def _solve_bucket(self, i: int, b, w0: Array, obj, residual_scores):
        """Dispatch bucket ``i``'s vmapped solve from ``w0``. Returns
        ``(SolveResult, coefficients [E, K], variances or None)``."""
        bucket = b
        if residual_scores is not None:
            bucket = dataclasses.replace(
                b, offsets=_re_offsets()(b, residual_scores)
            )
        dense = self._dense_x[i] is not None
        if dense:
            # packed flat design + per-row arrays; reshaped to
            # [E, R, K] INSIDE the solver jit (_packed_dense_batch)
            bb = (
                self._dense_x[i],
                bucket.labels,
                bucket.offsets,
                bucket.weights,
            )
        else:
            bb = bucket.entity_batch()
        cons = self._bucket_constraints[i]
        solver = self._dense_solver if dense else self._solver
        if self.mesh is None:
            res, var = solver(obj, bb, w0, self._l1, cons)
            return res, res.w, var
        n_dev = psharding.axis_size(self.mesh, self._axis)
        num_e = w0.shape[0]
        total = -(-num_e // n_dev) * n_dev
        bb_p, w0_p = _pad_entities(bb, w0, total)
        cons_p = _pad_constraints(cons, total)
        bb_p, w0_p, cons_p = place_entity_solve(
            self.mesh, self._axis, bb_p, w0_p, cons_p
        )
        record_entity_solve_comms(
            "re_solve", self.mesh, self._axis, self.config.max_iterations
        )
        res, var = solver(obj, bb_p, w0_p, self._l1, cons_p)
        return res, res.w[:num_e], None if var is None else var[:num_e]

    def _report_stragglers(self, iterations: np.ndarray) -> None:
        """Counters ``re.<name>.lane_iterations`` (what every entity's own
        solve needed, summed) and ``.lane_iterations_run`` (a vmapped
        ``while_loop`` runs every lane of a bucket to its slowest entity:
        entities x the bucket's longest solve, summed), with their sums
        over the coordinates ``re.lane_iterations*``; ``.pass_cells`` is
        each entity's iterations x its own rows x its own local features,
        the design cells its passes had to read. From the tracker's
        per-entity iterations, which lie bucket after bucket."""
        needed = run = cells = lo = 0
        for own in self._entity_cells:
            its = iterations[lo:lo + len(own)]
            lo += len(own)
            if len(its):
                needed += int(its.sum())
                run += int(its.max()) * len(its)
                cells += int(np.dot(its.astype(np.int64), own))
        for scope in (f"re.{self.name}", "re"):
            counter(f"{scope}.lane_iterations").inc(needed)
            counter(f"{scope}.lane_iterations_run").inc(run)
            counter(f"{scope}.pass_cells").inc(cells)

    def _report_solve_route(self, b) -> None:
        """Counters ``re.<name>.hand_solve_lanes`` / ``.xla_solve_lanes``
        (and their sums over the coordinates, ``re.*``): the entities a
        Newton update dispatched through the hand SPD solve over entity
        lanes and through XLA's batched factorisation
        (``optim/spd_solve.py::takes_hand_solve``: by the bucket's K)."""
        if self.config.optimizer_type != OptimizerType.NEWTON:
            return
        hand = takes_hand_solve(b.num_local_features)
        for scope in (f"re.{self.name}", "re"):
            counter(f"{scope}.hand_solve_lanes").inc(
                b.num_entities if hand else 0)
            counter(f"{scope}.xla_solve_lanes").inc(
                0 if hand else b.num_entities)

    def update_model(
        self, model: RandomEffectModel, residual_scores: Optional[Array]
    ) -> RandomEffectModel:
        from photon_ml_tpu.optim.trackers import RandomEffectOptimizationTracker

        new_buckets = []
        tracker_its = []
        tracker_reasons = []
        tracker_vals = []
        healths = []
        obj = damped_objective(self._obj, self.extra_l2)
        for i, (b, bm) in enumerate(zip(self._buckets, model.buckets)):
            # the class's dispatch (the solve runs on after it returns): in
            # a trace, which bucket the device programs that follow belong to
            with span(f"re_bucket:{b.rows_per_entity}x{b.num_local_features}"):
                res, w, var = self._solve_bucket(
                    i, b, bm.coefficients, obj, residual_scores
                )
            self._report_solve_route(b)
            # keep only the tiny telemetry vectors (the full SolveResult
            # frees per bucket); stay ON DEVICE — every host fetch is a
            # wait on this bucket's solve, so both arrays cross in ONE
            # np.asarray each after a device-side concat
            n_real = int(w.shape[0])
            tracker_its.append(res.iterations[:n_real])
            tracker_reasons.append(res.reason[:n_real])
            tracker_vals.append(res.value[:n_real])
            if self.health_check:
                # mesh-padded entities are all-zero problems (value 0 at
                # w=0), so the full padded res passes the reduce harmlessly
                healths.append(solve_health(res, res.w))
            new_buckets.append(
                dataclasses.replace(bm, coefficients=w, variances=var)
            )
        self.last_health = (
            (jnp.all(jnp.stack(healths)) if healths else jnp.bool_(True))
            if self.health_check
            else None
        )
        with span("re_tracker"):  # the host's wait on every bucket's solve
            self.last_tracker = (
                RandomEffectOptimizationTracker.from_device_parts(
                    tracker_its, tracker_reasons, tracker_vals
                )
            )
        self._report_stragglers(self.last_tracker.iterations)
        if new_buckets:
            _record_placement(
                f"{self.name}.coefficients", new_buckets[0].coefficients
            )
        return dataclasses.replace(model, buckets=tuple(new_buckets))

    def score(self, model: RandomEffectModel) -> Array:
        """Scores on the training data: fast bucket path for active rows,
        model searchsorted path for passive rows."""
        n_pad = self.data.shard(self.re_data.shard_name).num_rows
        scores = jnp.zeros((n_pad,), jnp.float32)
        for i, (b, bm) in enumerate(zip(self._buckets, model.buckets)):
            if self._dense_x[i] is not None:
                scores = _re_dense_scorer()(
                    bm.coefficients, self._dense_x[i], b.row_index, scores
                )
            else:
                scores = self._scorer(
                    bm.coefficients, b.entity_batch(), b.row_index, scores
                )
        if len(self.re_data.passive_rows):
            passive_scores = model.score(self.data)
            mask = np.zeros(n_pad, bool)
            mask[self.re_data.passive_rows] = True
            scores = jnp.where(jnp.asarray(mask), passive_scores, scores)
        return scores
