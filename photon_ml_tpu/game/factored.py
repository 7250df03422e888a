"""Factored random effects (the matrix-factorization coordinate) and the
standalone matrix-factorization scoring model.

Reference analog: photon-api algorithm/FactoredRandomEffectCoordinate.scala
:39-287 and model/MatrixFactorizationModel.scala:35-64. The factored
coordinate represents each entity's model as a K-dim latent vector c_e plus
a SHARED latent projection matrix A [K, d]; a row of entity e scores
(A x) . c_e. Training alternates (numIterations times):

  1. latent-space RE solve: project each entity's data through A and run the
     per-entity GLM solves in R^K (reusing RandomEffectCoordinate.updateModel
     in the reference, :111-130; here the vmapped DENSE bucket solver, so a
     K <= 32 Newton step is ``optim/spd_solve.py``'s hand solve),
  2. latent matrix refit: fix the c_e and refit vec(A) as ONE GLM over
     kronecker(x, c_e) features (updateLatentProjectionMatrix :226-255,
     kroneckerProductFeaturesAndCoefficients :269-287).

The Kronecker design is NEVER built. With X the shard's design and
C[row] = c_entity(row), the refit's margins are sum_l C[:, l] * (X A[l, :])
and its gradient w.r.t. A[l, :] is X^T (g * C[:, l]): K right-hand sides
over ONE design of nnz(X) nonzeros (:class:`LatentRefitBatch`, a
``SparseBatch`` duck type over vec(A), so every optimizer of
``dispatch_solve`` runs on it unchanged). That design holds the shard's
rows in the COORDINATE'S OWN ORDER: bucket after bucket, entity after
entity, each entity padded to its bucket's R rows. In that order

  - C is a broadcast of the latent table (no gather),
  - P = X A^T [K, rows], the projection pass, IS every bucket's latent
    design [E, K, R] after a reshape: the per-entity solves read it
    feature-major (``_re_solver(packed=True, kmajor=True)``), and no
    [.., K]-trailing array (whose lanes a TPU pads 128/K-fold) is ever
    formed,
  - the per-row arrays are the buckets' own, concatenated.

The REFIT alone may walk the rows in another order: the latent vectors are
constant inside it and its state is vec(A), so its per-row arrays are its
own. Where the base design has one nonzero a row (a one-hot shard: row id x
column id, upstream's ``MatrixFactorizationModel``) and is tiled, the refit
runs over a SECOND layout of it, sorted by column
(:class:`SortedRefitRows`, ``ops/tiled.py::ColumnSortedTiles``): a tile's
slots then touch a window of 16 table rows and not all of A, the gradient
is a windowed segment sum, and twice a fit the latent vectors and the
offsets are gathered into that order. Read off the data, never set: counter
``mf.<name>.refit_column_sorted``.

The design is a ``TiledBatch`` on a TPU (``layout``; one sweep of its tiles
serves all K tables: ``%mf_margins_k`` / ``%mf_scatter_k``) and a COO
``SparseBatch`` elsewhere (K passes of ``dot_rows`` / ``scatter_features``
under one loop). The reference's sparsityToleranceThreshold (drop tiny
products) does not apply: nothing is multiplied out.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from photon_ml_tpu.data.projection import (
    ProjectionMatrix,
    build_gaussian_projection_matrix,
)
from photon_ml_tpu.game.dataset import GameDataset
from photon_ml_tpu.game.models import map_vocab_codes
from photon_ml_tpu.game.random_effect_data import RandomEffectDataset
from photon_ml_tpu.ops.losses import get_loss
from photon_ml_tpu.ops.objective import make_objective
from photon_ml_tpu.ops.sparse import SparseBatch
from photon_ml_tpu.optim.adapter import glm_adapter
from photon_ml_tpu.optim.factory import (
    OptimizerConfig,
    OptimizerType,
    dispatch_solve,
)
from photon_ml_tpu.optim.spd_solve import takes_hand_solve
from photon_ml_tpu.optim.trackers import (
    FactoredRandomEffectOptimizationTracker,
    FixedEffectOptimizationTracker,
    RandomEffectOptimizationTracker,
)
from photon_ml_tpu.ops.panels import report_layout
from photon_ml_tpu.ops.tiled import (
    K_SWEEP_TILES_A_STEP,
    ROWS_PER_TILE,
    WINDOW,
    ColumnSortedTiles,
    TiledBatch,
    sorted_slots,
)
from photon_ml_tpu.telemetry.device import accounted_upload
from photon_ml_tpu.telemetry.metrics import counter
from photon_ml_tpu.telemetry.trace import span
from photon_ml_tpu.telemetry.xla import instrumented_jit

Array = jax.Array


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FactoredRandomEffectModel:
    """Latent per-entity vectors + the shared projection matrix.

    ``latent`` is one flat [n_active_entities, K] table (entities of every
    geometry bucket concatenated); ``entity_flat`` maps a TRAINING entity
    code to its row (-1 = entity unseen / inactive). Models always score in
    projected space: score = (A x) . c_e (FactoredRandomEffectModel
    .toRandomEffectModel + RandomEffectCoordinate.score in the reference).
    """

    id_name: str
    shard_name: str
    projection: ProjectionMatrix  # A: [K, d]
    latent: Array  # f[n_flat, K]
    entity_flat: np.ndarray  # host i64[num_entities] code -> flat row | -1
    vocab: np.ndarray  # training id vocabulary

    @property
    def latent_dim(self) -> int:
        return self.latent.shape[1]

    def score(self, data: GameDataset) -> Array:
        """[n_pad] scores; entities without a latent vector score 0."""
        if data.id_columns.get(self.id_name) is None:
            raise KeyError(f"scoring data lacks id column '{self.id_name}'")
        batch = data.shard(self.shard_name)
        n = data.num_rows
        idc = data.id_columns[self.id_name]
        codes = map_vocab_codes(self.vocab, idc.vocab[idc.codes])
        flat_of_row = np.where(codes >= 0, self.entity_flat[np.maximum(codes, 0)], -1)

        vals = np.asarray(batch.values)
        rows = np.asarray(batch.rows)
        cols = np.asarray(batch.cols)
        live_idx = np.nonzero((vals != 0) & (rows < n))[0]

        # TRANSPOSED per-nnz gathers in bounded chunks: [K, m] keeps the
        # long nnz dim in lanes (a [m, K] gather pads lanes 128/K-fold;
        # measured 12.3 GB of pure padding at K=2 on 16M nnz), and the
        # chunking bounds the transient at any shard size
        CHUNK = 8_000_000
        out = jnp.zeros((batch.num_rows,), batch.dtype)
        for lo in range(0, len(live_idx), CHUNK):
            part = live_idx[lo:lo + CHUNK]
            v = jnp.asarray(vals[part], batch.dtype)
            r = jnp.asarray(rows[part], jnp.int32)
            g = jnp.asarray(cols[part], jnp.int32)
            f = jnp.asarray(flat_of_row[rows[part]], jnp.int32)
            c_t = self.latent.T[:, jnp.maximum(f, 0)]  # [K, m]
            # features beyond the training dimension score 0 (a scoring
            # shard's vocabulary may be larger than training's; clamped
            # gathers would otherwise alias them onto the last training
            # column)
            known = g < self.projection.original_dim
            a_t = self.projection.matrix[
                :, jnp.minimum(g, self.projection.original_dim - 1)
            ]  # [K, m]
            contrib = jnp.where(
                (f >= 0) & known, v * jnp.sum(c_t * a_t, axis=0), 0.0
            )
            out = out.at[r].add(contrib)
        return out

    def to_summary_string(self) -> str:
        n_models = int(np.sum(self.entity_flat >= 0))
        return (
            f"FactoredRandomEffectModel(id={self.id_name}, "
            f"shard={self.shard_name}, entities={n_models}/{len(self.vocab)}, "
            f"latent_dim={self.latent_dim}, "
            f"original_dim={self.projection.original_dim})"
        )

    def effective_coefficients(self, entity_value) -> Optional[Array]:
        """Original-space d-dim coefficients A^T c_e for one entity (the
        projectCoefficients view), or None if the entity is unseen."""
        code = map_vocab_codes(self.vocab, np.asarray([entity_value]))[0]
        if code < 0 or self.entity_flat[code] < 0:
            return None
        return self.projection.project_coefficients(
            self.latent[int(self.entity_flat[code])]
        )


@dataclasses.dataclass(frozen=True)
class MatrixFactorizationModel:
    """Row/column latent-factor scoring model
    (model/MatrixFactorizationModel.scala:35-64): score(datum) =
    rowFactors[row_id] . colFactors[col_id]; rows/cols unseen in either
    vocabulary score 0."""

    row_effect: str  # id column naming matrix rows (e.g. "userId")
    col_effect: str  # id column naming matrix cols (e.g. "movieId")
    row_factors: Array  # f[n_row_entities, K]
    col_factors: Array  # f[n_col_entities, K]
    row_vocab: np.ndarray
    col_vocab: np.ndarray

    @property
    def num_latent_factors(self) -> int:
        return self.row_factors.shape[1]

    def score(self, data: GameDataset) -> Array:
        for eff in (self.row_effect, self.col_effect):
            if data.id_columns.get(eff) is None:
                raise KeyError(f"scoring data lacks id column '{eff}'")
        rc = data.id_columns[self.row_effect]
        cc = data.id_columns[self.col_effect]
        r_codes = map_vocab_codes(self.row_vocab, rc.vocab[rc.codes])
        c_codes = map_vocab_codes(self.col_vocab, cc.vocab[cc.codes])
        ok = (r_codes >= 0) & (c_codes >= 0)
        rf = self.row_factors[jnp.asarray(np.maximum(r_codes, 0), jnp.int32)]
        cf = self.col_factors[jnp.asarray(np.maximum(c_codes, 0), jnp.int32)]
        s = jnp.where(jnp.asarray(ok), jnp.sum(rf * cf, axis=1), 0.0)
        # align with the padded row count every score path uses
        n_pad = data.shard(next(iter(data.feature_shards))).num_rows
        return jnp.pad(s, (0, n_pad - s.shape[0]))


# ---------------------------------------------------------------------------
# the refit's design: vec(A) as a GLM without the Kronecker product
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class LatentRefitBatch:
    """The latent-matrix refit's examples: feature ``j*K + l`` of a row is
    ``x[j] * c[l]`` (kronecker(x, c_entity)), held as the base design and
    the rows' latent vectors and never multiplied out.

    Duck-type compatible with :class:`SparseBatch` for everything
    ``GLMObjective`` and ``glm_adapter`` use; ``w`` is vec(A) with A[l, j]
    at ``j*K + l``. Every pass is one of the design's contracted forms
    (``ops/sparse.py::ContractedRows``): ``project_rows`` / ``scatter_rows``
    plus elementwise work on [K, rows] arrays where the rows lie in the
    coordinate's order, one windowed sweep where the design is the
    column-sorted second layout (:class:`SortedRefitRows`). The rows may lie
    in ANY order: nothing outside the batch sees a per-row array of it.
    """

    design: object  # SparseBatch | TiledBatch | ColumnSortedTiles
    c_rows: Array  # f[K, rows]: the latent vector of each row's entity
    labels: Array  # f[rows]
    offsets: Array  # f[rows]
    weights: Array  # f[rows]; 0 on padding rows

    @property
    def latent_dim(self) -> int:
        return self.c_rows.shape[0]

    @property
    def num_features(self) -> int:
        return self.design.num_features * self.latent_dim

    @property
    def num_rows(self) -> int:
        return self.labels.shape[0]

    @property
    def dtype(self):
        return self.c_rows.dtype

    def _matrix(self, w: Array) -> Array:
        return w.reshape(-1, self.latent_dim).T  # [K, d]

    # -- sweeps (SparseBatch duck-type) --------------------------------------

    def dot_rows(self, w: Array) -> Array:
        return self.design.contract_rows(self._matrix(w), self.c_rows)

    def margins(self, w: Array, shift: Array | float = 0.0) -> Array:
        return self.dot_rows(w) + shift + self.offsets

    def margins_pair(self, w, shift, p, p_shift):
        return self.margins(w, shift), self.dot_rows(p) + p_shift

    def scatter_features(self, per_row: Array) -> Array:
        return self.design.scatter_contracted(
            per_row, self.c_rows).T.reshape(-1)

    def scatter_features_sq(self, per_row: Array) -> Array:
        return self.design.scatter_contracted(
            per_row, self.c_rows, square=True).T.reshape(-1)

    def fused_value_grad(self, w, shift, loss_name: str):
        loss = get_loss(loss_name)
        l, dz = loss.loss_and_dz(self.margins(w, shift), self.labels)
        wdz = self.weights * dz
        return (jnp.sum(self.weights * l), self.scatter_features(wdz),
                jnp.sum(wdz))

    def fused_hessian_vector(self, w, shift, v, v_shift, loss_name: str):
        loss = get_loss(loss_name)
        z, u = self.margins_pair(w, shift, v, v_shift)
        q = self.weights * loss.d2z(z, self.labels) * u
        return self.scatter_features(q), jnp.sum(q)

    def fused_hv_at(self, d2_row, v, v_shift):
        q = d2_row * (self.dot_rows(v) + v_shift)
        return self.scatter_features(q), jnp.sum(q)

    def with_offsets(self, offsets: Array) -> "LatentRefitBatch":
        return dataclasses.replace(
            self, offsets=jnp.asarray(offsets, self.offsets.dtype))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SortedRefitRows:
    """The refit's SECOND layout of a base design with one nonzero a row:
    the rows sorted by column (``ops/tiled.py::ColumnSortedTiles``), and
    what brings the coordinate's per-row arrays into that order. Inside a
    refit the latent vectors are constant and the optimizer's state is
    vec(A) alone, so the refit may walk the rows in any order; in this one a
    tile touches a window of 16 table rows and not all of them. Lengths are
    the layout's slots; a padding slot has weight 0 and points at row 0 /
    entity 0."""

    design: ColumnSortedTiles
    labels: Array  # f[slots]
    weights: Array  # f[slots]; 0 on padding slots
    order: Array  # i32[slots]: the slot's row, as a position in the coordinate's order
    entity: Array  # i32[slots]: its entity's row of the flat latent table


#: rows a step of :func:`_latent_rows`: one gather's [rows, K] result is
#: lane-padded 128/K-fold, so it has to stay small (PERF.md, Findings PR 33)
_GATHER_ROWS = 1 << 17


def _latent_rows(latent: Array, entity: Array) -> Array:
    """[K, slots]: ``latent[entity].T`` with the slots on lanes. One row
    take of K floats a slot, in chunks that are flattened before they leave
    the loop (a 1-D array has one layout: stacked as [chunks, K, rows] XLA
    keeps K minor and pads the lanes 8x at K = 16)."""
    n, k = entity.shape[0], latent.shape[1]
    chunks = -(-n // _GATHER_ROWS)
    idx = jnp.pad(entity, (0, chunks * _GATHER_ROWS - n))
    flat = jax.lax.map(
        lambda i: jnp.take(latent, i, axis=0).T.reshape(-1),
        idx.reshape(chunks, _GATHER_ROWS))
    return flat.reshape(chunks, k, _GATHER_ROWS).transpose(1, 0, 2).reshape(
        k, -1)[:, :n]


def _rows_of_buckets(parts, shapes, total: int, lead: tuple = ()) -> Array:
    """Per-bucket arrays [*lead, E, R] -> one [*lead, total] array in the
    coordinate's row order (zeros past the last bucket)."""
    flat = [p.reshape(*lead, e * r) for p, (_, e, r) in zip(parts, shapes)]
    used = sum(e * r for _, e, r in shapes)
    flat.append(jnp.zeros((*lead, total - used), jnp.float32))
    return jnp.concatenate(flat, axis=-1)


def _c_rows(latents, shapes, total: int) -> Array:
    """[K, total]: each row's entity's latent vector, a broadcast of the
    buckets' tables [E, K] along their R rows."""
    k = latents[0].shape[1]
    return _rows_of_buckets(
        [jnp.broadcast_to(c.T[:, :, None], (k, e, r))
         for c, (_, e, r) in zip(latents, shapes)],
        shapes, total, lead=(k,))


@lru_cache(maxsize=64)
def _latent_design_fn(shapes: tuple):
    """(design, A) -> every bucket's latent design, feature-major and flat
    [E, K*R]: the projection pass P = X A^T [K, rows], cut at the buckets
    and turned entity-major. No gather: the design's rows lie in bucket
    order."""

    def designs(design, a):
        p = design.project_rows(a)
        k = a.shape[0]
        return tuple(
            p[:, off:off + e * r].reshape(k, e, r).transpose(1, 0, 2)
            .reshape(e, k * r)
            for off, e, r in shapes)

    return instrumented_jit(designs, name="factored_project", multi_shape=True)


@lru_cache(maxsize=64)
def _row_scores_fn(shapes: tuple):
    """(design, A, latents, place) -> scores [len(place)]: the coordinate's
    margins in its own row order, each read at its example row (``place``
    [n]: a row's position in that order, -1 for none)."""

    def scores(design, a, latents, place):
        z = jnp.sum(
            _c_rows(latents, shapes, design.num_rows) * design.project_rows(a),
            axis=0)
        return jnp.where(place >= 0, jnp.take(z, jnp.maximum(place, 0)), 0.0)

    return instrumented_jit(scores, name="factored_score", multi_shape=True)


@lru_cache(maxsize=1)
def _foreign_scores_fn():
    """(design, A, latent, flat) -> scores of another dataset's rows (its
    own order): (A x) . c_entity, 0 where ``flat`` [rows] is -1. The latent
    vectors come by ONE flat take with the rows on lanes ([K, rows])."""

    def scores(design, a, latent, flat):
        k = latent.shape[1]
        idx = jnp.maximum(flat, 0)[None, :] * k + jnp.arange(
            k, dtype=flat.dtype)[:, None]
        c = jnp.take(latent.reshape(-1), idx)  # [K, rows]
        return jnp.where(
            flat >= 0, jnp.sum(c * design.project_rows(a), axis=0), 0.0)

    return instrumented_jit(
        scores, name="factored_score_rows", multi_shape=True)


@lru_cache(maxsize=64)
def _latent_fit_solver(config: OptimizerConfig, loss_name: str,
                       shapes: tuple):
    def run(obj, design, labels, weights, offsets, latents, w0, l1,
            by_column=None):
        total = design.num_rows
        row_offsets = _rows_of_buckets(offsets, shapes, total)
        if by_column is None:
            batch = LatentRefitBatch(
                design=design,
                c_rows=_c_rows(latents, shapes, total),
                labels=labels,
                offsets=row_offsets,
                weights=weights,
            )
        else:  # SortedRefitRows: the [K, total] broadcast is never formed
            batch = LatentRefitBatch(
                design=by_column.design,
                c_rows=_latent_rows(
                    jnp.concatenate(latents, axis=0), by_column.entity),
                labels=by_column.labels,
                offsets=jnp.take(row_offsets, by_column.order),
                weights=by_column.weights,
            )
        return dispatch_solve(glm_adapter(obj, batch), w0, config, l1)

    return instrumented_jit(run, name="factored_latent_fit", multi_shape=True)


# ---------------------------------------------------------------------------
# coordinate
# ---------------------------------------------------------------------------


def _solver_key(config: OptimizerConfig) -> OptimizerConfig:
    """The compiled solver's cache key: the weight is a traced leaf of the
    objective, so a lambda sweep shares one program."""
    return dataclasses.replace(config, regularization_weight=0.0)


def _objective_and_l1(loss_name: str, config: OptimizerConfig):
    """(objective carrying the config's L2 weight, its L1 weight)."""
    reg, weight = config.regularization, config.regularization_weight
    return (make_objective(loss_name, l2_weight=reg.l2_weight(weight)),
            jnp.float32(reg.l1_weight(weight)))


#: what ``benchmark/drivers/game_fit_mf.py`` asks for before it generates a
#: row: this coordinate refits vec(A) without a Kronecker design
KRON_FREE_REFIT = True


@dataclasses.dataclass
class FactoredRandomEffectCoordinate:
    """Alternating latent RE solve + latent-matrix GLM refit
    (FactoredRandomEffectCoordinate.scala:111-147).

    ``latent_dim`` is the latent-space dimension K and ``mf_iterations`` the
    alternation count (MFOptimizationConfiguration analog);
    ``re_config``/``latent_config`` are the per-entity and latent-matrix
    optimizer configs (FactoredRandomEffectOptimizationProblem)."""

    name: str
    data: GameDataset
    re_data: RandomEffectDataset
    loss_name: str
    re_config: OptimizerConfig
    latent_config: OptimizerConfig
    latent_dim: int
    mf_iterations: int = 1
    seed: int = 0
    mesh: Optional[Mesh] = None  # 1-D mesh: entity-shards the latent RE
    # solves (GSPMD over the lanes) and row-shards the refit's design
    # refit_projection=False freezes A after random initialization: the
    # coordinate becomes RandomEffectCoordinateInProjectedSpace with a
    # Gaussian RandomProjection (ProjectorType.RANDOM analog) — per-entity
    # solves in the fixed projected space, no refit.
    refit_projection: bool = True
    # with refit_projection=False, optionally pass the intercept through the
    # projection untouched (buildGaussianRandomProjectionMatrix's
    # isKeepingInterceptTerm dummy row)
    projection_intercept_index: Optional[int] = None
    # the base design's layout: "auto" tiles it on a TPU, COO elsewhere
    layout: str = "auto"

    def __post_init__(self):
        if self.latent_dim < 1:
            raise ValueError("latent_dim must be >= 1")
        if self.mf_iterations < 1:
            raise ValueError("mf_iterations must be >= 1")
        if self.projection_intercept_index is not None and self.refit_projection:
            raise ValueError(
                "projection_intercept_index requires refit_projection=False "
                "(the MF refit would overwrite the passthrough row; the "
                "reference's MF init uses isKeepingInterceptTerm=false)"
            )
        if self.layout not in ("auto", "coo", "tiled"):
            raise ValueError(f"unknown layout '{self.layout}'")
        self.re_config.validate(self.loss_name)
        self.latent_config.validate(self.loss_name)
        if self.re_config.box_constraints or self.latent_config.box_constraints:
            raise ValueError(
                "box constraints are not supported in latent/projected spaces"
            )
        k = self.latent_dim
        buckets = self.re_data.buckets
        self._batch = self.data.shard(self.re_data.shard_name)
        # rows of A, including the optional intercept passthrough row
        self._proj_rows = k + (1 if self.projection_intercept_index is not None else 0)

        # flat latent-table layout: bucket entities concatenated in order
        with span("build.entity_map"):
            sizes = [b.num_entities for b in buckets]
            self._flat_offsets = np.concatenate(
                [[0], np.cumsum(sizes)]).astype(np.int64)
            self._n_flat = int(self._flat_offsets[-1])
            eb, ep = self.re_data.entity_bucket, self.re_data.entity_pos
            self._entity_flat = np.where(
                eb >= 0, self._flat_offsets[np.maximum(eb, 0)] + ep, -1
            ).astype(np.int64)

        if self.mesh is not None:
            self._resolve_mesh_axis()
        self._use_tiled = self.mesh is None and (
            self.layout == "tiled"
            or (self.layout == "auto" and jax.default_backend() == "tpu"))
        self._build_design()

        from photon_ml_tpu.game.coordinates import _re_solver

        # the dense bucket route over the projection pass's feature-major
        # designs: one lru_cache entry with every coordinate of this config
        self._re_solver = _re_solver(
            _solver_key(self.re_config), self.loss_name, packed=True,
            kmajor=True)
        with span("build.objective"):  # their eager one-op programs
            self._re_obj, self._re_l1 = _objective_and_l1(
                self.loss_name, self.re_config)
            if self.refit_projection:
                self._lat_solver = _latent_fit_solver(
                    _solver_key(self.latent_config), self.loss_name,
                    self._shapes)
                self._lat_obj, self._lat_l1 = _objective_and_l1(
                    self.loss_name, self.latent_config)
        # the last foreign dataset scored through device-resident state
        # (score_dataset): (weakref to it, (design, flat) or None)
        self._foreign = None
        self.last_tracker = None

    # -- layout --------------------------------------------------------------

    def _build_design(self) -> None:
        """The shard's rows in the coordinate's own order (bucket, entity,
        row of the entity; an entity padded to its bucket's R), as ONE base
        design with the buckets' per-row arrays beside it. Host spans
        ``mf_layout.group`` (the order) and ``mf_layout.design`` (the
        packing) under ``layout`` / ``mf_layout``; the placement is span
        ``mf_upload`` under ``upload``. Nothing of length nnz x K exists."""
        buckets = self.re_data.buckets
        n = self.data.num_rows
        n_pad = self._batch.num_rows
        d = self.re_data.num_global_features
        with span("layout"), span("mf_layout"):
            with span("mf_layout.group"):
                shapes, off = [], 0
                place = np.full(n_pad, -1, np.int32)
                for b in buckets:
                    e, r = b.num_entities, b.rows_per_entity
                    shapes.append((off, e, r))
                    ri = np.asarray(b.row_index).reshape(-1)
                    at = np.flatnonzero(ri >= 0)
                    place[ri[at]] = off + at
                    off += e * r
                self._shapes = tuple(shapes)
                # whole grid steps of the K-table sweeps
                step = ROWS_PER_TILE * K_SWEEP_TILES_A_STEP
                total = max(-(-off // step), 1) * step

                def rows(field):
                    out = np.zeros(total, np.float32)
                    for b, (o, e, r) in zip(buckets, shapes):
                        out[o:o + e * r] = np.asarray(
                            getattr(b, field)).reshape(-1)
                    return out

                labels, weights = rows("labels"), rows("weights")
                vals = np.asarray(self._batch.values)
                src = np.asarray(self._batch.rows)
                cols = np.asarray(self._batch.cols)
                keep = np.flatnonzero((vals != 0) & (src < n))
                keep = keep[place[src[keep]] >= 0]
                vals, cols = vals[keep], cols[keep]
                mf_rows = place[src[keep]]
                del src, keep
                # one nonzero in every placed row: the refit gets a second
                # layout, sorted by column (a property of the data; no key)
                by_column = order = None
                if (self._use_tiled and len(vals)
                        and len(vals) == np.count_nonzero(place >= 0)
                        and np.bincount(mf_rows).max() == 1):
                    with span("mf_refit_layout.sort"):
                        # 16-bit keys take numpy's radix sort
                        order = np.argsort(
                            cols.astype(np.uint16) if d <= 1 << 16 else cols,
                            kind="stable")
            with span("mf_layout.design"):
                if self._use_tiled:
                    host = TiledBatch.pack_coo(
                        values=vals.astype(np.float32), rows=mf_rows,
                        cols=cols, labels=labels, num_features=d,
                        weights=weights,
                    ).traced_as("mf")
                    report_layout(host, f"mf.{self.name}.layout")
                    if order is not None:
                        with span("mf_refit_layout.pack"):
                            by_column = self._pack_by_column(
                                order, vals, cols, mf_rows, labels, weights)
                else:
                    host = SparseBatch.from_coo(
                        values=vals, rows=mf_rows, cols=cols, labels=labels,
                        num_features=d, weights=weights,
                    )
        self._nnz = int(len(vals))
        shard = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            # row- and nonzero-sharded over the coordinate's one axis where
            # the lengths divide it; GSPMD partitions the refit's passes
            def spec(x):
                even = x.ndim and x.shape[0] % self._n_dev == 0
                return NamedSharding(
                    self.mesh, PartitionSpec(self._axis if even else None))

            shard = spec
        with span("upload"):
            placed = accounted_upload(
                lambda: jax.tree.map(
                    jnp.asarray if shard is None
                    else (lambda x: jax.device_put(x, shard(np.asarray(x)))),
                    (host, labels, weights, place, by_column)),
                name="mf_upload")
        (self._design, self._labels, self._weights, self._place,
         self._by_column) = placed
        self._report_layout()

    def _pack_by_column(self, order, vals, cols, mf_rows, labels,
                        weights) -> SortedRefitRows:
        """The second layout from ``order`` (the stable sort of the
        nonzeros by column): the design, and the per-row arrays the refit
        needs in its slots' order."""
        host, slot = ColumnSortedTiles.pack(
            np.asarray(vals, np.float32)[order], cols[order],
            self.re_data.num_global_features, sorted_slots(self._proj_rows))
        at = mf_rows[order]  # the sorted rows' places in the coordinate's order
        # the flat latent row of every place: a bucket's entities, R each
        entity = np.zeros(len(labels), np.int32)
        for (o, e, r), first in zip(self._shapes, self._flat_offsets):
            entity[o:o + e * r] = np.repeat(
                np.arange(first, first + e, dtype=np.int32), r)

        def slots(dtype, per_row):
            out = np.zeros(host.num_rows, dtype)
            out[slot] = per_row
            return out

        return SortedRefitRows(
            design=host.traced_as("mf"),
            labels=slots(np.float32, labels[at]),
            weights=slots(np.float32, weights[at]),
            order=slots(np.int32, at),
            entity=slots(np.int32, entity[at]))

    def _report_layout(self) -> None:
        """Counters ``re.<name>.*`` as a random-effect coordinate writes
        them for its buckets (``nnz_padded`` the latent designs' E*R*K
        cells), and ``mf.<name>.refit_nnz`` / ``.latent_dim`` /
        ``.kron_nnz_materialised`` (0: the Kronecker design is not built)."""
        k = self._proj_rows
        totals = dict.fromkeys(
            ("entities", "rows", "rows_padded", "nnz", "nnz_padded",
             "buckets"), 0)
        for hb in self.re_data.buckets:
            e, r = hb.num_entities, hb.rows_per_entity
            totals["entities"] += e
            totals["rows"] += int(np.count_nonzero(hb.row_index >= 0))
            totals["rows_padded"] += e * r
            totals["nnz_padded"] += e * r * k
            totals["buckets"] += 1
        totals["nnz"] = self._nnz
        for key, value in totals.items():
            counter(f"re.{self.name}.{key}").inc(value)
        counter(f"mf.{self.name}.refit_nnz").inc(self._nnz)
        counter(f"mf.{self.name}.latent_dim").inc(k)
        counter(f"mf.{self.name}.kron_nnz_materialised").inc(0)
        counter(f"mf.{self.name}.refit_column_sorted").inc(
            self._by_column is not None)
        counter(f"mf.{self.name}.refit_window_rows").inc(
            WINDOW if self._by_column is not None else 0)

    def _resolve_mesh_axis(self) -> None:
        """Pick the ONE mesh axis this coordinate parallelizes over: the
        entity-sharded latent solves and the row-sharded refit both use it.
        A model/entity axis wins (the latent table is per-entity state),
        then a batch/data axis, then the mesh's first axis (legacy 1-D
        meshes)."""
        from photon_ml_tpu.parallel import sharding as psharding

        self._axis = (
            psharding.model_axis(self.mesh)
            or psharding.data_axis(self.mesh)
            or self.mesh.axis_names[0]
        )
        self._n_dev = psharding.axis_size(self.mesh, self._axis)

    # -- model plumbing ------------------------------------------------------

    def initialize_model(self) -> FactoredRandomEffectModel:
        """Zero latent vectors + a Gaussian random projection
        (FactoredRandomEffectCoordinate.initializeModel:190-212, which seeds
        A with buildRandomProjectionBroadcastProjector)."""
        proj = build_gaussian_projection_matrix(
            self.latent_dim,
            self.re_data.num_global_features,
            intercept_index=self.projection_intercept_index,
            seed=self.seed,
        )
        return FactoredRandomEffectModel(
            id_name=self.re_data.id_name,
            shard_name=self.re_data.shard_name,
            projection=proj,
            latent=jnp.zeros((self._n_flat, self._proj_rows), jnp.float32),
            entity_flat=self._entity_flat,
            vocab=self.data.id_columns[self.re_data.id_name].vocab,
        )

    def _bucket_slice(self, latent: Array, b_idx: int) -> Array:
        lo = int(self._flat_offsets[b_idx])
        hi = int(self._flat_offsets[b_idx + 1])
        return latent[lo:hi]

    def _latents(self, latent: Array) -> tuple:
        return tuple(
            self._bucket_slice(latent, i)
            for i in range(len(self.re_data.buckets)))

    def _bucket_offsets(self, residual: Optional[Array]) -> tuple:
        """Every bucket's offsets [E, R], the other coordinates' scores
        added at its rows (``re_offsets``, one program a bucket)."""
        from photon_ml_tpu.game.coordinates import _re_offsets

        buckets = self.re_data.device_buckets_stripped()
        if residual is None:
            return tuple(b.offsets for b in buckets)
        return tuple(_re_offsets()(b, residual) for b in buckets)

    def solve_bucket(self, obj, x_flat, labels, offsets, weights, w0):
        """One bucket's (or any stack of lanes') vmapped latent solve on
        the dense route from its feature-major design [E, K*R]. Under a
        mesh the lanes are padded to the axis and entity-sharded. Returns
        the solver's result over the lanes given."""
        bb = (x_flat, labels, offsets, weights)
        if self.mesh is None:
            return self._re_solver(obj, bb, w0, self._re_l1, None)[0]
        from photon_ml_tpu.game.coordinates import (
            _pad_entities,
            place_entity_solve,
            record_entity_solve_comms,
        )

        lanes = w0.shape[0]
        total = -(-lanes // self._n_dev) * self._n_dev
        bb_p, w0_p = _pad_entities(bb, w0, total)
        bb_p, w0_p, _ = place_entity_solve(self.mesh, self._axis, bb_p, w0_p)
        record_entity_solve_comms(
            "latent_re_solve", self.mesh, self._axis,
            self.re_config.max_iterations,
        )
        res = self._re_solver(obj, bb_p, w0_p, self._re_l1, None)[0]
        return jax.tree.map(
            lambda x: x[:lanes] if getattr(x, "ndim", 0) else x, res)

    def latent_designs(self, a: Array) -> tuple:
        """Every bucket's latent design [E, K*R] (feature-major) under the
        projection ``a`` [K, d]: one projection pass."""
        return _latent_design_fn(self._shapes)(self._design, a)

    def _latent_re_step(self, latent: Array, a: Array, offsets: tuple):
        """One pass of per-entity solves in latent space over all buckets.
        Returns ``(latent', RandomEffectOptimizationTracker)``."""
        k = self._proj_rows
        with span("latent_design"):
            designs = self.latent_designs(a)
        parts, t_its, t_reasons, t_vals = [], [], [], []
        buckets = self.re_data.device_buckets_stripped()
        for b_idx, b in enumerate(buckets):
            e, r = b.num_entities, b.rows_per_entity
            with span(f"latent_bucket:{r}x{k}"):
                res = self.solve_bucket(
                    self._re_obj, designs[b_idx], b.labels, offsets[b_idx],
                    b.weights,
                    self._bucket_slice(latent, b_idx))
            if self.re_config.optimizer_type == OptimizerType.NEWTON:
                hand = takes_hand_solve(k)
                for scope in (f"mf.{self.name}", "mf"):
                    counter(f"{scope}.hand_solve_lanes").inc(e if hand else 0)
                    counter(f"{scope}.xla_solve_lanes").inc(0 if hand else e)
            parts.append(res.w)
            t_its.append(res.iterations)
            t_reasons.append(res.reason)
            t_vals.append(res.value)
        new_latent = jnp.concatenate(parts, axis=0) if parts else latent
        with span("latent_tracker"):  # the host's wait on every bucket
            tracker = RandomEffectOptimizationTracker.from_device_parts(
                t_its, t_reasons, t_vals)
        self._report_stragglers(tracker.iterations)
        return new_latent, tracker

    def _report_stragglers(self, iterations: np.ndarray) -> None:
        """Counters ``mf.<name>.lane_iterations`` / ``.lane_iterations_run``
        (and their sums ``mf.*``): every entity's own Newton iterations, and
        entities x its bucket's longest solve (a vmapped ``while_loop`` runs
        every lane to the slowest)."""
        needed = run = 0
        for i in range(len(self.re_data.buckets)):
            its = iterations[
                int(self._flat_offsets[i]):int(self._flat_offsets[i + 1])]
            if len(its):
                needed += int(its.sum())
                run += int(its.max()) * len(its)
        for scope in (f"mf.{self.name}", "mf"):
            counter(f"{scope}.lane_iterations").inc(needed)
            counter(f"{scope}.lane_iterations_run").inc(run)

    def _latent_matrix_step(self, latent: Array, a: Array, offsets: tuple):
        """Refit vec(A) as one GLM over :class:`LatentRefitBatch`. Returns
        ``(A', FixedEffectOptimizationTracker)``."""
        k = self.latent_dim
        res = self._lat_solver(
            self._lat_obj, self._design, self._labels, self._weights,
            offsets, self._latents(latent), a.T.reshape(-1), self._lat_l1,
            self._by_column)
        tracker = FixedEffectOptimizationTracker.from_result(res)
        for scope in (f"mf.{self.name}", "mf"):
            counter(f"{scope}.refit_iterations").inc(tracker.iterations)
            # a margin-carrying iteration is one projection and one scatter
            # pass, and so is the start
            counter(f"{scope}.refit_evaluations").inc(tracker.iterations + 1)
        return res.w.reshape(-1, k).T, tracker  # [K, d]

    def update_model(
        self,
        model: FactoredRandomEffectModel,
        residual_scores: Optional[Array],
    ) -> FactoredRandomEffectModel:
        latent = model.latent
        a = model.projection.matrix
        offsets = self._bucket_offsets(residual_scores)
        if not self.refit_projection:
            # fixed random projection: per-entity solves only
            latent, re_t = self._latent_re_step(latent, a, offsets)
            self.last_tracker = FactoredRandomEffectOptimizationTracker(
                steps=((re_t, None),)
            )
            return dataclasses.replace(model, latent=latent)
        steps = []
        for i in range(self.mf_iterations):
            with span(f"mf_iteration:{i}"):
                latent, re_t = self._latent_re_step(latent, a, offsets)
                with span("latent_refit"):
                    a, lat_t = self._latent_matrix_step(latent, a, offsets)
            steps.append((re_t, lat_t))
        self.last_tracker = FactoredRandomEffectOptimizationTracker(
            steps=tuple(steps))
        return dataclasses.replace(
            model, latent=latent, projection=ProjectionMatrix(matrix=a)
        )

    def score(self, model: FactoredRandomEffectModel) -> Array:
        """Training-data scores: one projection pass in the coordinate's
        row order for active rows, the generic model path for passive
        rows."""
        scores = _row_scores_fn(self._shapes)(
            self._design, model.projection.matrix,
            self._latents(model.latent), self._place)
        if len(self.re_data.passive_rows):
            passive = model.score(self.data)
            mask = np.zeros(self._batch.num_rows, bool)
            mask[self.re_data.passive_rows] = True
            scores = jnp.where(jnp.asarray(mask), passive, scores)
        return scores

    # -- another dataset's rows (validation) ---------------------------------

    def score_dataset(
        self, model: FactoredRandomEffectModel, data: GameDataset
    ) -> Array:
        """``model.score(data)`` for rows that are not this coordinate's own
        (validation), from state kept on the device beside ``data``: its
        shard laid out like the training design and each row's place in the
        latent table, built and uploaded the first time ``data`` is scored
        (spans ``mf_validation_layout`` / ``mf_validation_upload``) and kept
        for as long as it is the dataset asked for. ``model.score`` walks
        the shard's nonzeros on the host and uploads four arrays a call."""
        from photon_ml_tpu.game.coordinates import _fit_rows

        if data is self.data:
            return self.score(model)
        state = self._foreign_state(model, data)
        if state is None:
            return model.score(data)
        design, flat = state
        z = _foreign_scores_fn()(
            design, model.projection.matrix, model.latent, flat)
        return _fit_rows(z, data.shard(self.re_data.shard_name).num_rows)

    def _foreign_state(self, model, data: GameDataset):
        import weakref

        if self._foreign is not None and self._foreign[0]() is data:
            if self._foreign[1] is not None:
                counter("validate.mf_design_hits").inc()
            return self._foreign[1]
        self._foreign = None  # the last dataset's state goes first
        state = None
        batch = data.shard(self.re_data.shard_name)
        idc = data.id_columns.get(self.re_data.id_name)
        if (idc is not None and self.mesh is None
                and batch.num_features == self.re_data.num_global_features):
            with span("mf_validation_layout"):
                n = data.num_rows
                codes = map_vocab_codes(model.vocab, idc.vocab[idc.codes])
                flat = np.full(batch.num_rows, -1, np.int32)
                flat[:n] = np.where(
                    codes >= 0, self._entity_flat[np.maximum(codes, 0)], -1)
                if self._use_tiled:
                    host = TiledBatch.pack_batch(batch).traced_as(
                        "mf_validate")
                    flat = np.pad(
                        flat, (0, host.num_rows - len(flat)),
                        constant_values=-1)
                else:
                    host = batch
            state = accounted_upload(
                lambda: jax.tree.map(jnp.asarray, (host, flat)),
                name="mf_validation_upload")
            counter("validate.mf_design_builds").inc()
        self._foreign = (weakref.ref(data), state)
        return state
