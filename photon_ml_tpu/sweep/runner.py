"""Vmapped multi-λ training: G regularization configs in ONE executable.

Reference analog: photon-api GameEstimator trains one CoordinateDescent
run PER regularization weight and picks the best by evaluator
(GameEstimator.scala:279-398). Because this repo's solvers are jitted
``lax.while_loop``s, a λ-grid is just one more ``vmap`` axis: the G
configs of the fixed-effect solve (and of every per-entity random-effect
bucket solve, where the config axis composes with the existing entity
vmap lane) batch into a single ``instrumented_jit`` executable — G small
dense problems is exactly the shape the MXU wants.

Warm-started regularization path: λs are ordered DESCENDING (grid.py), so
lane g-1 is lane g's more-regularized neighbor. Each round/CD iteration
initializes config g from config g-1's solution — but ONLY into lanes
that did not converge last round; converged lanes keep their own optimum,
enter the masked while-loop already-converged, and stop contributing
iterations (the per-config convergence mask the vmapped ``while_loop``
batching rule provides for free).

All solvers register with ``multi_shape=True``: the G-config warmup
compiles a by-design signature set and must never trip the
recompile-storm gate (``xla.recompiles`` stays flat across a warmed
sweep).

Telemetry: ``sweep.solves`` / ``sweep.nan_configs`` counters,
``sweep.configs_total`` / ``sweep.configs_done`` gauges (surfaced on the
30 s heartbeat line), a ``sweep > sweep_iteration > coordinate:<name>``
span tree, and one ``sweep_config`` span per lane at the end carrying the
per-config convergence summary the run report renders as a table.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import telemetry
from photon_ml_tpu.game.dataset import GameDataset
from photon_ml_tpu.game.models import (
    FixedEffectModel,
    GameModel,
    RandomEffectBucketModel,
    RandomEffectModel,
    map_vocab_codes,
)
from photon_ml_tpu.ops.objective import make_objective
from photon_ml_tpu.optim.adapter import glm_adapter
from photon_ml_tpu.optim.common import (
    CONVERGENCE_REASON_NAMES,
    FUNCTION_VALUES_CONVERGED,
    MAX_ITERATIONS,
    NOT_CONVERGED,
)
from photon_ml_tpu.optim.factory import (
    OptimizerConfig,
    dispatch_solve,
    split_reg_weights,
)
from photon_ml_tpu.sweep.grid import SweepGrid
from photon_ml_tpu.telemetry.xla import instrumented_jit

Array = jax.Array

__all__ = [
    "GlmSweepResult",
    "GameSweepResult",
    "SweepUnsupportedError",
    "path_warm_start",
    "re_bootstrap_solver",
    "sweep_glm",
    "sweep_game",
]


class SweepUnsupportedError(ValueError):
    """A training feature the vmapped sweep path does not batch yet; the
    message names the coordinate and the single-fit alternative."""


# ---------------------------------------------------------------------------
# batched solvers (one instrumented_jit each; multi_shape by design)
# ---------------------------------------------------------------------------


@lru_cache(maxsize=32)
def _fe_sweep_solver(config: OptimizerConfig, with_residual: bool):
    """G-config GLM solve: objective l2 leaf, OWLQN l1 and (optionally)
    per-config residual offsets map over the config axis; the design
    broadcasts, so data movement is shared across lanes."""
    if with_residual:
        def run(obj, batch, res_off, w0, l2s, l1s, constraints):
            def one(res_g, w0_g, l2_g, l1_g):
                b = batch.with_offsets(batch.offsets + res_g)
                return dispatch_solve(
                    glm_adapter(obj.with_l2(l2_g), b), w0_g, config, l1_g,
                    constraints,
                )

            return jax.vmap(one)(res_off, w0, l2s, l1s)
    else:
        def run(obj, batch, w0, l2s, l1s, constraints):
            def one(w0_g, l2_g, l1_g):
                return dispatch_solve(
                    glm_adapter(obj.with_l2(l2_g), batch), w0_g, config,
                    l1_g, constraints,
                )

            return jax.vmap(one)(w0, l2s, l1s)

    return instrumented_jit(run, name="sweep_fe_solve", multi_shape=True)


@lru_cache(maxsize=32)
def _re_sweep_solver(config: OptimizerConfig):
    """G-config x E-entity bucket solve: the config axis composes as an
    OUTER vmap over the existing per-entity vmap lane — one executable
    solves G*E independent small problems with the bucket design
    broadcast across configs."""

    def run(obj, ebatch, extra_off, w0, l2s, l1s):
        def one_cfg(extra_g, w0_g, l2_g, l1_g):
            obj_g = obj.with_l2(l2_g)
            eb = dataclasses.replace(
                ebatch, offsets=ebatch.offsets + extra_g
            )

            def one_entity(eb_e, w0_e):
                return dispatch_solve(
                    glm_adapter(obj_g, eb_e), w0_e, config, l1_g
                )

            return jax.vmap(one_entity)(eb, w0_g)

        return jax.vmap(one_cfg)(extra_off, w0, l2s, l1s)

    return instrumented_jit(run, name="sweep_re_solve", multi_shape=True)


@lru_cache(maxsize=32)
def re_bootstrap_solver(config: OptimizerConfig):
    """B-resample x E-entity bucket solve for the GLMix bootstrap
    (diagnostics.bootstrap): identical lane composition to
    :func:`_re_sweep_solver`, but the outer vmap axis carries B
    multinomial weight resamples instead of G regularization configs —
    ``lane_weights`` [B, E, R] scales the bucket's base row weights per
    lane, ``w0`` [E, K] (the point estimate) broadcasts across B so
    every lane warm-starts from the fitted coefficients. One executable
    solves B*E independent small problems with the bucket design
    broadcast across resamples (what B lanes cost over a single fit is
    not measured on the chip)."""

    def run(obj, ebatch, lane_weights, w0, l1):
        def one_sample(wts_b):
            eb = dataclasses.replace(
                ebatch, weights=ebatch.weights * wts_b
            )

            def one_entity(eb_e, w0_e):
                return dispatch_solve(
                    glm_adapter(obj, eb_e), w0_e, config, l1
                )

            return jax.vmap(one_entity)(eb, w0)

        return jax.vmap(one_sample)(lane_weights)

    return instrumented_jit(run, name="bootstrap_re_solve", multi_shape=True)


@lru_cache(maxsize=8)
def _fe_sweep_scorer():
    def run(batch, w):
        return jax.vmap(batch.dot_rows)(w)

    return instrumented_jit(run, name="sweep_fe_score", multi_shape=True)


@lru_cache(maxsize=8)
def _re_sweep_scorer():
    def run(scores, coeffs, ebatch, row_index):
        # coeffs [G, E, K] -> margins [G, E, R] -> scatter into [G, n_pad]
        def one_cfg(c):
            return jax.vmap(lambda w, b: b.dot_rows(w))(c, ebatch)

        margins = jax.vmap(one_cfg)(coeffs)
        idx = row_index.reshape(-1)
        vals = margins.reshape(margins.shape[0], -1)
        vals = jnp.where(idx[None, :] >= 0, vals, 0.0)
        return scores.at[:, jnp.maximum(idx, 0)].add(vals)

    return instrumented_jit(run, name="sweep_re_score", multi_shape=True)


@lru_cache(maxsize=8)
def _re_residual_gather():
    def run(residual, row_index):
        # residual [G, n_pad] -> bucket layout [G, E, R] (row_index gather;
        # padded rows contribute 0 — the addScoresToOffsets analog)
        def one(res_g):
            return jnp.where(
                row_index >= 0,
                jnp.take(res_g, jnp.maximum(row_index, 0)),
                0.0,
            )

        return jax.vmap(one)(residual)

    return instrumented_jit(run, name="sweep_re_residual", multi_shape=True)


@lru_cache(maxsize=8)
def _re_val_scorer():
    """Validation scoring of ALL G coefficient tables at once: the
    (bucket, pos, local-feature) lookup per nnz is config-independent and
    computed once; only the final coefficient gather carries the G axis —
    no per-config host round trips."""

    def run(scores, coeffs, projection, vals, rows, pos, gcols):
        proj_rows = projection[pos]  # [m, K] (config-independent)
        K = projection.shape[1]
        k = jnp.minimum(jax.vmap(jnp.searchsorted)(proj_rows, gcols), K - 1)
        hit = (
            jnp.take_along_axis(proj_rows, k[:, None], axis=1)[:, 0] == gcols
        )
        w = jnp.where(hit[None, :], coeffs[:, pos, k], 0.0)  # [G, m]
        return scores.at[:, rows].add(vals[None, :] * w)

    return instrumented_jit(run, name="sweep_re_val_score", multi_shape=True)


# ---------------------------------------------------------------------------
# warm-started path
# ---------------------------------------------------------------------------


def path_warm_start(w: Array, reasons: Array) -> Array:
    """Next-round inits along the regularization path: lane g takes lane
    g-1's solution (its more-regularized neighbor, λs descending) — but
    ONLY where lane g did not converge (``reasons`` says MaxIterations /
    still running); converged lanes keep their own optimum and freeze in
    the masked while-loop after the convergence check."""
    shifted = jnp.concatenate([w[:1], w[:-1]], axis=0)
    unconverged = (reasons == MAX_ITERATIONS) | (reasons == NOT_CONVERGED)
    keep = ~unconverged
    return jnp.where(keep.reshape((-1,) + (1,) * (w.ndim - 1)), w, shifted)


def _lane_unconverged(reasons: Array) -> Array:
    """Per-lane unconverged mask from a [G] or [G, E] reason array."""
    un = (reasons == MAX_ITERATIONS) | (reasons == NOT_CONVERGED)
    return un if un.ndim == 1 else jnp.any(un, axis=tuple(range(1, un.ndim)))


# ---------------------------------------------------------------------------
# plain-GLM sweep (the headline-config path; any batch layout)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GlmSweepResult:
    """One finished multi-λ GLM sweep (config axis = descending λ)."""

    lambdas: tuple[float, ...]
    w: Array  # [G, d]
    values: Array  # [G] final objective values
    iterations: np.ndarray  # i32[G]
    reasons: np.ndarray  # i32[G]
    data_passes: np.ndarray  # i32[G]
    rounds: int

    @property
    def size(self) -> int:
        return len(self.lambdas)

    def reason_names(self) -> list[str]:
        return [
            CONVERGENCE_REASON_NAMES.get(int(r), str(int(r)))
            for r in self.reasons
        ]


def sweep_glm(
    batch,
    task: str,
    lambdas: Sequence[float],
    config: OptimizerConfig,
    *,
    warm_start: bool = True,
    rounds: Optional[int] = None,
    w_start: Optional[Array] = None,
    constraints=None,
    mesh=None,
) -> GlmSweepResult:
    """Train one GLM per λ, all in one vmapped executable.

    ``rounds`` (default 2 with ``warm_start``, else 1) is the number of
    batched solve passes: round 0 is cold (every lane from ``w_start``),
    later rounds re-init unconverged lanes from their more-regularized
    neighbor (:func:`path_warm_start`). ``config.regularization_weight``
    is ignored — the grid is the sweep axis. With ``mesh`` (a mesh with a
    model or batch axis) the config axis is sharded across devices:
    lanes partition, the design replicates.
    """
    if not lambdas:
        raise ValueError("sweep_glm needs a non-empty lambda grid")
    config.validate(task)
    lams = tuple(sorted((float(v) for v in lambdas), reverse=True))
    G = len(lams)
    if rounds is None:
        rounds = 2 if (warm_start and G > 1) else 1
    if rounds < 1:
        raise ValueError(f"rounds must be >= 1, got {rounds}")
    n_feat = int(batch.num_features)
    if w_start is None:
        w_start = jnp.zeros((n_feat,), jnp.float32)
    if constraints is None:
        constraints = config.build_box_constraints(n_feat)
    key_cfg = dataclasses.replace(config, regularization_weight=0.0)
    solver = _fe_sweep_solver(key_cfg, with_residual=False)
    obj = make_objective(task)

    l2s, l1s = split_reg_weights(config.regularization, lams)
    W = jnp.broadcast_to(w_start, (G, n_feat))
    pad = 0
    if mesh is not None:
        from photon_ml_tpu.parallel import sharding as psharding
        from photon_ml_tpu.telemetry.xla import record_collective

        axis = psharding.model_axis(mesh) or psharding.data_axis(mesh)
        if axis is not None:
            n_dev = psharding.axis_size(mesh, axis)
            pad = (-G) % n_dev
            if pad:
                # duplicate the smallest λ into the pad lanes; sliced off
                lams_p = lams + (lams[-1],) * pad
                l2s, l1s = split_reg_weights(config.regularization, lams_p)
                W = jnp.broadcast_to(w_start, (G + pad, n_feat))
            eshard = psharding.entity_sharding(mesh, axis)
            W = jax.device_put(W, eshard)
            l2s = jax.device_put(l2s, eshard)
            l1s = jax.device_put(l1s, eshard)
            batch = psharding.place_replicated(batch, mesh)
            if constraints is not None:
                constraints = psharding.place_replicated(constraints, mesh)
            # lanes are independent; per-iteration traffic is the masked
            # while-loop's one-scalar convergence all-reduce
            record_collective(
                "sweep_glm_solve", "psum", n_dev, 4,
                count=max(int(config.max_iterations), 1) * rounds,
            )

    telemetry.gauge("sweep.configs_total").set(G)
    telemetry.gauge("sweep.configs_done").set(0)
    res = None
    with telemetry.span("sweep", task=task, configs=G, rounds=rounds):
        for r in range(rounds):
            with telemetry.span("sweep_round", round=r):
                w0 = W if r == 0 else path_warm_start(W, res.reason)
                res = solver(obj, batch, w0, l2s, l1s, constraints)
                W = res.w
            telemetry.counter("sweep.solves").inc(G)
            telemetry.gauge("sweep.configs_done").set(
                int(round(G * (r + 1) / rounds))
            )
    packed = jnp.concatenate(
        [
            res.iterations.astype(jnp.float32),
            res.reason.astype(jnp.float32),
            jnp.broadcast_to(
                jnp.asarray(res.data_passes, jnp.float32), res.reason.shape
            ),
        ]
    )
    fetched = np.asarray(
        telemetry.sync_fetch(packed, label="sweep_glm")
    ).reshape(3, -1)
    result = GlmSweepResult(
        lambdas=lams,
        w=W[:G],
        values=res.value[:G],
        iterations=fetched[0, :G].astype(np.int32),
        reasons=fetched[1, :G].astype(np.int32),
        data_passes=fetched[2, :G].astype(np.int32),
        rounds=rounds,
    )
    _emit_config_spans(
        result.lambdas,
        {"lambda": result.lambdas},
        result.iterations,
        result.reasons,
        values=np.asarray(
            telemetry.sync_fetch(result.values, label="sweep_glm_values")
        ),
    )
    return result


def _emit_config_spans(
    lambdas: Sequence[float],
    lambda_by_key: Mapping[str, Sequence[float]],
    iterations: np.ndarray,
    reasons: np.ndarray,
    values: Optional[np.ndarray] = None,
    metrics: Optional[np.ndarray] = None,
    metric_name: Optional[str] = None,
) -> None:
    """One ``sweep_config`` span per lane: the per-config convergence
    record the run report renders as a table (round-trips through the
    trace JSONL)."""
    for g in range(len(lambdas)):
        attrs = {
            "index": g,
            "iterations": int(iterations[g]),
            "reason": CONVERGENCE_REASON_NAMES.get(
                int(reasons[g]), str(int(reasons[g]))
            ),
        }
        for key, lams in lambda_by_key.items():
            attrs[f"lambda.{key}" if key != "lambda" else "lambda"] = float(
                lams[g]
            )
        if values is not None:
            attrs["final_loss"] = float(values[g])
        if metrics is not None:
            attrs["metric"] = (
                None if np.isnan(metrics[g]) else float(metrics[g])
            )
            attrs["metric_name"] = metric_name
        with telemetry.span("sweep_config", **attrs):
            pass


# ---------------------------------------------------------------------------
# GAME sweep (FE + per-entity RE coordinates; shared config axis)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _FeState:
    name: str
    shard_name: str
    config: OptimizerConfig
    lambdas: tuple[float, ...]
    batch: object  # device SparseBatch with labels/offsets/weights
    l2s: Array
    l1s: Array
    constraints: object
    normalization: object
    solver: object
    W: Array  # [G, d] in SOLVE (normalized) space
    reasons: Optional[Array] = None
    iterations: Optional[Array] = None
    values: Optional[Array] = None

    def original_w(self) -> Array:
        if self.normalization is None:
            return self.W
        return jax.vmap(self.normalization.transform_model_coefficients)(
            self.W
        )


@dataclasses.dataclass
class _ReState:
    name: str
    config: OptimizerConfig
    lambdas: tuple[float, ...]
    red: object  # RandomEffectDataset
    ebatches: tuple  # per bucket: SparseBatch with leading entity axis
    l2s: Array
    l1s: Array
    solver: object
    tables: list  # per bucket [G, E, K]
    vocab: np.ndarray
    reasons: Optional[Array] = None  # [G] lane-aggregated
    iterations: Optional[Array] = None
    values: Optional[Array] = None


class GameSweepResult:
    """A finished multi-config GAME sweep: device coefficient tables per
    coordinate per lane, convergence summaries, and on-device scoring of
    every lane against a validation dataset."""

    def __init__(self, task, states, history, n_pad):
        self.task = task
        self._states = states  # name -> _FeState | _ReState
        self.history = history
        self._n_pad = n_pad
        self._convergence = None  # fetched once; the sweep is immutable

    @property
    def size(self) -> int:
        return len(next(iter(self._states.values())).lambdas)

    @property
    def coordinate_names(self) -> list[str]:
        return list(self._states)

    @property
    def lambdas(self) -> dict[str, tuple[float, ...]]:
        return {name: s.lambdas for name, s in self._states.items()}

    def convergence(self) -> dict[str, dict[str, np.ndarray]]:
        """Per-coordinate per-lane summary of the LAST update: iterations
        (RE: max over entities), reason codes (RE: worst over entities),
        final objective values (RE: summed over entities). Fetched from
        device ONCE and cached — callers (selection spans, the CLI
        summary) must not each wait on the device again."""
        if self._convergence is not None:
            return self._convergence
        out = {}
        for name, s in self._states.items():
            packed = jnp.stack(
                [
                    s.iterations.astype(jnp.float32),
                    s.reasons.astype(jnp.float32),
                    s.values.astype(jnp.float32),
                ]
            )
            fetched = np.asarray(
                telemetry.sync_fetch(packed, label=f"sweep:{name}")
            )
            out[name] = {
                "iterations": fetched[0].astype(np.int32),
                "reasons": fetched[1].astype(np.int32),
                "values": fetched[2],
            }
        self._convergence = out
        return out

    # -- scoring -------------------------------------------------------------

    def _fe_scores(self, s: _FeState, data: GameDataset, n_pad: int) -> Array:
        vbatch = data.device_shard(s.shard_name)
        scores = _fe_sweep_scorer()(vbatch, s.original_w())
        if scores.shape[1] > n_pad:
            scores = scores[:, :n_pad]
        elif scores.shape[1] < n_pad:
            scores = jnp.pad(scores, ((0, 0), (0, n_pad - scores.shape[1])))
        return scores

    def _re_training_scores(self, s: _ReState, n_pad: int) -> Array:
        scores = jnp.zeros((self.size, n_pad), jnp.float32)
        for table, eb, bucket in zip(s.tables, s.ebatches, s.red.buckets):
            scores = _re_sweep_scorer()(scores, table, eb, bucket.row_index)
        return scores

    def _re_scores_for(
        self, s: _ReState, data: GameDataset, n_pad: int
    ) -> Array:
        """All-lane RE scores on an ARBITRARY dataset: one host pass maps
        the dataset's entity values through the training vocabulary to
        (bucket, position); the per-config coefficient gather runs on
        device (no per-config host round trips)."""
        idc = data.id_columns.get(s.red.id_name)
        if idc is None:
            raise KeyError(
                f"dataset lacks id column '{s.red.id_name}' needed by "
                f"coordinate '{s.name}'"
            )
        codes = map_vocab_codes(s.vocab, idc.vocab[idc.codes])
        known = codes >= 0
        safe = np.where(known, codes, 0)
        row_bucket = np.where(known, s.red.entity_bucket[safe], -1)
        row_pos = np.where(known, s.red.entity_pos[safe], -1)

        batch = data.shard(s.red.shard_name)
        n = data.num_rows
        vals = np.asarray(batch.values)
        rows = np.asarray(batch.rows)
        cols = np.asarray(batch.cols)
        live = (vals != 0) & (rows < n)
        scores = jnp.zeros((self.size, n_pad), jnp.float32)
        for b_idx, (table, bucket) in enumerate(zip(s.tables, s.red.buckets)):
            sel = live & (row_bucket[np.minimum(rows, n - 1)] == b_idx)
            if not np.any(sel):
                continue
            part = np.nonzero(sel)[0]
            scores = _re_val_scorer()(
                scores,
                table,
                jnp.asarray(bucket.projection),
                jnp.asarray(vals[part], jnp.float32),
                jnp.asarray(rows[part], jnp.int32),
                jnp.asarray(row_pos[rows[part]], jnp.int32),
                jnp.asarray(cols[part], jnp.int32),
            )
        return scores

    def validation_scores(self, data: GameDataset) -> Array:
        """Raw model scores (no offsets) of EVERY config lane on ``data``
        as one [G, n_pad] device array."""
        n_pad = max(b.num_rows for b in data.feature_shards.values())
        total = jnp.zeros((self.size, n_pad), jnp.float32)
        for s in self._states.values():
            if isinstance(s, _FeState):
                total = total + self._fe_scores(s, data, n_pad)
            else:
                total = total + self._re_scores_for(s, data, n_pad)
        return total

    # -- model materialization ----------------------------------------------

    def model_for(self, g: int) -> GameModel:
        """The GAME model of config lane ``g`` (host slicing of the device
        tables; used once, for the selected winner)."""
        if not 0 <= g < self.size:
            raise IndexError(f"config index {g} out of range [0, {self.size})")
        models: dict = {}
        for name, s in self._states.items():
            if isinstance(s, _FeState):
                models[name] = FixedEffectModel(
                    coefficients=s.original_w()[g],
                    shard_name=s.shard_name,
                )
            else:
                buckets = tuple(
                    RandomEffectBucketModel(
                        coefficients=table[g],
                        projection=bucket.projection,
                        entity_codes=bucket.entity_codes,
                    )
                    for table, bucket in zip(s.tables, s.red.buckets)
                )
                models[name] = RandomEffectModel(
                    id_name=s.red.id_name,
                    shard_name=s.red.shard_name,
                    buckets=buckets,
                    entity_bucket=s.red.entity_bucket,
                    entity_pos=s.red.entity_pos,
                    vocab=s.vocab,
                )
        return GameModel(task=self.task, models=models)

    def emit_config_spans(
        self,
        metrics: Optional[np.ndarray] = None,
        metric_name: Optional[str] = None,
    ) -> None:
        conv = self.convergence()
        iterations = np.max(
            np.stack([c["iterations"] for c in conv.values()]), axis=0
        )
        # lane reason: the worst (unconverged-first) across coordinates
        reasons = None
        for c in conv.values():
            r = c["reasons"]
            reasons = r if reasons is None else np.where(
                (reasons == MAX_ITERATIONS) | (reasons == NOT_CONVERGED),
                reasons,
                r,
            )
        values = np.sum(np.stack([c["values"] for c in conv.values()]), axis=0)
        lams = self.lambdas
        first = next(iter(lams.values()))
        _emit_config_spans(
            first,
            lams,
            iterations,
            reasons,
            values=values,
            metrics=metrics,
            metric_name=metric_name,
        )


def _build_fe_state(name, c, data, G, lams, task):
    from photon_ml_tpu.data.normalization import (
        NormalizationType,
        build_normalization_context,
    )
    from photon_ml_tpu.data.stats import summarize

    c.optimizer.validate(task)
    norm = None
    if NormalizationType(c.normalization) != NormalizationType.NONE:
        summary = summarize(data.batch_for(c.shard_name))
        norm = build_normalization_context(
            NormalizationType(c.normalization),
            summary,
            intercept_index=c.intercept_index,
        )
        if c.optimizer.box_constraints:
            raise SweepUnsupportedError(
                f"coordinate '{name}': box constraints under normalization "
                "are not batched by the sweep path; use GameEstimator.fit"
            )
    if c.optimizer.down_sampling_rate < 1.0:
        raise SweepUnsupportedError(
            f"coordinate '{name}': down-sampling re-draws per update and is "
            "not batched by the sweep path; use GameEstimator.fit_grid"
        )
    batch = data.batch_for(c.shard_name).device()
    key_cfg = dataclasses.replace(c.optimizer, regularization_weight=0.0)
    l2s, l1s = split_reg_weights(c.optimizer.regularization, lams)
    constraints = c.optimizer.build_box_constraints(int(batch.num_features))
    base_obj = make_objective(
        task,
        factors=None if norm is None else norm.factors,
        shifts=None if norm is None else norm.shifts,
    )
    return _FeState(
        name=name,
        shard_name=c.shard_name,
        config=c.optimizer,
        lambdas=lams,
        batch=batch,
        l2s=l2s,
        l1s=l1s,
        constraints=constraints,
        normalization=norm,
        solver=_fe_sweep_solver(key_cfg, with_residual=True),
        W=jnp.zeros((G, int(batch.num_features)), jnp.float32),
    ), base_obj


def _build_re_state(name, c, data, G, lams, task) -> _ReState:
    from photon_ml_tpu.game.random_effect_data import (
        build_random_effect_dataset,
    )

    c.optimizer.validate(task)
    if c.projector != "index_map":
        raise SweepUnsupportedError(
            f"coordinate '{name}': projector '{c.projector}' is not batched "
            "by the sweep path (index_map only); use GameEstimator.fit_grid"
        )
    if c.optimizer.box_constraints:
        raise SweepUnsupportedError(
            f"coordinate '{name}': per-entity box constraints are not "
            "batched by the sweep path; use GameEstimator.fit_grid"
        )
    red = build_random_effect_dataset(
        data,
        c.id_name,
        c.shard_name,
        active_rows_per_entity=c.active_rows_per_entity,
        min_rows_per_entity=c.min_rows_per_entity,
        features_to_samples_ratio=c.features_to_samples_ratio,
    )
    if len(red.passive_rows):
        raise SweepUnsupportedError(
            f"coordinate '{name}': active-row caps leave passive rows, "
            "which the sweep scoring path does not batch; drop "
            "active_rows_per_entity or use GameEstimator.fit_grid"
        )
    key_cfg = dataclasses.replace(c.optimizer, regularization_weight=0.0)
    l2s, l1s = split_reg_weights(c.optimizer.regularization, lams)
    ebatches = tuple(b.entity_batch().device() for b in red.device_buckets())
    tables = [
        jnp.zeros((G, b.num_entities, b.num_local_features), jnp.float32)
        for b in red.buckets
    ]
    return _ReState(
        name=name,
        config=c.optimizer,
        lambdas=lams,
        red=red,
        ebatches=ebatches,
        l2s=l2s,
        l1s=l1s,
        solver=_re_sweep_solver(key_cfg),
        tables=tables,
        vocab=data.id_columns[c.id_name].vocab,
    )


def sweep_game(
    config,
    data: GameDataset,
    grid: SweepGrid,
    *,
    num_iterations: Optional[int] = None,
    warm_start: bool = True,
) -> GameSweepResult:
    """Run coordinate descent over ALL G configs simultaneously.

    ``config`` is a :class:`~photon_ml_tpu.game.estimator.GameConfig`;
    every coordinate must be a fixed-effect or an index-map random-effect
    block (:class:`SweepUnsupportedError` names anything else). The
    updating sequence and residual trick follow ``run_coordinate_descent``
    exactly, with every score/residual carrying the leading config axis.
    From the second CD iteration on, unconverged lanes warm-start from
    their more-regularized neighbor (:func:`path_warm_start`).
    """
    from photon_ml_tpu.game.estimator import (
        FixedEffectConfig,
        RandomEffectConfig,
    )

    G = grid.size
    if num_iterations is None:
        num_iterations = config.num_iterations
    states: dict = {}
    objs: dict = {}
    for name, c in config.coordinates.items():
        lams = grid.for_coordinate(name)
        if isinstance(c, FixedEffectConfig):
            states[name], objs[name] = _build_fe_state(
                name, c, data, G, lams, config.task
            )
        elif isinstance(c, RandomEffectConfig):
            states[name] = _build_re_state(name, c, data, G, lams, config.task)
            objs[name] = make_objective(config.task)
        else:
            raise SweepUnsupportedError(
                f"coordinate '{name}': {type(c).__name__} is not batched by "
                "the sweep path; use GameEstimator.fit_grid"
            )

    names = list(states)
    n_pad = max(b.num_rows for b in data.feature_shards.values())
    scores: dict[str, Array] = {
        name: jnp.zeros((G, n_pad), jnp.float32) for name in names
    }
    history: list[dict] = []
    total_steps = max(num_iterations * len(names), 1)
    telemetry.gauge("sweep.configs_total").set(G)
    telemetry.gauge("sweep.configs_done").set(0)

    result = GameSweepResult(config.task, states, history, n_pad)
    with telemetry.span(
        "sweep", task=config.task, configs=G, num_coordinates=len(names)
    ):
        for it in range(num_iterations):
            with telemetry.span("sweep_iteration", iteration=it):
                for idx, name in enumerate(names):
                    s = states[name]
                    with telemetry.span(
                        f"coordinate:{name}", iteration=it
                    ) as sp:
                        residual = None
                        if len(names) > 1:
                            residual = sum(
                                (scores[o] for o in names if o != name),
                                start=jnp.zeros_like(scores[name]),
                            )
                        if isinstance(s, _FeState):
                            _update_fe(s, objs[name], residual, it, warm_start)
                            scores[name] = result._fe_scores(s, data, n_pad)
                        else:
                            _update_re(s, objs[name], residual, it, warm_start)
                            scores[name] = result._re_training_scores(s, n_pad)
                        telemetry.sync_fetch(
                            scores[name][0, 0], label=f"sweep:{name}"
                        )
                        seconds = telemetry.trace.TRACER.now() - sp.ts
                        sp.set_attr(seconds=round(seconds, 6))
                    telemetry.counter("sweep.solves").inc(G)
                    step = it * len(names) + idx + 1
                    telemetry.gauge("sweep.configs_done").set(
                        int(G * step / total_steps)
                    )
                    history.append(
                        {
                            "iteration": it,
                            "coordinate": name,
                            "seconds": round(seconds, 6),
                            "configs": G,
                        }
                    )
    return result


def _update_fe(s: _FeState, obj, residual, it: int, warm_start: bool) -> None:
    G = len(s.lambdas)
    w0 = s.W
    if warm_start and it > 0 and s.reasons is not None:
        w0 = path_warm_start(s.W, s.reasons)
    if residual is None:
        residual = jnp.zeros((G, s.batch.num_rows), jnp.float32)
    res = s.solver(obj, s.batch, residual, w0, s.l2s, s.l1s, s.constraints)
    s.W = res.w
    s.reasons = res.reason
    s.iterations = res.iterations
    s.values = res.value


def _update_re(s: _ReState, obj, residual, it: int, warm_start: bool) -> None:
    G = len(s.lambdas)
    lane_un = None
    iters_parts = []
    values_parts = []
    for i, (eb, bucket) in enumerate(zip(s.ebatches, s.red.buckets)):
        if residual is not None:
            extra = _re_residual_gather()(residual, bucket.row_index)
        else:
            extra = jnp.zeros(
                (G,) + tuple(bucket.row_index.shape), jnp.float32
            )
        w0 = s.tables[i]
        if warm_start and it > 0 and s.reasons is not None:
            w0 = path_warm_start(w0, s.reasons)
        res = s.solver(obj, eb, extra, w0, s.l2s, s.l1s)
        s.tables[i] = res.w
        un = _lane_unconverged(res.reason)
        lane_un = un if lane_un is None else (lane_un | un)
        iters_parts.append(jnp.max(res.iterations, axis=1))
        values_parts.append(jnp.sum(res.value, axis=1))
    # lane-level aggregates: worst reason, max iterations, summed values
    s.reasons = jnp.where(
        lane_un,
        jnp.int32(MAX_ITERATIONS),
        jnp.int32(FUNCTION_VALUES_CONVERGED),
    )
    s.iterations = jnp.max(jnp.stack(iters_parts), axis=0)
    s.values = jnp.sum(jnp.stack(values_parts), axis=0)
