"""Block coordinate descent over named GAME coordinates.

Reference analog: photon-lib algorithm/CoordinateDescent.scala:93-271. Per
iteration, per coordinate (in update-sequence order): the coordinate's
training offsets become base_offset + sum of OTHER coordinates' scores (the
residual trick, :152-156), its sub-model is retrained warm-started, its
scores are recomputed, and the full model is validated; the best model by
the FIRST validation evaluator is tracked across full-model states only
(:130-137).

Scores live as [n_pad] device arrays keyed by coordinate name — the
KeyValueScore analog, where "+" is vector addition instead of an RDD join.
The loop itself is host-side Python (as in the reference); all per-step
compute is jit-compiled device work.

Span tree of one call (every caller — ``GameEstimator.fit``, sweeps, the
incremental path, a benchmark — gets it, because it opens here)::

    coordinate_descent
      initial_scores          ends on a 1-element fetch per coordinate
      cd_iteration
        coordinate:<name>     history ``seconds`` = its start -> score fetch
          residual            only with more than one coordinate
          update              to the end of the tracker's and guard's fetch
            re_bucket:<R>x<K>   a random effect only: one bucket's dispatch
            re_tracker          and the wait on every bucket's solve
          score               coord.score + its 1-element fetch
          validate            validation scoring, evaluators, their fetches
            validation_layout   first validation of a dataset only: its rows
            validation_upload   laid out like a tiled coordinate's design
"""

from __future__ import annotations

import dataclasses
import logging
from functools import lru_cache
from typing import Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import faults, telemetry
from photon_ml_tpu.telemetry import memory as telemetry_memory
from photon_ml_tpu.evaluation import EVALUATORS, better_than, sharded_auc, sharded_precision_at_k
from photon_ml_tpu.evaluation.evaluators import parse_evaluator
from photon_ml_tpu.game.checkpoint import (
    CheckpointError,
    CheckpointManager,
    CheckpointState,
    TrainingInterrupted,
)
from photon_ml_tpu.game.dataset import GameDataset
from photon_ml_tpu.game.models import GameModel
from photon_ml_tpu.optim.guard import (
    FP_SOLVE_HEALTH,
    GuardSpec,
    model_is_finite,
)

logger = logging.getLogger("photon_ml_tpu.game")

# Injection seam between a completed (iteration, coordinate) step and its
# checkpoint/stop handling — an injected raise here must leave the last
# step's checkpoint intact and resumable.
_FP_STEP_BOUNDARY = faults.register_point(
    "cd.step.boundary",
    description="after a CD step completes, before checkpoint/stop logic",
)


@dataclasses.dataclass
class ValidationSpec:
    data: GameDataset
    evaluators: Sequence[str]  # first one selects the best model


@dataclasses.dataclass
class CoordinateDescentResult:
    model: GameModel
    best_model: GameModel
    best_metric: Optional[float]
    history: list[dict]  # per (iteration, coordinate) telemetry


def padded_validation_arrays(
    data: GameDataset, n_pad: int
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """(labels, weights, offsets) as [n_pad] f32 device arrays with
    weight-0 padding rows — the evaluator input layout. Shared by the CD
    validation path below and the sweep selector (sweep/select.py), so
    both score against identical padded arrays. Built and uploaded once a
    dataset and row count, and kept with the dataset (as
    ``GameDataset.device_shard`` keeps its copy)."""
    cache = data.__dict__.setdefault("_validation_arrays", {})
    hit = cache.get(n_pad)
    if hit is None:

        def pad(a):
            out = np.zeros((n_pad,), np.float32)
            out[: data.num_rows] = a
            return jnp.asarray(out)

        hit = cache[n_pad] = (
            pad(data.response), pad(data.weight), pad(data.offset))
    return hit


@lru_cache(maxsize=64)
def _evaluator_program(kind: str, num_groups: int = 0, k: int = 0):
    """One evaluator as a named executable ``evaluate_<kind>`` over the
    sub-models' score vectors: their sum, the offsets and the metric in one
    program (eagerly, ``auc`` alone was a dozen one-op programs a call)."""

    def run(parts, offsets, labels, weights, group_ids=None):
        full_scores = sum(parts[1:], start=parts[0]) + offsets
        if kind == "sharded_auc":
            return sharded_auc(
                full_scores, labels, weights, group_ids, num_groups)
        if kind == "sharded_precision_at_k":
            return sharded_precision_at_k(
                full_scores, labels, weights, group_ids, num_groups, k)
        return EVALUATORS[kind](full_scores, labels, weights)

    return telemetry.instrumented_jit(
        run, name="evaluate_" + kind, multi_shape=True)


def _evaluate(
    model: GameModel,
    spec: ValidationSpec,
    coordinates: Optional[Mapping[str, object]] = None,
) -> dict[str, float]:
    """Validation metrics of ``model``: one accounted fetch per evaluator
    (the host's wait on validation scoring and the evaluator's program).
    A sub-model whose coordinate (``coordinates``, by name) can score
    another dataset's rows through its own layout does (``score_dataset``:
    a tiled fixed effect); every other one is scored by ``model.score``.
    Either way a score vector has the dataset's padded row count."""

    def fetch(value, spec_str: str) -> float:
        return float(telemetry.sync_fetch(value, label=f"evaluate:{spec_str}"))

    if not model.models:
        raise ValueError("GAME model has no sub-models")
    through_layout = {
        name: coord.score_dataset
        for name, coord in (coordinates or {}).items()
        if hasattr(coord, "score_dataset")}
    parts = tuple(
        through_layout[name](sub, spec.data) if name in through_layout
        else sub.score(spec.data)
        for name, sub in model.models.items())
    n = spec.data.num_rows
    n_pad = parts[0].shape[0]
    labels, weights, offsets = padded_validation_arrays(spec.data, n_pad)

    out = {}
    for spec_str in spec.evaluators:
        kind, group_col, k = parse_evaluator(spec_str)
        if kind in EVALUATORS:
            out[spec_str] = fetch(
                _evaluator_program(kind)(parts, offsets, labels, weights),
                spec_str,
            )
            continue
        col = next(
            (c for c in spec.data.id_columns if c.lower() == group_col), None
        )
        if col is None:
            raise KeyError(
                f"evaluator '{spec_str}' needs id column '{group_col}'; "
                f"have {sorted(spec.data.id_columns)}"
            )
        idc = spec.data.id_columns[col]
        gids = jnp.asarray(
            np.pad(idc.codes, (0, n_pad - n)), jnp.int32
        )
        out[spec_str] = fetch(
            _evaluator_program(kind, idc.num_entities, k or 0)(
                parts, offsets, labels, weights, gids),
            spec_str,
        )
    return out


def _num_coefficients(model) -> int:
    """Coefficient count of a coordinate model — shape metadata only, no
    device transfer. Feeds the ``progress.coeffs`` counter the heartbeat
    and run report turn into coeffs/s."""
    if model is None:
        return 0
    coeffs = getattr(model, "coefficients", None)
    if coeffs is not None:
        return int(getattr(coeffs, "size", 0))
    buckets = getattr(model, "buckets", None)
    if buckets is not None:
        return sum(_num_coefficients(b) for b in buckets)
    models = getattr(model, "models", None)
    if isinstance(models, Mapping):
        return sum(_num_coefficients(m) for m in models.values())
    return sum(
        int(getattr(leaf, "size", 0)) for leaf in jax.tree.leaves(model)
    )


def _record_step_progress(coord, model, name: str, seconds: float) -> None:
    """Publish per-step progress + memory telemetry: the rows/coeffs
    counters (heartbeat rate sources), the rows/s / coeffs/s gauges (run
    report key metrics), and the per-coordinate HBM phase peak."""
    rows = int(getattr(getattr(coord, "data", None), "num_rows", 0) or 0)
    coeffs = _num_coefficients(model)
    if rows:
        telemetry.counter("progress.rows").inc(rows)
    if coeffs:
        telemetry.counter("progress.coeffs").inc(coeffs)
    if seconds > 0:
        if rows:
            telemetry.gauge("progress.rows_per_sec").set(rows / seconds)
        if coeffs:
            telemetry.gauge("progress.coeffs_per_sec").set(coeffs / seconds)
    telemetry_memory.record_phase_memory(f"coordinate:{name}")


def _guarded_update(coord, model, residual, guard: GuardSpec, name: str):
    """One guarded coordinate update: solve, health-check, damped retries,
    rollback. Returns ``(model', attempts_used, rolled_back)``.

    Coordinates exposing ``extra_l2`` get damped retries (the l2 leaf is
    traced, so retries reuse the compiled solver); others — whose re-run
    would be bit-identical — roll straight back after the first divergence.
    """
    supports_damping = hasattr(coord, "extra_l2")
    if hasattr(coord, "health_check"):
        coord.health_check = True  # opt the coordinate into health reduces
    max_attempts = (guard.max_retries if supports_damping else 0) + 1
    for attempt in range(max_attempts):
        if attempt:
            telemetry.counter("solves.retried").inc()
            logger.warning(
                "coordinate %s diverged; retrying with extra L2 damping %g",
                name, guard.damping_for(attempt),
            )
        if supports_damping:
            coord.extra_l2 = guard.damping_for(attempt)
        try:
            new_model = coord.update_model(model, residual)
        finally:
            if supports_damping:
                coord.extra_l2 = 0.0
        health = getattr(coord, "last_health", None)
        if health is None:
            health = model_is_finite(new_model)
        # injection seam: a `nan` rule marks THIS solve diverged,
        # exercising the damped-retry/rollback path deterministically
        health = faults.corrupt_health(FP_SOLVE_HEALTH, health)
        if bool(telemetry.sync_fetch(health, label=f"guard:{name}")):
            return new_model, attempt, False
        telemetry.counter("solves.diverged").inc()
    telemetry.counter("solves.rolled_back").inc()
    logger.warning(
        "coordinate %s still diverging after %d attempt(s); rolling back "
        "to the pre-solve model", name, max_attempts,
    )
    return model, max_attempts - 1, True


def run_coordinate_descent(
    coordinates: Mapping[str, object],
    task: str,
    num_iterations: int,
    validation: Optional[ValidationSpec] = None,
    initial_models: Optional[Mapping[str, object]] = None,
    on_step=None,
    guard: Optional[GuardSpec] = None,
    checkpoint: Optional[CheckpointManager] = None,
    should_stop=None,
) -> CoordinateDescentResult:
    """Train all coordinates for ``num_iterations`` outer sweeps.

    ``coordinates`` is ordered (the updating sequence). ``initial_models``
    enables warm-starting whole coordinates from a previous run.
    ``on_step(entry)`` fires after every (iteration, coordinate) update
    with that step's telemetry dict (the event-bus hook).

    Fault tolerance (game.checkpoint / optim.guard):

    - ``checkpoint``: a CheckpointManager. On entry the newest valid
      checkpoint is restored — models reloaded, completed steps skipped,
      scores recomputed; after each completed step (per the spec's
      ``every``) the full state is atomically persisted.
    - ``guard``: a GuardSpec; every coordinate solve is health-checked and
      diverging solves are retried with escalating L2 damping, then rolled
      back. A coordinate rolling back ``freeze_after`` consecutive times is
      frozen (skipped; its last good model keeps scoring).
    - ``should_stop``: zero-arg predicate polled after every step; when it
      turns true a final checkpoint is written and TrainingInterrupted is
      raised (the graceful-preemption handshake).
    """
    with telemetry.span(
        "coordinate_descent",
        num_iterations=num_iterations,
        num_coordinates=len(coordinates),
    ):
        names = list(coordinates)
        models = {
            name: (
                initial_models[name]
                if initial_models and name in initial_models
                else coordinates[name].initialize_model()
            )
            for name in names
        }

        best_model: Optional[GameModel] = None
        best_metric: Optional[float] = None
        history: list[dict] = []
        start_step = 0
        if checkpoint is not None:
            restored = checkpoint.restore()
            if restored is not None:
                if list(restored.model.models) != names:
                    raise CheckpointError(
                        f"checkpoint at {checkpoint.spec.directory} was written "
                        f"by a fit with coordinates "
                        f"{list(restored.model.models)}, not {names}"
                    )
                models = dict(restored.model.models)
                best_model = restored.best_model
                best_metric = restored.best_metric
                history = list(restored.history)
                start_step = restored.step + 1
        # scores recomputed from the (possibly restored) models — checkpoints
        # persist models only; scores are derived state
        with telemetry.span("initial_scores"):
            scores = {}
            for name in names:
                scores[name] = coordinates[name].score(models[name])
                # wait for them, or the first `update` span holds their
                # device time (dispatch does not wait)
                telemetry.sync_fetch(
                    scores[name][0], label=f"initial_scores:{name}"
                )

        # guard bookkeeping survives resume: a coordinate already proved
        # divergent must not re-burn its retries every remaining iteration.
        # Restored ONLY when a guard is active — resuming with guard=None is
        # an explicit request to train every coordinate again.
        frozen: set[str] = set()
        consecutive_rollbacks = {name: 0 for name in names}
        if guard is not None and checkpoint is not None and restored is not None:
            frozen = {n for n in restored.frozen if n in consecutive_rollbacks}
            for n, count in (restored.consecutive_rollbacks or {}).items():
                if n in consecutive_rollbacks:
                    consecutive_rollbacks[n] = int(count)
        last_ckpt_path: Optional[str] = None

        for it in range(num_iterations):
            with telemetry.span("cd_iteration", iteration=it):
                for idx, name in enumerate(names):
                    step = it * len(names) + idx
                    if step < start_step:
                        continue  # completed before the restored checkpoint
                    if name in frozen:
                        continue  # divergent coordinate: last good model stands
                    coord = coordinates[name]
                    with telemetry.span(f"coordinate:{name}", iteration=it) as sp:
                        residual = None
                        if len(names) > 1:
                            with telemetry.span("residual"):
                                residual = sum(
                                    (scores[o] for o in names if o != name),
                                    start=jnp.zeros_like(scores[name]),
                                )
                                if guard is not None:
                                    # a NaN-scoring coordinate (e.g. rolled
                                    # back to zeros over NaN features) must
                                    # not poison its neighbors' solves
                                    # through the residual
                                    residual = jnp.nan_to_num(
                                        residual, nan=0.0, posinf=0.0,
                                        neginf=0.0,
                                    )
                        rolled_back = False
                        attempts = 0
                        with telemetry.span("update"):
                            if guard is None:
                                models[name] = coord.update_model(
                                    models[name], residual
                                )
                            else:
                                models[name], attempts, rolled_back = (
                                    _guarded_update(
                                        coord, models[name], residual, guard,
                                        name,
                                    )
                                )
                        with telemetry.span("score"):
                            if not rolled_back:
                                # a rolled-back model is unchanged; its
                                # scores stand
                                scores[name] = coord.score(models[name])
                            # wait for the scores before stopping the clock:
                            # a 1-element fetch through the accounted crossing
                            telemetry.sync_fetch(
                                scores[name][0], label=f"coordinate:{name}"
                            )

                        entry = {
                            "iteration": it,
                            "coordinate": name,
                            "seconds": telemetry.trace.TRACER.now() - sp.ts,
                        }
                        if guard is not None and (attempts or rolled_back):
                            entry["solve_retries"] = attempts
                            entry["rolled_back"] = rolled_back
                        tracker = getattr(coord, "last_tracker", None)
                        if tracker is not None and not rolled_back:
                            # per-update optimization telemetry (the reference's
                            # OptimizationTracker surfaced in CD logs)
                            entry["tracker"] = tracker.to_summary_string()
                        if validation is not None:
                            game_model = GameModel(task=task, models=dict(models))
                            with telemetry.span("validate"):
                                metrics = _evaluate(
                                    game_model, validation, coordinates)
                            entry["metrics"] = metrics
                            primary = validation.evaluators[0]
                            value = metrics[primary]
                            if best_metric is None or better_than(
                                primary, value, best_metric
                            ):
                                best_metric = value
                                best_model = game_model
                            logger.info(
                                "CD iter %d coord %s: %s (%.2fs)", it, name,
                                metrics, entry["seconds"],
                            )
                        sp.set_attr(seconds=round(entry["seconds"], 6))
                        _record_step_progress(
                            coord, models[name], name, entry["seconds"]
                        )
                    history.append(entry)
                    if on_step is not None:
                        on_step(entry)

                    if rolled_back:
                        consecutive_rollbacks[name] += 1
                        if consecutive_rollbacks[name] >= guard.freeze_after:
                            frozen.add(name)
                            telemetry.counter("solves.frozen").inc()
                            logger.warning(
                                "coordinate %s frozen after %d consecutive "
                                "rollbacks; its last good model keeps scoring",
                                name, consecutive_rollbacks[name],
                            )
                    else:
                        consecutive_rollbacks[name] = 0

                    faults.fault_point(_FP_STEP_BOUNDARY)
                    stop = should_stop is not None and should_stop()
                    if checkpoint is not None and (
                        stop or checkpoint.should_save(step)
                    ):
                        last_ckpt_path = checkpoint.save(
                            CheckpointState(
                                step=step,
                                model=GameModel(task=task, models=dict(models)),
                                best_model=best_model,
                                best_metric=best_metric,
                                history=history,
                                frozen=sorted(frozen),
                                consecutive_rollbacks=dict(consecutive_rollbacks),
                            )
                        )
                    if stop:
                        raise TrainingInterrupted(step, last_ckpt_path)

        final = GameModel(task=task, models=dict(models))
        if best_model is None:
            best_model = final
        return CoordinateDescentResult(
            model=final, best_model=best_model, best_metric=best_metric, history=history
        )
