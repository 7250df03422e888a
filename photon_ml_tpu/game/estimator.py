"""GameEstimator: typed-config end-to-end GAME training.

Reference analog: photon-client estimators/GameEstimator.scala:53-472 (the
programmatic fit surface) and GameParams.scala:215-492 (the flag system).
One typed config replaces both (SURVEY.md §5 "Config / flag system"): it
names the coordinates in updating-sequence order, their shards/optimizers/
normalization, the evaluators, and the CD schedule; ``fit`` builds the
datasets and coordinates, runs coordinate descent, and returns the final +
best models, optionally persisting them (the training driver's
"best/" output layout, cli/game/training/Driver.scala:262-312).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Mapping, Optional, Sequence

from jax.sharding import Mesh

from photon_ml_tpu import telemetry
from photon_ml_tpu.telemetry import memory as telemetry_memory
from photon_ml_tpu.data.normalization import (
    NormalizationContext,
    NormalizationType,
    build_normalization_context,
)
from photon_ml_tpu.data.stats import summarize
from photon_ml_tpu.game.coordinate_descent import (
    CoordinateDescentResult,
    ValidationSpec,
    run_coordinate_descent,
)
from photon_ml_tpu.game.coordinates import (
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.game.factored import FactoredRandomEffectCoordinate
from photon_ml_tpu.game.dataset import GameDataset
from photon_ml_tpu.game.models import GameModel
from photon_ml_tpu.game.random_effect_data import build_random_effect_dataset
from photon_ml_tpu.optim.factory import OptimizerConfig
from photon_ml_tpu.parallel.mesh import DATA_AXIS, ENTITY_AXIS


@dataclasses.dataclass(frozen=True)
class FixedEffectConfig:
    """One global GLM coordinate (FixedEffectDataConfiguration +
    GLMOptimizationConfiguration analog)."""

    shard_name: str
    optimizer: OptimizerConfig = OptimizerConfig()
    normalization: NormalizationType | str = NormalizationType.NONE
    intercept_index: Optional[int] = None
    down_sampling_seed: int = 0
    # training layout: "auto" picks the tiled one-hot-matmul pallas fast
    # path on TPU and padded-COO elsewhere; "tiled"/"coo" force it
    layout: str = "auto"


@dataclasses.dataclass(frozen=True)
class RandomEffectConfig:
    """One per-entity coordinate (RandomEffectDataConfiguration analog:
    randomEffectType = id_name, featureShardId = shard_name, active-data
    caps as in RandomEffectDataSet.scala:294-357, projectorType, and the
    numFeaturesToSamplesRatio Pearson bound of :420-434)."""

    shard_name: str
    id_name: str
    optimizer: OptimizerConfig = OptimizerConfig()
    active_rows_per_entity: Optional[int] = None
    min_rows_per_entity: int = 1
    # cap each entity's feature count at ceil(ratio * its row count), picked
    # by |Pearson(feature, label)| (numFeaturesToSamplesRatioUpperBound)
    features_to_samples_ratio: Optional[float] = None
    # "index_map": per-entity observed-feature reindexing (default);
    # "random": shared Gaussian random projection into projected_dim dims
    # (ProjectorType.{INDEX_MAP,RANDOM}_PROJECTION analog)
    projector: str = "index_map"
    projected_dim: Optional[int] = None
    projection_seed: int = 0
    projection_intercept_index: Optional[int] = None
    # per-coefficient posterior variances via Hessian-diagonal inverse at
    # each entity's optimum (SingleNodeOptimizationProblem.scala:57-88)
    compute_variances: bool = False

    def __post_init__(self):
        if self.projector == "random" and self.compute_variances:
            raise ValueError(
                "compute_variances needs the index_map projector: under a "
                "Gaussian random projection the local coordinates are mixtures "
                "of global features, so per-coefficient variances have no "
                "original-space meaning"
            )
        if self.projector not in ("index_map", "random"):
            raise ValueError(f"unknown projector '{self.projector}'")
        if self.projector == "random" and not self.projected_dim:
            raise ValueError("projector='random' requires projected_dim")


@dataclasses.dataclass(frozen=True)
class FactoredRandomEffectConfig:
    """One factored (matrix-factorization) random-effect coordinate
    (FactoredRandomEffectOptimizationProblem + MFOptimizationConfiguration
    analog: latent_dim = numLatentFactors, mf_iterations = numIterations)."""

    shard_name: str
    id_name: str
    latent_dim: int
    mf_iterations: int = 1
    re_optimizer: OptimizerConfig = OptimizerConfig()
    latent_optimizer: OptimizerConfig = OptimizerConfig()
    active_rows_per_entity: Optional[int] = None
    min_rows_per_entity: int = 1
    seed: int = 0
    # the refit's base design: "auto" tiles it on a TPU, as a fixed effect's
    layout: str = "auto"


@dataclasses.dataclass(frozen=True)
class GameConfig:
    """Full training configuration (GameParams analog).

    ``coordinates`` is ordered: iteration order IS the updating sequence
    (GameEstimator.scala updatingSequence). The first evaluator selects the
    best model (CoordinateDescent.scala:130-137).
    """

    task: str
    coordinates: Mapping[
        str, FixedEffectConfig | RandomEffectConfig | FactoredRandomEffectConfig
    ]
    num_iterations: int = 1
    evaluators: Sequence[str] = ()

    def __post_init__(self):
        if not self.coordinates:
            raise ValueError("GameConfig needs at least one coordinate")


@dataclasses.dataclass
class GameFitResult:
    model: GameModel
    best_model: GameModel
    best_metric: Optional[float]
    history: list


@dataclasses.dataclass
class SweepFitResult:
    """A finished vmapped λ sweep: the selection, the winning model, and
    the full per-config record (sweep.runner.GameSweepResult)."""

    model: GameModel  # the selected winner
    selection: "SweepSelection"
    sweep: "GameSweepResult"
    published_version: Optional[str] = None  # registry path when exported


@dataclasses.dataclass
class GridFitEntry:
    """One combination of a fit_grid sweep: the per-coordinate optimizer
    configs used and the resulting fit (the reference's (config, model,
    evaluation) triple)."""

    optimizer_configs: Mapping[str, OptimizerConfig]
    result: GameFitResult


def _record_table_estimate(name: str, red, dim=None) -> None:
    """Publish the predicted HBM residency of one random-effect
    coordinate's coefficient table (``memory.table_bytes.<name>`` gauge)
    and pre-check headroom BEFORE the solve allocates it — the warning
    lands in the log and run report instead of an XLA OOM mid-fit.

    ``dim``: per-entity coefficient dim for projected/factored tables
    (projected_dim / latent_dim); None = the index-map layout, whose table
    is the per-bucket [entities, local_features] stacks."""
    if dim is not None:
        table_bytes = telemetry_memory.estimate_table_bytes(
            red.num_entities, dim
        )
    else:
        table_bytes = sum(
            telemetry_memory.estimate_table_bytes(
                b.num_entities, b.num_local_features
            )
            for b in red.buckets
        )
    telemetry.gauge(f"memory.table_bytes.{name}").set(table_bytes)
    telemetry_memory.check_headroom(
        table_bytes, label=f"coordinate:{name} coefficient table"
    )


class GameEstimator:
    """Builds datasets + coordinates from a GameConfig and trains via CD."""

    def __init__(self, config: GameConfig):
        from photon_ml_tpu.utils.events import EventEmitter

        self.config = config
        self._re_datasets: dict = {}
        self._coordinates: dict = {}
        # lifecycle event bus (EventEmitter.scala analog); register
        # listeners before fit() to observe setup/start/step/finish events
        self.events = EventEmitter()

    def _re_dataset(self, data: GameDataset, c) -> "RandomEffectDataset":
        """Build (or reuse) the grouped/bucketed RE dataset for a config.

        Keyed by the DATA-side parameters only, so a grid sweep over
        optimizer configs shares one dataset build per coordinate
        (prepareTrainingDataSet is outside the config loop in the
        reference, GameEstimator.scala:135-187 vs :279-398)."""
        ratio = getattr(c, "features_to_samples_ratio", None)
        # a factored coordinate solves every entity at latent_dim: its
        # buckets are classed by rows alone
        by_features = not isinstance(c, FactoredRandomEffectConfig)
        key = (
            id(data), c.id_name, c.shard_name, c.active_rows_per_entity,
            c.min_rows_per_entity, ratio, by_features,
        )
        hit = self._re_datasets.get(key)
        # the cached entry pins a strong reference to its dataset, so the
        # id() in the key cannot be recycled while the entry lives; the
        # identity check guards the (impossible-by-construction) mismatch
        if hit is not None and hit[0] is data:
            return hit[1]
        if len(self._re_datasets) >= 8:  # bound growth on long-lived estimators
            self._re_datasets.pop(next(iter(self._re_datasets)))
        with telemetry.span("layout"):  # host grouping + bucketing
            red = build_random_effect_dataset(
                data,
                c.id_name,
                c.shard_name,
                active_rows_per_entity=c.active_rows_per_entity,
                min_rows_per_entity=c.min_rows_per_entity,
                features_to_samples_ratio=ratio,
                class_by_features=by_features,
            )
        self._re_datasets[key] = (data, red)
        return red

    def _build_coordinates(
        self,
        data: GameDataset,
        mesh: Optional[Mesh],
        opt_overrides: Optional[Mapping[str, OptimizerConfig]] = None,
        only: Optional[set] = None,
    ) -> dict:
        """The fit's coordinates, built or reused, under a
        ``build_coordinates`` span: every caller (``fit``, sweeps, the
        incremental path, a benchmark) gets it. A build runs under a
        ``build:<coordinate>`` span whose ``layout`` / ``upload`` children
        the coordinate and its datasets open; ``build.*`` children name
        the rest (``build.normalization``, ``build.table_estimate`` here,
        ``build.rows``, ``build.objective``, ``build.layout_report``,
        ``build.entity_map`` in the coordinates)."""
        with telemetry.span("build_coordinates") as sp:
            coords, built = self._build_or_reuse(
                data, mesh, opt_overrides or {}, only
            )
            # cached: every coordinate was a reuse — no layout, no upload
            sp.set_attr(cached=not built, built=built)
        return coords

    def _build_or_reuse(
        self, data: GameDataset, mesh: Optional[Mesh], overrides, only
    ) -> tuple[dict, list]:
        """(coordinates, names of those built anew)."""
        # Meshes with named batch/model axes (the GSPMD vocabulary,
        # parallel.sharding; `--mesh batch=N,model=M`) are used AS GIVEN:
        # each coordinate resolves its own axis, so FE rows shard over
        # 'batch' and RE entity state over 'model' on one physical mesh.
        # A legacy 1-D mesh still becomes two logical 1-D views over the
        # same devices ('data' for FE rows, 'entity' for RE batches,
        # SURVEY.md §2.f). Views are free — no data movement.
        data_mesh = entity_mesh = None
        if mesh is not None:
            from photon_ml_tpu.parallel.sharding import BATCH_AXIS, MODEL_AXIS

            named = set(mesh.axis_names) & {BATCH_AXIS, MODEL_AXIS}
            if named or len(mesh.axis_names) > 1:
                from photon_ml_tpu.parallel.sharding import data_axis, model_axis

                if data_axis(mesh) is None and model_axis(mesh) is None:
                    # every coordinate would silently drop the mesh and the
                    # user's N provisioned devices would train single-device
                    raise ValueError(
                        f"mesh axes {mesh.axis_names} name neither a "
                        "batch/data nor a model/entity axis — nothing would "
                        "shard; use --mesh batch=N,model=M (or a 1-D mesh)"
                    )
                data_mesh = entity_mesh = mesh
            else:
                devices = mesh.devices.reshape(-1)
                data_mesh = Mesh(devices, (DATA_AXIS,))
                entity_mesh = Mesh(devices, (ENTITY_AXIS,))
        # the caches serve REPEATED fits over the same data (benchmarks,
        # grid sweeps, warm-started re-fits); entries for other datasets are
        # dropped so device-resident design matrices never pin old data
        self._coordinates = {
            k: v for k, v in self._coordinates.items() if v[0] is data
        }
        self._re_datasets = {
            k: v for k, v in self._re_datasets.items() if v[0] is data
        }
        coords = {}
        built = []
        for name, c in self.config.coordinates.items():
            if only is not None and name not in only:
                continue
            opt = overrides.get(name)
            # reuse a coordinate built for the SAME (data, config, mesh):
            # FE construction in particular re-tiles and re-uploads the full
            # design matrix, which dominates repeated fit() calls
            mesh_key = None if mesh is None else tuple(mesh.devices.reshape(-1))
            cache_key = (id(data), name, opt or "default", mesh_key)
            hit = self._coordinates.get(cache_key)
            if hit is not None and hit[0] is data:
                coord = hit[1]
                # fresh-fit semantics: reset per-fit mutable state so a
                # cached coordinate behaves exactly like a new one (the
                # down-sampling rng salt restarts, stale trackers clear)
                if hasattr(coord, "_update_count"):
                    coord._update_count = 0
                if hasattr(coord, "last_tracker"):
                    coord.last_tracker = None
                if hasattr(coord, "health_check"):
                    # guard state is per-fit: re-opted-in by _guarded_update
                    coord.health_check = False
                    coord.extra_l2 = 0.0
                    coord.last_health = None
                coords[name] = coord
                continue
            with telemetry.span(f"build:{name}"):
                if isinstance(c, FixedEffectConfig):
                    with telemetry.span("build.normalization"):
                        norm = self._normalization_for(data, c)
                    coords[name] = FixedEffectCoordinate(
                        name=name,
                        data=data,
                        shard_name=c.shard_name,
                        loss_name=self.config.task,
                        config=opt or c.optimizer,
                        seed=c.down_sampling_seed,
                        normalization=norm,
                        mesh=data_mesh,
                        layout=c.layout,
                    )
                elif isinstance(c, RandomEffectConfig):
                    red = self._re_dataset(data, c)
                    with telemetry.span("build.table_estimate"):
                        _record_table_estimate(
                            name, red, dim=c.projected_dim
                            if c.projector == "random" else None,
                        )
                    if c.projector == "random":
                        # fixed Gaussian projection: per-entity solves in the
                        # shared projected space (RandomEffectCoordinateIn
                        # ProjectedSpace + ProjectorType.RANDOM analog)
                        coords[name] = FactoredRandomEffectCoordinate(
                            name=name,
                            data=data,
                            re_data=red,
                            loss_name=self.config.task,
                            re_config=opt or c.optimizer,
                            latent_config=opt or c.optimizer,
                            latent_dim=c.projected_dim,
                            refit_projection=False,
                            projection_intercept_index=c.projection_intercept_index,
                            seed=c.projection_seed,
                            mesh=entity_mesh,
                        )
                    else:
                        coords[name] = RandomEffectCoordinate(
                            name=name,
                            data=data,
                            re_data=red,
                            loss_name=self.config.task,
                            config=opt or c.optimizer,
                            mesh=entity_mesh,
                            compute_variances=c.compute_variances,
                        )
                elif isinstance(c, FactoredRandomEffectConfig):
                    red = self._re_dataset(data, c)
                    with telemetry.span("build.table_estimate"):
                        _record_table_estimate(name, red, dim=c.latent_dim)
                    coords[name] = FactoredRandomEffectCoordinate(
                        name=name,
                        data=data,
                        re_data=red,
                        loss_name=self.config.task,
                        re_config=opt or c.re_optimizer,
                        latent_config=c.latent_optimizer,
                        latent_dim=c.latent_dim,
                        mf_iterations=c.mf_iterations,
                        seed=c.seed,
                        mesh=entity_mesh,
                        layout=c.layout,
                    )
                else:
                    raise TypeError(
                        f"coordinate '{name}': unknown config {type(c).__name__}"
                    )
            built.append(name)
            if len(self._coordinates) >= 16:
                self._coordinates.pop(next(iter(self._coordinates)))
            self._coordinates[cache_key] = (data, coords[name])
        return coords, built

    @staticmethod
    def _normalization_for(
        data: GameDataset, c: FixedEffectConfig
    ) -> Optional[NormalizationContext]:
        ntype = NormalizationType(c.normalization)
        if ntype == NormalizationType.NONE:
            return None
        summary = summarize(data.batch_for(c.shard_name))
        return build_normalization_context(
            ntype, summary, intercept_index=c.intercept_index
        )

    def fit(
        self,
        data: GameDataset,
        validation_data: Optional[GameDataset] = None,
        initial_models: Optional[Mapping[str, object]] = None,
        output_dir: Optional[str] = None,
        mesh: Optional[Mesh] = None,
        checkpoint_spec: Optional["CheckpointSpec"] = None,
        guard: Optional["GuardSpec"] = None,
        should_stop=None,
    ) -> GameFitResult:
        """Train; optionally save final + best models under ``output_dir``.

        With ``mesh`` (any device mesh; its flattened device list is used),
        fixed-effect solves shard examples over the devices (DP via
        distributed_solve) and random-effect bucket solves shard the entity
        axis (shard_map, no cross-entity comms) — the GAME analog of the
        reference's cluster mode. Results match the single-device fit.

        Fault tolerance: ``checkpoint_spec`` (game.checkpoint.CheckpointSpec)
        persists coordinate-descent state after each step and resumes from
        the newest valid checkpoint; ``guard`` (optim.guard.GuardSpec)
        health-checks every solve with damped-retry/rollback recovery;
        ``should_stop`` is polled per step — when true, a final checkpoint
        is written and game.checkpoint.TrainingInterrupted raised.

        Output layout mirrors the reference training driver
        (cli/game/training/Driver.scala:262-312): ``<output_dir>/final`` and
        ``<output_dir>/best`` model directories.
        """
        from photon_ml_tpu.game.checkpoint import CheckpointManager
        from photon_ml_tpu.utils.events import (
            OptimizationLogEvent,
            SetupEvent,
            TrainingFinishEvent,
            TrainingStartEvent,
        )
        from photon_ml_tpu.utils.timing import Timer

        t = Timer().start()
        self.events.send(SetupEvent(config=_config_metadata(self.config)))
        with telemetry.span(
            "fit",
            task=self.config.task,
            num_coordinates=len(self.config.coordinates),
        ):
            coordinates = self._build_coordinates(data, mesh)
            telemetry_memory.record_phase_memory("build_coordinates")
            validation = None
            if validation_data is not None:
                if not self.config.evaluators:
                    raise ValueError(
                        "validation data provided but no evaluators"
                    )
                validation = ValidationSpec(
                    data=validation_data,
                    evaluators=list(self.config.evaluators),
                )
            self.events.send(TrainingStartEvent(num_rows=data.num_rows))
            result: CoordinateDescentResult = run_coordinate_descent(
                coordinates,
                task=self.config.task,
                num_iterations=self.config.num_iterations,
                validation=validation,
                initial_models=initial_models,
                on_step=lambda entry: self.events.send(
                    OptimizationLogEvent(
                        iteration=entry["iteration"],
                        coordinate=entry["coordinate"],
                        seconds=entry["seconds"],
                        metrics=entry.get("metrics"),
                    )
                ),
                guard=guard,
                checkpoint=(
                    None if checkpoint_spec is None
                    else CheckpointManager(checkpoint_spec)
                ),
                should_stop=should_stop,
            )
            telemetry_memory.record_phase_memory("fit")
        self.events.send(
            TrainingFinishEvent(
                best_metric=result.best_metric,
                seconds=t.stop(),
                metrics_snapshot=telemetry.snapshot(),
            )
        )
        fit = GameFitResult(
            model=result.model,
            best_model=result.best_model,
            best_metric=result.best_metric,
            history=result.history,
        )
        if output_dir is not None:
            # local import: model_store imports game.models, which would be
            # circular through game/__init__ at module load time
            from photon_ml_tpu.data.model_store import save_game_model

            meta = {
                "config": _config_metadata(self.config),
                "best_metric": result.best_metric,
            }
            save_game_model(
                result.model, os.path.join(output_dir, "final"),
                extra_metadata=meta,
            )
            save_game_model(
                result.best_model, os.path.join(output_dir, "best"),
                extra_metadata=meta,
            )
        return fit

    def fit_incremental(
        self,
        data: GameDataset,
        warm_start,
        delta=None,
        validation_data: Optional[GameDataset] = None,
        output_dir: Optional[str] = None,
        mesh: Optional[Mesh] = None,
        num_iterations: Optional[int] = None,
        lambda_factors=None,
        metric: Optional[str] = None,
        policy: str = "best",
        rel_tol: float = 0.01,
        guard: Optional["GuardSpec"] = None,
        checkpoint_spec: Optional["CheckpointSpec"] = None,
        should_stop=None,
        bootstrap_samples: int = 0,
        bootstrap_seed: int = 0,
    ):
        """Delta-aware warm-start refresh over the COMBINED data.

        ``warm_start`` (:func:`photon_ml_tpu.incremental.load_warm_start`)
        seeds every coordinate from the base model — per-entity rows
        re-homed by entity value, so vocabulary growth zero-inits only
        genuinely new entities. With ``delta``
        (:func:`photon_ml_tpu.incremental.scan_delta`), random-effect
        coordinates re-solve ONLY the touched entities' lanes (untouched
        rows stay bit-identical; zero-touched bucket solves are skipped
        entirely) while the fixed effect refreshes over all rows.

        ``lambda_factors`` (descending multipliers, e.g. from
        :func:`photon_ml_tpu.incremental.local_lambda_factors`) runs a
        small local λ sweep around the incumbent regularization, each
        lane path-warm-started from its more-regularized neighbor, and
        selects with the ``sweep.select`` policies (needs
        ``validation_data``).

        Returns :class:`photon_ml_tpu.incremental.IncrementalFitResult`.
        """
        from photon_ml_tpu.incremental.refit import run_incremental_fit

        result = run_incremental_fit(
            self,
            data,
            warm_start,
            delta=delta,
            validation_data=validation_data,
            mesh=mesh,
            num_iterations=num_iterations,
            lambda_factors=lambda_factors,
            metric=metric,
            policy=policy,
            rel_tol=rel_tol,
            guard=guard,
            checkpoint_spec=checkpoint_spec,
            should_stop=should_stop,
            bootstrap_samples=bootstrap_samples,
            bootstrap_seed=bootstrap_seed,
        )
        if output_dir is not None:
            from photon_ml_tpu.data.model_store import save_game_model
            from photon_ml_tpu.incremental.publish import lineage_record

            meta = {
                "config": _config_metadata(self.config),
                "best_metric": result.best_metric,
                "lineage": lineage_record(result.lineage,
                                          delta=result.delta),
            }
            save_game_model(
                result.model, os.path.join(output_dir, "final"),
                extra_metadata=meta,
            )
            save_game_model(
                result.best_model, os.path.join(output_dir, "best"),
                extra_metadata=meta,
            )
        return result

    def fit_sweep(
        self,
        data: GameDataset,
        validation_data: GameDataset,
        grid: "SweepGrid",
        metric: Optional[str] = None,
        policy: str = "best",
        rel_tol: float = 0.01,
        num_iterations: Optional[int] = None,
        warm_start: bool = True,
        output_dir: Optional[str] = None,
        registry_dir: Optional[str] = None,
        index_maps: Optional[Mapping] = None,
    ) -> SweepFitResult:
        """Train EVERY λ of ``grid`` simultaneously and ship the best.

        The vmapped multi-config path (sweep.runner.sweep_game): one
        batched executable per coordinate update covers all G configs,
        unconverged lanes warm-start from their more-regularized
        neighbor, every lane is scored on device against
        ``validation_data``, and the winner is selected by ``metric``
        (default: the task's ModelSelection metric) under ``policy``.

        With ``output_dir`` the winner is saved under ``<output_dir>/best``
        (the training driver's best/ layout); with ``registry_dir`` (+
        ``index_maps`` pinning the feature space) it is published through
        ``serving.registry.publish_version`` for live hot-swap.
        """
        from photon_ml_tpu.sweep.runner import sweep_game
        from photon_ml_tpu.sweep.select import export_winner, run_selection

        result = sweep_game(
            self.config,
            data,
            grid,
            num_iterations=num_iterations,
            warm_start=warm_start,
        )
        selection = run_selection(
            result, validation_data, metric=metric, policy=policy,
            rel_tol=rel_tol,
        )
        model = result.model_for(selection.index)
        meta = {
            "config": _config_metadata(self.config),
            "sweep_grid": grid.to_json(),
        }
        if output_dir is not None:
            from photon_ml_tpu.data.model_store import save_game_model

            save_game_model(
                model,
                os.path.join(output_dir, "best"),
                extra_metadata={**meta,
                                "sweep_selection": selection.to_json()},
            )
        published = None
        if registry_dir is not None:
            if not index_maps:
                raise ValueError(
                    "publishing a sweep winner to a registry requires "
                    "index_maps (the registry refuses versions without a "
                    "pinned feature space)"
                )
            published = export_winner(
                model, index_maps, registry_dir,
                selection=selection, extra_metadata=meta,
            )
        return SweepFitResult(
            model=model,
            selection=selection,
            sweep=result,
            published_version=published,
        )

    def fit_grid(
        self,
        data: GameDataset,
        validation_data: GameDataset,
        grid: Mapping[str, Sequence[OptimizerConfig]],
        mesh: Optional[Mesh] = None,
    ) -> list["GridFitEntry"]:
        """Sweep the cartesian product of per-coordinate optimizer configs.

        The reference trains one CoordinateDescent run per combination of
        FE x RE x factored-RE optimization configs and returns (config,
        model, evaluation) triples (GameEstimator.scala:279-398). Datasets
        are built once and shared across combinations; compiled solvers are
        shared whenever two combinations agree on a coordinate's config
        (lru-cached jit programs). Entries come back sorted best-first by
        the primary evaluator.
        """
        if not self.config.evaluators:
            raise ValueError("fit_grid needs evaluators to rank combinations")
        unknown = set(grid) - set(self.config.coordinates)
        if unknown:
            raise ValueError(f"grid names unknown coordinates: {sorted(unknown)}")
        import itertools

        from photon_ml_tpu.evaluation import better_than
        from photon_ml_tpu.utils.events import (
            OptimizationLogEvent,
            SetupEvent,
            TrainingFinishEvent,
            TrainingStartEvent,
        )

        names = list(grid)
        combos = list(itertools.product(*(grid[n] for n in names)))
        validation = ValidationSpec(
            data=validation_data, evaluators=list(self.config.evaluators)
        )
        primary = self.config.evaluators[0]
        self.events.send(SetupEvent(config=_config_metadata(self.config)))

        # coordinates whose config doesn't vary in a combo are reused (the
        # FE tiled/sharded layout build is the dominant per-coordinate setup
        # cost); keyed per (name, effective config) within this sweep
        coord_cache: dict = {}

        def coordinates_for(overrides):
            missing = {
                n for n in self.config.coordinates
                if (n, overrides.get(n)) not in coord_cache
            }
            built = (
                self._build_coordinates(data, mesh, overrides, only=missing)
                if missing
                else {}
            )
            out = {}
            for n in self.config.coordinates:
                key = (n, overrides.get(n))
                if key not in coord_cache:
                    coord_cache[key] = built[n]
                out[n] = coord_cache[key]
            return out

        from photon_ml_tpu.utils.timing import Timer

        entries: list[GridFitEntry] = []
        for i, combo in enumerate(combos):
            overrides = dict(zip(names, combo))
            t = Timer().start()
            self.events.send(TrainingStartEvent(num_rows=data.num_rows))
            with telemetry.span("fit", task=self.config.task, combination=i):
                result = run_coordinate_descent(
                    coordinates_for(overrides),
                    task=self.config.task,
                    num_iterations=self.config.num_iterations,
                    validation=validation,
                    on_step=lambda entry: self.events.send(
                        OptimizationLogEvent(
                            iteration=entry["iteration"],
                            coordinate=entry["coordinate"],
                            seconds=entry["seconds"],
                            metrics=entry.get("metrics"),
                        )
                    ),
                )
            self.events.send(
                TrainingFinishEvent(
                    best_metric=result.best_metric,
                    seconds=t.stop(),
                    metrics_snapshot=telemetry.snapshot(),
                )
            )
            entries.append(
                GridFitEntry(
                    optimizer_configs=overrides,
                    result=GameFitResult(
                        model=result.model,
                        best_model=result.best_model,
                        best_metric=result.best_metric,
                        history=result.history,
                    ),
                )
            )
        return sorted(
            entries,
            key=lambda e: e.result.best_metric,
            reverse=better_than(primary, 1.0, 0.0),  # True iff maximizing
        )


def _config_metadata(config: GameConfig) -> dict:
    """JSON-safe description of the training config (model-metadata analog)."""

    def describe_opt(opt):
        out = {
            "type": str(opt.optimizer_type.value),
            "max_iterations": opt.max_iterations,
            "tolerance": opt.tolerance,
            "regularization": str(opt.regularization.reg_type.value),
            "alpha": opt.regularization.alpha,
            "regularization_weight": opt.regularization_weight,
            "lbfgs_history": opt.lbfgs_history,
            "down_sampling_rate": opt.down_sampling_rate,
        }
        if opt.box_constraints:
            out["box_constraints"] = [
                [
                    i,
                    None if lo == float("-inf") else lo,
                    None if hi == float("inf") else hi,
                ]
                for i, lo, hi in opt.box_constraints
            ]
        return out

    def describe(c):
        out = {"shard_name": c.shard_name}
        if isinstance(c, RandomEffectConfig):
            out["type"] = "random_effect"
            out["id_name"] = c.id_name
            out["active_rows_per_entity"] = c.active_rows_per_entity
            out["min_rows_per_entity"] = c.min_rows_per_entity
            out["features_to_samples_ratio"] = c.features_to_samples_ratio
            out["projector"] = c.projector
            out["projected_dim"] = c.projected_dim
            out["projection_seed"] = c.projection_seed
            out["projection_intercept_index"] = c.projection_intercept_index
            out["compute_variances"] = c.compute_variances
            out["optimizer"] = describe_opt(c.optimizer)
        elif isinstance(c, FactoredRandomEffectConfig):
            out["type"] = "factored_random_effect"
            out["id_name"] = c.id_name
            out["active_rows_per_entity"] = c.active_rows_per_entity
            out["min_rows_per_entity"] = c.min_rows_per_entity
            out["latent_dim"] = c.latent_dim
            out["mf_iterations"] = c.mf_iterations
            out["seed"] = c.seed
            out["layout"] = c.layout
            out["optimizer"] = describe_opt(c.re_optimizer)
            out["latent_optimizer"] = describe_opt(c.latent_optimizer)
        else:
            out["type"] = "fixed_effect"
            out["normalization"] = str(NormalizationType(c.normalization).value)
            out["intercept_index"] = c.intercept_index
            out["layout"] = c.layout
            out["down_sampling_seed"] = c.down_sampling_seed
            out["optimizer"] = describe_opt(c.optimizer)
        return out

    return {
        "task": config.task,
        "num_iterations": config.num_iterations,
        "evaluators": list(config.evaluators),
        "coordinates": {n: describe(c) for n, c in config.coordinates.items()},
    }
