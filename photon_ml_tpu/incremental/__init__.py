"""Continuous-freshness loop: delta-aware incremental warm-start retrains.

The production GLMix cadence in the reference is a slow offline Spark
batch — every retrain re-reads everything and re-solves every entity,
even when a day's delta touches 5% of them. This package composes three
landed contracts into a retrain that is minutes-shaped instead of
hours-shaped:

- deterministic ``ChunkPlan`` ordering (``ingest.planner``) makes
  "yesterday's data ∪ today's delta" a stable, replayable stream —
  appending delta shards never renumbers yesterday's chunks;
- sharded elastic checkpoints (``game.checkpoint.restore_placed``) make
  yesterday's coefficient table a warm-start artifact on ANY mesh
  (:func:`load_warm_start`);
- the masked-lane vmap pattern (the sweep's lane re-init idea) drives
  coordinate descent so that ONLY the random-effect lanes the delta
  touched re-solve (:class:`MaskedRandomEffectCoordinate`) — the
  untouched majority keeps its converged coefficients **bit-identical**,
  and bucket solves containing zero touched entities are skipped
  entirely — while the fixed effect refreshes over the combined stream.

Stages:

- :mod:`.warmstart` — :func:`load_warm_start` (step checkpoints, saved
  model dirs, AND sharded streaming checkpoints restored straight onto
  the training mesh), vocabulary-growth row expansion
  (:func:`grow_entity_rows`: new entities zero-init, existing rows
  bit-identical), and :class:`BaseLineage` recording the base artifact's
  identity for registry metadata.
- :mod:`.delta` — touched-entity detection over the interned entity-id
  columns of the delta, both the in-core reader path
  (:func:`scan_delta`) and the out-of-core ``ChunkStream`` path
  (:func:`scan_delta_stream`); telemetry
  ``incremental.touched_entities`` / ``incremental.touched_fraction``.
- :mod:`.refit` — the selective re-solve
  (:func:`run_incremental_fit`, surfaced as
  ``GameEstimator.fit_incremental``), with an optional small
  descending-λ sweep around the incumbent's regularization selected by
  the existing ``sweep.select`` policies.
- :mod:`.publish` — :func:`publish_incremental`: registry publish with
  the lineage record (``base_version`` / ``warm_start_checkpoint`` /
  delta digest) in version metadata, rendered by ``cli report`` and
  ``/healthz``.

Surfaces: ``cli train --warm-start <dir> [--delta <paths>]``, the
``cli refresh`` subcommand, ``GameEstimator.fit_incremental`` and the
RunReport "Freshness" section.
"""

from photon_ml_tpu.incremental.warmstart import (  # noqa: F401
    BaseLineage,
    WarmStart,
    WarmStartError,
    detect_warm_start_kind,
    grow_entity_rows,
    load_warm_start,
)
from photon_ml_tpu.incremental.delta import (  # noqa: F401
    CoordinateDelta,
    DeltaScan,
    delta_digest,
    scan_delta,
    scan_delta_stream,
)
from photon_ml_tpu.incremental.refit import (  # noqa: F401
    IncrementalFitResult,
    MaskedFactoredRandomEffectCoordinate,
    MaskedRandomEffectCoordinate,
    local_lambda_factors,
    run_incremental_fit,
    transplant_factored_random_effect,
    transplant_fixed_effect,
    transplant_random_effect,
)
from photon_ml_tpu.incremental.publish import (  # noqa: F401
    StaleDeltaError,
    check_delta_freshness,
    lineage_record,
    publish_incremental,
)

__all__ = [
    "BaseLineage",
    "CoordinateDelta",
    "DeltaScan",
    "IncrementalFitResult",
    "MaskedFactoredRandomEffectCoordinate",
    "MaskedRandomEffectCoordinate",
    "StaleDeltaError",
    "WarmStart",
    "WarmStartError",
    "check_delta_freshness",
    "delta_digest",
    "detect_warm_start_kind",
    "grow_entity_rows",
    "lineage_record",
    "load_warm_start",
    "local_lambda_factors",
    "publish_incremental",
    "run_incremental_fit",
    "scan_delta",
    "scan_delta_stream",
    "transplant_factored_random_effect",
    "transplant_fixed_effect",
    "transplant_random_effect",
]
