"""Selective re-solve: coordinate descent where only touched RE lanes
re-solve.

The sweep's masked-lane idea (re-init only the lanes that need work,
PR 8's ``path_warm_start``) applied at the entity axis: per random-effect
bucket, the touched entities' sub-problems are GATHERED out of the
resident bucket stack, solved by the SAME lru-shared ``_re_solver``
executable family every other trainer uses (lanes padded to the next
power of two by repeating the last real lane — idempotent, and the
padded duplicate is already converged), and SCATTERED back into the
coefficient table. Untouched rows are never rewritten — they stay
**bit-identical** to the warm start. Buckets containing zero touched
entities are skipped entirely (no solve dispatched at all); the
fixed-effect coordinate refreshes normally over the combined stream.

Telemetry: ``incremental.lanes_solved`` / ``incremental.lanes_skipped``
(real entities re-solved vs kept), ``incremental.bucket_solves`` /
``incremental.buckets_skipped`` — what the RunReport "Freshness"
section renders (time-to-fresh against a full retrain is not measured
on the chip; ROADMAP W9).

Transplanting (:func:`transplant_random_effect`): the combined run's
bucket geometry is rebuilt from scratch, so the base model's per-entity
rows are re-homed by entity VALUE (vocabulary growth shifts codes) and
per-feature by GLOBAL feature id (an exact searchsorted take, so an
untouched entity's row — whose geometry cannot have changed — lands
bit-identical). Entities the base never saw zero-init, exactly like a
fresh fit would have initialized them.
"""

from __future__ import annotations

import dataclasses
import logging
import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import telemetry
from photon_ml_tpu.game.models import GameModel, map_vocab_codes
from photon_ml_tpu.optim.guard import damped_objective, solve_health

logger = logging.getLogger("photon_ml_tpu.incremental")


def _next_pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (int(x - 1).bit_length())


# ---------------------------------------------------------------------------
# warm-start transplanting
# ---------------------------------------------------------------------------


def transplant_fixed_effect(base, coord):
    """The base FE model, validated against the combined run's feature
    space. Incremental fits require the feature space pinned — a delta
    that grows/reorders features would silently mis-map every
    coefficient, so a dimension mismatch is a typed refusal."""
    from photon_ml_tpu.incremental.warmstart import WarmStartError

    fresh = coord.initialize_model()
    base_w = np.asarray(base.coefficients)
    if base_w.shape != tuple(fresh.coefficients.shape):
        raise WarmStartError(
            f"fixed-effect '{coord.name}': warm-start coefficients have "
            f"{base_w.shape[0]} features but the combined data has "
            f"{fresh.coefficients.shape[0]} — the feature space must stay "
            "pinned across incremental retrains (new entities are "
            "supported; new features are not)"
        )
    return dataclasses.replace(
        fresh, coefficients=jnp.asarray(base_w, fresh.coefficients.dtype)
    )


def transplant_random_effect(base, coord) -> tuple[object, np.ndarray]:
    """Re-home a base :class:`RandomEffectModel`'s per-entity rows into
    the combined run's freshly built bucket geometry.

    Returns ``(model, untransplanted_codes)`` — the combined-vocab codes
    of entities that zero-initialized because the base never trained a
    row for them (unseen value, or seen but without an active model).
    Those lanes MUST re-solve whatever the delta says: they have no
    converged coefficients to keep. Matching is by entity VALUE then
    global feature id (exact element take, bit-identical for entities
    whose geometry is unchanged — i.e. every entity the delta did not
    touch)."""
    red = coord.re_data
    fresh = coord.initialize_model()
    base_vocab = np.asarray(base.vocab)
    base_bucket = np.asarray(base.entity_bucket)
    base_pos = np.asarray(base.entity_pos)
    base_projs = [np.asarray(b.projection) for b in base.buckets]
    base_coeffs = [np.asarray(b.coefficients) for b in base.buckets]
    new_vocab = np.asarray(fresh.vocab)
    sentinel = red.num_global_features
    untransplanted: list[np.ndarray] = []

    out_buckets = []
    for bm in fresh.buckets:
        codes_new = np.asarray(bm.entity_codes)
        values = new_vocab[codes_new]
        bcodes = map_vocab_codes(base_vocab, values)  # -1 = never seen
        known = bcodes >= 0
        src_bucket = np.where(known, base_bucket[np.maximum(bcodes, 0)], -1)
        untransplanted.append(codes_new[~known | (src_bucket < 0)])
        W = np.zeros(tuple(bm.coefficients.shape), np.float64)
        tgt_proj = np.asarray(bm.projection)
        k_new = tgt_proj.shape[1]
        for src in range(len(base_projs)):
            sel = np.nonzero(src_bucket == src)[0]
            if not len(sel):
                continue
            pp = base_pos[bcodes[sel]]
            old_proj = base_projs[src][pp]  # [S, K_old]
            old_w = base_coeffs[src][pp]  # [S, K_old]
            S, k_old = old_proj.shape
            # exact per-row lookup: encode (row, global id) into one
            # sorted key space and searchsorted — a TAKE of the old
            # value, never an arithmetic reconstruction (bit-identity)
            stride = np.int64(sentinel) + 1
            base_keys = (
                np.arange(S, dtype=np.int64)[:, None] * stride
                + old_proj.astype(np.int64)
            ).ravel()
            tgt_keys = (
                np.arange(S, dtype=np.int64)[:, None] * stride
                + tgt_proj[sel].astype(np.int64)
            ).ravel()
            pos = np.searchsorted(base_keys, tgt_keys)
            pos_c = np.minimum(pos, base_keys.size - 1)
            hit = (base_keys[pos_c] == tgt_keys) & (
                tgt_proj[sel].ravel() != sentinel
            )
            w_rows = np.where(hit, old_w.ravel()[pos_c], 0.0)
            W[sel] = w_rows.reshape(len(sel), k_new)
        out_buckets.append(
            dataclasses.replace(
                bm,
                coefficients=jnp.asarray(W, bm.coefficients.dtype),
            )
        )
    return (
        dataclasses.replace(fresh, buckets=tuple(out_buckets)),
        (
            np.concatenate(untransplanted)
            if untransplanted
            else np.zeros(0, np.int64)
        ).astype(np.int64),
    )


def transplant_factored_random_effect(base, coord) -> tuple[object, np.ndarray]:
    """Re-home a base :class:`FactoredRandomEffectModel`'s latent rows
    into the combined run's flat latent table.

    Factored per-entity state is one K-vector with no per-feature
    geometry, so re-homing is a pure row move by entity VALUE —
    bit-identical for every entity the base trained. The base's shared
    projection matrix A is carried verbatim (the latent rows are only
    meaningful against the A they trained under; by construction A is
    also seed-deterministic, so base and fresh agree anyway). Returns
    ``(model, untransplanted_codes)`` like
    :func:`transplant_random_effect` — active combined-vocab codes with
    no base latent row must re-solve whatever the delta says."""
    from photon_ml_tpu.incremental.warmstart import WarmStartError

    fresh = coord.initialize_model()
    base_latent = np.asarray(base.latent)
    if base_latent.shape[1] != int(fresh.latent.shape[1]):
        raise WarmStartError(
            f"factored coordinate '{coord.name}': warm-start latent "
            f"dimension {base_latent.shape[1]} != configured "
            f"{int(fresh.latent.shape[1])} — the latent space must stay "
            "pinned across incremental retrains"
        )
    base_mat = np.asarray(base.projection.matrix)
    fresh_mat = np.asarray(fresh.projection.matrix)
    if base_mat.shape != fresh_mat.shape:
        raise WarmStartError(
            f"factored coordinate '{coord.name}': warm-start projection "
            f"is {base_mat.shape} but the combined data needs "
            f"{fresh_mat.shape} — the feature space must stay pinned "
            "across incremental retrains"
        )
    new_vocab = np.asarray(fresh.vocab)
    bcodes = map_vocab_codes(np.asarray(base.vocab), new_vocab)
    base_flat = np.asarray(base.entity_flat)
    new_flat = np.asarray(fresh.entity_flat)
    active = np.nonzero(new_flat >= 0)[0]
    src = np.where(
        bcodes[active] >= 0, base_flat[np.maximum(bcodes[active], 0)], -1
    )
    known = src >= 0
    L = np.zeros(
        (int(fresh.latent.shape[0]), base_latent.shape[1]), np.float64
    )
    L[new_flat[active[known]]] = base_latent[src[known]]
    return (
        dataclasses.replace(
            fresh,
            latent=jnp.asarray(L, fresh.latent.dtype),
            projection=base.projection,
        ),
        active[~known].astype(np.int64),
    )


# ---------------------------------------------------------------------------
# the masked coordinate
# ---------------------------------------------------------------------------


class MaskedRandomEffectCoordinate:
    """A :class:`RandomEffectCoordinate` whose ``update_model`` re-solves
    ONLY the touched entities' lanes.

    Implements the ``Coordinate`` protocol, so ``run_coordinate_descent``
    drives it unchanged (guard damping included: ``extra_l2`` /
    ``health_check`` behave exactly like the inner coordinate's). Scoring
    delegates to the inner coordinate — the full model still scores every
    row, so FE residuals see the whole table.
    """

    def __init__(self, inner, touched_mask: np.ndarray):
        self.inner = inner
        self.name = inner.name
        self.data = inner.data  # progress telemetry reads .data.num_rows
        red = inner.re_data
        mask = np.asarray(touched_mask, bool)
        if len(mask) != red.num_entities:
            raise ValueError(
                f"touched mask covers {len(mask)} entities but coordinate "
                f"'{inner.name}' has {red.num_entities}"
            )
        codes = np.nonzero(mask)[0]
        self._positions: list[np.ndarray] = []
        for i in range(len(red.buckets)):
            sel = codes[red.entity_bucket[codes] == i]
            self._positions.append(
                np.sort(red.entity_pos[sel]).astype(np.int64)
            )
        # per-fit guard hooks (the _guarded_update contract)
        self.extra_l2 = 0.0
        self.health_check = False
        self.last_health = None
        self.last_tracker = None
        # structural-speedup evidence, also mirrored into telemetry
        self.lanes_solved = 0
        self.lanes_skipped = 0
        self.bucket_solves = 0
        self.buckets_skipped = 0
        # per-bucket solve inputs from the LAST update_model pass, kept
        # so bootstrap_touched() can re-solve the exact same gathered
        # problems under resampled weights (references, not copies)
        self._last_inputs: list[dict] = []

    def initialize_model(self):
        return self.inner.initialize_model()

    def score(self, model):
        return self.inner.score(model)

    def update_model(self, model, residual_scores):
        from photon_ml_tpu.game.coordinates import (
            place_entity_solve,
            record_entity_solve_comms,
        )
        from photon_ml_tpu.optim.trackers import (
            RandomEffectOptimizationTracker,
        )
        from photon_ml_tpu.parallel import sharding as psharding

        inner = self.inner
        obj = damped_objective(inner._obj, self.extra_l2)
        n_dev = (
            0 if inner.mesh is None
            else psharding.axis_size(inner.mesh, inner._axis)
        )
        new_buckets = []
        tracker_its, tracker_reasons, tracker_vals = [], [], []
        healths = []
        self._last_inputs = []
        for i, (b, bm) in enumerate(zip(inner._buckets, model.buckets)):
            ti = self._positions[i]
            n_real = int(bm.coefficients.shape[0])
            if not len(ti):
                # zero touched entities: no solve dispatched at all —
                # the bucket's rows stand bit-identical
                self.buckets_skipped += 1
                self.lanes_skipped += n_real
                telemetry.counter("incremental.buckets_skipped").inc()
                telemetry.counter("incremental.lanes_skipped").inc(n_real)
                new_buckets.append(bm)
                continue
            T = len(ti)
            total = _next_pow2(T)
            if n_dev:
                total = -(-total // n_dev) * n_dev
            # pad by REPEATING the last touched lane: the duplicate is a
            # real already-warm problem (converges like its twin) and the
            # scatter below only writes the first T lanes
            idx = np.concatenate(
                [ti, np.full(total - T, ti[-1], np.int64)]
            )
            idx_dev = jnp.asarray(idx, jnp.int32)

            def take(x):
                return jnp.take(x, idx_dev, axis=0)

            bucket = (
                b if residual_scores is None
                else b.with_extra_offsets(residual_scores)
            )
            dense = inner._dense_x[i] is not None
            if dense:
                bb = (
                    take(inner._dense_x[i]),
                    take(bucket.labels),
                    take(bucket.offsets),
                    take(bucket.weights),
                )
            else:
                bb = jax.tree.map(take, bucket.entity_batch())
            w0 = take(bm.coefficients)
            cons = inner._bucket_constraints[i]
            if cons is not None:
                cons = jax.tree.map(take, cons)
            solver = inner._dense_solver if dense else inner._solver
            if inner.mesh is not None:
                bb, w0, cons = place_entity_solve(
                    inner.mesh, inner._axis, bb, w0, cons
                )
                record_entity_solve_comms(
                    "re_solve", inner.mesh, inner._axis,
                    inner.config.max_iterations,
                )
            res, var = solver(obj, bb, w0, inner._l1, cons)
            w = res.w[:T]
            # scatter ONLY the touched rows; untouched rows are copied
            # bit-identical by the functional .at[].set
            ti_dev = jnp.asarray(ti, jnp.int32)
            coeffs = bm.coefficients.at[ti_dev].set(
                w.astype(bm.coefficients.dtype)
            )
            variances = bm.variances
            if var is not None:
                base_var = (
                    bm.variances
                    if bm.variances is not None
                    else jnp.zeros_like(bm.coefficients)
                )
                variances = base_var.at[ti_dev].set(
                    var[:T].astype(base_var.dtype)
                )
            tracker_its.append(res.iterations[:T])
            tracker_reasons.append(res.reason[:T])
            tracker_vals.append(res.value[:T])
            if self.health_check:
                healths.append(solve_health(res, res.w))
            self.bucket_solves += 1
            self.lanes_solved += T
            self.lanes_skipped += n_real - T
            telemetry.counter("incremental.bucket_solves").inc()
            telemetry.counter("incremental.lanes_solved").inc(T)
            telemetry.counter("incremental.lanes_skipped").inc(n_real - T)
            # bootstrap_touched re-solves these gathered problems later;
            # dense-path buckets carry stripped (1, 1) COO stubs, so it
            # rebuilds the COO view from _dense_x. Only sharded solves
            # (mesh) are out of scope
            if inner.mesh is None:
                self._last_inputs.append(
                    {
                        "bucket": i,
                        "bucket_obj": bucket,
                        "idx": idx,
                        "ti": ti,
                        "w0": res.w,
                    }
                )
            new_buckets.append(
                dataclasses.replace(
                    bm, coefficients=coeffs, variances=variances
                )
            )
        self.last_health = (
            (jnp.all(jnp.stack(healths)) if healths else jnp.bool_(True))
            if self.health_check
            else None
        )
        self.last_tracker = (
            RandomEffectOptimizationTracker.from_device_parts(
                tracker_its, tracker_reasons, tracker_vals
            )
            if tracker_its
            else None
        )
        return dataclasses.replace(model, buckets=tuple(new_buckets))

    def bootstrap_touched(self, num_samples: int = 32, seed: int = 0):
        """Masked-lane bootstrap: CI exactly the RE rows the last
        ``update_model`` pass touched, reusing its gather machinery —
        B x touched lanes solve in ONE executable per bucket.

        The [B, E, R] resample weights are drawn for the FULL bucket
        from the shared seed and then gathered down to the touched
        lanes, so each touched lane sees byte-identical draws to a
        full-lane ``bootstrap_random_effect`` run over the same bucket
        — which is why masked and full CIs agree exactly on touched
        rows. Returns ``{bucket_index: {"report": ReBootstrapReport,
        "touched": positions}}``."""
        from photon_ml_tpu.diagnostics.bootstrap import (
            bootstrap_random_effect,
            bootstrap_re_weights,
        )

        inner = self.inner
        out: dict[int, dict] = {}
        for stash in self._last_inputs:
            bucket = stash["bucket_obj"]
            idx = stash["idx"]
            full_w = np.asarray(
                telemetry.sync_fetch(
                    bucket.weights, label="bootstrap_touched_weights"
                )
            )
            counts = bootstrap_re_weights(num_samples, full_w, seed)
            idx_dev = jnp.asarray(idx, jnp.int32)

            def take(x):
                return jnp.take(x, idx_dev, axis=0)

            dense_x = inner._dense_x[stash["bucket"]]
            if dense_x is not None:
                # the bucket solved on its packed dense design and its COO
                # arrays may be stripped (1, 1) stubs — rebuild an explicit
                # dense-as-COO view [P, R*K] from the design instead
                from photon_ml_tpu.ops.sparse import SparseBatch

                R = bucket.labels.shape[1]
                K = int(bucket.num_local_features)
                x = take(dense_x)
                rows = jnp.broadcast_to(
                    jnp.repeat(jnp.arange(R, dtype=jnp.int32), K),
                    x.shape,
                )
                cols = jnp.broadcast_to(
                    jnp.tile(jnp.arange(K, dtype=jnp.int32), R),
                    x.shape,
                )
                eb = SparseBatch(
                    values=x,
                    rows=rows,
                    cols=cols,
                    labels=take(bucket.labels),
                    offsets=take(bucket.offsets),
                    weights=take(bucket.weights),
                    num_features=K,
                )
            else:
                eb = jax.tree.map(take, bucket.entity_batch())
            report = bootstrap_random_effect(
                eb,
                inner.loss_name,
                inner.config,
                stash["w0"],
                num_samples=num_samples,
                seed=seed,
                lane_weights=counts[:, idx, :],
            )
            out[stash["bucket"]] = {
                "report": report,
                "touched": stash["ti"],
            }
        return out


class MaskedFactoredRandomEffectCoordinate:
    """A :class:`FactoredRandomEffectCoordinate` whose ``update_model``
    re-solves ONLY the touched entities' latent vectors.

    The shared projection matrix A is FROZEN regardless of the inner
    coordinate's ``refit_projection``: a matrix refit rewrites every
    entity's effective coefficients ``A^T c_e``, which would defeat the
    untouched-lanes-bit-identical guarantee the masked path exists for.
    Touched entities re-solve in the fixed projected space — exactly the
    ``refit_projection=False`` per-entity step, gathered down to the
    touched lanes (same pad-to-pow2 / scatter-back protocol as
    :class:`MaskedRandomEffectCoordinate`). A base whose A has drifted
    stale escalates to a full retrain — the conductor's escalation path.
    """

    def __init__(self, inner, touched_mask: np.ndarray):
        self.inner = inner
        self.name = inner.name
        self.data = inner.data
        red = inner.re_data
        mask = np.asarray(touched_mask, bool)
        if len(mask) != red.num_entities:
            raise ValueError(
                f"touched mask covers {len(mask)} entities but coordinate "
                f"'{inner.name}' has {red.num_entities}"
            )
        if inner.refit_projection:
            logger.warning(
                "masked incremental solve freezes coordinate '%s's shared "
                "projection matrix (refit_projection is configured on); "
                "escalate to a full retrain to refresh it", inner.name,
            )
        codes = np.nonzero(mask)[0]
        self._positions: list[np.ndarray] = []
        for i in range(len(red.buckets)):
            sel = codes[red.entity_bucket[codes] == i]
            self._positions.append(
                np.sort(red.entity_pos[sel]).astype(np.int64)
            )
        self.extra_l2 = 0.0
        self.health_check = False
        self.last_health = None
        self.last_tracker = None
        self.lanes_solved = 0
        self.lanes_skipped = 0
        self.bucket_solves = 0
        self.buckets_skipped = 0

    def initialize_model(self):
        return self.inner.initialize_model()

    def score(self, model):
        return self.inner.score(model)

    def update_model(self, model, residual_scores):
        from photon_ml_tpu.optim.trackers import (
            FactoredRandomEffectOptimizationTracker,
            RandomEffectOptimizationTracker,
        )
        from photon_ml_tpu.parallel import sharding as psharding

        inner = self.inner
        obj = damped_objective(inner._re_obj, self.extra_l2)
        n_dev = (
            0 if inner.mesh is None
            else psharding.axis_size(inner.mesh, inner._axis)
        )
        latent = model.latent
        tracker_its, tracker_reasons, tracker_vals = [], [], []
        healths = []
        # one projection pass gives every bucket's latent design; the
        # touched lanes are then taken out of it
        designs = inner.latent_designs(model.projection.matrix)
        offsets = inner._bucket_offsets(residual_scores)
        buckets = inner.re_data.device_buckets_stripped()
        for b_idx, b in enumerate(buckets):
            ti = self._positions[b_idx]
            n_real = int(b.num_entities)
            if not len(ti):
                # zero touched entities: no solve dispatched at all —
                # the bucket's latent rows stand bit-identical
                self.buckets_skipped += 1
                self.lanes_skipped += n_real
                telemetry.counter("incremental.buckets_skipped").inc()
                telemetry.counter("incremental.lanes_skipped").inc(n_real)
                continue
            T = len(ti)
            total = _next_pow2(T)
            if n_dev:
                total = -(-total // n_dev) * n_dev
            # pad by REPEATING the last touched lane (idempotent; scatter
            # below only writes the first T lanes)
            idx = np.concatenate(
                [ti, np.full(total - T, ti[-1], np.int64)]
            )
            idx_dev = jnp.asarray(idx, jnp.int32)

            def take(x):
                return jnp.take(x, idx_dev, axis=0)

            flat = inner._flat_offsets[b_idx] + idx
            w0 = jnp.take(latent, jnp.asarray(flat, jnp.int32), axis=0)
            res = inner.solve_bucket(
                obj, take(designs[b_idx]), take(b.labels),
                take(offsets[b_idx]), take(b.weights), w0)
            w = res.w[:T]
            flat_t = jnp.asarray(
                inner._flat_offsets[b_idx] + ti, jnp.int32
            )
            latent = latent.at[flat_t].set(w.astype(latent.dtype))
            tracker_its.append(res.iterations[:T])
            tracker_reasons.append(res.reason[:T])
            tracker_vals.append(res.value[:T])
            if self.health_check:
                healths.append(solve_health(res, res.w))
            self.bucket_solves += 1
            self.lanes_solved += T
            self.lanes_skipped += n_real - T
            telemetry.counter("incremental.bucket_solves").inc()
            telemetry.counter("incremental.lanes_solved").inc(T)
            telemetry.counter("incremental.lanes_skipped").inc(n_real - T)
        self.last_health = (
            (jnp.all(jnp.stack(healths)) if healths else jnp.bool_(True))
            if self.health_check
            else None
        )
        self.last_tracker = (
            FactoredRandomEffectOptimizationTracker(
                steps=(
                    (
                        RandomEffectOptimizationTracker.from_device_parts(
                            tracker_its, tracker_reasons, tracker_vals
                        ),
                        None,
                    ),
                )
            )
            if tracker_its
            else None
        )
        return dataclasses.replace(model, latent=latent)


# ---------------------------------------------------------------------------
# the incremental fit driver
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class IncrementalFitResult:
    """A finished incremental refresh: the fresh model plus the evidence
    trail (what re-solved, what stood, where it came from)."""

    model: GameModel
    best_model: GameModel
    best_metric: Optional[float]
    history: list
    lineage: "BaseLineage"
    delta: Optional["DeltaScan"]
    lanes_solved: int
    lanes_skipped: int
    bucket_solves: int
    buckets_skipped: int
    new_entities: int
    seconds: float
    selection: Optional[object] = None  # SweepSelection when λ-swept
    published_version: Optional[str] = None
    # JSON-safe masked-lane bootstrap summaries per coordinate (only when
    # run with bootstrap_samples > 0) — the error bars the publish gate
    # attaches to the version's quality block
    bootstrap: Optional[dict] = None


def local_lambda_factors(points: int = 3, span: float = 4.0) -> list[float]:
    """A small DESCENDING multiplier grid around the incumbent λ (the
    sweep convention: index 0 = most regularized). ``points=3, span=4``
    → ``[4.0, 1.0, 0.25]``; the incumbent itself is always a lane."""
    if points < 1:
        raise ValueError("lambda points must be >= 1")
    if span <= 1.0:
        raise ValueError("lambda span must be > 1")
    if points == 1:
        return [1.0]
    factors = np.logspace(
        np.log10(span), -np.log10(span), points
    ).tolist()
    # the incumbent must be an exact lane, not a float-noise neighbor
    mid = min(range(points), key=lambda i: abs(np.log(factors[i])))
    factors[mid] = 1.0
    return factors


def _scaled_overrides(config, factor: float) -> dict:
    """Per-coordinate OptimizerConfig overrides with every coordinate's
    regularization weight scaled by ``factor`` (the local λ sweep)."""
    from photon_ml_tpu.game.estimator import (
        FactoredRandomEffectConfig,
        FixedEffectConfig,
        RandomEffectConfig,
    )

    overrides = {}
    for name, c in config.coordinates.items():
        if isinstance(c, (FixedEffectConfig, RandomEffectConfig)):
            opt = c.optimizer
        elif isinstance(c, FactoredRandomEffectConfig):
            opt = c.re_optimizer
        else:  # pragma: no cover - config types are closed
            continue
        overrides[name] = dataclasses.replace(
            opt, regularization_weight=opt.regularization_weight * factor
        )
    return overrides


def _wrap_masked(coords: dict, delta, data, untransplanted: dict) -> dict:
    """Wrap every RE coordinate whose id column the delta names.

    The touched mask is the delta's touched set UNIONED with the
    coordinate's untransplanted entities (combined-vocab codes the base
    had no row for): an entity that entered through a shifted base
    window rather than the delta shards still has only a zero-init row —
    skipping its lane would publish an all-zero random effect."""
    from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
    from photon_ml_tpu.game.factored import FactoredRandomEffectCoordinate

    if delta is None:
        return dict(coords)
    out = {}
    for name, coord in coords.items():
        if isinstance(coord, RandomEffectCoordinate):
            cd = delta.for_id(coord.re_data.id_name)
            masked_cls = MaskedRandomEffectCoordinate
        elif isinstance(coord, FactoredRandomEffectCoordinate):
            cd = delta.for_id(coord.re_data.id_name)
            masked_cls = MaskedFactoredRandomEffectCoordinate
        else:
            cd = None
        if cd is None:
            out[name] = coord
            continue
        vocab = data.id_columns[coord.re_data.id_name].vocab
        mask = cd.touched_mask(vocab)
        missing = untransplanted.get(name)
        if missing is not None and len(missing):
            mask[missing] = True
        out[name] = masked_cls(coord, mask)
    return out


def _transplant_models(
    coords: dict, base_model: GameModel
) -> tuple[dict, int, dict]:
    """``(initial_models, new_entities, untransplanted)`` for the
    combined-geometry coordinates, re-homed from the base model.
    ``untransplanted`` maps coordinate name -> combined-vocab codes with
    no base row (zero-init lanes that must not be mask-skipped).
    Coordinates the base lacks (or whose type the transplant does not
    support) start fresh with a warning."""
    from photon_ml_tpu.game.coordinates import (
        FixedEffectCoordinate,
        RandomEffectCoordinate,
    )
    from photon_ml_tpu.game.factored import (
        FactoredRandomEffectCoordinate,
        FactoredRandomEffectModel,
    )
    from photon_ml_tpu.incremental.warmstart import WarmStartError

    initial = {}
    new_entities = 0
    untransplanted: dict = {}
    for name, coord in coords.items():
        base = base_model.models.get(name)
        if base is None:
            logger.warning(
                "warm start lacks coordinate '%s'; it initializes fresh",
                name,
            )
            continue
        if isinstance(coord, FixedEffectCoordinate):
            initial[name] = transplant_fixed_effect(base, coord)
        elif isinstance(coord, RandomEffectCoordinate):
            model, missing = transplant_random_effect(base, coord)
            initial[name] = model
            new_entities += int(len(missing))
            untransplanted[name] = missing
        elif isinstance(coord, FactoredRandomEffectCoordinate):
            if not isinstance(base, FactoredRandomEffectModel):
                raise WarmStartError(
                    f"coordinate '{name}' is factored in this config but "
                    f"the warm start holds a {type(base).__name__} — the "
                    "coordinate structure must stay pinned across "
                    "incremental retrains"
                )
            model, missing = transplant_factored_random_effect(base, coord)
            initial[name] = model
            new_entities += int(len(missing))
            untransplanted[name] = missing
        else:
            logger.warning(
                "coordinate '%s' (%s) does not support warm-start "
                "transplanting; it initializes fresh",
                name, type(coord).__name__,
            )
    return initial, new_entities, untransplanted


def _primary_metric_value(model, validation_data, metric: str) -> float:
    """One validation metric for a full model — the λ-sweep scorer
    (EVALUATORS parity with sweep.select.evaluate_sweep)."""
    from photon_ml_tpu.evaluation.evaluators import EVALUATORS
    from photon_ml_tpu.game.coordinate_descent import (
        padded_validation_arrays,
    )

    scores = model.score(validation_data)
    labels, weights, offsets = padded_validation_arrays(
        validation_data, int(scores.shape[0])
    )
    return float(
        telemetry.sync_fetch(
            EVALUATORS[metric](scores + offsets, labels, weights),
            label=f"incremental_eval:{metric}",
        )
    )


def run_incremental_fit(
    estimator,
    data,
    warm_start,
    delta=None,
    validation_data=None,
    mesh=None,
    num_iterations: Optional[int] = None,
    lambda_factors: Optional[Sequence[float]] = None,
    metric: Optional[str] = None,
    policy: str = "best",
    rel_tol: float = 0.01,
    guard=None,
    checkpoint_spec=None,
    should_stop=None,
    bootstrap_samples: int = 0,
    bootstrap_seed: int = 0,
) -> IncrementalFitResult:
    """Delta-aware warm-start refresh of ``estimator``'s model over the
    COMBINED data (base ∪ delta). See ``GameEstimator.fit_incremental``
    for the public contract."""
    from photon_ml_tpu.game.checkpoint import CheckpointManager
    from photon_ml_tpu.game.coordinate_descent import (
        ValidationSpec,
        run_coordinate_descent,
    )
    from photon_ml_tpu.incremental.warmstart import WarmStartError
    from photon_ml_tpu.utils.timing import Timer

    if warm_start.model is None:
        raise WarmStartError(
            "fit_incremental needs a warm start carrying a full GAME "
            f"model (kind '{warm_start.lineage.kind}' restored a bare "
            "coefficient table; streamed tables warm-start "
            "StreamingRandomEffectTrainer via "
            "ShardedCoefficientTable.from_coefficients instead)"
        )
    if checkpoint_spec is not None and os.path.realpath(
        checkpoint_spec.directory
    ) == os.path.realpath(warm_start.lineage.checkpoint_dir):
        raise WarmStartError(
            "the incremental fit's checkpoint directory must not be its "
            "own warm-start base — a crash mid-refresh would corrupt "
            "the base checkpoint it restarts from"
        )
    config = estimator.config
    validation = None
    if validation_data is not None:
        if not config.evaluators:
            raise ValueError("validation data provided but no evaluators")
        validation = ValidationSpec(
            data=validation_data, evaluators=list(config.evaluators)
        )
    iters = num_iterations or config.num_iterations
    t = Timer().start()
    lineage = warm_start.lineage
    attrs = {
        "base": lineage.checkpoint_dir,
        "kind": lineage.kind,
    }
    if lineage.digest:
        attrs["base_digest"] = lineage.digest
    if lineage.step is not None:
        attrs["base_step"] = int(lineage.step)
    if delta is not None:
        attrs["delta_digest"] = delta.digest
        attrs["delta_rows"] = int(delta.delta_rows)
        attrs["touched_fraction"] = round(
            max(
                (c.touched_fraction for c in delta.coordinates.values()),
                default=0.0,
            ),
            6,
        )
    with telemetry.span("incremental_fit", **attrs):
        factors = list(lambda_factors) if lambda_factors else [1.0]
        if len(factors) > 1 and validation is None:
            raise ValueError(
                "a local λ sweep needs validation data to select on"
            )
        lane_results = []
        lane_wrapped: list[dict] = []
        initial = None
        new_entities = 0
        untransplanted: dict = {}
        for li, factor in enumerate(factors):
            overrides = (
                None if factor == 1.0 else _scaled_overrides(config, factor)
            )
            coords = estimator._build_coordinates(
                data, mesh, opt_overrides=overrides
            )
            if initial is None:
                initial, new_entities, untransplanted = _transplant_models(
                    coords, warm_start.model
                )
            wrapped = _wrap_masked(coords, delta, data, untransplanted)
            # path warm start: each lane starts from its more-regularized
            # neighbor's refreshed models (lane 0 from the transplant)
            result = run_coordinate_descent(
                wrapped,
                task=config.task,
                num_iterations=iters,
                validation=validation,
                initial_models=initial,
                guard=guard,
                checkpoint=(
                    None if checkpoint_spec is None or li > 0
                    else CheckpointManager(checkpoint_spec)
                ),
                should_stop=should_stop,
            )
            lane_results.append(result)
            lane_wrapped.append(wrapped)
            initial = dict(result.model.models)

        selection = None
        pick = 0
        if len(factors) > 1:
            from photon_ml_tpu.sweep.select import (
                SweepSelection,
                default_metric,
                select_best,
            )

            metric_name = metric or default_metric(config.task)
            values = np.asarray(
                [
                    _primary_metric_value(
                        r.model, validation.data, metric_name
                    )
                    for r in lane_results
                ],
                np.float64,
            )
            pick = select_best(
                values, metric_name, policy=policy, rel_tol=rel_tol
            )
            selection = SweepSelection(
                index=pick, metric=metric_name, metrics=values,
                policy=policy,
            )
            telemetry.gauge("sweep.selected_metric").set(
                float(values[pick])
            )
        result = lane_results[pick]
        bootstrap = None
        if bootstrap_samples > 0:
            # masked-lane bootstrap on the SELECTED lane: CI exactly the
            # touched rows, B resamples per bucket in one executable
            with telemetry.span(
                "incremental_bootstrap", samples=bootstrap_samples
            ):
                per_coord = {}
                for name, coord in lane_wrapped[pick].items():
                    if not hasattr(coord, "bootstrap_touched"):
                        continue
                    buckets = coord.bootstrap_touched(
                        num_samples=bootstrap_samples, seed=bootstrap_seed
                    )
                    if not buckets:
                        continue
                    agg = {}
                    for bi, entry in buckets.items():
                        summ = entry["report"].summary()
                        summ["touched_lanes"] = int(len(entry["touched"]))
                        agg[str(bi)] = summ
                    per_coord[name] = agg
                if per_coord:
                    bootstrap = {
                        "num_samples": int(bootstrap_samples),
                        "coordinates": per_coord,
                    }
                    telemetry.counter("quality.bootstrap_fits").inc()
        lanes_solved = sum(
            getattr(c, "lanes_solved", 0)
            for w in lane_wrapped for c in w.values()
        )
        lanes_skipped = sum(
            getattr(c, "lanes_skipped", 0)
            for w in lane_wrapped for c in w.values()
        )
        bucket_solves = sum(
            getattr(c, "bucket_solves", 0)
            for w in lane_wrapped for c in w.values()
        )
        buckets_skipped = sum(
            getattr(c, "buckets_skipped", 0)
            for w in lane_wrapped for c in w.values()
        )
    seconds = t.stop()
    telemetry.gauge("incremental.time_to_fresh_s").set(seconds)
    telemetry.counter("incremental.fits").inc()
    return IncrementalFitResult(
        model=result.model,
        best_model=result.best_model or result.model,
        best_metric=result.best_metric,
        history=result.history,
        lineage=lineage,
        delta=delta,
        lanes_solved=lanes_solved,
        lanes_skipped=lanes_skipped,
        bucket_solves=bucket_solves,
        buckets_skipped=buckets_skipped,
        new_entities=new_entities,
        seconds=seconds,
        selection=selection,
        bootstrap=bootstrap,
    )
