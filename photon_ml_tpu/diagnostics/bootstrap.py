"""Bootstrap training: per-coefficient confidence intervals and metric
distributions from resampled refits.

Reference analog: photon-diagnostics BootstrapTraining.scala:30-181 and
supervised/model/CoefficientSummary.scala. The reference tags rows into
1000 splits and filters RDDs per bootstrap sample; TPU-first, each sample
is a WEIGHT VECTOR (multinomial resample counts over the training portion,
0 on the holdout) and all B refits run as ONE vmapped jit-compiled solve —
same shapes, no data movement, B-way parallel on the MXU.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import telemetry
from photon_ml_tpu.diagnostics.evaluation import evaluate
from photon_ml_tpu.models.glm import make_model
from photon_ml_tpu.ops.objective import make_objective
from photon_ml_tpu.optim.adapter import glm_adapter
from photon_ml_tpu.optim.factory import OptimizerConfig, dispatch_solve

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class CoefficientSummary:
    """Per-scalar accumulation summary (CoefficientSummary.scala analog:
    count/mean/stddev/min/max + quartile estimates)."""

    count: int
    mean: float
    std_dev: float
    min: float
    max: float
    q1: float
    median: float
    q3: float

    @staticmethod
    def of(samples: np.ndarray) -> "CoefficientSummary":
        s = np.asarray(samples, np.float64)
        q1, med, q3 = np.percentile(s, [25, 50, 75])
        return CoefficientSummary(
            count=int(s.size),
            mean=float(s.mean()),
            std_dev=float(s.std(ddof=1)) if s.size > 1 else 0.0,
            min=float(s.min()),
            max=float(s.max()),
            q1=float(q1),
            median=float(med),
            q3=float(q3),
        )

    def contains_zero(self) -> bool:
        return self.min <= 0.0 <= self.max

    def to_summary_string(self) -> str:
        return (
            f"Range: [Min: {self.min:.3f}, Q1: {self.q1:.3f}, "
            f"Med: {self.median:.3f}, Q3: {self.q3:.3f}, Max: {self.max:.3f}) "
            f"Mean: [{self.mean:.3f}], Std. Dev.[{self.std_dev:.3f}], "
            f"# samples = [{self.count}]"
        )


@dataclasses.dataclass
class BootstrapReport:
    """Aggregates over bootstrap refits (BootstrapReport analog)."""

    coefficient_summaries: list[CoefficientSummary]  # 1:1 with coefficients
    metric_summaries: dict[str, CoefficientSummary]
    models: Optional[list] = None  # per-sample GLMs when keep_models

    def significant_coefficients(self) -> np.ndarray:
        """Indices whose bootstrap CI (min..max) excludes zero — the
        'very unlikely to be zero' set the reference doc describes."""
        return np.asarray(
            [i for i, s in enumerate(self.coefficient_summaries)
             if not s.contains_zero()],
            np.int64,
        )


@lru_cache(maxsize=32)
def _bootstrap_solver(config: OptimizerConfig, loss_name: str):
    def solve_one(obj, batch, weights, w0, l1, constraints):
        b = dataclasses.replace(batch, weights=weights)
        return dispatch_solve(
            glm_adapter(obj, b), w0, config, l1, constraints=constraints
        )

    # weights vmap over the sample axis; batch/obj/w0/l1/constraints broadcast
    return telemetry.instrumented_jit(
        jax.vmap(solve_one, in_axes=(None, None, 0, None, None, None)),
        name="bootstrap_glm_solve",
        multi_shape=True,
    )


def bootstrap_train(
    batch,
    task: str,
    config: OptimizerConfig,
    num_samples: int = 16,
    train_portion: float = 0.8,
    seed: int = 0,
    keep_models: bool = False,
    metrics_fn: Optional[Callable] = None,
    normalization=None,
) -> BootstrapReport:
    """Train ``num_samples`` bootstrap refits and aggregate.

    Each sample: rows are split train/holdout at ``train_portion`` (capped
    at 0.9 like the reference's 900/1000 splits), the training rows receive
    multinomial resample counts as weight multipliers (sampling with
    replacement), and the model refits from zero. Holdout metrics feed the
    metric distributions (Evaluation.evaluate per model in the reference).
    """
    if num_samples < 2:
        raise ValueError("num_samples must be at least 2")
    if not 0.0 < train_portion <= 1.0:
        raise ValueError(f"train_portion must be in (0, 1], got {train_portion}")
    train_portion = min(train_portion, 0.9)
    config.validate(task)

    rng = np.random.default_rng(seed)
    base_w = np.asarray(batch.weights)
    n_pad = len(base_w)
    live = base_w > 0
    n_live = int(live.sum())

    sample_weights = np.zeros((num_samples, n_pad))
    holdout_masks = np.zeros((num_samples, n_pad), bool)
    live_idx = np.nonzero(live)[0]
    n_train = max(int(round(train_portion * n_live)), 1)
    for b in range(num_samples):
        perm = rng.permutation(n_live)
        train_rows = live_idx[perm[:n_train]]
        holdout_rows = live_idx[perm[n_train:]]
        counts = rng.multinomial(n_train, np.full(n_train, 1.0 / n_train))
        sample_weights[b, train_rows] = base_w[train_rows] * counts
        holdout_masks[b, holdout_rows] = True

    factors = shifts = None
    if normalization is not None:
        factors, shifts = normalization.factors, normalization.shifts
    obj = make_objective(
        task,
        l2_weight=config.regularization.l2_weight(config.regularization_weight),
        factors=factors,
        shifts=shifts,
    )
    l1 = jnp.float32(config.regularization.l1_weight(config.regularization_weight))
    key_cfg = dataclasses.replace(config, regularization_weight=0.0)
    solver = _bootstrap_solver(key_cfg, task)
    w0 = jnp.zeros((batch.num_features,), jnp.float32)
    constraints = config.build_box_constraints(int(batch.num_features))
    res = solver(
        obj, batch, jnp.asarray(sample_weights, jnp.float32), w0, l1, constraints
    )
    # [B, d] coefficient matrix, fetched ONCE through the accounted
    # crossing (lint L019: a bare np.asarray here would be an invisible
    # device->host sync); optimization (normalized) space
    W = telemetry.sync_fetch(res.w, label="bootstrap_coefficients")
    if normalization is not None:
        # models live in original space (createModel parity)
        W = telemetry.sync_fetch(
            jax.vmap(normalization.transform_model_coefficients)(res.w),
            label="bootstrap_coefficients",
        )

    coef_summaries = [CoefficientSummary.of(W[:, j]) for j in range(W.shape[1])]

    metric_samples: dict[str, list[float]] = {}
    models = []
    for b in range(num_samples):
        m = make_model(task, jnp.asarray(W[b]))
        models.append(m)
        hold_w = jnp.asarray(
            np.where(holdout_masks[b], base_w, 0.0), jnp.float32
        )
        hb = dataclasses.replace(batch, weights=hold_w)
        mm = metrics_fn(m, hb) if metrics_fn is not None else evaluate(m, hb)
        for k, v in mm.items():
            metric_samples.setdefault(k, []).append(v)

    return BootstrapReport(
        coefficient_summaries=coef_summaries,
        metric_summaries={
            k: CoefficientSummary.of(np.asarray(v))
            for k, v in metric_samples.items()
        },
        models=models if keep_models else None,
    )


# ---------------------------------------------------------------------------
# GLMix (random-effect) bootstrap: B resamples as vmapped lanes riding the
# sweep machinery (ISSUE 20 leg 1)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ReBootstrapReport:
    """Per-entity-coefficient bootstrap aggregates for one RE bucket:
    every array is [E, K] over the bucket's entity x coefficient grid.
    The CI bounds are the 2.5/97.5 bootstrap percentiles — the error
    bars the publish gate's quality block carries per version."""

    num_samples: int
    mean: np.ndarray
    std_dev: np.ndarray
    q1: np.ndarray
    median: np.ndarray
    q3: np.ndarray
    ci_low: np.ndarray
    ci_high: np.ndarray
    live_entities: np.ndarray  # bool [E]; False = padding / empty lane

    def contains_zero(self) -> np.ndarray:
        """bool [E, K]: CI straddles zero (NOT significant)."""
        return (self.ci_low <= 0.0) & (0.0 <= self.ci_high)

    def summary(self) -> dict:
        """JSON-safe rollup for version metadata: how wide the error
        bars are and how much of the grid is distinguishable from
        zero, restricted to live (non-padding) entity lanes."""
        live = np.asarray(self.live_entities, bool)
        width = (self.ci_high - self.ci_low)[live]
        cz = self.contains_zero()[live]
        if width.size == 0:
            return {"entities": 0, "num_samples": self.num_samples}
        return {
            "entities": int(live.sum()),
            "coefficients_per_entity": int(self.mean.shape[1]),
            "num_samples": self.num_samples,
            "mean_ci_width": round(float(width.mean()), 6),
            "max_ci_width": round(float(width.max()), 6),
            "contains_zero_fraction": round(float(cz.mean()), 6),
        }


def bootstrap_re_weights(
    num_samples: int, base_weights: np.ndarray, seed: int = 0
) -> np.ndarray:
    """[B, E, R] multinomial resample-count multipliers, drawn per
    entity over its live (weight > 0) rows; padding rows stay zero.

    Entity draws are independent and consumed in entity order from one
    seeded generator, so gathering entity lanes out of the full array
    (the masked-lane bootstrap) sees EXACTLY the draws the full-lane
    bootstrap used for those entities — which is what makes
    masked-vs-full CI agreement on touched rows exact."""
    bw = np.asarray(base_weights, np.float64)
    B, (E, R) = num_samples, bw.shape
    rng = np.random.default_rng(seed)
    out = np.zeros((B, E, R))
    for e in range(E):
        live = np.nonzero(bw[e] > 0)[0]
        n = live.size
        if n == 0:
            continue
        counts = rng.multinomial(n, np.full(n, 1.0 / n), size=B)
        out[:, e, live] = counts
    return out


def bootstrap_random_effect(
    ebatch,
    task: str,
    config: OptimizerConfig,
    w0,
    num_samples: int = 32,
    seed: int = 0,
    lane_weights: Optional[np.ndarray] = None,
    normalization=None,
) -> ReBootstrapReport:
    """Bootstrap one random-effect bucket: B weight-resample lanes
    composed with the per-entity vmap (sweep.runner.re_bootstrap_solver)
    solve B*E problems in ONE executable, every lane warm-started from
    the point estimate ``w0`` [E, K]. The bucket design broadcasts
    across the B axis (what B lanes cost over a single fit is not
    measured on the chip).

    ``lane_weights`` [B, E, R] overrides the drawn multipliers — the
    masked-lane path passes a gathered slice of the full-bucket draw.
    """
    from photon_ml_tpu.sweep.runner import re_bootstrap_solver

    if num_samples < 2:
        raise ValueError("num_samples must be at least 2")
    config.validate(task)

    if lane_weights is None:
        base_w = np.asarray(
            telemetry.sync_fetch(
                ebatch.weights, label="bootstrap_re_base_weights"
            )
        )
        lane_weights = bootstrap_re_weights(num_samples, base_w, seed)
    else:
        lane_weights = np.asarray(lane_weights)
        num_samples = int(lane_weights.shape[0])
    live_entities = lane_weights.sum(axis=(0, 2)) > 0

    factors = shifts = None
    if normalization is not None:
        factors, shifts = normalization.factors, normalization.shifts
    obj = make_objective(
        task,
        l2_weight=config.regularization.l2_weight(config.regularization_weight),
        factors=factors,
        shifts=shifts,
    )
    l1 = jnp.float32(
        config.regularization.l1_weight(config.regularization_weight)
    )
    key_cfg = dataclasses.replace(config, regularization_weight=0.0)
    solver = re_bootstrap_solver(key_cfg)
    res = solver(
        obj,
        ebatch,
        jnp.asarray(lane_weights, jnp.float32),
        jnp.asarray(w0, jnp.float32),
        l1,
    )
    # [B, E, K], fetched once through the accounted crossing
    W = telemetry.sync_fetch(res.w, label="bootstrap_re_coefficients")
    W = np.asarray(W, np.float64)

    q1, med, q3 = np.percentile(W, [25, 50, 75], axis=0)
    lo, hi = np.percentile(W, [2.5, 97.5], axis=0)
    return ReBootstrapReport(
        num_samples=int(W.shape[0]),
        mean=W.mean(axis=0),
        std_dev=(
            W.std(axis=0, ddof=1)
            if W.shape[0] > 1
            else np.zeros(W.shape[1:], np.float64)
        ),
        q1=q1,
        median=med,
        q3=q3,
        ci_low=lo,
        ci_high=hi,
        live_entities=live_entities,
    )
