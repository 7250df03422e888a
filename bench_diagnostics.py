"""Bootstrap overhead: B=64 GLMix random-effect bootstrap vs ONE fit
(ISSUE 20 acceptance: ``bootstrap_overhead_ratio`` <= 2.0 on TPU).

The diagnostics claim is that B bootstrap resamples ride the sweep
machinery as B vmapped lanes composed with the per-entity vmap — so the
marginal cost of 64 resampled re-fits is vectorization, not 64x wall
clock. This bench measures exactly that composition through the public
:func:`photon_ml_tpu.diagnostics.bootstrap.bootstrap_random_effect`
entry point:

  1. the SINGLE fit: one all-ones lane (identity resample weights) —
     the same compiled solver family a plain per-entity vmap fit uses,
  2. the BOOTSTRAP: B=64 multinomial-count lanes drawn by
     ``bootstrap_re_weights`` (the same draws the publish path attaches
     CIs from),

both warmed (compilation excluded; every rep starts from other values),
min-of-reps timed, and reports
``bootstrap_overhead_ratio`` = bootstrap_s / single_s — LOWER is
better, gated at <= 2.0 by ``bench_suite --diagnostics --gate``.

On non-TPU backends the entity geometry shrinks and the line carries
``"simulated": true`` — lane-vectorization economics are a TPU claim;
the CPU run proves wiring, not the ratio.

Budget: ``PHOTON_BENCH_BUDGET_S`` honored; skipped phases emit valid
``"truncated": true`` lines.
"""

from __future__ import annotations

import json
import time

import numpy as np

DIAGNOSTICS_METRICS = ("bootstrap_overhead_ratio",)

NUM_SAMPLES = 64
RATIO_CEILING = 2.0
REPS = 3


def _entity_batch(rng, n_entities, rows, feats):
    """A dense-as-COO entity batch: E same-geometry per-entity logistic
    problems with planted coefficients, leading entity axis for vmap."""
    import jax.numpy as jnp

    from photon_ml_tpu.ops.sparse import SparseBatch

    x = rng.normal(size=(n_entities, rows, feats))
    w_true = rng.normal(size=(n_entities, feats)) * 0.5
    margins = np.einsum("erk,ek->er", x, w_true)
    y = (rng.random((n_entities, rows)) < 1.0 / (1.0 + np.exp(-margins)))
    nnz = rows * feats
    batch = SparseBatch(
        values=jnp.asarray(x.reshape(n_entities, nnz), jnp.float32),
        rows=jnp.asarray(
            np.broadcast_to(
                np.repeat(np.arange(rows, dtype=np.int32), feats),
                (n_entities, nnz),
            )
        ),
        cols=jnp.asarray(
            np.broadcast_to(
                np.tile(np.arange(feats, dtype=np.int32), rows),
                (n_entities, nnz),
            )
        ),
        labels=jnp.asarray(y, jnp.float32),
        offsets=jnp.zeros((n_entities, rows), jnp.float32),
        weights=jnp.ones((n_entities, rows), jnp.float32),
        num_features=feats,
    )
    return batch


def run_diagnostics(deadline=None) -> dict[str, float | None]:
    from bench_suite import truncated_line

    def truncated():
        for metric in DIAGNOSTICS_METRICS:
            print(truncated_line(metric), flush=True)
        return {metric: None for metric in DIAGNOSTICS_METRICS}

    if deadline is not None and time.monotonic() > deadline:
        return truncated()

    import jax
    import jax.numpy as jnp

    from photon_ml_tpu import telemetry
    from photon_ml_tpu.diagnostics.bootstrap import (
        bootstrap_random_effect,
        bootstrap_re_weights,
    )
    from photon_ml_tpu.optim import (
        OptimizerConfig,
        OptimizerType,
        RegularizationContext,
        RegularizationType,
    )

    telemetry.configure_from_env()
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu:
        # a realistic RE bucket: the bench_game per-user shape
        n_entities, rows, feats = 4096, 64, 16
    else:
        n_entities, rows, feats = 16, 8, 4

    rng = np.random.default_rng(0)
    ebatch = _entity_batch(rng, n_entities, rows, feats)
    w0 = jnp.zeros((n_entities, feats), jnp.float32)
    config = OptimizerConfig(
        optimizer_type=OptimizerType.NEWTON,
        max_iterations=10,
        tolerance=1e-7,
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )

    # identity lanes = the single fit; multinomial lanes = the bootstrap
    single_lanes = np.ones((1, n_entities, rows), np.float32)
    boot_lanes = bootstrap_re_weights(
        NUM_SAMPLES, np.ones((n_entities, rows), np.float32), seed=0
    )

    def timed(lane_weights):
        # warm-up compiles this lane count's executable; the timed reps
        # then each start from a perturbed w0
        bootstrap_random_effect(
            ebatch, "logistic", config, w0, lane_weights=lane_weights
        )
        best = None
        for rep in range(1, REPS + 1):
            t0 = time.perf_counter()
            report = bootstrap_random_effect(
                ebatch, "logistic", config, w0 + 1e-6 * rep,
                lane_weights=lane_weights,
            )
            elapsed = time.perf_counter() - t0
            best = elapsed if best is None else min(best, elapsed)
        return best, report

    single_s, _ = timed(single_lanes)
    if deadline is not None and time.monotonic() > deadline:
        return truncated()
    boot_s, report = timed(boot_lanes)
    ratio = boot_s / max(single_s, 1e-9)

    if on_tpu:
        assert ratio <= RATIO_CEILING, (
            f"B={NUM_SAMPLES} bootstrap cost {ratio:.2f}x a single fit "
            f"(> {RATIO_CEILING}x): the resample lanes are not riding "
            "the vmap composition"
        )
    print(
        json.dumps(
            {
                "metric": "bootstrap_overhead_ratio",
                "value": round(ratio, 3),
                "unit": "x",
                "vs_baseline": None,
                "detail": {
                    "num_samples": NUM_SAMPLES,
                    "single_fit_s": round(single_s, 4),
                    "bootstrap_s": round(boot_s, 4),
                    "entities": n_entities,
                    "rows_per_entity": rows,
                    "features_per_entity": feats,
                    "mean_ci_width": report.summary().get("mean_ci_width"),
                    "ceiling": RATIO_CEILING,
                    "platform": jax.devices()[0].platform,
                    "simulated": not on_tpu,
                },
            }
        ),
        flush=True,
    )
    return {"bootstrap_overhead_ratio": round(ratio, 3)}


def main():
    from bench_suite import budget_deadline

    run_diagnostics(deadline=budget_deadline())


if __name__ == "__main__":
    main()
