"""GAME benchmark: GLMix (fixed effect + per-user random effect) logistic
training throughput on one chip — BASELINE.md config #4.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Workload: MovieLens-1M-shaped synthetic — 1M rows, a 10K-feature sparse FE
shard (~20 nnz/row, trained on the tiled one-hot-matmul pallas fast path)
plus a 10-feature per-user RE shard over 100K users (vmapped bucket solves).
Metric = model coefficients trained per second: every coordinate update
trains its full coefficient set (FE features + sum of per-entity local
dimensions), times CD iterations, over the wall-clock of fit(). The
reference publishes no numbers (BASELINE.json "published": {}), so
vs_baseline is null.
"""

from __future__ import annotations

import json
import time

import numpy as np


def main():
    import jax

    from photon_ml_tpu.game import (
        FixedEffectConfig,
        GameConfig,
        GameEstimator,
        RandomEffectConfig,
        build_game_dataset,
        build_random_effect_dataset,
    )
    from photon_ml_tpu.ops.sparse import SparseBatch
    from photon_ml_tpu.optim import (
        OptimizerConfig,
        RegularizationContext,
        RegularizationType,
    )

    n_rows = 1_000_000
    n_users = 100_000
    fe_features = 10_000
    fe_nnz_per_row = 20
    re_features = 10
    cd_iterations = 2

    rng = np.random.default_rng(0)

    # --- fixed-effect shard: sparse 1M x 10K ---
    nnz = n_rows * fe_nnz_per_row
    fe_rows = np.repeat(np.arange(n_rows, dtype=np.int64), fe_nnz_per_row)
    fe_cols = rng.integers(0, fe_features, size=nnz)
    fe_vals = rng.normal(size=nnz)
    w_true = rng.normal(size=fe_features) * 0.5

    # --- random-effect shard: dense 10 features per row, 100K users ---
    users = rng.integers(0, n_users, size=n_rows)
    Xu = rng.normal(size=(n_rows, re_features))
    wu_true = rng.normal(size=(n_users, re_features)) * 0.5

    margins = np.zeros(n_rows)
    np.add.at(margins, fe_rows, fe_vals * w_true[fe_cols])
    margins += np.einsum("ij,ij->i", Xu, wu_true[users])
    y = (rng.random(n_rows) < 1.0 / (1.0 + np.exp(-margins))).astype(np.float64)

    fe_batch = SparseBatch.from_coo(
        values=fe_vals, rows=fe_rows, cols=fe_cols, labels=y,
        num_features=fe_features,
    )
    ru_rows, ru_cols = np.nonzero(Xu)
    re_batch = SparseBatch.from_coo(
        values=Xu[ru_rows, ru_cols], rows=ru_rows, cols=ru_cols, labels=y,
        num_features=re_features,
    )
    gds = build_game_dataset(
        response=y,
        feature_shards={"global": fe_batch, "user": re_batch},
        id_columns={"userId": users},
    )

    from photon_ml_tpu.optim import OptimizerType

    opt = OptimizerConfig(
        max_iterations=20,
        tolerance=0.0,
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )
    # per-entity solves use the batched-Newton fast path (explicit [K,K]
    # Hessians on the MXU): same optima, ~5x fewer sequential loop steps
    # than vmapped LBFGS for these tiny local dims
    import dataclasses as _dc

    re_opt = _dc.replace(
        opt, optimizer_type=OptimizerType.NEWTON, tolerance=1e-7
    )
    config = GameConfig(
        task="logistic",
        coordinates={
            "fixed": FixedEffectConfig(shard_name="global", optimizer=opt),
            "per-user": RandomEffectConfig(
                shard_name="user", id_name="userId", optimizer=re_opt),
        },
        num_iterations=cd_iterations,
    )

    # count trainable coefficients: FE features + per-entity local dims
    t_build0 = time.perf_counter()
    red = build_random_effect_dataset(gds, "userId", "user")
    build_s = time.perf_counter() - t_build0
    re_coeffs = sum(
        b.num_entities * b.num_local_features for b in red.buckets
    )
    total_coeffs = fe_features + re_coeffs

    est = GameEstimator(config)
    # warmup/compile: tiny prefix of the same structure is NOT possible
    # (shapes differ) — instead run one full fit and time the second, which
    # hits every jit cache (fresh coefficients still solved from zero).
    est.fit(gds)

    t0 = time.perf_counter()
    result = est.fit(gds)
    # wait for the fit: fetch the final model's coefficients
    fe_w = np.asarray(result.model.models["fixed"].coefficients)
    elapsed = time.perf_counter() - t0

    coeffs_per_sec = total_coeffs * cd_iterations / elapsed

    print(
        json.dumps(
            {
                "metric": "glmix_fe_re_logistic_1Mx100Kusers_coeffs_per_sec",
                "value": round(coeffs_per_sec, 1),
                "unit": "coeffs/s",
                "vs_baseline": None,
                "detail": {
                    "elapsed_s": round(elapsed, 3),
                    "re_build_s": round(build_s, 3),
                    "total_coeffs": int(total_coeffs),
                    "cd_iterations": cd_iterations,
                    "n_rows": n_rows,
                    "n_users": n_users,
                    "fe_final_norm": float(np.linalg.norm(fe_w)),
                    "platform": jax.devices()[0].platform,
                    "device": str(jax.devices()[0]),
                },
            }
        )
    )


if __name__ == "__main__":
    main()
