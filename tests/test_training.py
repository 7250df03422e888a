"""Lambda-sweep training API (ModelTraining analog): warm-start chaining,
single compiled program across lambdas, variances, best-model selection."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data.normalization import (
    NormalizationType,
    build_normalization_context,
)
from photon_ml_tpu.data.stats import summarize
from photon_ml_tpu.ops.sparse import SparseBatch
from photon_ml_tpu.optim import (
    OptimizerConfig,
    RegularizationContext,
    RegularizationType,
    solve,
)
from photon_ml_tpu.training import select_best_model, train_glm


def _logistic_data(rng, n=400, d=12):
    X = rng.normal(size=(n, d))
    X[:, 0] = 1.0  # intercept column
    w_true = rng.normal(size=d)
    p = 1.0 / (1.0 + np.exp(-(X @ w_true)))
    y = (rng.random(n) < p).astype(np.float64)
    return X, y, SparseBatch.from_dense(X, y)


def _l2_config(**kw):
    return OptimizerConfig(
        regularization=RegularizationContext(RegularizationType.L2),
        **kw,
    )


def test_sweep_matches_individual_solves(rng):
    X, y, batch = _logistic_data(rng)
    lambdas = [0.1, 10.0, 1.0]
    entries = train_glm(batch, "logistic", lambdas, _l2_config())
    assert [e.reg_weight for e in entries] == lambdas  # caller order preserved
    for lam, e in zip(lambdas, entries):
        cfg = _l2_config(regularization_weight=lam)
        ref = solve(
            "logistic", batch, cfg, jnp.zeros(X.shape[1], jnp.float32)
        )
        np.testing.assert_allclose(
            e.model.coefficients.means, ref.w, rtol=1e-3, atol=1e-3
        )


@pytest.mark.slow
def test_warm_start_beats_cold_start_iterations(rng):
    X, y, batch = _logistic_data(rng, n=600)
    lambdas = [100.0, 10.0, 1.0, 0.1, 0.01]
    entries = train_glm(batch, "logistic", lambdas, _l2_config())
    warm_iters = sum(int(e.result.iterations) for e in entries)
    cold_iters = 0
    for lam in lambdas:
        cfg = _l2_config(regularization_weight=lam)
        cold_iters += int(
            solve("logistic", batch, cfg, jnp.zeros(X.shape[1], jnp.float32))
            .iterations
        )
    # descending warm-started sweep must do no more total work
    assert warm_iters <= cold_iters
    # and the later (small-lambda) solves individually benefit
    assert int(entries[-1].result.iterations) < int(
        solve(
            "logistic",
            batch,
            _l2_config(regularization_weight=0.01),
            jnp.zeros(X.shape[1], jnp.float32),
        ).iterations
    )


def test_sweep_compiles_once(rng):
    X, y, batch = _logistic_data(rng, n=100, d=6)
    with jax.log_compiles():
        import logging

        class Counter(logging.Handler):
            count = 0

            def emit(self, record):
                msg = record.getMessage()
                if "Finished XLA compilation" in msg and "_sweep_solve" in msg:
                    type(self).count += 1

        h = Counter()
        logging.getLogger("jax").addHandler(h)
        try:
            train_glm(batch, "logistic", [3.0, 1.0, 0.3, 0.1], _l2_config())
        finally:
            logging.getLogger("jax").removeHandler(h)
    # all lambdas share ONE compiled solve program (traced reg weight)
    assert Counter.count == 1


def test_variances_match_inverse_hessian_diagonal(rng):
    X, y, batch = _logistic_data(rng)
    lam = 2.0
    entries = train_glm(
        batch, "logistic", [lam], _l2_config(), compute_variances=True
    )
    m = entries[0].model
    assert m.coefficients.variances is not None
    w = m.coefficients.means
    z = X @ np.asarray(w)
    p = 1.0 / (1.0 + np.exp(-z))
    hdiag = (X**2 * (p * (1 - p))[:, None]).sum(axis=0) + lam
    np.testing.assert_allclose(
        m.coefficients.variances, 1.0 / (hdiag + 1e-12), rtol=5e-3
    )


def test_variances_round_trip_model_store(rng, tmp_path):
    from photon_ml_tpu.data.model_store import load_glm, save_glm

    X, y, batch = _logistic_data(rng, n=150, d=8)
    entries = train_glm(
        batch, "logistic", [1.0], _l2_config(), compute_variances=True
    )
    save_glm(entries[0].model, str(tmp_path / "m"))
    loaded = load_glm(str(tmp_path / "m"))
    np.testing.assert_allclose(
        loaded.coefficients.variances,
        entries[0].model.coefficients.variances,
        rtol=1e-6,
    )


def test_sweep_with_normalization_round_trips_space(rng):
    X, y, batch = _logistic_data(rng)
    # badly scaled column: normalization should still converge to the
    # optimum of the (normalized-space-regularized) problem; at lambda=0
    # the original-space optimum is normalization-invariant
    Xs = X.copy()
    Xs[:, 3] *= 100.0
    batch_s = SparseBatch.from_dense(Xs, y)
    summary = summarize(batch_s)
    norm = build_normalization_context(
        NormalizationType.STANDARDIZATION, summary, intercept_index=0
    )
    entries = train_glm(
        batch_s,
        "logistic",
        [0.0],
        OptimizerConfig(max_iterations=300, tolerance=1e-10),
        normalization=norm,
    )
    plain = train_glm(
        batch_s,
        "logistic",
        [0.0],
        OptimizerConfig(max_iterations=300, tolerance=1e-10),
    )
    np.testing.assert_allclose(
        entries[0].model.coefficients.means,
        plain[0].model.coefficients.means,
        rtol=2e-2,
        atol=2e-2,
    )


def test_select_best_model(rng):
    X, y, batch = _logistic_data(rng, n=500)
    Xv, yv, val_batch = _logistic_data(rng, n=300)
    lambdas = [100.0, 1.0, 0.01]
    entries = train_glm(batch, "logistic", lambdas, _l2_config())
    best, metric = select_best_model(entries, val_batch)
    assert best in entries
    assert 0.0 <= metric <= 1.0  # AUC for the logistic task
    # selection is argmax of the validation metric (AUC: larger is better)
    from photon_ml_tpu.evaluation import auc

    aucs = [
        float(auc(e.model.compute_score(val_batch), val_batch.labels,
                  val_batch.weights))
        for e in entries
    ]
    assert metric == pytest.approx(max(aucs))
    assert best is entries[int(np.argmax(aucs))]
    # RMSE selection direction (smaller is better) on the same entries
    best_rmse, val_rmse = select_best_model(entries, val_batch, metric="rmse")
    from photon_ml_tpu.evaluation import rmse as rmse_fn

    rmses = [
        float(rmse_fn(e.model.compute_score(val_batch), val_batch.labels,
                      val_batch.weights))
        for e in entries
    ]
    assert val_rmse == pytest.approx(min(rmses))


def test_owlqn_sweep_sparsity_increases_with_lambda(rng):
    X, y, batch = _logistic_data(rng)
    cfg = OptimizerConfig(
        regularization=RegularizationContext(RegularizationType.L1),
    )
    entries = train_glm(batch, "logistic", [5.0, 0.005], cfg)
    nnz_hi = int(np.sum(np.abs(np.asarray(entries[0].model.coefficients.means)) > 1e-8))
    nnz_lo = int(np.sum(np.abs(np.asarray(entries[1].model.coefficients.means)) > 1e-8))
    assert nnz_hi < nnz_lo


@pytest.mark.slow
def test_sweep_on_mesh_matches_single_device(rng):
    from photon_ml_tpu.parallel.mesh import make_mesh, shard_rows

    X, y, batch = _logistic_data(rng, n=256, d=10)
    mesh = make_mesh({"data": 4}, devices=jax.devices()[:4])
    stacked = shard_rows(batch, 4)
    lambdas = [1.0, 0.1]
    dist = train_glm(stacked, "logistic", lambdas, _l2_config(), mesh=mesh)
    local = train_glm(batch, "logistic", lambdas, _l2_config())
    for d_e, l_e in zip(dist, local):
        np.testing.assert_allclose(
            d_e.model.coefficients.means,
            l_e.model.coefficients.means,
            rtol=1e-3,
            atol=1e-3,
        )


def test_game_fit_finish_event_carries_telemetry_snapshot(rng, tmp_path):
    """A toy GameEstimator.fit emits TrainingFinishEvent with the metrics
    snapshot attached — nonzero device_fetches, compile counters, and a
    JSONL span tree nesting fit > coordinate_descent > cd_iteration >
    coordinate:<name> that the Perfetto exporter converts without error
    (ISSUE 1 acceptance; ``coordinate_descent`` since ISSUE 24)."""
    import json

    from photon_ml_tpu import telemetry
    from photon_ml_tpu.game import (
        FixedEffectConfig,
        GameConfig,
        GameEstimator,
        RandomEffectConfig,
        build_game_dataset,
    )
    from photon_ml_tpu.utils.events import TrainingFinishEvent

    telemetry.reset()
    trace_out = tmp_path / "fit.trace.jsonl"
    telemetry.configure(trace_out=str(trace_out))
    try:
        X = rng.normal(size=(120, 5))
        users = rng.integers(0, 3, 120)
        y = (rng.random(120) < 0.5).astype(float)
        data = build_game_dataset(
            response=y,
            feature_shards={"f": SparseBatch.from_dense(X, y)},
            id_columns={"u": users},
        )
        est = GameEstimator(
            GameConfig(
                task="logistic",
                coordinates={
                    "fixed": FixedEffectConfig(shard_name="f"),
                    "perUser": RandomEffectConfig(shard_name="f", id_name="u"),
                },
            )
        )
        seen = []
        est.events.register(seen.append)
        est.fit(data)

        (finish,) = [e for e in seen if isinstance(e, TrainingFinishEvent)]
        snap = finish.metrics_snapshot
        assert snap is not None
        assert snap["counters"]["device_fetches"] > 0
        assert snap["counters"]["device_fetch_bytes"] > 0
        assert "jit_compiles" in snap["counters"]
        assert snap["histograms"]["re_solve_iterations"]["count"] > 0

        # per-coordinate span names, nested fit > coordinate_descent >
        # cd_iteration > coordinate:*
        spans = telemetry.finished_spans()
        by_id = {s.span_id: s for s in spans}
        names = {s.name for s in spans}
        assert {"fit", "coordinate_descent", "cd_iteration",
                "coordinate:fixed", "coordinate:perUser"} <= names
        for cname in ("coordinate:fixed", "coordinate:perUser"):
            (coord,) = [s for s in spans if s.name == cname]
            it = by_id[coord.parent_id]
            assert it.name == "cd_iteration"
            cd = by_id[it.parent_id]
            assert cd.name == "coordinate_descent"
            assert by_id[cd.parent_id].name == "fit"

        # the JSONL sink saw the same tree; the Perfetto export round-trips
        recorded = {
            json.loads(line)["name"]
            for line in trace_out.read_text().splitlines()
            if json.loads(line).get("type") == "span"
        }
        assert "coordinate:perUser" in recorded
        out = tmp_path / "fit.perfetto.json"
        assert telemetry.export_chrome_trace(str(trace_out), str(out)) > 0
        json.loads(out.read_text())
    finally:
        telemetry.reset()


def test_train_glm_emits_sweep_spans(rng):
    from photon_ml_tpu import telemetry

    telemetry.reset()
    try:
        X, y, batch = _logistic_data(rng, n=100, d=6)
        train_glm(batch, "logistic", [1.0, 0.1], _l2_config())
        (sweep,) = telemetry.finished_spans("train_glm")
        assert sweep.attrs["num_lambdas"] == 2
        solves = telemetry.finished_spans("lambda_solve")
        assert [s.attrs["reg_weight"] for s in solves] == [1.0, 0.1]
        assert all(s.parent_id == sweep.span_id for s in solves)
        assert telemetry.snapshot()["counters"]["glm_sweep_solves"] == 2
    finally:
        telemetry.reset()


def test_game_fit_with_nan_coordinate_completes_via_guard(rng):
    """ISSUE 2 acceptance: a fit with an injected NaN-producing coordinate
    completes — the bad coordinate rolls back (then freezes) instead of
    crashing the run, the divergence shows up in the telemetry snapshot,
    and the healthy coordinate still trains."""
    from photon_ml_tpu import telemetry
    from photon_ml_tpu.game import (
        FixedEffectConfig,
        GameConfig,
        GameEstimator,
        RandomEffectConfig,
        build_game_dataset,
    )
    from photon_ml_tpu.optim import GuardSpec

    n = 100
    Xf = rng.normal(size=(n, 4))
    Xg = rng.normal(size=(n, 4))
    Xg[3, 2] = np.nan  # one poisoned feature value -> NaN objective
    users = rng.integers(0, 3, n)
    y = (rng.random(n) < 0.5).astype(float)
    data = build_game_dataset(
        response=y,
        feature_shards={
            "f": SparseBatch.from_dense(Xf, y),
            "g": SparseBatch.from_dense(Xg, y),
        },
        id_columns={"u": users},
    )
    config = GameConfig(
        task="logistic",
        num_iterations=2,
        coordinates={
            "bad": FixedEffectConfig(shard_name="g"),
            "perUser": RandomEffectConfig(shard_name="f", id_name="u"),
        },
    )
    telemetry.reset()
    try:
        result = GameEstimator(config).fit(
            data, guard=GuardSpec(max_retries=1)
        )
        counters = telemetry.snapshot()["counters"]
        assert counters["solves.diverged"] >= 1
        assert counters["solves.retried"] >= 1
        assert counters["solves.rolled_back"] >= 1
        w_bad = np.asarray(result.model.models["bad"].coefficients)
        np.testing.assert_array_equal(w_bad, np.zeros_like(w_bad))
        # NaN scores were sanitized out of the residual: the healthy
        # coordinate trained to a finite non-zero model
        w_user = np.asarray(
            result.model.models["perUser"].buckets[0].coefficients
        )
        assert np.isfinite(w_user).all()
        assert np.any(np.abs(w_user) > 0)
    finally:
        telemetry.reset()


def test_variances_with_normalization_positive_and_scaled(rng):
    """The variance back-transform deviates from the reference deliberately:
    Var(c*X) = c^2 Var(X) — factor-squared scaling, no intercept shift term
    (the reference's means-transform on variances can go negative)."""
    X, y, _ = _logistic_data(rng, n=300, d=8)
    X = X.copy()
    X[:, 3] *= 50.0  # badly scaled column -> factor ~ 1/50
    batch = SparseBatch.from_dense(X, y)
    norm = build_normalization_context(
        NormalizationType.STANDARDIZATION, summarize(batch), intercept_index=0
    )
    e = train_glm(
        batch, "logistic", [1.0], _l2_config(), normalization=norm,
        compute_variances=True,
    )[0]
    v = np.asarray(e.model.coefficients.variances)
    assert np.all(v > 0)
    assert np.all(np.isfinite(v))
    # normalized-space variance is O(1) across columns; the factor^2 map
    # must shrink the scaled column's variance by ~50^2
    others = np.delete(v, [0, 3])
    assert v[3] < 0.05 * np.median(others)
