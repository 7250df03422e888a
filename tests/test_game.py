"""GAME layer tests: bucketing/projection correctness, vmapped RE solves vs
per-entity references, coordinate descent on synthetic GLMix data."""

import jax.numpy as jnp
import re

import numpy as np
import pytest

from photon_ml_tpu.game import (
    FixedEffectCoordinate,
    RandomEffectCoordinate,
    ValidationSpec,
    build_game_dataset,
    build_random_effect_dataset,
    run_coordinate_descent,
)
from photon_ml_tpu.ops.objective import make_objective
from photon_ml_tpu.ops.sparse import SparseBatch
from photon_ml_tpu.optim import (
    OptimizerConfig,
    OptimizerType,
    RegularizationContext,
    RegularizationType,
    glm_adapter,
    lbfgs_solve,
)


def _glmix_data(rng, n=600, d_global=12, n_users=25, d_user=6, noise=0.3):
    """response = sigmoid(X_g w_g + X_u w_u[user]) — FE + per-user RE."""
    Xg = rng.normal(size=(n, d_global)) * (rng.random((n, d_global)) < 0.5)
    Xu = rng.normal(size=(n, d_user)) * (rng.random((n, d_user)) < 0.7)
    users = rng.integers(0, n_users, size=n)
    wg = rng.normal(size=d_global)
    wu = rng.normal(size=(n_users, d_user)) * 1.5
    margin = Xg @ wg + np.einsum("ij,ij->i", Xu, wu[users])
    y = (rng.random(n) < 1 / (1 + np.exp(-margin))).astype(float)

    gds = build_game_dataset(
        response=y,
        feature_shards={
            "global": SparseBatch.from_dense(Xg, y),
            "user": SparseBatch.from_dense(Xu, y),
        },
        id_columns={"userId": [f"u{u:03d}" for u in users]},
    )
    return gds, Xg, Xu, users, wg, wu


_CFG = OptimizerConfig(
    optimizer_type=OptimizerType.LBFGS,
    max_iterations=50,
    tolerance=1e-7,
    regularization=RegularizationContext(RegularizationType.L2),
    regularization_weight=1.0,
)


def test_bucketing_roundtrip(rng):
    gds, Xg, Xu, users, *_ = _glmix_data(rng, n=200, n_users=10)
    red = build_random_effect_dataset(gds, "userId", "user")
    # every example row appears exactly once across buckets
    seen = []
    for b in red.buckets:
        idx = np.asarray(b.row_index).reshape(-1)
        seen.extend(idx[idx >= 0].tolist())
    assert sorted(seen) == list(range(200))
    # projection reconstructs the original features
    for b in red.buckets:
        E = b.num_entities
        for e in range(min(E, 3)):
            proj = np.asarray(b.projection[e])
            vals = np.asarray(b.values[e])
            lrows = np.asarray(b.rows[e])
            lcols = np.asarray(b.cols[e])
            ridx = np.asarray(b.row_index[e])
            for v, lr, lc in zip(vals, lrows, lcols):
                if v == 0:
                    continue
                grow = ridx[lr]
                gcol = proj[lc]
                assert np.isclose(Xu[grow, gcol], v, atol=1e-5)


@pytest.mark.slow
def test_re_coordinate_matches_per_entity_solves(rng):
    gds, Xg, Xu, users, *_ = _glmix_data(rng, n=300, n_users=8)
    red = build_random_effect_dataset(gds, "userId", "user")
    coord = RandomEffectCoordinate("per-user", gds, red, "logistic", _CFG)
    model = coord.update_model(coord.initialize_model(), None)

    # reference: solve each entity independently with the same optimizer
    obj = make_objective("logistic", l2_weight=1.0)
    vocab = gds.id_columns["userId"].vocab
    for code in range(min(len(vocab), 5)):
        rows = np.where(gds.id_columns["userId"].codes == code)[0]
        sub = Xu[rows]
        support = np.where(np.any(sub != 0, axis=0))[0]
        ref_batch = SparseBatch.from_dense(
            sub[:, support], gds.response[rows], weights=gds.weight[rows]
        )
        ref = lbfgs_solve(
            glm_adapter(obj, ref_batch), jnp.zeros(len(support), jnp.float32)
        )
        b_idx, pos = red.entity_bucket[code], red.entity_pos[code]
        bm = model.buckets[b_idx]
        proj = np.asarray(bm.projection[pos])
        w_game = np.asarray(bm.coefficients[pos])[np.searchsorted(proj, support)]
        np.testing.assert_allclose(w_game, np.asarray(ref.w), rtol=2e-2, atol=2e-2)


@pytest.mark.slow
def test_re_scores_match_dense_computation(rng):
    gds, Xg, Xu, users, *_ = _glmix_data(rng, n=250, n_users=7)
    red = build_random_effect_dataset(gds, "userId", "user")
    coord = RandomEffectCoordinate("per-user", gds, red, "logistic", _CFG)
    model = coord.update_model(coord.initialize_model(), None)

    scores_fast = np.asarray(coord.score(model))[:250]
    scores_model = np.asarray(model.score(gds))[:250]
    np.testing.assert_allclose(scores_fast, scores_model, rtol=1e-3, atol=1e-3)

    # dense check: scores = Xu . w_user
    codes = gds.id_columns["userId"].codes
    for i in list(range(0, 250, 37)):
        code = codes[i]
        b_idx, pos = red.entity_bucket[code], red.entity_pos[code]
        bm = model.buckets[b_idx]
        proj = np.asarray(bm.projection[pos])
        w_dense = np.zeros(Xu.shape[1])
        valid = proj < Xu.shape[1]
        w_dense[proj[valid]] = np.asarray(bm.coefficients[pos])[valid]
        np.testing.assert_allclose(
            scores_fast[i], Xu[i] @ w_dense, rtol=1e-3, atol=1e-3
        )


@pytest.mark.slow
def test_coordinate_descent_glmix_beats_fe_only(rng):
    gds, Xg, Xu, users, wg, wu = _glmix_data(rng, n=600, n_users=20)
    red = build_random_effect_dataset(gds, "userId", "user")
    val = ValidationSpec(data=gds, evaluators=["auc", "logistic_loss"])

    fe_only = run_coordinate_descent(
        {"fixed": FixedEffectCoordinate("fixed", gds, "global", "logistic", _CFG)},
        task="logistic",
        num_iterations=1,
        validation=val,
    )
    full = run_coordinate_descent(
        {
            "fixed": FixedEffectCoordinate("fixed", gds, "global", "logistic", _CFG),
            "per-user": RandomEffectCoordinate("per-user", gds, red, "logistic", _CFG),
        },
        task="logistic",
        num_iterations=2,
        validation=val,
    )
    assert full.best_metric > fe_only.best_metric + 0.02, (
        f"GLMix {full.best_metric} should beat FE-only {fe_only.best_metric}"
    )
    # residual trick: history has metrics for every (iter, coordinate)
    assert len(full.history) == 4
    assert full.history[-1]["metrics"]["auc"] == pytest.approx(
        max(h["metrics"]["auc"] for h in full.history), abs=0.05
    )


@pytest.mark.slow
def test_best_model_tracking(rng):
    gds, *_ = _glmix_data(rng, n=200, n_users=6)
    red = build_random_effect_dataset(gds, "userId", "user")
    val = ValidationSpec(data=gds, evaluators=["logistic_loss"])  # minimize
    res = run_coordinate_descent(
        {
            "fixed": FixedEffectCoordinate("fixed", gds, "global", "logistic", _CFG),
            "per-user": RandomEffectCoordinate("per-user", gds, red, "logistic", _CFG),
        },
        task="logistic",
        num_iterations=2,
        validation=val,
    )
    losses = [h["metrics"]["logistic_loss"] for h in res.history]
    assert res.best_metric == pytest.approx(min(losses))


def test_active_data_cap_and_passive_scoring(rng):
    gds, Xg, Xu, users, *_ = _glmix_data(rng, n=400, n_users=5)
    red = build_random_effect_dataset(
        gds, "userId", "user", active_rows_per_entity=32, seed=3
    )
    assert len(red.passive_rows) > 0
    active_count = sum(
        int((np.asarray(b.weights) > 0).sum()) for b in red.buckets
    )
    assert active_count + len(red.passive_rows) == 400
    # capped rows carry rescaled weights (sum of active weights ~ total)
    total_active_w = sum(float(np.asarray(b.weights).sum()) for b in red.buckets)
    assert total_active_w == pytest.approx(400, rel=0.01)

    coord = RandomEffectCoordinate("per-user", gds, red, "logistic", _CFG)
    model = coord.update_model(coord.initialize_model(), None)
    scores = np.asarray(coord.score(model))
    # passive rows scored (non-zero for rows with features)
    pr = red.passive_rows[:20]
    model_scores = np.asarray(model.score(gds))
    np.testing.assert_allclose(scores[pr], model_scores[pr], rtol=1e-4, atol=1e-4)


def test_unseen_entity_scores_zero(rng):
    gds, Xg, Xu, users, *_ = _glmix_data(rng, n=150, n_users=5)
    red = build_random_effect_dataset(gds, "userId", "user")
    coord = RandomEffectCoordinate("per-user", gds, red, "logistic", _CFG)
    model = coord.update_model(coord.initialize_model(), None)

    # scoring data with brand-new users must get zero RE scores
    gds2 = build_game_dataset(
        response=gds.response[:50],
        feature_shards={"user": SparseBatch.from_dense(Xu[:50], gds.response[:50])},
        id_columns={"userId": [f"new{u}" for u in range(50)]},
    )
    s = np.asarray(model.score(gds2))
    np.testing.assert_allclose(s[:50], 0.0, atol=1e-6)


@pytest.mark.slow
def test_fe_down_sampling_resamples_per_update(rng):
    """Regression (ADVICE r1-d): the FE coordinate must draw a FRESH negative
    down-sample on every update_model call (runWithSampling parity), not
    freeze one sample at construction."""
    from photon_ml_tpu.game.coordinates import FixedEffectCoordinate
    from photon_ml_tpu.optim import OptimizerConfig

    n = 200
    X = rng.normal(size=(n, 5))
    y = (rng.random(n) > 0.7).astype(float)
    gds = build_game_dataset(
        response=y, feature_shards={"g": SparseBatch.from_dense(X, y)})
    coord = FixedEffectCoordinate(
        name="fe", data=gds, shard_name="g", loss_name="logistic",
        config=OptimizerConfig(max_iterations=3, down_sampling_rate=0.5),
    )
    b0 = coord._maybe_downsample(coord._base_batch, 0)
    b1 = coord._maybe_downsample(coord._base_batch, 1)
    w0 = np.asarray(b0.weights)
    w1 = np.asarray(b1.weights)
    assert not np.array_equal(w0, w1)  # different draws
    # positives always kept at weight 1; kept negatives reweighted by 1/rate
    pos = np.asarray(coord._base_batch.labels) > 0.5
    real = np.asarray(coord._base_batch.weights) > 0
    np.testing.assert_allclose(w0[pos & real], 1.0)
    kept_neg = (~pos) & real & (w0 > 0)
    np.testing.assert_allclose(w0[kept_neg], 2.0)
    # update_model advances the sample index
    m = coord.initialize_model()
    m = coord.update_model(m, None)
    assert coord._update_count == 1


def test_random_effect_newton_matches_lbfgs(rng):
    """The batched-Newton RE fast path reaches the same per-entity optima
    as vmapped LBFGS."""
    import dataclasses as _dc

    from photon_ml_tpu.game import (
        GameConfig, GameEstimator, RandomEffectConfig, build_game_dataset,
    )
    from photon_ml_tpu.optim import (
        OptimizerConfig, OptimizerType, RegularizationContext,
        RegularizationType,
    )
    from photon_ml_tpu.ops.sparse import SparseBatch

    n_users, rows, d = 12, 20, 6
    n = n_users * rows
    users = np.repeat(np.arange(n_users), rows)
    X = rng.normal(size=(n, d))
    w_u = rng.normal(size=(n_users, d))
    y = np.einsum("nd,nd->n", X, w_u[users]) + 0.05 * rng.normal(size=n)
    data = build_game_dataset(
        response=y,
        feature_shards={"f": SparseBatch.from_dense(X, y)},
        id_columns={"u": users},
    )
    base = OptimizerConfig(
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=0.1,
        tolerance=1e-9,
    )

    def fit(opt_type):
        cfg = GameConfig(
            task="squared",
            coordinates={
                "re": RandomEffectConfig(
                    shard_name="f", id_name="u",
                    optimizer=_dc.replace(base, optimizer_type=opt_type),
                )
            },
        )
        return GameEstimator(cfg).fit(data).model

    m_newton = fit(OptimizerType.NEWTON)
    m_lbfgs = fit(OptimizerType.LBFGS)
    s_n = np.asarray(m_newton.score(data))[:n]
    s_l = np.asarray(m_lbfgs.score(data))[:n]
    np.testing.assert_allclose(s_n, s_l, rtol=5e-3, atol=5e-3)


def test_re_variances_match_hessian_diag(rng):
    """computeVariances parity (SingleNodeOptimizationProblem.scala:57-88):
    RE bucket models carry 1/(diag H(w*) + eps) per entity when configured."""
    import dataclasses as _dc

    import jax

    gds, Xg, Xu, users, *_ = _glmix_data(rng, n=300, n_users=8)
    red = build_random_effect_dataset(gds, "userId", "user")
    coord = RandomEffectCoordinate(
        "per-user", gds, red, "logistic", _CFG, compute_variances=True
    )
    model = coord.update_model(coord.initialize_model(), None)

    obj = make_objective("logistic", l2_weight=1.0)
    checked = 0
    for code in range(len(gds.id_columns["userId"].vocab)):
        b_idx, pos = int(red.entity_bucket[code]), int(red.entity_pos[code])
        if b_idx < 0:
            continue
        bm = model.buckets[b_idx]
        assert bm.variances is not None
        one = jax.tree.map(lambda x: x[pos], red.buckets[b_idx].entity_batch())
        hdiag = np.asarray(obj.hessian_diagonal(bm.coefficients[pos], one))
        np.testing.assert_allclose(
            np.asarray(bm.variances[pos]), 1.0 / (hdiag + 1e-12), rtol=1e-4
        )
        checked += 1
        if checked >= 3:
            break
    assert checked == 3

    # unconfigured fits carry no variances
    plain = RandomEffectCoordinate("per-user", gds, red, "logistic", _CFG)
    m2 = plain.update_model(plain.initialize_model(), None)
    assert all(b.variances is None for b in m2.buckets)


@pytest.mark.slow
def test_re_box_constraints_respected_and_match_reference(rng):
    """Per-entity solves honor GLOBAL-space box constraints through the
    index-map projection (SingleNodeOptimizationProblem.scala:124-139)."""
    import dataclasses as _dc

    from photon_ml_tpu.optim import solve

    gds, Xg, Xu, users, *_ = _glmix_data(rng, n=400, n_users=6)
    red = build_random_effect_dataset(gds, "userId", "user")
    bounds = ((0, -0.05, 0.05), (2, 0.0, float("inf")))
    cfg = _dc.replace(_CFG, box_constraints=bounds)
    coord = RandomEffectCoordinate("per-user", gds, red, "logistic", cfg)
    model = coord.update_model(coord.initialize_model(), None)

    # every entity's coefficient at a bounded global feature is in its box
    for bm in model.buckets:
        proj = np.asarray(bm.projection)
        w = np.asarray(bm.coefficients)
        assert np.all(w[proj == 0] >= -0.05 - 1e-6)
        assert np.all(w[proj == 0] <= 0.05 + 1e-6)
        assert np.all(w[proj == 2] >= -1e-6)

    # parity with an independent constrained solve on one entity
    codes = gds.id_columns["userId"].codes
    code = int(codes[0])
    rows = np.where(codes == code)[0]
    sub = Xu[rows]
    support = np.where(np.any(sub != 0, axis=0))[0]
    local_bounds = tuple(
        (int(np.searchsorted(support, g)), lo, hi)
        for g, lo, hi in bounds
        if g in support
    )
    ref_batch = SparseBatch.from_dense(
        sub[:, support], gds.response[rows], weights=gds.weight[rows]
    )
    ref = solve(
        "logistic",
        ref_batch,
        _dc.replace(cfg, box_constraints=local_bounds),
        jnp.zeros(len(support), jnp.float32),
    )
    b_idx, pos = red.entity_bucket[code], red.entity_pos[code]
    bm = model.buckets[b_idx]
    proj = np.asarray(bm.projection[pos])
    w_game = np.asarray(bm.coefficients[pos])[np.searchsorted(proj, support)]
    np.testing.assert_allclose(w_game, np.asarray(ref.w), rtol=2e-2, atol=2e-2)


def _trained_re_model(rng, n=250, n_users=7):
    """(dataset, model, Xu) for the RE scoring-kernel tests below."""
    gds, _Xg, Xu, _users, _wg, _wu = _glmix_data(rng, n=n, n_users=n_users)
    red = build_random_effect_dataset(gds, "userId", "user")
    coord = RandomEffectCoordinate("per-user", gds, red, "logistic", _CFG)
    model = coord.update_model(coord.initialize_model(), None)
    return gds, model, Xu


def _pad_local_dim(model, num_global, new_k):
    """The same RE model with every bucket's local dim padded to ``new_k``
    (sentinel projections, zero coefficients) — semantically identical,
    but scored through the K>64 searchsorted kernel when new_k > 64."""
    import dataclasses

    buckets = []
    for bm in model.buckets:
        num_e, k = bm.projection.shape
        proj = np.full((num_e, new_k), num_global, np.int32)
        proj[:, :k] = np.asarray(bm.projection)
        coef = np.zeros((num_e, new_k), np.float32)
        coef[:, :k] = np.asarray(bm.coefficients)
        buckets.append(
            dataclasses.replace(
                bm,
                projection=jnp.asarray(proj),
                coefficients=jnp.asarray(coef),
                variances=None,
            )
        )
    return dataclasses.replace(model, buckets=tuple(buckets))


def test_re_score_kernel_parity_compare_scan_vs_searchsorted(rng):
    """K<=64 (transposed compare-scan) and K>64 (vmapped searchsorted)
    paths must agree on the same data: pad the projection past the kernel
    switchover with sentinels and assert identical scores."""
    gds, model, Xu = _trained_re_model(rng)
    small_k = np.asarray(model.score(gds))[: gds.num_rows]
    assert model.buckets[0].projection.shape[1] <= 64  # compare-scan path
    padded = _pad_local_dim(model, num_global=Xu.shape[1], new_k=65)
    assert padded.buckets[0].projection.shape[1] > 64  # searchsorted path
    large_k = np.asarray(padded.score(gds))[: gds.num_rows]
    np.testing.assert_allclose(small_k, large_k, rtol=1e-6, atol=1e-6)


def test_re_score_chunk_boundary(rng, monkeypatch):
    """Scores must not depend on the nnz chunking: shrink SCORE_CHUNK so
    every bucket crosses the boundary several times and compare against
    the unchunked result."""
    from photon_ml_tpu.game import models as models_mod

    gds, model, _Xu = _trained_re_model(rng)
    unchunked = np.asarray(model.score(gds))[: gds.num_rows]
    nnz = int(np.sum(np.asarray(gds.shard("user").values) != 0))
    assert nnz > 7  # the patched chunk really splits the work
    monkeypatch.setattr(models_mod, "SCORE_CHUNK", 7)
    chunked = np.asarray(model.score(gds))[: gds.num_rows]
    np.testing.assert_allclose(chunked, unchunked, rtol=1e-6, atol=1e-6)


def test_re_grouping_memoized_per_model_and_dataset(rng):
    """Repeated scoring of one dataset must not redo the host-side
    vocabulary join / bucket grouping (validation every CD iteration);
    a DIFFERENT model on the same dataset must not reuse stale arrays."""
    from photon_ml_tpu import telemetry

    gds, model, Xu = _trained_re_model(rng)
    counters = lambda: telemetry.snapshot()["counters"]  # noqa: E731
    model.score(gds)
    assert counters().get("scoring.code_cache.misses", 0) == 1
    first = model._codes_for(gds)
    second = model._codes_for(gds)
    assert first is second  # cached object, not a recomputed copy
    model.score(gds)
    assert counters().get("scoring.code_cache.misses", 0) == 1
    assert counters().get("scoring.code_cache.hits", 0) >= 3
    # a different model (its own vocab/placement identities) recomputes
    other = _pad_local_dim(model, num_global=Xu.shape[1], new_k=65)
    other = other.__class__(
        id_name=other.id_name,
        shard_name=other.shard_name,
        buckets=other.buckets,
        entity_bucket=other.entity_bucket.copy(),
        entity_pos=other.entity_pos.copy(),
        vocab=other.vocab.copy(),
    )
    other.score(gds)
    assert counters().get("scoring.code_cache.misses", 0) == 2


# -- the span tree of a fit (ISSUE 24) ----------------------------------------


def _span_children(spans):
    kids = {}
    for s in sorted(spans, key=lambda s: s.ts):
        kids.setdefault(s.parent_id, []).append(s)
    return kids


def _contained_in_order(parent, children):
    """Children lie inside the parent, one after the other, and their
    durations sum to the parent's less its (non-negative) self time."""
    cursor = parent.ts
    for c in children:
        assert c.ts >= cursor - 1e-6
        cursor = c.ts + c.dur
    assert cursor <= parent.ts + parent.dur + 1e-6
    assert sum(c.dur for c in children) <= parent.dur + 1e-6


def test_fit_span_tree_two_coordinates(rng):
    """GameEstimator.fit over FE + per-user RE: exactly the tree README's
    span schema names — one ``initial_scores``, one ``residual`` /
    ``update`` / ``score`` / ``validate`` per step under its
    ``coordinate:<name>``, ``layout`` apart from ``upload`` under each
    ``build:<name>`` — and history ``seconds`` still ends at the score
    fetch."""
    from photon_ml_tpu import telemetry
    from photon_ml_tpu.game import (
        FixedEffectConfig,
        GameConfig,
        GameEstimator,
        RandomEffectConfig,
    )

    gds, *_ = _glmix_data(rng, n=200, n_users=8)
    opt = OptimizerConfig(
        optimizer_type=OptimizerType.LBFGS, max_iterations=4, tolerance=1e-6,
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )
    est = GameEstimator(GameConfig(
        task="logistic",
        coordinates={
            "fixed": FixedEffectConfig(
                shard_name="global", optimizer=opt, layout="tiled"),
            "per-user": RandomEffectConfig(
                shard_name="user", id_name="userId", optimizer=opt),
        },
        num_iterations=2,
        evaluators=["auc"],
    ))
    telemetry.reset()
    result = est.fit(gds, validation_data=gds)
    spans = telemetry.finished_spans()
    kids = _span_children(spans)
    names = lambda sid: [s.name for s in kids.get(sid, [])]  # noqa: E731

    (fit,) = kids[None]
    assert fit.name == "fit"
    build, cd = kids[fit.span_id]
    assert (build.name, cd.name) == ("build_coordinates", "coordinate_descent")
    assert build.attrs["cached"] is False
    assert build.attrs["built"] == ["fixed", "per-user"]
    assert names(build.span_id) == ["build:fixed", "build:per-user"]
    for b in kids[build.span_id]:
        parts = kids[b.span_id]
        placed = [p for p in parts if p.name in ("layout", "upload")]
        assert {p.name for p in placed} == {"layout", "upload"}
        # the rest of a build names its own steps (PR 35)
        assert {p.name for p in parts} - {"layout", "upload"} <= {
            "build.normalization", "build.rows", "build.objective",
            "build.table_estimate", "build.layout_report"}
        _contained_in_order(b, parts)  # layout and upload never overlap
        assert placed[0].name == "layout" and placed[-1].name == "upload"
        for p in parts:
            if p.name == "upload":
                assert p.attrs["bytes"] > 0
                assert [e["attrs"]["label"] for e in p.events
                        if e["name"] == "device_fetch"] == ["upload"]
    assert telemetry.snapshot()["counters"]["upload.bytes"] == sum(
        s.attrs["bytes"] for s in spans if s.name == "upload")

    assert names(cd.span_id) == ["initial_scores", "cd_iteration",
                                 "cd_iteration"]
    _contained_in_order(cd, kids[cd.span_id])
    steps = []
    for it in kids[cd.span_id][1:]:
        assert names(it.span_id) == ["coordinate:fixed", "coordinate:per-user"]
        _contained_in_order(it, kids[it.span_id])
        steps.extend(kids[it.span_id])
    assert len(steps) == len(result.history) == 4
    for step, entry in zip(steps, result.history):
        parts = kids[step.span_id]
        assert [p.name for p in parts] == [
            "residual", "update", "score", "validate"]
        for p in parts:  # leaves, but for a random effect's update
            inner = [k.name for k in kids.get(p.span_id, [])]
            if p.name == "update" and step.name == "coordinate:per-user":
                # one dispatch span a geometry bucket, then the wait
                assert inner[-1] == "re_tracker" and len(inner) > 1
                assert all(re.fullmatch(r"re_bucket:\d+x\d+", n)
                           for n in inner[:-1])
                _contained_in_order(p, kids[p.span_id])
            else:
                assert not inner
        _contained_in_order(step, parts)
        score = parts[2]
        assert entry["seconds"] == pytest.approx(
            score.ts + score.dur - step.ts, abs=5e-3)
        assert entry["seconds"] <= step.dur

    # a second build over the same data reuses both coordinates
    est._build_coordinates(gds, mesh=None)
    again = telemetry.finished_spans("build_coordinates")[-1]
    assert again.attrs["cached"] is True and again.parent_id is None
    assert not [s for s in telemetry.finished_spans()
                if s.parent_id == again.span_id]
    telemetry.reset()


def test_cd_span_tree_single_coordinate_called_directly(rng):
    """Callers that bypass GameEstimator.fit (benchmarks, sweeps) get the
    same tree, rooted at ``coordinate_descent``; one coordinate has no
    ``residual``, and without validation no ``validate``."""
    from photon_ml_tpu import telemetry

    gds, *_ = _glmix_data(rng, n=120, n_users=5)
    telemetry.reset()
    coord = FixedEffectCoordinate("fixed", gds, "global", "logistic", _CFG)
    before = len(telemetry.finished_spans())
    run_coordinate_descent({"fixed": coord}, task="logistic", num_iterations=1)
    spans = telemetry.finished_spans()[before:]
    kids = _span_children(spans)
    (cd,) = kids[None]
    assert cd.name == "coordinate_descent"
    init, it = kids[cd.span_id]
    assert (init.name, it.name) == ("initial_scores", "cd_iteration")
    (step,) = kids[it.span_id]
    assert [p.name for p in kids[step.span_id]] == ["update", "score"]
    telemetry.reset()
