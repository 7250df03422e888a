"""Optimizer tests: convergence to closed forms / KKT conditions, parity
between LBFGS and TRON, vmap-batched solves, box constraints, warm starts."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.ops.objective import make_objective
from photon_ml_tpu.ops.sparse import SparseBatch
from photon_ml_tpu.optim import (
    FUNCTION_VALUES_CONVERGED,
    GRADIENT_CONVERGED,
    BoxConstraints,
    LBFGSConfig,
    OptimizerConfig,
    OptimizerType,
    RegularizationContext,
    RegularizationType,
    from_value_and_grad,
    glm_adapter,
    lbfgs_solve,
    owlqn_solve,
    solve,
    tron_solve,
)


def _make_batch(rng, n=200, d=15, loss="squared", density=0.5):
    X = rng.normal(size=(n, d)) * (rng.random((n, d)) < density)
    if loss == "squared":
        y = X @ rng.normal(size=d) + 0.1 * rng.normal(size=n)
    elif loss == "poisson":
        rate = np.exp(np.clip(X @ (rng.normal(size=d) * 0.3), -3, 3))
        y = rng.poisson(rate).astype(np.float64)
    else:
        y = (rng.random(n) < 1 / (1 + np.exp(-(X @ rng.normal(size=d))))).astype(
            np.float64
        )
    wt = rng.random(n) + 0.5
    return X, y, wt, SparseBatch.from_dense(X, y, weights=wt)


def _ridge_closed_form(X, y, wt, l2):
    W = np.diag(wt)
    return np.linalg.solve(X.T @ W @ X + l2 * np.eye(X.shape[1]), X.T @ (wt * y))


def test_lbfgs_matches_ridge_closed_form(rng):
    X, y, wt, batch = _make_batch(rng)
    w_star = _ridge_closed_form(X, y, wt, l2=2.0)
    obj = make_objective("squared", l2_weight=2.0)
    res = lbfgs_solve(glm_adapter(obj, batch), jnp.zeros(X.shape[1], jnp.float32))
    np.testing.assert_allclose(res.w, w_star, rtol=2e-3, atol=2e-3)
    assert int(res.reason) in (FUNCTION_VALUES_CONVERGED, GRADIENT_CONVERGED)


def test_tron_matches_ridge_closed_form(rng):
    X, y, wt, batch = _make_batch(rng)
    w_star = _ridge_closed_form(X, y, wt, l2=2.0)
    obj = make_objective("squared", l2_weight=2.0)
    res = tron_solve(glm_adapter(obj, batch), jnp.zeros(X.shape[1], jnp.float32))
    np.testing.assert_allclose(res.w, w_star, rtol=2e-3, atol=2e-3)


def test_lbfgs_tron_agree_logistic(rng):
    X, y, wt, batch = _make_batch(rng, loss="logistic")
    obj = make_objective("logistic", l2_weight=1.0)
    ad = glm_adapter(obj, batch)
    d = X.shape[1]
    r1 = lbfgs_solve(ad, jnp.zeros(d, jnp.float32))
    r2 = tron_solve(ad, jnp.zeros(d, jnp.float32))
    np.testing.assert_allclose(r1.w, r2.w, rtol=5e-3, atol=5e-3)
    # both at a stationary point
    assert float(jnp.linalg.norm(obj.grad(r1.w, batch))) < 1e-2
    assert float(jnp.linalg.norm(obj.grad(r2.w, batch))) < 1e-2


def test_poisson_convergence(rng):
    X, y, wt, batch = _make_batch(rng, loss="poisson")
    obj = make_objective("poisson", l2_weight=0.5)
    res = lbfgs_solve(glm_adapter(obj, batch), jnp.zeros(X.shape[1], jnp.float32))
    gn = float(jnp.linalg.norm(obj.grad(res.w, batch)))
    assert gn < 5e-2, f"gradient norm {gn}"


def test_owlqn_lasso_kkt(rng):
    X, y, wt, batch = _make_batch(rng)
    obj = make_objective("squared", l2_weight=0.0)
    # pick l1 between the at-zero gradient magnitudes so SOME coords stay zero
    g0 = np.abs(np.asarray(obj.grad(jnp.zeros(X.shape[1], jnp.float32), batch)))
    l1 = float(np.median(g0))
    res = owlqn_solve(glm_adapter(obj, batch), jnp.zeros(X.shape[1], jnp.float32), l1)
    w, g = np.asarray(res.w), np.asarray(obj.grad(res.w, batch))
    # KKT: |g_j| <= l1 where w_j = 0 ; g_j = -l1*sign(w_j) where w_j != 0
    tol = 5e-2 * max(1.0, np.abs(g).max())
    zero = w == 0.0
    assert np.all(np.abs(g[zero]) <= l1 + tol)
    np.testing.assert_allclose(g[~zero], -l1 * np.sign(w[~zero]), atol=tol)
    # sparsity actually induced
    assert zero.sum() > 0


def test_owlqn_produces_sparser_models_with_larger_l1(rng):
    X, y, wt, batch = _make_batch(rng)
    obj = make_objective("squared")
    ad = glm_adapter(obj, batch)
    g0 = np.abs(np.asarray(obj.grad(jnp.zeros(X.shape[1], jnp.float32), batch)))
    nnz = []
    for l1 in (0.01 * float(g0.min()), 0.9 * float(g0.max())):
        res = owlqn_solve(ad, jnp.zeros(X.shape[1], jnp.float32), l1)
        nnz.append(int(np.sum(np.asarray(res.w) != 0)))
    assert nnz[1] < nnz[0]


def test_box_constraints_projection_and_kkt(rng):
    X, y, wt, batch = _make_batch(rng)
    d = X.shape[1]
    lo = jnp.full((d,), -0.1)
    hi = jnp.full((d,), 0.1)
    obj = make_objective("squared", l2_weight=1.0)
    res = lbfgs_solve(
        glm_adapter(obj, batch),
        jnp.zeros(d, jnp.float32),
        constraints=BoxConstraints(lower=lo, upper=hi),
    )
    w = np.asarray(res.w)
    assert np.all(w >= -0.1 - 1e-6) and np.all(w <= 0.1 + 1e-6)
    # KKT for box: at interior points gradient ~ 0; at bounds gradient pushes out
    g = np.asarray(obj.grad(res.w, batch))
    interior = (w > -0.1 + 1e-4) & (w < 0.1 - 1e-4)
    scale = max(1.0, np.abs(g).max())
    assert np.all(np.abs(g[interior]) < 0.05 * scale)
    assert np.all(g[w >= 0.1 - 1e-6] <= 1e-3 * scale)
    assert np.all(g[w <= -0.1 + 1e-6] >= -1e-3 * scale)


@pytest.mark.slow
def test_vmap_batched_lbfgs_matches_individual(rng):
    # the random-effect pattern: vmap over K independent problems
    K, n, d = 5, 40, 6
    Xs = rng.normal(size=(K, n, d))
    ys = np.stack([X @ rng.normal(size=d) for X in Xs])
    obj = make_objective("squared", l2_weight=1.0)

    # build K batches with identical shapes, stack their arrays
    batches = [SparseBatch.from_dense(Xs[k], ys[k]) for k in range(K)]
    nnz_max = max(b.nnz for b in batches)
    batches = [b.pad_rows_to(n, nnz_max) for b in batches]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)

    cfg = LBFGSConfig(max_iterations=50)

    def solve_one(b):
        return lbfgs_solve(glm_adapter(obj, b), jnp.zeros(d, jnp.float32), cfg)

    batched = jax.jit(jax.vmap(solve_one))(stacked)
    for k in range(K):
        single = solve_one(batches[k])
        np.testing.assert_allclose(batched.w[k], single.w, rtol=1e-3, atol=1e-3)


def test_warm_start_converges_quickly(rng):
    X, y, wt, batch = _make_batch(rng)
    obj = make_objective("squared", l2_weight=2.0)
    ad = glm_adapter(obj, batch)
    d = X.shape[1]
    cold = lbfgs_solve(ad, jnp.zeros(d, jnp.float32))
    warm = lbfgs_solve(
        ad,
        cold.w,
        init_value=cold.values[0],
        init_grad_norm=cold.grad_norms[0],
    )
    assert int(warm.iterations) <= 3
    np.testing.assert_allclose(warm.w, cold.w, rtol=1e-3, atol=1e-3)


def test_factory_dispatch_and_validation(rng):
    X, y, wt, batch = _make_batch(rng, loss="logistic")
    d = X.shape[1]
    w0 = jnp.zeros(d, jnp.float32)
    for opt, reg in [
        (OptimizerType.LBFGS, RegularizationType.L2),
        (OptimizerType.TRON, RegularizationType.L2),
        (OptimizerType.LBFGS, RegularizationType.ELASTIC_NET),
    ]:
        cfg = OptimizerConfig(
            optimizer_type=opt,
            regularization=RegularizationContext(reg, alpha=0.5),
            regularization_weight=1.0,
            max_iterations=40,
        )
        res = solve("logistic", batch, cfg, w0)
        assert np.all(np.isfinite(np.asarray(res.w)))

    with pytest.raises(ValueError, match="TRON does not support L1"):
        solve(
            "logistic",
            batch,
            OptimizerConfig(
                optimizer_type=OptimizerType.TRON,
                regularization=RegularizationContext(RegularizationType.L1),
                regularization_weight=1.0,
            ),
            w0,
        )
    with pytest.raises(ValueError, match="twice-differentiable"):
        solve(
            "smoothed_hinge",
            batch,
            OptimizerConfig(optimizer_type=OptimizerType.TRON),
            w0,
        )


def test_generic_objective_rosenbrock():
    # non-GLM objective through the generic adapter: Rosenbrock in 2D
    def f(w):
        v = 100.0 * (w[1] - w[0] ** 2) ** 2 + (1.0 - w[0]) ** 2
        return v

    ad = from_value_and_grad(jax.value_and_grad(f))
    res = lbfgs_solve(
        ad,
        jnp.asarray([-1.2, 1.0], jnp.float32),
        LBFGSConfig(max_iterations=200, tolerance=1e-12),
    )
    np.testing.assert_allclose(res.w, [1.0, 1.0], atol=2e-2)


# -- batched Newton (TPU-first small-d fast path) ----------------------------


def test_newton_matches_lbfgs_logistic(rng):
    X, y, wt, batch = _make_batch(rng, loss="logistic")
    w0 = jnp.zeros(X.shape[1], jnp.float32)
    cfg_n = OptimizerConfig(
        optimizer_type=OptimizerType.NEWTON,
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=0.5,
        tolerance=1e-9,
    )
    cfg_l = dataclasses.replace(cfg_n, optimizer_type=OptimizerType.LBFGS)
    rn = solve("logistic", batch, cfg_n, w0)
    rl = solve("logistic", batch, cfg_l, w0)
    np.testing.assert_allclose(rn.w, rl.w, rtol=2e-3, atol=2e-3)
    # quadratic convergence: far fewer iterations than LBFGS
    assert int(rn.iterations) <= int(rl.iterations)


def test_newton_ridge_closed_form(rng):
    X, y, wt, batch = _make_batch(rng)
    w_star = _ridge_closed_form(X, y, wt, l2=2.0)
    cfg = OptimizerConfig(
        optimizer_type=OptimizerType.NEWTON,
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=2.0,
        tolerance=1e-10,
    )
    res = solve("squared", batch, cfg, jnp.zeros(X.shape[1], jnp.float32))
    np.testing.assert_allclose(res.w, w_star, rtol=2e-3, atol=2e-3)
    # a quadratic solves in ~1 Newton step
    assert int(res.iterations) <= 3


def test_newton_vmapped_batch(rng):
    """Batched per-entity solves: vmap over independent problems."""
    E, n, d = 8, 40, 6
    Xs = rng.normal(size=(E, n, d))
    ys = rng.normal(size=(E, n))
    batches = [SparseBatch.from_dense(Xs[e], ys[e]) for e in range(E)]
    import jax

    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *batches)
    cfg = OptimizerConfig(
        optimizer_type=OptimizerType.NEWTON,
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
        tolerance=1e-10,
    )
    res = jax.vmap(
        lambda b, w0: solve("squared", b, cfg, w0), in_axes=(0, None)
    )(stacked, jnp.zeros(d, jnp.float32))
    for e in range(E):
        w_star = _ridge_closed_form(Xs[e], ys[e], np.ones(n), l2=1.0)
        np.testing.assert_allclose(res.w[e], w_star, rtol=3e-3, atol=3e-3)


def _newton_logistic(X, y, offsets, cap=20):
    batch = SparseBatch.from_dense(X, y, offsets=offsets)
    cfg = OptimizerConfig(
        optimizer_type=OptimizerType.NEWTON,
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
        tolerance=1e-7,
        max_iterations=cap,
    )
    return solve("logistic", batch, cfg, jnp.zeros(X.shape[1], jnp.float32))


@pytest.mark.parametrize("offset", [9.0, 12.0, -14.0])
def test_newton_one_row_entity_ends_under_the_ceiling(offset):
    """A one-row intercept far out on the sigmoid: softplus(z) - y z moves
    by its own rounding (1e-6) where a Newton step gains 1e-9, so a stop
    that waits for the objective to hold still runs to the ceiling by
    chance. The step's own forecast ends it: taken whole, then the last."""
    y = np.asarray([1.0 if offset > 0 else 0.0])
    res = _newton_logistic(np.ones((1, 1)), y, np.asarray([offset]))
    assert int(res.iterations) <= 3
    assert res.reason == FUNCTION_VALUES_CONVERGED
    # the L2 optimum: w = -(sigmoid(z) - y) to first order, and float32's
    # sigmoid next to 1 is an ulp of 1 (6e-8) off
    want = -(1.0 / (1.0 + np.exp(-offset)) - y[0])
    np.testing.assert_allclose(float(res.w[0]), want, rtol=1e-3, atol=1e-7)


def test_newton_takes_its_last_step(rng):
    """Where the forecast gain falls under float32's resolution of the
    objective the step is still taken: the end sits at the float64
    optimum to float32's grade, not one step short of it, and permuting
    the rows (another rounding of every sum) ends at the same count."""
    n, d = 300, 6
    X = (rng.random((n, d)) < 0.4).astype(np.float64)
    X[:, 0] = 1.0
    w_true = rng.normal(size=d)
    y = (rng.random(n) < 1 / (1 + np.exp(-X @ w_true))).astype(np.float64)
    res = _newton_logistic(X, y, np.zeros(n))
    w = np.zeros(d)
    for _ in range(30):  # float64 Newton
        p = 1 / (1 + np.exp(-X @ w))
        H = (X * (p * (1 - p))[:, None]).T @ X + np.eye(d)
        w = w - np.linalg.solve(H, X.T @ (p - y) + w)
    assert res.reason == FUNCTION_VALUES_CONVERGED
    np.testing.assert_allclose(res.w, w, rtol=0, atol=2e-5)
    counts = set()
    for _ in range(5):
        order = rng.permutation(n)
        counts.add(int(_newton_logistic(X[order], y[order],
                                        np.zeros(n)).iterations))
    assert counts == {int(res.iterations)}


def test_newton_rejects_l1_and_hinge():
    cfg = OptimizerConfig(
        optimizer_type=OptimizerType.NEWTON,
        regularization=RegularizationContext(RegularizationType.L1),
        regularization_weight=1.0,
    )
    with pytest.raises(ValueError, match="NEWTON"):
        cfg.validate("logistic")
    cfg2 = OptimizerConfig(optimizer_type=OptimizerType.NEWTON)
    with pytest.raises(ValueError, match="twice-differentiable"):
        cfg2.validate("smoothed_hinge")


def test_newton_with_box_constraints(rng):
    X, y, wt, batch = _make_batch(rng)
    cfg = OptimizerConfig(
        optimizer_type=OptimizerType.NEWTON,
        box_constraints=((0, 0.0, 0.0),),
        tolerance=1e-9,
    )
    res = solve("squared", batch, cfg, jnp.zeros(X.shape[1], jnp.float32))
    assert abs(float(res.w[0])) < 1e-7
