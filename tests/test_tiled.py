"""TiledBatch (pallas one-hot-matmul layout) parity vs SparseBatch.

The tiled kernels are the TPU fast path for the GLM hot loop
(ValueAndGradientAggregator.scala:132-153 analog); on CPU they run in
pallas interpret mode. Every quantity must match the padded-COO
segment-sum path to f32 tolerance.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from photon_ml_tpu.ops.objective import make_objective
from photon_ml_tpu.ops.sparse import SparseBatch
from photon_ml_tpu.ops.tiled import TiledBatch
from photon_ml_tpu.optim import (
    LBFGSConfig,
    TRONConfig,
    glm_adapter,
    lbfgs_solve,
    tron_solve,
)


def _problem(rng, n=300, f=37, density=0.3, weights=True):
    X = rng.normal(size=(n, f)) * (rng.random((n, f)) < density)
    y = (rng.random(n) > 0.5).astype(np.float64)
    off = rng.normal(size=n) * 0.1
    wgt = rng.random(n) + 0.5 if weights else None
    sb = SparseBatch.from_dense(X, y, offsets=off, weights=wgt)
    tb = TiledBatch.from_dense(X, y, offsets=off, weights=wgt)
    return sb, tb


def _pad_to(x, n):
    return np.pad(np.asarray(x), (0, n - len(np.asarray(x))))


def test_margins_and_dot_rows_parity(rng):
    sb, tb = _problem(rng)
    w = jnp.asarray(rng.normal(size=37), jnp.float32)
    z_sb = np.asarray(sb.margins(w, shift=0.37))
    z_tb = np.asarray(tb.margins(w, shift=0.37))
    # padded rows differ (tb pads to 128-multiples); compare real rows
    np.testing.assert_allclose(z_tb[: len(z_sb)], z_sb, rtol=1e-4, atol=1e-4)

    u_sb = np.asarray(sb.dot_rows(w))
    u_tb = np.asarray(tb.dot_rows(w))
    np.testing.assert_allclose(u_tb[: len(u_sb)], u_sb, rtol=1e-4, atol=1e-4)


def test_margins_pair_matches_separate(rng):
    _, tb = _problem(rng)
    w = jnp.asarray(rng.normal(size=37), jnp.float32)
    p = jnp.asarray(rng.normal(size=37), jnp.float32)
    z, u = tb.margins_pair(w, 0.5, p, -0.25)
    np.testing.assert_allclose(
        np.asarray(z), np.asarray(tb.margins(w, 0.5)), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(u), np.asarray(tb.dot_rows(p)) - 0.25, rtol=1e-5, atol=1e-5)


def test_scatter_parity(rng):
    sb, tb = _problem(rng)
    per_row = rng.normal(size=sb.num_rows)
    g_sb = np.asarray(sb.scatter_features(jnp.asarray(per_row, jnp.float32)))
    g_tb = np.asarray(
        tb.scatter_features(jnp.asarray(_pad_to(per_row, tb.num_rows),
                                        jnp.float32)))
    np.testing.assert_allclose(g_tb, g_sb, rtol=1e-4, atol=1e-4)

    s_sb = np.asarray(sb.scatter_features_sq(jnp.asarray(per_row, jnp.float32)))
    s_tb = np.asarray(
        tb.scatter_features_sq(jnp.asarray(_pad_to(per_row, tb.num_rows),
                                           jnp.float32)))
    np.testing.assert_allclose(s_tb, s_sb, rtol=1e-4, atol=1e-4)


def test_feature_moment_sums_parity(rng):
    sb, tb = _problem(rng)
    for a, b in zip(tb.feature_moment_sums(), sb.feature_moment_sums()):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("loss", ["logistic", "squared", "poisson"])
def test_objective_value_and_grad_parity(rng, loss):
    sb, tb = _problem(rng)
    obj = make_objective(loss, l2_weight=0.7)
    w = jnp.asarray(rng.normal(size=37) * 0.1, jnp.float32)
    v_sb, g_sb = obj.value_and_grad(w, sb)
    v_tb, g_tb = obj.value_and_grad(w, tb)
    np.testing.assert_allclose(float(v_tb), float(v_sb), rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(g_tb), np.asarray(g_sb), rtol=1e-3, atol=1e-4)


def test_objective_parity_with_normalization(rng):
    sb, tb = _problem(rng)
    factors = jnp.asarray(rng.random(37) + 0.5, jnp.float32)
    shifts = jnp.asarray(rng.normal(size=37) * 0.2, jnp.float32)
    obj = make_objective("logistic", l2_weight=0.3, factors=factors,
                         shifts=shifts)
    w = jnp.asarray(rng.normal(size=37) * 0.1, jnp.float32)
    v_sb, g_sb = obj.value_and_grad(w, sb)
    v_tb, g_tb = obj.value_and_grad(w, tb)
    np.testing.assert_allclose(float(v_tb), float(v_sb), rtol=1e-4)
    np.testing.assert_allclose(
        np.asarray(g_tb), np.asarray(g_sb), rtol=1e-3, atol=1e-4)

    hv_sb = obj.hessian_vector(w, w, sb)
    hv_tb = obj.hessian_vector(w, w, tb)
    np.testing.assert_allclose(
        np.asarray(hv_tb), np.asarray(hv_sb), rtol=1e-3, atol=1e-4)

    hd_sb = obj.hessian_diagonal(w, sb)
    hd_tb = obj.hessian_diagonal(w, tb)
    np.testing.assert_allclose(
        np.asarray(hd_tb), np.asarray(hd_sb), rtol=1e-3, atol=1e-4)


def test_lbfgs_solve_matches_sparse_path(rng):
    sb, tb = _problem(rng, n=200, f=24)
    obj = make_objective("logistic", l2_weight=1.0)
    cfg = LBFGSConfig(max_iterations=30)
    w0 = jnp.zeros((24,), jnp.float32)
    res_sb = jax.jit(lambda w: lbfgs_solve(glm_adapter(obj, sb), w, cfg))(w0)
    res_tb = jax.jit(lambda w: lbfgs_solve(glm_adapter(obj, tb), w, cfg))(w0)
    np.testing.assert_allclose(float(res_tb.value), float(res_sb.value),
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(res_tb.w), np.asarray(res_sb.w),
                               rtol=1e-2, atol=1e-3)


def test_tron_solve_matches_sparse_path(rng):
    sb, tb = _problem(rng, n=200, f=24)
    obj = make_objective("logistic", l2_weight=1.0)
    cfg = TRONConfig(max_iterations=10)
    w0 = jnp.zeros((24,), jnp.float32)
    res_sb = jax.jit(lambda w: tron_solve(glm_adapter(obj, sb), w, cfg))(w0)
    res_tb = jax.jit(lambda w: tron_solve(glm_adapter(obj, tb), w, cfg))(w0)
    np.testing.assert_allclose(float(res_tb.value), float(res_sb.value),
                               rtol=1e-4)


def test_from_batch_roundtrip(rng):
    sb, _ = _problem(rng, n=100, f=16)
    tb = TiledBatch.from_batch(sb)
    dense_sb = sb.to_dense()
    dense_tb = tb.to_dense()[: sb.num_rows]
    np.testing.assert_allclose(dense_tb, dense_sb, rtol=1e-6)


def test_bounds_validation():
    with pytest.raises(ValueError, match="feature indices"):
        TiledBatch.from_coo(
            values=np.ones(2), rows=np.array([0, 1]), cols=np.array([0, 9]),
            labels=np.zeros(2), num_features=5)
    with pytest.raises(ValueError, match="row indices"):
        TiledBatch.from_coo(
            values=np.ones(2), rows=np.array([0, 7]), cols=np.array([0, 1]),
            labels=np.zeros(2), num_features=5)


def test_with_offsets_flows_into_margins(rng):
    _, tb = _problem(rng, n=100, f=16)
    w = jnp.asarray(rng.normal(size=16), jnp.float32)
    new_off = jnp.asarray(rng.normal(size=tb.num_rows), jnp.float32)
    tb2 = tb.with_offsets(new_off)
    z1 = np.asarray(tb.dot_rows(w))
    z2 = np.asarray(tb2.margins(w))
    np.testing.assert_allclose(z2, z1 + np.asarray(new_off), rtol=1e-5,
                               atol=1e-5)


# -- exactness against float64, at the shapes whose edges the kernels have --
#
# Values, coefficients and per-row inputs span orders of magnitude, so a pass
# that rounded an operand to ONE bfloat16 (2e-3) cannot meet the limit; the
# kernels' bf16x2 splits with float32 accumulation read a few 1e-6.

EXACT_REL = 2e-5

# name -> (rows, features, nonzeros per 128-row tile)
_EDGE_SHAPES = {
    # S = 128 exactly, one column block, a last tile of 40 real rows
    "S128_B1_padded_rows": (296, 100, [100, 128, 60]),
    # S = 384, B = 3 (sentinel row 3 of a 16-row table), a tile of padding only
    "S384_B3_empty_tile": (300, 300, [300, 0, 384]),
    # B = 79: not a multiple of 8 or 16 (sentinel row 79 of 80)
    "S256_B79": (200, 10_000, [200, 256]),
    # B = 16: the table has no spare row, the sentinel matches none
    "S128_B16_empty_tile": (256, 2_048, [50, 0]),
}


@pytest.fixture(scope="module", params=list(_EDGE_SHAPES))
def edge(request):
    n, f, counts = _EDGE_SHAPES[request.param]
    rng = np.random.default_rng(sorted(_EDGE_SHAPES).index(request.param))
    rows, cols = [], []
    for t, c in enumerate(counts):
        hi_row = min(n, (t + 1) * 128)
        rows.append(rng.integers(t * 128, hi_row, size=c))
        cols.append(rng.integers(0, f, size=c))
    rows, cols = np.concatenate(rows), np.concatenate(cols)
    values = rng.normal(size=len(rows)) * np.exp(2 * rng.normal(size=len(rows)))
    tb = TiledBatch.from_coo(
        values=values, rows=rows, cols=cols, num_features=f,
        labels=(rng.random(n) > 0.5).astype(np.float64),
        offsets=rng.normal(size=n) * 0.1, weights=rng.random(n) + 0.5)
    assert tb.vals.shape == (len(counts), 1, -(-max(counts) // 128) * 128)

    def f32(x):
        return jnp.asarray(x, jnp.float32)

    wide = np.exp(2 * rng.normal(size=f))
    inputs = dict(
        w=f32(rng.normal(size=f) * wide), v=f32(rng.normal(size=f) * wide),
        # the loss kernels get margins of order one: exp() of a wide margin
        # is the loss's conditioning, not the pass's precision
        w_small=f32(rng.normal(size=f) * 0.02),
        per_row=f32(rng.normal(size=tb.num_rows)
                    * np.exp(2 * rng.normal(size=tb.num_rows))),
    )
    return tb, inputs


def _f64(x):
    return np.asarray(x, np.float64)


def _logistic64(tb, X, w, shift):
    z = X @ _f64(w) + _f64(tb.offsets) + shift
    p = 1.0 / (1.0 + np.exp(-z))
    return p, _f64(tb.weights), _f64(tb.labels)


def _entry_points():
    """name -> (tiled result, float64 reference), each a tuple of arrays;
    where the result is a sum over rows the reference is its terms."""
    s1, s2 = np.float32(0.37), np.float32(-0.25)

    def margins(tb, X, a):
        return (tb.margins(a["w"], s1),), (
            X @ _f64(a["w"]) + _f64(tb.offsets) + s1,)

    def dot_rows(tb, X, a):
        return (tb.dot_rows(a["v"]),), (X @ _f64(a["v"]),)

    def margins_pair(tb, X, a):
        return tb.margins_pair(a["w"], s1, a["v"], s2), (
            X @ _f64(a["w"]) + _f64(tb.offsets) + s1, X @ _f64(a["v"]) + s2)

    def scatter_features(tb, X, a):
        return (tb.scatter_features(a["per_row"]),), (
            X.T @ _f64(a["per_row"]),)

    def scatter_features_sq(tb, X, a):
        return (tb.scatter_features_sq(a["per_row"]),), (
            (X * X).T @ _f64(a["per_row"]),)

    def fused_value_grad(tb, X, a):
        value, grad, dz_sum = tb.fused_value_grad(a["w_small"], s1, "logistic")
        p, wgt, y = _logistic64(tb, X, a["w_small"], s1)
        dz = wgt * (p - y)
        nll = wgt * -(y * np.log(p) + (1 - y) * np.log1p(-p))
        return (value, grad, dz_sum), (nll, X.T @ dz, dz)

    def fused_hessian_vector(tb, X, a):
        p, wgt, _ = _logistic64(tb, X, a["w_small"], s1)
        q = wgt * p * (1 - p) * (X @ _f64(a["v"]) + s2)
        return tb.fused_hessian_vector(
            a["w_small"], s1, a["v"], s2, "logistic"), (X.T @ q, q)

    def fused_hv_at(tb, X, a):
        q = _f64(a["per_row"]) * (X @ _f64(a["v"]) + s2)
        return tb.fused_hv_at(a["per_row"], a["v"], s2), (X.T @ q, q)

    return [margins, dot_rows, margins_pair, scatter_features,
            scatter_features_sq, fused_value_grad, fused_hessian_vector,
            fused_hv_at]


@pytest.mark.parametrize("entry", _entry_points(), ids=lambda f: f.__name__)
def test_exact_against_float64(edge, entry):
    tb, inputs = edge
    got, want = entry(tb, tb.to_dense(), inputs)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        g, w = _f64(g).reshape(-1), np.asarray(w).reshape(-1)
        if g.size == 1:  # a sum that cancels: the scale is its terms'
            err = abs(g[0] - w.sum()) / np.abs(w).sum()
        else:
            err = np.linalg.norm(g - w) / np.linalg.norm(w)
        assert err < EXACT_REL, err


def test_exactness_limit_rejects_a_single_bfloat16_pass(edge):
    """The limit has teeth: the same products with the coefficients rounded
    once to bfloat16 miss it by two orders."""
    tb, inputs = edge
    X = tb.to_dense()
    w = inputs["w"]
    exact = X @ _f64(w)
    once = X @ _f64(w.astype(jnp.bfloat16))
    assert np.linalg.norm(once - exact) / np.linalg.norm(exact) > 10 * EXACT_REL
