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

from photon_ml_tpu.ops import tiled
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


ASSIGNMENTS = ["strided", "sorted"]


def _assigned(mp, how):
    """Hold ``pack_coo``'s rule to one row assignment: the rule is the
    design's own (no option says it), so a test steers it here."""
    mp.setattr(tiled, "strided_is_cheaper", lambda *_: how == "strided")


@pytest.fixture(params=ASSIGNMENTS)
def assignment(request, monkeypatch):
    _assigned(monkeypatch, request.param)
    return request.param


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


def test_from_batch_roundtrip(rng, assignment):
    sb, _ = _problem(rng, n=100, f=16)
    tb = TiledBatch.from_batch(sb)
    assert tb.strided == (assignment == "strided")
    assert (tb.rlo is None) == tb.strided
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
    # tile counts of the cells' kinds, several tiles a grid step: an odd
    # count (75 = 3 x 5^2), a power of two, and a prime over the cap (one)
    "T75_odd_count": (75 * 128, 300, [150] * 75),
    "T16_power_of_two": (16 * 128, 300, [150] * 16),
    "T37_prime_count": (37 * 128 - 50, 300, [150] * 37),
}

# the tiles a grid step each edge design's calls run, by tile count
_TILES_A_STEP = {2: 2, 3: 3, 16: 16, 37: 1, 75: 25}


@pytest.fixture(scope="module", params=[
    f"{shape}-{how}" for shape in _EDGE_SHAPES for how in ASSIGNMENTS])
def edge(request):
    shape, how = request.param.rsplit("-", 1)
    with pytest.MonkeyPatch.context() as mp:
        _assigned(mp, how)
        return _edge_design(shape, how)


def _edge_design(shape, how):
    n, f, counts = _EDGE_SHAPES[shape]
    rng = np.random.default_rng(sorted(_EDGE_SHAPES).index(shape))
    rows, cols = [], []
    for t, c in enumerate(counts):
        hi_row = min(n, (t + 1) * 128)
        rows.append(rng.integers(t * 128, hi_row, size=c))
        cols.append(rng.integers(0, f, size=c))
    rows, cols = np.concatenate(rows), np.concatenate(cols)
    # one nonzero a cell: X * X is then the square of every slot's value
    first = np.sort(np.unique(rows * f + cols, return_index=True)[1])
    rows, cols = rows[first], cols[first]
    values = rng.normal(size=len(rows)) * np.exp(2 * rng.normal(size=len(rows)))
    tb = TiledBatch.from_coo(
        values=values, rows=rows, cols=cols, num_features=f,
        labels=(rng.random(n) > 0.5).astype(np.float64),
        offsets=rng.normal(size=n) * 0.1, weights=rng.random(n) + 0.5)
    if how == "sorted":
        slots = -(-max(counts) // 128) * 128
    else:
        slots = 128 * int(np.bincount(rows).max())
        assert tb.rlo is None
    assert tb.vals.shape == (len(counts), 1, slots)

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


@pytest.mark.parametrize("entry", _entry_points(), ids=lambda f: f.__name__)
def test_tiles_a_step_is_bit_identical_to_one(edge, entry, monkeypatch):
    """G tiles a grid step run the tile's body G times in tile order, so
    every output is the one-tile-a-step call's to the last bit."""
    tb, inputs = edge
    X = tb.to_dense()
    assert tb.tiles_a_step() == _TILES_A_STEP[tb.num_tiles]
    got, _ = entry(tb, X, inputs)
    monkeypatch.setattr(TiledBatch, "tiles_a_step", lambda *_, **__: 1)
    one, _ = entry(tb, X, inputs)
    for g, o in zip(got, one):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(o))


def test_exactness_limit_rejects_a_single_bfloat16_pass(edge):
    """The limit has teeth: the same products with the coefficients rounded
    once to bfloat16 miss it by two orders."""
    tb, inputs = edge
    X = tb.to_dense()
    w = inputs["w"]
    exact = X @ _f64(w)
    once = X @ _f64(w.astype(jnp.bfloat16))
    assert np.linalg.norm(once - exact) / np.linalg.norm(exact) > 10 * EXACT_REL


# -- which row assignment a design gets, and that both are the same design --


def _ragged(rng, n=300, f=500, long_row=77, long_nnz=500, short_nnz=5):
    """Rows of ``short_nnz`` nonzeros and one of ``long_nnz``, row-sorted."""
    lengths = np.full(n, short_nnz)
    lengths[long_row] = long_nnz
    rows = np.repeat(np.arange(n), lengths)
    cols = np.concatenate([rng.permutation(f)[:k] for k in lengths])
    return rng.normal(size=len(rows)), rows, cols


def _arrival_order_leaves(values, rows, cols, num_tiles, num_features):
    """The sorted layout as its definition reads, slot by slot."""
    S = -(-int(np.bincount(rows // 128, minlength=num_tiles).max()) // 128) * 128
    vals = np.zeros((num_tiles, 1, S), np.float32)
    hi = np.full((num_tiles, 1, S), -(-num_features // 128), np.int32)
    lo = np.zeros((num_tiles, 1, S), np.int32)
    rlo = np.zeros((num_tiles, 1, S), np.int32)
    filled = np.zeros(num_tiles, int)
    for v, r, c in zip(values, rows, cols):
        t, k = r // 128, filled[r // 128]
        vals[t, 0, k], hi[t, 0, k], lo[t, 0, k] = v, c // 128, c % 128
        rlo[t, 0, k] = r % 128
        filled[t] += 1
    return vals, hi, lo, rlo


def test_a_long_row_among_short_ones_stays_sorted(rng):
    """One row of 500 nonzeros among rows of 5: strided would store 128 x
    500 slots a tile for ~1,100 nonzeros, so the rule keeps the arrival-order
    layout, leaf for leaf what the packer made before it had a choice."""
    values, rows, cols = _ragged(rng)
    tb = TiledBatch.pack_coo(values, rows, cols, np.zeros(300), 500)
    assert not tb.strided
    want = _arrival_order_leaves(values, rows, cols, 3, 500)
    for got, leaf in zip((tb.vals, tb.hi, tb.lo, tb.rlo), want):
        np.testing.assert_array_equal(got, leaf)
    # and the same design read back
    X = np.zeros((384, 500))
    X[rows, cols] = values.astype(np.float32)
    np.testing.assert_array_equal(tb.to_dense(), X)


@pytest.mark.parametrize("nnz_per_row,num_features,strided", [
    (20, 10_000, True),    # constant rows: S stays 2,560, one pass fewer
    (39, 4_096, True),     # the hot panel's table at the weight-load floor
    (1, 100, True),
])
def test_constant_length_rows_go_strided(rng, nnz_per_row, num_features,
                                         strided):
    n = 300
    rows = np.repeat(np.arange(n), nnz_per_row)
    cols = rng.integers(0, num_features, size=len(rows))
    tb = TiledBatch.pack_coo(
        np.ones(len(rows)), rows, cols, np.zeros(n), num_features)
    assert tb.strided == strided and tb.rlo is None
    assert tb.vals.shape == (3, 1, 128 * nnz_per_row)


def test_the_rule_weighs_slots_against_the_pass_saved():
    # B = 79: a 160-row pass is 26.7 ns a 128 slots, the rt pass 15
    assert tiled.strided_is_cheaper(2560, 2560, 79)
    assert tiled.strided_is_cheaper(3968, 2560, 79)       # 1.55x the slots
    assert not tiled.strided_is_cheaper(4096, 2560, 79)   # 1.6x
    # B = 32: both passes on the floor, so up to twice the slots
    assert tiled.strided_is_cheaper(4992, 4352, 32)
    assert tiled.strided_is_cheaper(8704, 4352, 32)
    assert not tiled.strided_is_cheaper(8832, 4352, 32)


def test_tiles_a_step_rule():
    """The largest divisor of the tile count up to the cap whose grid step
    fits the VMEM budget; one where none above one does."""
    rule = tiled.tiles_a_step
    assert tiled.MAX_TILES_A_STEP == 25
    # the cells' training designs (T, S, B8), strided: glm_fe (3 x 5^6),
    # the criteo hot panel (2^9 x 107), the MovieLens fixed effect (3^2 x 5^6)
    assert rule(46_875, 2_560, 80) == 25
    assert rule(54_784, 4_992, 32) == 16
    assert rule(140_625, 1_280, 16) == 25
    # their validation designs, and the chip smoke's 7,813 = 13 x 601
    assert rule(4_688, 2_560, 80) == 16
    assert rule(5_632, 4_992, 32) == 22
    assert rule(7_813, 2_560, 80) == 13
    # a prime count over the cap, and glm_fe's per-shard count on four
    # devices (46,875 padded to 46,876): the call as it was
    assert rule(7_919, 2_560, 80) == rule(11_719, 2_560, 80) == 1
    # sorted: one slot array more, the same choice at the cells' widths
    assert rule(46_875, 2_560, 80, strided=False) == 25


def test_tiles_a_step_stops_at_the_vmem_budget():
    """Rows of 200 nonzeros: 25,600 slots a tile, so 13 tiles' double-buffered
    blocks fill the budget and a count of 75 takes 5 a step."""
    S = 128 * 200
    step = tiled._step_vmem_bytes
    assert step(13, S, 16, True) <= tiled.STEP_VMEM_BYTES < step(14, S, 16, True)
    assert tiled.tiles_a_step(75, S, 16) == 5
    # the fourth slot array of a sorted design costs a fifth of a step
    assert tiled.tiles_a_step(75, S, 16, strided=False) == 5
    assert step(5, S, 16, False) > step(5, S, 16, True)
    # a tile too wide for the budget alone still runs, one a step
    assert tiled.tiles_a_step(75, 128 * 3_000, 16) == 1
    # at the cells' shapes the blocks are far inside it (described-v5e
    # compile: 1.53 MB at G = 25 in glm_fe)
    assert step(25, 2_560, 80, True) < tiled.STEP_VMEM_BYTES / 4


def test_k_sweeps_keep_eight_tiles_a_step(rng):
    """The K-table sweeps take the same rule with their own cap: the
    factored coordinate pads its rows to whole steps of 8, so its
    projection runs 8 a step at ml20m_mf.cd_fit's 212,296 tiles."""
    assert tiled.K_SWEEP_TILES_A_STEP == 8
    assert tiled.tiles_a_step(
        212_296, 128, 224, most=tiled.K_SWEEP_TILES_A_STEP) == 8
    for T, G in [(8, 8), (24, 8), (12, 6), (7, 7), (13, 1)]:
        assert tiled.tiles_a_step(
            T, 128, 224, most=tiled.K_SWEEP_TILES_A_STEP) == G
    rows = np.arange(24 * 128)
    tb = TiledBatch.pack_coo(np.ones(len(rows)), rows, rows % 300,
                             np.zeros(len(rows)), 300)
    assert tb.tiles_a_step(most=tiled.K_SWEEP_TILES_A_STEP) == 8
    assert tb.tiles_a_step() == 24


def test_plain_design_reports_its_tiles_a_step(rng):
    """Gauge ``layout.tiles_a_step``: the G of the training design's calls,
    per shard where the design is packed for a mesh."""
    from photon_ml_tpu import telemetry
    from photon_ml_tpu.ops.panels import pack_design

    n = 60 * 128
    rows = np.repeat(np.arange(n), 3)
    cols = rng.integers(0, 1_000, size=len(rows))
    batch = SparseBatch.from_coo(rng.normal(size=len(rows)), rows, cols,
                                 np.zeros(n), 1_000)
    design = pack_design(batch)
    assert isinstance(design, TiledBatch) and design.num_tiles == 60
    assert telemetry.snapshot()["gauges"]["layout.tiles_a_step"] == 20
    pack_design(batch, shards=4)
    assert telemetry.snapshot()["gauges"]["layout.tiles_a_step"] == 15


def test_unsorted_coo_packs_strided(rng, monkeypatch):
    """Nonzeros in no order at all: slot k*128 + r still holds the k-th
    nonzero of row r in INPUT order, and the design is the same design."""
    _assigned(monkeypatch, "strided")
    values, rows, cols = _ragged(rng, long_nnz=9)
    shuffle = rng.permutation(len(rows))
    v, r, c = values[shuffle], rows[shuffle], cols[shuffle]
    tb = TiledBatch.pack_coo(v, r, c, np.zeros(300), 500)
    assert tb.strided and tb.vals.shape == (3, 1, 128 * 9)
    X = np.zeros((384, 500))
    X[r, c] = v.astype(np.float32)
    np.testing.assert_array_equal(tb.to_dense(), X)
    slots = np.asarray(tb.vals).reshape(3, 9, 128)
    for row in (0, 77, 299):
        mine = v[r == row].astype(np.float32)
        got = slots[row // 128, :, row % 128]
        np.testing.assert_array_equal(got[:len(mine)], mine)
        assert not got[len(mine):].any()
    w = jnp.asarray(rng.normal(size=500), jnp.float32)
    ordered = TiledBatch.pack_coo(values, rows, cols, np.zeros(300), 500)
    np.testing.assert_allclose(
        np.asarray(tb.device().dot_rows(w)),
        np.asarray(ordered.device().dot_rows(w)), rtol=1e-5, atol=1e-5)


def test_both_assignments_are_one_design(rng, monkeypatch):
    """The same nonzeros packed both ways: one dense matrix, and every pass
    agrees to float32 rounding."""
    values, rows, cols = _ragged(rng, long_nnz=12)
    labels = (rng.random(300) > 0.5).astype(float)
    designs = {}
    for how in ASSIGNMENTS:
        _assigned(monkeypatch, how)
        designs[how] = TiledBatch.from_coo(values, rows, cols, labels, 500)
    a, b = designs["strided"], designs["sorted"]
    assert a.strided and not b.strided
    assert a.nnz_slots == 3 * 128 * 12 and b.nnz_slots < a.nnz_slots
    np.testing.assert_array_equal(a.to_dense(), b.to_dense())
    w = jnp.asarray(rng.normal(size=500), jnp.float32)
    per_row = jnp.asarray(rng.normal(size=384), jnp.float32)
    for got, want in [
        (a.margins(w, 0.3), b.margins(w, 0.3)),
        (a.scatter_features(per_row), b.scatter_features(per_row)),
        (a.fused_value_grad(w * 0.05, 0.1, "logistic")[1],
         b.fused_value_grad(w * 0.05, 0.1, "logistic")[1]),
        (a.feature_moment_sums()[2], b.feature_moment_sums()[2]),
    ]:
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-4)


# -- one-hot rows sorted by column: the windowed K-table kernels -------------

# name -> (features, slots a tile, the sorted rows' columns)
_SORTED_SHAPES = {
    # every seventh column has no row; two windows, the last tile padded
    "columns_with_no_row": (
        5_000, 256, lambda rng: np.delete(
            np.sort(rng.integers(0, 5_000, 2_600)),
            np.s_[::7])),
    # one tile whose 128 slots lie in two adjacent 128-column blocks
    "tile_straddles_two_blocks": (
        2_048, 128, lambda rng: np.sort(rng.integers(300, 460, 128))),
    # rows either side of column 2,048 (table row 16): the first window's
    # tile is closed at 37 slots, the next opens in the second window
    "tile_closed_at_the_window": (
        4_096, 128, lambda rng: np.concatenate(
            [np.sort(rng.integers(1_990, 2_048, 37)),
             np.sort(rng.integers(2_048, 2_100, 60))])),
    # the tile K = 16 gets (4,096 slots), 1.3 of them: a padded last step
    "padded_last_grid_step": (
        1_500, tiled.sorted_slots(16),
        lambda rng: np.sort(rng.integers(0, 1_500, 5_324))),
    # no row at all: one tile of padding
    "empty": (300, 128, lambda rng: np.zeros(0, np.int64)),
}


@pytest.mark.parametrize("K", [16, 3])
@pytest.mark.parametrize("shape", _SORTED_SHAPES)
def test_windowed_kernels_exact_against_float64(shape, K):
    """``ColumnSortedTiles.contract_rows`` / ``.scatter_contracted`` (the
    refit's two passes over the column-sorted layout) against a float64
    dense computation, at the tolerance of the standing kernels."""
    f, slots, columns = _SORTED_SHAPES[shape]
    rng = np.random.default_rng(sorted(_SORTED_SHAPES).index(shape))
    cols = columns(rng)
    n = len(cols)
    values = rng.normal(size=n) * np.exp(rng.normal(size=n))
    host, slot = tiled.ColumnSortedTiles.pack(values, cols, f, slots=slots)
    design = jax.tree.map(jnp.asarray, host)
    windows = np.asarray(host.window)
    assert np.all(np.diff(windows) >= 0)
    X = design.to_dense()
    rows = design.num_rows
    # a row lands in its slot, in its window, and nothing else is there
    want_x = np.zeros((n, f))
    want_x[np.arange(n), cols] = values.astype(np.float32)
    np.testing.assert_array_equal(X[slot], want_x)
    assert np.count_nonzero(X) == np.count_nonzero(want_x)
    assert np.array_equal(windows[slot // slots], cols // (128 * tiled.WINDOW))
    if shape == "tile_straddles_two_blocks":
        assert len(windows) == 1 and len(set(cols // 128)) == 2
    if shape == "tile_closed_at_the_window":
        assert list(windows) == [0, 1] and list(slot[[36, 37]]) == [36, 128]
    if shape == "padded_last_grid_step":
        assert rows == 2 * tiled.sorted_slots(16) > n
    if shape == "empty":
        assert rows == slots and not X.any()

    a = rng.normal(size=(K, f)) * np.exp(rng.normal(size=f))
    c = rng.normal(size=(K, rows))
    q = rng.normal(size=rows) * np.exp(rng.normal(size=rows))
    a32, c32, q32 = (jnp.asarray(x, jnp.float32) for x in (a, c, q))
    a, c, q = _f64(a32), _f64(c32), _f64(q32)
    for got, want in (
            (design.contract_rows(a32, c32), np.sum(c * (a @ X.T), axis=0)),
            (design.scatter_contracted(q32, c32), (c * q) @ X),
            (design.scatter_contracted(q32, c32, square=True),
             (c * c * q) @ (X * X))):
        assert got.shape == want.shape
        if want.any():
            err = np.linalg.norm(_f64(got) - want) / np.linalg.norm(want)
            assert err < EXACT_REL, err
        else:
            assert not np.asarray(got).any()
    # the contracted forms of a design in any row order agree with it
    coo = SparseBatch.from_coo(
        values=values, rows=np.arange(n), cols=cols, labels=np.zeros(max(n, 1)),
        num_features=f)
    ours = _f64(design.contract_rows(a32, c32))[slot]
    theirs = _f64(coo.contract_rows(a32, c32[:, slot]))[:n]
    assert np.linalg.norm(ours - theirs) <= EXACT_REL * np.linalg.norm(theirs)


def test_column_sorted_pack_refuses_unsorted_columns():
    with pytest.raises(ValueError, match="not sorted"):
        tiled.ColumnSortedTiles.pack(
            np.ones(3), np.asarray([4, 2, 9]), 16, slots=128)
    with pytest.raises(ValueError, match="feature indices"):
        tiled.ColumnSortedTiles.pack(
            np.ones(2), np.asarray([4, 16]), 16, slots=128)


def test_a_column_sorted_tile_shrinks_as_the_tables_grow():
    """The tile's one matmul writes [2K x 16, slots] float32: 8 MiB at
    most, so that a wider latent space still compiles."""
    assert [tiled.sorted_slots(k) for k in (1, 3, 16, 17, 32, 64, 1024)] == [
        4096, 4096, 4096, 2048, 2048, 1024, 128]
