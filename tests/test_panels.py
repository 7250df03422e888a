"""Column panels (``ops/panels.py``): the layout for fixed effects too wide
for the plain tiled kernels, on the CPU in Pallas interpret mode.

Every pass against float64 algebra over the stored nonzeros (never a dense
matrix: at d = 1,000,000 there is none to be had), the layout's round trip,
the decision between plain tiles and panels, a whole ``GameEstimator`` fit
against the benchmark's plain reference, and mesh parity on virtual devices.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from photon_ml_tpu.ops import panels, tiled
from photon_ml_tpu.ops.panels import PanelBatch, pack_design
from photon_ml_tpu.ops.sparse import SparseBatch
from photon_ml_tpu.ops.tiled import TiledBatch
from photon_ml_tpu.parallel import sharding as psharding
from photon_ml_tpu.parallel.mesh import make_mesh

criteo = importlib.import_module("benchmark.generators.criteo_hashed")


def _coo(kind: str, seed: int = 7):
    """(rows, cols, vals float32, labels, offsets, weights, d): ``skewed`` is
    2,000 rows of the click-log generator's columns at d = 1,000,000 (hash
    collisions inside a row included), ``uniform`` 1,500 rows x 20 uniform
    columns at d = 20,000."""
    rng = np.random.default_rng(seed)
    if kind == "skewed":
        n, d = 2000, 1_000_000
        split = criteo.generate(
            {"rows": n, "validation_rows": 8, "fe_features": d,
             "fe_nnz_per_row": criteo.FIELDS}, seed)["train"]
        cols, y = split["cols"], split["y"]
    else:
        n, d = 1500, 20_000
        cols = rng.integers(0, d, size=(n, 20), dtype=np.int32)
        y = (rng.random(n) > 0.5).astype(np.float32)
    vals = rng.standard_normal(cols.shape).astype(np.float32)
    rows = np.repeat(np.arange(n, dtype=np.int32), cols.shape[1])
    return (rows, cols.reshape(-1), vals.reshape(-1), y,
            (rng.standard_normal(n) * 0.1).astype(np.float32),
            (rng.random(n) + 0.5).astype(np.float32), d)


@pytest.fixture(scope="module", params=[
    "skewed", "uniform", "skewed-sorted", "uniform-sorted"])
def design(request):
    """Both column histograms with the hot part as ``pack_coo``'s rule
    gives it (strided: the rows are of one length or near it), and again
    held to the sorted assignment."""
    kind, _, how = request.param.partition("-")
    rows, cols, vals, y, off, wgt, d = _coo(kind)
    sb = SparseBatch.from_coo(vals, rows, cols, y, d, offsets=off, weights=wgt)
    with pytest.MonkeyPatch.context() as mp:
        if how == "sorted":
            mp.setattr(tiled, "strided_is_cheaper", lambda *_: False)
        packed = pack_design(sb)
    assert isinstance(packed, PanelBatch)
    assert packed.hot.strided == (how != "sorted")
    assert (packed.hot.rlo is None) == packed.hot.strided
    return (rows, cols, vals.astype(np.float64), y, off, wgt, d), packed.device()


class _Float64:
    """The passes in float64 over the nonzeros, in FEATURE order."""

    def __init__(self, coo, n_pad):
        (self.rows, self.cols, self.vals, y, off, wgt, self.d) = coo
        self.n_pad = n_pad
        self.y, self.off, self.wgt = (self._rows(a) for a in (y, off, wgt))

    def _rows(self, a):
        out = np.zeros(self.n_pad)
        out[: len(a)] = a
        return out

    def dot(self, w):
        return np.bincount(self.rows, self.vals * w[self.cols], self.n_pad)

    def scatter(self, r, square=False):
        v = self.vals ** 2 if square else self.vals
        return np.bincount(self.cols, v * r[self.rows], self.d)


def _loss64(z, y):
    return np.logaddexp(0.0, z) - y * z, 1 / (1 + np.exp(-z)) - y


# name -> (the layout's call over rank-ordered float32 inputs, the same in
# float64 over feature-ordered inputs)
_METHODS = {
    "margins": (lambda b, w, v, r: b.margins(w, 0.3),
                lambda f, w, v, r: f.dot(w) + f.off + 0.3),
    "dot_rows": (lambda b, w, v, r: b.dot_rows(w),
                 lambda f, w, v, r: f.dot(w)),
    "margins_pair": (
        lambda b, w, v, r: b.margins_pair(w, 0.3, v, -0.2),
        lambda f, w, v, r: (f.dot(w) + f.off + 0.3, f.dot(v) - 0.2)),
    "scatter_features": (lambda b, w, v, r: b.scatter_features(r),
                         lambda f, w, v, r: f.scatter(r)),
    "scatter_features_sq": (lambda b, w, v, r: b.scatter_features_sq(r),
                            lambda f, w, v, r: f.scatter(r, square=True)),
    "fused_value_grad": (
        lambda b, w, v, r: b.fused_value_grad(w, 0.3, "logistic"),
        lambda f, w, v, r: _value_grad64(f, w)),
    "fused_hessian_vector": (
        lambda b, w, v, r: b.fused_hessian_vector(w, 0.3, v, -0.2, "logistic"),
        lambda f, w, v, r: _hv64(f, w, v)),
    "fused_hv_at": (lambda b, w, v, r: b.fused_hv_at(jnp.abs(r), v, -0.2),
                    lambda f, w, v, r: _hv_at64(f, v, np.abs(r))),
    "feature_moment_sums": (lambda b, w, v, r: b.feature_moment_sums(),
                            lambda f, w, v, r: _moments64(f)),
}


def _value_grad64(f, w):
    l, dz = _loss64(f.dot(w) + f.off + 0.3, f.y)
    return np.sum(f.wgt * l), f.scatter(f.wgt * dz), np.sum(f.wgt * dz)


def _hv64(f, w, v):
    z = f.dot(w) + f.off + 0.3
    p = 1 / (1 + np.exp(-z))
    q = f.wgt * p * (1 - p) * (f.dot(v) - 0.2)
    return f.scatter(q), np.sum(q)


def _hv_at64(f, v, d2):
    q = d2 * (f.dot(v) - 0.2)
    return f.scatter(q), np.sum(q)


def _moments64(f):
    valid = (f.wgt > 0).astype(np.float64)
    count = np.bincount(f.cols, (f.vals != 0) * valid[f.rows], f.d)
    return f.scatter(valid), f.scatter(valid, square=True), count


@pytest.mark.parametrize("method", sorted(_METHODS))
def test_panel_method_matches_float64(method, design):
    """(a): every layout method to 1e-5 of float64, at d = 1,000,000 with
    skewed columns and at d = 20,000 with uniform ones."""
    coo, batch = design
    rng = np.random.default_rng(3)
    f = _Float64(coo, batch.num_rows)
    w, v = (rng.standard_normal(f.d) * 0.3 for _ in range(2))
    r = rng.standard_normal(batch.num_rows)
    order = np.asarray(batch.order)

    def ranked(x):  # feature order -> the layout's rank order, float32
        return jnp.asarray(x[order], jnp.float32)

    on_layout, in_float64 = _METHODS[method]
    got = on_layout(batch, ranked(w), ranked(v), jnp.asarray(r, jnp.float32))
    want = in_float64(f, w, v, r)
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        a, b = np.asarray(a, np.float64), np.asarray(b)
        if b.shape == (f.d,):
            b = b[order]
        assert a.shape == b.shape
        assert np.linalg.norm(a - b) <= 1e-5 * max(np.linalg.norm(b), 1e-30)


def test_to_dense_round_trip_sums_duplicates():
    """(b): rows that hold a column twice (hash collisions) densify to the
    sum, in the original feature order, through every part."""
    rng = np.random.default_rng(0)
    n, d, k = 300, 16_640, 12
    cols = rng.integers(0, d, size=(n, k), dtype=np.int32)
    cols[:, 0] = rng.integers(0, 64, size=n)       # hot features
    cols[::3, 5] = cols[::3, 4]                    # duplicates in the tail
    cols[::5, 1] = cols[::5, 0]                    # and in the hot panel
    vals = rng.standard_normal((n, k)).astype(np.float32)
    rows = np.repeat(np.arange(n), k)
    y = np.zeros(n, np.float32)
    batch = pack_design(SparseBatch.from_coo(
        vals.reshape(-1), rows, cols.reshape(-1), y, d))
    assert isinstance(batch, PanelBatch)
    X = np.zeros((n, d))
    np.add.at(X, (rows, cols.reshape(-1)), vals.reshape(-1))
    dense = batch.to_dense()
    np.testing.assert_array_equal(dense[:n], X)
    assert not dense[n:].any()
    assert sum(batch.stored) == n * k
    assert batch.nnz_slots >= n * k


# -- (d) the decision: from the design alone --------------------------------------


def _uniform_batch(n, d, k, rng):
    cols = rng.integers(0, d, size=(n, k), dtype=np.int32)
    return SparseBatch.from_coo(
        rng.standard_normal(n * k).astype(np.float32),
        np.repeat(np.arange(n), k), cols.reshape(-1),
        np.zeros(n, np.float32), d)


def test_narrow_uniform_design_keeps_the_plain_kernels(rng, monkeypatch):
    """B = 79 with uniform columns: today's layout, and the histogram is
    never even taken."""
    monkeypatch.setattr(
        panels, "column_order",
        lambda *a: pytest.fail("the histogram was consulted at B = 79"))
    batch = _uniform_batch(600, 10_000, 20, rng)
    packed = pack_design(batch)
    assert type(packed) is TiledBatch
    assert packed.num_blocks == 79
    plain = TiledBatch.pack_batch(batch)
    for a, b in zip(jax.tree.leaves(packed), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(a, b)


def test_wide_skewed_design_takes_the_panels():
    rows, cols, vals, y, _, _, d = _coo("skewed")
    packed = pack_design(SparseBatch.from_coo(vals, rows, cols, y, d))
    assert isinstance(packed, PanelBatch)
    assert packed.hot.num_blocks == panels.HOT_BLOCKS
    # most of a click log's slots are on the hot panel
    assert packed.stored[0] > 0.5 * sum(packed.stored)


@pytest.mark.parametrize("blocks,expect", [(79, True), (128, True),
                                           (129, False), (7813, False)])
def test_plain_is_near_floor(blocks, expect):
    assert panels.plain_is_near_floor(blocks) is expect


def test_plan_at_the_criteo_cell_histogram():
    """The plan for a steep histogram of the cell's size: a few classes,
    windows doubling, contiguous from the hot panel to the last block."""
    ranks = np.arange(1, 1_000_001)
    counts = 273e6 * ranks ** -1.05 / np.sum(ranks ** -1.05)
    blocks = np.zeros(7813 * 128)
    blocks[: len(counts)] = counts
    classes = panels.plan_panels(
        blocks.reshape(-1, 128).sum(axis=1), 7_000_000)
    assert classes is not None and 2 <= len(classes) <= 5
    b = panels.HOT_BLOCKS
    for prev, c in zip((None,) + classes, classes):
        assert c.first_block == b
        assert prev is None or c.window > prev.window
        b += c.num_blocks
    assert b >= 7813 and classes[-1].window == panels.MAX_WINDOW
    # the plan prices the SORTED hot panel (two passes); the row assignment
    # is pack_coo's own and moves no class: these are PR 26's
    assert [(c.window, c.first_block, c.num_windows) for c in classes] == [
        (32, 32, 4), (64, 160, 6), (128, 544, 12), (256, 2080, 23)]


def test_plan_cuts_a_class_to_the_vmem_budget():
    """A class's coefficient grid is whole in VMEM: at ten million features
    no class is wider than ``MAX_CLASS_BLOCKS``, and the classes still run
    on from the hot panel to the last block."""
    ranks = np.arange(1, 10_240_001)
    counts = 3e9 * ranks ** -1.05 / np.sum(ranks ** -1.05)
    classes = panels.plan_panels(counts.reshape(-1, 128).sum(axis=1),
                                 70_000_000)
    b = panels.HOT_BLOCKS
    for c in classes:
        assert c.first_block == b and c.num_blocks <= panels.MAX_CLASS_BLOCKS
        b += c.num_blocks
    assert b >= 80_000
    assert sum(c.window == panels.MAX_WINDOW for c in classes) > 1


@pytest.mark.parametrize("method", ["dot_rows", "scatter_features"])
def test_cut_classes_match_float64(method, monkeypatch):
    """The same passes through a tail cut into several classes of one
    window (the budget lowered so that d = 20,000 is cut)."""
    monkeypatch.setattr(panels, "MAX_CLASS_BLOCKS", 32)
    rows, cols, vals, y, off, wgt, d = _coo("uniform")
    batch = pack_design(SparseBatch.from_coo(vals, rows, cols, y, d))
    windows = [p.cls.window for p in batch.parts]
    assert len(windows) > len(set(windows))
    assert len(batch.stored) == 1 + len(batch.parts)
    assert sum(batch.stored) == len(vals)
    f = _Float64((rows, cols, vals.astype(np.float64), y, off, wgt, d),
                 batch.num_rows)
    rng = np.random.default_rng(4)
    w, r = rng.standard_normal(d), rng.standard_normal(batch.num_rows)
    order = np.asarray(batch.order)
    if method == "dot_rows":
        got = batch.device().dot_rows(jnp.asarray(w[order], jnp.float32))
        want = f.dot(w)
    else:
        got = batch.device().scatter_features(jnp.asarray(r, jnp.float32))
        want = f.scatter(r)[order]
    got = np.asarray(got, np.float64)
    assert np.linalg.norm(got - want) <= 1e-5 * np.linalg.norm(want)


def test_uniform_wide_plan_beats_plain_in_the_model():
    # B = 2000 uniform: every window alike, so the first class takes all
    classes = panels.plan_panels(np.full(2000, 1e6), 1_000_000)
    assert classes is not None and len(classes) == 1


# -- (c) a whole fit against the plain reference ------------------------------------


def _fit_data(seed=11, n=2000, d=20_480):
    shape = {"rows": n, "validation_rows": 400, "fe_features": d,
             "fe_nnz_per_row": criteo.FIELDS, "users": 0}
    return shape, criteo.generate(shape, seed)


def _dataset(split, d):
    from photon_ml_tpu.game import build_game_dataset

    n, k = split["cols"].shape
    shard = SparseBatch.from_coo(
        split["vals"].reshape(-1), np.repeat(np.arange(n), k),
        split["cols"].reshape(-1), split["y"], d)
    return build_game_dataset(
        response=split["y"], feature_shards={"global": shard}, id_columns={})


def _train_json(optimizer, layout):
    return {
        "task": "logistic", "num_iterations": 1, "evaluators": ["auc"],
        "coordinates": {"fixed": {
            "type": "fixed_effect", "shard_name": "global", "layout": layout,
            "optimizer": optimizer}},
    }


def _estimator_fit(train_json, raw, d):
    from photon_ml_tpu.config import parse_game_config
    from photon_ml_tpu.game import GameEstimator

    est = GameEstimator(parse_game_config(train_json))
    train = _dataset(raw["train"], d)
    result = est.fit(train)
    coord = est._build_coordinates(train, mesh=None)["fixed"]
    model = result.model if hasattr(result, "model") else result
    val = _dataset(raw["validation"], d)
    scores = np.asarray(model.score(val), np.float64)[: val.num_rows]
    coef = np.asarray(model.models["fixed"].coefficients, np.float64)
    return coord, coef, scores


def test_estimator_fit_on_panels_matches_plain_reference():
    """(c): GameEstimator through FixedEffectCoordinate on the panel layout
    (rank order inside, feature order outside) against
    benchmark/reference/glmix_plain.py."""
    reference = importlib.import_module("benchmark.reference.glmix_plain")
    shape, raw = _fit_data()
    d = shape["fe_features"]
    opt = {"type": "lbfgs", "max_iterations": 40, "tolerance": 0.0,
           "regularization": "l2", "regularization_weight": 1.0}
    coord, coef, scores = _estimator_fit(_train_json(opt, "tiled"), raw, d)
    assert isinstance(coord._tiled, PanelBatch)
    ref = reference.fit(raw, shape, _train_json(opt, "tiled"))
    want = ref["coefficients"]["fixed"]
    assert np.linalg.norm(coef - want) <= 2e-3 * np.linalg.norm(want)
    assert np.linalg.norm(scores - ref["validation_scores"]) <= (
        2e-3 * np.linalg.norm(ref["validation_scores"]))


@pytest.mark.parametrize("optimizer", [
    {"type": "tron", "max_iterations": 8, "tolerance": 1e-6,
     "regularization": "l2", "regularization_weight": 1.0},
    # L1 -> OWLQN, run to its float32 end: a relative tolerance of 1e-6 stops
    # two layouts at different points of a flat objective (8e-3 apart in the
    # coefficients with the strided hot part, at the LOWER objective)
    {"type": "lbfgs", "max_iterations": 100, "tolerance": 0.0,
     "regularization": "l1", "regularization_weight": 0.5},
], ids=["tron", "owlqn"])
def test_tron_and_owlqn_run_on_panels(optimizer):
    """The Hv / Hv-at compositions (TRON) and the L1 path (OWLQN) on the
    panel layout land where the COO layout lands."""
    shape, raw = _fit_data(seed=5, n=1200)
    d = shape["fe_features"]
    coord, coef, _ = _estimator_fit(_train_json(optimizer, "tiled"), raw, d)
    assert isinstance(coord._tiled, PanelBatch)
    _, want, _ = _estimator_fit(_train_json(optimizer, "coo"), raw, d)
    assert np.linalg.norm(coef - want) <= 5e-3 * max(np.linalg.norm(want), 1)


def test_coordinate_renumbers_bounds_and_normalization():
    """Box constraints and normalization factors are declared by FEATURE;
    the panel coordinate applies them by rank and returns feature order."""
    from photon_ml_tpu.config import parse_game_config
    from photon_ml_tpu.game import GameEstimator

    shape, raw = _fit_data(seed=9, n=1000)
    d = shape["fe_features"]
    cols = raw["train"]["cols"]
    hot = int(np.bincount(cols.reshape(-1)).argmax())
    rare = int(cols[0, -1])
    opt = {"type": "lbfgs", "max_iterations": 15, "tolerance": 1e-7,
           "regularization": "l2", "regularization_weight": 1.0,
           "box_constraints": [[hot, -0.01, 0.01], [rare, 0.2, 0.3]]}
    out = {}
    for layout in ("tiled", "coo"):
        est = GameEstimator(parse_game_config(_train_json(opt, layout)))
        result = est.fit(_dataset(raw["train"], d))
        model = result.model if hasattr(result, "model") else result
        out[layout] = np.asarray(model.models["fixed"].coefficients)
    assert abs(out["tiled"][hot]) <= 0.01 + 1e-6
    assert 0.2 - 1e-6 <= out["tiled"][rare] <= 0.3 + 1e-6
    np.testing.assert_allclose(out["tiled"], out["coo"], atol=5e-3)


# -- (e) mesh parity on virtual devices ------------------------------------------------


@pytest.fixture
def panel_pair(multichip):
    rows, cols, vals, y, off, wgt, d = _coo("uniform", seed=2)
    sb = SparseBatch.from_coo(vals, rows, cols, y, d, offsets=off, weights=wgt)
    one = pack_design(sb).device()
    mesh = make_mesh({"batch": 4, "model": 2})
    placed = psharding.place_batch(pack_design(sb, shards=4), mesh)
    assert placed.shard == (mesh, "batch") and placed.hot.shard == placed.shard
    assert one.hot.strided and placed.hot.strided and placed.hot.rlo is None
    assert placed.parts[0].vals.sharding.spec == P("batch")
    assert placed.order.sharding.is_fully_replicated
    return one, placed


@pytest.mark.parametrize("case", ["margins", "scatter_features",
                                  "scatter_features_sq", "fused_value_grad"])
def test_sharded_panels_match_one_device(case, panel_pair, rng):
    one, placed = panel_pair
    d = one.num_features
    w = jnp.asarray(rng.normal(size=d) * 0.1, jnp.float32)
    v = jnp.asarray(rng.normal(size=d) * 0.1, jnp.float32)
    np.testing.assert_array_equal(one.order, placed.order)

    def run(b):
        r = jnp.sin(jnp.arange(b.num_rows, dtype=jnp.float32))
        return _METHODS[case][0](b, w, v, r)

    ref = jax.tree.leaves(run(one))
    got = jax.tree.leaves(jax.jit(run)(placed))
    n = 1500
    for a, b in zip(ref, got):
        a, b = np.asarray(a), np.asarray(b)
        if a.shape != b.shape or a.shape == (one.num_rows,):
            a, b = a[:n], b[:n]      # per-row: padding rows differ in count
        np.testing.assert_allclose(b, a, rtol=2e-5, atol=2e-5)


def test_sharded_panel_solve_matches_one_device(panel_pair):
    from photon_ml_tpu.optim import (
        OptimizerConfig, RegularizationContext, RegularizationType,
    )
    from photon_ml_tpu.optim.adapter import glm_adapter
    from photon_ml_tpu.optim.factory import build_objective, dispatch_solve
    from photon_ml_tpu.parallel.distributed import gspmd_solve

    one, placed = panel_pair
    cfg = OptimizerConfig(
        max_iterations=12, tolerance=1e-8,
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )
    w0 = jnp.zeros((one.num_features,), jnp.float32)
    obj = build_objective("logistic", cfg)
    ref = jax.jit(
        lambda b: dispatch_solve(glm_adapter(obj, b), w0, cfg, jnp.float32(0))
    )(one)
    res = gspmd_solve("logistic", placed, cfg, w0, placed.shard[0])
    assert res.w.sharding.is_fully_replicated
    np.testing.assert_allclose(float(res.value), float(ref.value), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(res.w), np.asarray(ref.w), rtol=1e-3, atol=2e-3)


def test_place_refuses_another_shard_count(multichip):
    rows, cols, vals, y, _, _, d = _coo("uniform", seed=2)
    packed = pack_design(SparseBatch.from_coo(vals, rows, cols, y, d))
    with pytest.raises(ValueError, match="packed for 1 shards"):
        psharding.place_batch(packed, make_mesh({"batch": 4, "model": 2}))


def test_layout_counters_and_spans():
    """Nonzeros by part, slots, the padding ratio and the layout's child
    spans are in the one telemetry registry."""
    from photon_ml_tpu import telemetry

    rows, cols, vals, y, _, _, d = _coo("uniform", seed=4)
    packed = pack_design(SparseBatch.from_coo(vals, rows, cols, y, d))
    snap = telemetry.snapshot()
    c, g = snap["counters"], snap["gauges"]
    assert c["layout.nnz"] == len(vals) == sum(packed.stored)
    assert c["layout.nnz.hot"] == packed.stored[0]
    assert c["layout.slots"] == packed.nnz_slots
    assert packed.hot.strided
    assert c["layout.tiles.strided"] == packed.hot.num_tiles
    assert "layout.tiles.sorted" not in c
    assert g["layout.padding_ratio"] == pytest.approx(
        packed.nnz_slots / len(vals))
    # the hot part's 16 tiles (1,500 rows padded to whole steps of the
    # widest class): all of them a grid step
    assert g["layout.tiles_a_step"] == packed.hot.tiles_a_step() == 16
    names = {s.name for s in telemetry.finished_spans()}
    assert {"layout.histogram", "layout.rank", "layout.hot",
            "layout.tail"} <= names
