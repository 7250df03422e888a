"""Validation through the training layout (``FixedEffectCoordinate
.score_dataset``, ``game/coordinate_descent.py::_evaluate``): a tiled
coordinate lays the validation rows out like its own design once, scores
them with the kernels that score the training rows, and the evaluators run
as named programs. On the CPU in Pallas interpret mode, against
``GameModel.score`` (COO) and the evaluators called eagerly."""

import importlib
import logging

import jax
import numpy as np
import pytest

from photon_ml_tpu import telemetry
from photon_ml_tpu.evaluation import EVALUATORS
from photon_ml_tpu.game import (
    FixedEffectConfig,
    GameConfig,
    GameEstimator,
    RandomEffectConfig,
    build_game_dataset,
)
from photon_ml_tpu.game.coordinate_descent import (
    ValidationSpec,
    _evaluate,
    padded_validation_arrays,
)
from photon_ml_tpu.ops import panels
from photon_ml_tpu.ops.panels import PanelBatch
from photon_ml_tpu.ops.sparse import SparseBatch
from photon_ml_tpu.ops.tiled import TiledBatch
from photon_ml_tpu.optim import (
    OptimizerConfig,
    OptimizerType,
    RegularizationContext,
    RegularizationType,
)
from photon_ml_tpu.parallel.mesh import make_mesh

criteo = importlib.import_module("benchmark.generators.criteo_hashed")

EVALS = ["auc", "logistic_loss"]
OPT = OptimizerConfig(
    optimizer_type=OptimizerType.LBFGS, max_iterations=6, tolerance=0.0,
    regularization=RegularizationContext(RegularizationType.L2),
    regularization_weight=1.0,
)


def _uniform(seed, n, d=2000, k=12, users=0):
    """``n`` rows x ``k`` uniform columns of ``d`` (column 0 an intercept),
    labels from a planted model; with ``users`` a per-user shard beside."""
    rng = np.random.default_rng(seed)
    cols = rng.integers(1, d, size=(n, k), dtype=np.int32)
    cols[:, 0] = 0
    vals = rng.standard_normal((n, k)).astype(np.float32)
    vals[:, 0] = 1.0
    w = np.random.default_rng(0).standard_normal(d)
    y = (rng.random(n) < 1 / (1 + np.exp(-(vals * w[cols]).sum(1)))).astype(
        np.float32)
    rows = np.repeat(np.arange(n, dtype=np.int32), k)
    shards = {"global": SparseBatch.from_coo(
        vals.reshape(-1), rows, cols.reshape(-1), y, d)}
    ids = {}
    if users:
        xu = rng.standard_normal((n, 4)) * (rng.random((n, 4)) < 0.7)
        shards["user"] = SparseBatch.from_dense(xu, y)
        ids["userId"] = [f"u{u:03d}" for u in rng.integers(0, users, n)]
    return build_game_dataset(
        response=y, feature_shards=shards, id_columns=ids,
        offset=rng.standard_normal(n) * 0.1, weight=rng.random(n) + 0.5)


def _click_log(seed, n, d=20_480):
    """Rows of the click-log generator (Zipf columns, a fixed hash)."""
    split = criteo.generate(
        {"rows": n, "validation_rows": 8, "fe_features": d,
         "fe_nnz_per_row": criteo.FIELDS}, seed)["train"]
    k = split["cols"].shape[1]
    shard = SparseBatch.from_coo(
        split["vals"].reshape(-1), np.repeat(np.arange(n, dtype=np.int32), k),
        split["cols"].reshape(-1), split["y"], d)
    return build_game_dataset(
        response=split["y"], feature_shards={"global": shard}, id_columns={})


def _estimator(layout="tiled", users=False, **fixed):
    coordinates = {"fixed": FixedEffectConfig(
        shard_name="global", optimizer=OPT, layout=layout, **fixed)}
    if users:
        coordinates["per-user"] = RandomEffectConfig(
            shard_name="user", id_name="userId", optimizer=OPT)
    return GameEstimator(GameConfig(
        task="logistic", coordinates=coordinates, num_iterations=1,
        evaluators=EVALS))


def _eager_metrics(model, data):
    """The evaluators as they ran before: eagerly, over COO scores."""
    scores = model.score(data)
    labels, weights, offsets = padded_validation_arrays(data, scores.shape[0])
    return {k: float(EVALUATORS[k](scores + offsets, labels, weights))
            for k in EVALS}


def _counters():
    return telemetry.snapshot()["counters"]


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


def _check_fit(est, train, val, design_type, mesh=None):
    """Fit with validation; the laid-out scores against COO scores and the
    metrics of the fit's last step against the eager evaluators."""
    telemetry.reset()
    result = est.fit(train, validation_data=val, mesh=mesh)
    coord = est._build_coordinates(train, mesh)["fixed"]
    assert isinstance(coord._tiled, design_type)
    design = coord._foreign[1]
    assert isinstance(design, design_type) and coord._foreign[0]() is val
    sub = result.model.models["fixed"]
    got = coord.score_dataset(sub, val)
    want = sub.score(val)
    assert got.shape == want.shape
    assert _rel(got[: val.num_rows], want[: val.num_rows]) <= 1e-5
    metrics = result.history[-1]["metrics"]
    for name, value in _eager_metrics(result.model, val).items():
        assert metrics[name] == pytest.approx(value, abs=1e-6)
    c = _counters()
    steps = len(result.history)
    assert c["validate.design_builds"] == 1
    # every step's validation after the first, and the call above
    assert c["validate.design_hits"] == steps
    assert "validate.coo_scores" not in c
    return result, coord, design


def test_plain_tiles_score_validation_rows():
    """(a) a plain TiledBatch design; 333 validation rows are not whole
    tiles. The validation design's calls have a trace name of their own,
    its layout its own spans and counters, the training design's untouched."""
    train, val = _uniform(1, 1500), _uniform(2, 333)
    _, coord, design = _check_fit(_estimator(), train, val, TiledBatch)
    assert design.num_rows == 384 and coord._tiled.num_rows == 1536
    assert design.margins_name == "validate_margins"
    assert coord._tiled.margins_name == "tiled_margins"
    c = _counters()
    assert c["layout.nnz"] == 1500 * 12 and c["validate.layout.nnz"] == 333 * 12
    assert c["layout.slots"] == coord._tiled.nnz_slots
    assert c["validate.layout.slots"] == design.nnz_slots
    # 12 training tiles and 3 validation tiles: each design's calls run all
    # of its tiles in one grid step
    g = telemetry.snapshot()["gauges"]
    assert g["layout.tiles_a_step"] == coord._tiled.tiles_a_step() == 12
    assert g["validate.layout.tiles_a_step"] == design.tiles_a_step() == 3
    spans = {s.span_id: s for s in telemetry.finished_spans()}
    for name in ("validation_layout", "validation_upload"):
        (s,) = [s for s in spans.values() if s.name == name]
        assert spans[s.parent_id].name == "validate"
    (up,) = [s for s in spans.values() if s.name == "validation_upload"]
    assert c["validation_upload.bytes"] == up.attrs["bytes"] > 0
    assert c["upload.bytes"] == sum(
        s.attrs["bytes"] for s in spans.values() if s.name == "upload")
    named = {r.name for r in telemetry.XLA_REGISTRY.executables()}
    assert {"evaluate_auc", "evaluate_logistic_loss",
            "fe_score_tiled"} <= named


def test_panels_score_validation_rows_on_the_training_plan(monkeypatch):
    """(b) a PanelBatch design over Zipf columns, its tail cut into several
    classes: the validation rows go on the TRAINING design's order, rank and
    classes, hold columns the training rows never had, and have nonzeros in
    the hot panel and in every class."""
    monkeypatch.setattr(panels, "MAX_CLASS_BLOCKS", 32)
    train, val = _click_log(11, 1500), _click_log(12, 300)
    _, coord, design = _check_fit(_estimator(), train, val, PanelBatch)
    assert len(design.parts) >= 3
    assert [p.cls for p in design.parts] == [p.cls for p in coord._tiled.parts]
    np.testing.assert_array_equal(design.rank, coord._tiled.rank)
    assert all(nnz > 0 for nnz in design.stored)
    unseen = np.setdiff1d(np.asarray(val.shard("global").cols),
                          np.asarray(train.shard("global").cols))
    assert unseen.size > 100
    assert design.hot.margins_name == "validate_margins"
    assert {p.margins_name for p in design.parts} == {"validate_panel_margins"}
    assert {p.margins_name for p in coord._tiled.parts} == {"panel_margins"}
    c = _counters()
    assert c["validate.layout.nnz"] == sum(design.stored)
    assert c["validate.layout.nnz.hot"] == design.stored[0]
    assert c["layout.nnz"] == sum(coord._tiled.stored)
    assert c["layout.slots"] == coord._tiled.nnz_slots


def test_normalized_coordinate_scores_validation_rows():
    """(c) factors and shifts: the model lives in the original space, so
    the laid-out scores are the raw x.w of ``FixedEffectModel.score``."""
    est = _estimator(normalization="standardization", intercept_index=0)
    _, coord, _ = _check_fit(est, _uniform(3, 1500), _uniform(4, 333),
                             TiledBatch)
    assert coord._factors is not None and coord._shifts is not None


def test_fixed_and_random_effect_scores_are_summed_aligned():
    """(d) FE + per-user RE: the random effect is scored as before, and the
    two vectors have the dataset's padded row count before they are added.
    Every step revalidates every sub-model."""
    train, val = _uniform(5, 1500, users=20), _uniform(6, 333, users=25)
    result, coord, _ = _check_fit(
        _estimator(users=True), train, val, TiledBatch)
    assert len(result.history) == 2
    n_pad = val.shard("global").num_rows
    fixed = coord.score_dataset(result.model.models["fixed"], val)
    user = result.model.models["per-user"].score(val)
    assert fixed.shape == user.shape == (n_pad,)
    assert float(np.abs(np.asarray(user)).max()) > 0


def test_cache_follows_the_dataset_and_the_width(caplog):
    """A second evaluation builds nothing; another dataset builds anew; a
    shard of another width is scored as COO, with a warning and a count."""
    train, val = _uniform(7, 700), _uniform(8, 200)
    est = _estimator()
    result = est.fit(train)
    coords = est._build_coordinates(train, None)
    telemetry.reset()
    spec = ValidationSpec(val, EVALS)
    first = _evaluate(result.model, spec, coords)
    assert _counters()["validate.design_builds"] == 1
    assert "validate.design_hits" not in _counters()
    assert _evaluate(result.model, spec, coords) == first
    c = _counters()
    assert (c["validate.design_builds"], c["validate.design_hits"]) == (1, 1)
    for name, value in _evaluate(result.model, spec).items():
        assert first[name] == pytest.approx(value, abs=1e-6)  # COO, no coords

    other = _uniform(9, 250)
    _evaluate(result.model, ValidationSpec(other, EVALS), coords)
    assert _counters()["validate.design_builds"] == 2
    assert coords["fixed"]._foreign[0]() is other

    # the training rows themselves need no second design
    own = _evaluate(result.model, ValidationSpec(train, EVALS), coords)
    assert _counters()["validate.design_builds"] == 2
    for name, value in _eager_metrics(result.model, train).items():
        assert own[name] == pytest.approx(value, abs=1e-6)

    narrow = _uniform(10, 200, d=1000)
    with caplog.at_level(logging.WARNING, logger="photon_ml_tpu.game"):
        got = _evaluate(result.model, ValidationSpec(narrow, EVALS), coords)
    assert "1000 features, the training design 2000" in caplog.text
    c = _counters()
    assert c["validate.coo_scores"] == 1 and c["validate.design_builds"] == 2
    assert coords["fixed"]._foreign[1] is None
    for name, value in _eager_metrics(result.model, narrow).items():
        assert got[name] == pytest.approx(value, abs=1e-6)


def test_coo_layout_never_builds_a_design():
    train, val = _uniform(13, 700), _uniform(14, 200)
    est = _estimator(layout="coo")
    telemetry.reset()
    result = est.fit(train, validation_data=val)
    coord = est._build_coordinates(train, None)["fixed"]
    assert coord._foreign is None
    assert not [k for k in _counters() if k.startswith("validate.")]
    assert not [s for s in telemetry.finished_spans()
                if s.name.startswith("validation_")]
    metrics = result.history[-1]["metrics"]
    for name, value in _eager_metrics(result.model, val).items():
        assert metrics[name] == pytest.approx(value, abs=1e-6)


@pytest.mark.parametrize("data", ["plain", "panels"])
def test_batch_mesh_metrics_match_one_device(data, multichip, monkeypatch):
    """Four virtual devices on a ``batch`` axis: the validation design is
    packed for four shards and placed like the training one, and the fit's
    metrics are the one-device fit's."""
    if data == "panels":
        monkeypatch.setattr(panels, "MAX_CLASS_BLOCKS", 32)
        train, val = _click_log(15, 1500), _click_log(16, 300)
        kind = PanelBatch
    else:
        train, val = _uniform(15, 1500), _uniform(16, 333)
        kind = TiledBatch
    one, _, _ = _check_fit(_estimator(), train, val, kind)
    mesh = make_mesh({"batch": 4}, devices=jax.devices()[:4])
    est = _estimator()
    four, coord, design = _check_fit(est, train, val, kind, mesh)
    leaf = design.hot.vals if kind is PanelBatch else design.vals
    assert len(leaf.sharding.device_set) == 4
    assert (design.hot if kind is PanelBatch else design).shard == (
        mesh, "batch")
    # the one-device model through the mesh's coordinates: the same metrics
    same = _evaluate(one.model, ValidationSpec(val, EVALS),
                     est._build_coordinates(train, mesh))
    for name, value in one.history[-1]["metrics"].items():
        assert same[name] == pytest.approx(value, abs=1e-6)
        assert four.history[-1]["metrics"][name] == pytest.approx(
            value, abs=1e-3)
