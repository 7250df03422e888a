"""The three-coordinate GLMix fit of ``ml20m_glmix.cd_fit`` (fixed effect +
per-user + per-movie random effects over heavy-tailed ids), on the CPU at a
rehearsal's size: the program against the blocked plain reference
(``benchmark/reference/glmix_plain_ragged.py``) on every coordinate's
coefficients, every step's loss and AUC and the validation scores; a
random effect with one entity of thousands of rows beside hundreds of
one-row entities; a K = 1 coordinate alone; the reference in blocks against
the reference unblocked (``glmix_plain.fit``); the generator's buckets from
seed to seed; and the rule that bounds the geometry classes."""

from __future__ import annotations

import numpy as np
import pytest

from benchmark import compare, run
from benchmark.drivers import game_fit_mixed
from benchmark.generators import movielens_mixed
from benchmark.reference import glmix_plain, glmix_plain_ragged
from photon_ml_tpu.game import random_effect_data
from photon_ml_tpu.game.random_effect_data import (
    MAX_GEOMETRY_CLASSES,
    build_random_effect_dataset,
    merge_geometry_classes,
)

CELL = run.load_json("workloads", "ml20m_glmix.cd_fit.json")
CONFIG = run.load_json("configs", CELL["config"] + ".json")
TRAFFIC = run.load_json("traffic", CELL["traffic"] + ".json")
# the cell caps LBFGS where float32 ends the solve at ITS size; a few
# thousand rows end later, and program and reference agree only where
# float32 has ended both
TRAFFIC["per_type"]["fixed_effect"]["optimizer"]["max_iterations"] = 60
ROWS = 3000
NEWTON = {"type": "newton", "max_iterations": 20, "tolerance": 1e-7,
          "regularization": "l2", "regularization_weight": 1.0}


def _padded(cols_by_row):
    """Rows of column lists -> ([n, width] columns, values 1, pad value 0)."""
    width = max(len(c) for c in cols_by_row)
    cols = np.zeros((len(cols_by_row), width), np.int32)
    vals = np.zeros((len(cols_by_row), width), np.float32)
    for i, c in enumerate(cols_by_row):
        cols[i, :len(c)] = c
        vals[i, :len(c)] = 1.0
    return cols, vals


def _run_program(raw, entities, shards, coordinates, num_iterations=1):
    """One fit of ``coordinates`` over ``raw`` through the cell's driver
    (its datasets, ``GameEstimator``, ``run_coordinate_descent``, its
    outputs), without the generator."""
    from photon_ml_tpu.config import parse_game_config
    from photon_ml_tpu.game import GameEstimator
    from photon_ml_tpu.game.coordinate_descent import ValidationSpec
    from photon_ml_tpu.optim.guard import GuardSpec

    config = {"data": {"shape": dict(entities), "entities": {
        k: k for k in entities}, "shards": shards},
        "train": {"task": "logistic", "coordinates": coordinates}}
    traffic = {"num_iterations": num_iterations, "evaluators": ["auc"],
               "per_type": {"random_effect": {"optimizer": NEWTON},
                            "fixed_effect": TRAFFIC["per_type"][
                                "fixed_effect"]}}
    d = game_fit_mixed.Driver(config, traffic, 0)
    d.shape["rows"] = len(raw["train"]["y"])
    d.raw = raw
    d.train = d._dataset(raw["train"])
    d.validation_data = d._dataset(raw["validation"])
    d.game_config = parse_game_config(d.train_json)
    d.estimator = GameEstimator(d.game_config)
    d.guard = GuardSpec()
    d.coordinates = d.estimator._build_coordinates(d.train, mesh=None)
    d.validation = ValidationSpec(
        data=d.validation_data, evaluators=list(d.game_config.evaluators))
    record = d.fit()
    assert record["ok"], record
    return d, d.outputs()


# -- the cell's own rehearsal ----------------------------------------------------


@pytest.fixture(scope="module")
def rehearsal():
    driver = game_fit_mixed.Driver(
        CONFIG, TRAFFIC, 2147483659, rows=ROWS, force_tiled=True)
    driver.setup()
    program = driver.outputs()
    reference = glmix_plain_ragged.fit(
        driver.raw, driver.shape, driver.train_json)
    return driver, program, reference


def test_program_follows_the_reference_on_every_coordinate(rehearsal):
    _, program, reference = rehearsal
    assert list(program["coefficients"]) == ["fixed", "per-user", "per-movie"]
    values = compare.numbers(program, reference)
    # float32 against float32. What a float32 objective pins is the SCORES:
    # a rare flag (a decade of fifteen rows here) is held by little more
    # than the L2 term, LBFGS ends with it ~1e-2 of the vector from where
    # the reference's ends, and the per-user and per-movie intercepts take
    # up what the fixed effect leaves (the same features serve both). So
    # the coefficients agree to a few 1e-3 and the scores to under 1e-3.
    assert values["coef_rel.fixed"] < 1e-1, values
    assert values["coef_rel.per-user"] < 6e-3, values
    assert values["coef_rel.per-movie"] < 6e-3, values
    assert values["val_score_rel"] < 3e-3, values
    assert values["first_loss_rel"] < 1e-5, values
    assert values["step_loss_rel"] < 5e-5, values
    assert values["val_metric_gap"] < 1e-3, values


def test_every_step_has_its_loss_and_auc_beside_the_references(rehearsal):
    _, program, reference = rehearsal
    names = ["fixed", "per-user", "per-movie"] * TRAFFIC["num_iterations"]
    assert [s["coordinate"] for s in program["steps"]] == names
    assert [s["coordinate"] for s in reference["steps"]] == names
    for p, r in zip(program["steps"], reference["steps"]):
        assert p["loss"] == pytest.approx(r["loss"], rel=5e-5)
        assert p["metrics"]["auc"] == pytest.approx(
            r["metrics"]["auc"], abs=1e-3)
        assert 1 <= p["solver_iterations"] <= (
            60 if p["coordinate"] == "fixed" else 8)
    # the traffic's ceiling of 20 is one no solve reaches: the step's own
    # forecast ends an entity (optim/newton.py), one-row movies too
    ceiling = TRAFFIC["per_type"]["random_effect"]["optimizer"][
        "max_iterations"]
    driver = rehearsal[0]
    for name in ("per-user", "per-movie"):
        its = driver.coordinates[name].last_tracker.iterations
        assert 1 <= its.min() and its.max() <= 8 < ceiling


def test_control_in_bfloat16_is_told_apart_by_scores_and_losses(rehearsal):
    """Every product's operands rounded to bfloat16 moves the validation
    scores and the updates' objectives several times further from the
    reference than the program sits (the coefficients' own slack, above,
    is as large as the rounding's)."""
    driver, program, reference = rehearsal
    control = glmix_plain_ragged.fit(
        driver.raw, driver.shape, driver.train_json, lower="bfloat16")
    got = compare.numbers(program, reference)
    low = compare.numbers(control, reference)
    assert low["val_score_rel"] > 3 * got["val_score_rel"], (low, got)
    assert low["step_loss_rel"] > 3 * got["step_loss_rel"], (low, got)
    assert low["coef_rel.per-movie"] > 3 * got["coef_rel.per-movie"], (
        low, got)


def test_unseen_validation_entities_score_zero_on_that_coordinate(rehearsal):
    driver, program, reference = rehearsal
    train_ids = driver.raw["train"]["movieId"]
    val_ids = driver.raw["validation"]["movieId"]
    unseen = ~np.isin(val_ids, train_ids)
    assert unseen.any()  # movies whose only ratings are validation rows
    table = program["coefficients"]["per-movie"]
    assert np.all(table[np.unique(val_ids[unseen])] == 0.0)
    assert np.all(reference["coefficients"]["per-movie"][
        np.unique(val_ids[unseen])] == 0.0)


def test_generator_gives_every_seed_the_same_buckets(rehearsal):
    driver = rehearsal[0]
    other = game_fit_mixed.Driver(CONFIG, TRAFFIC, 5, rows=ROWS)
    raw = movielens_mixed.generate(other.shape, other.seed)
    assert not np.array_equal(
        raw["train"]["userId"], driver.raw["train"]["userId"])
    data = other._dataset(raw["train"])
    for name, coord in CONFIG["train"]["coordinates"].items():
        if coord["type"] != "random_effect":
            continue
        mine = build_random_effect_dataset(
            data, coord["id_name"], coord["shard_name"])
        theirs = driver.coordinates[name].re_data

        def classes(d):
            return [(b.num_entities, b.rows_per_entity,
                     b.num_local_features, b.values.shape[1])
                    for b in d.buckets]

        assert classes(mine) == classes(theirs), name
        assert len(mine.buckets) <= MAX_GEOMETRY_CLASSES


def test_every_seed_poses_the_same_fit_under_other_ids(rehearsal):
    """The planted model, the labels and the rows' order come from the
    shape alone; a seed renames the users and the movies. So every seed's
    rows carry the same labels over the same features, row for row, and a
    user's ratings stay together under its new name."""
    driver = rehearsal[0]
    other = game_fit_mixed.Driver(CONFIG, TRAFFIC, 5, rows=ROWS)
    theirs = movielens_mixed.generate(other.shape, other.seed)
    for name in ("train", "validation"):
        a, b = driver.raw[name], theirs[name]
        for key in a:
            if key in ("userId", "movieId"):
                assert not np.array_equal(a[key], b[key]), key
                # one renaming: equal ids here are equal ids there
                _, first = np.unique(a[key], return_inverse=True)
                _, second = np.unique(b[key], return_inverse=True)
                pairs = np.unique(np.stack([first, second]), axis=1)
                assert pairs.shape[1] == first.max() + 1 == second.max() + 1
            else:
                np.testing.assert_array_equal(a[key], b[key], err_msg=key)


def test_generator_keeps_the_floor_and_draws_no_pair_twice():
    shape = game_fit_mixed.Driver(CONFIG, TRAFFIC, 1, rows=20000).shape
    user, movie, per_user = movielens_mixed.incidence(shape)
    assert per_user.min() >= 20 and per_user.sum() == len(user)
    assert len(np.unique(user * shape["rated_movies"] + movie)) == len(user)
    assert np.bincount(movie, minlength=shape["rated_movies"]).min() >= 1
    raw = movielens_mixed.generate(shape, 3000000019)
    for split in raw.values():
        width = (split["global_vals"] != 0).sum(axis=1)
        assert 2 <= width.min() and width.max() <= 10
        own = (split["user_vals"] != 0).sum(axis=1)
        assert set(width - own) == {0, 1}  # a decade flag, but for 1%
        assert split["global_cols"].max() < 32 > split["user_cols"].max()
        assert np.all(split["movie_vals"] == 1.0)
        assert set(split["y"]) == {0.0, 1.0}
    assert abs(raw["train"]["y"].mean() - 0.5) < 0.03


# -- a real tail, and K = 1 ----------------------------------------------------------


def _entity_problem(counts, features, seed, validation=200):
    """Rows of entities with the given row counts over ``features`` binary
    features (feature 0 an intercept), labels from a planted model."""
    rng = np.random.default_rng(seed)
    ids = np.repeat(np.arange(len(counts)), counts)
    ids = np.concatenate([ids, rng.integers(0, len(counts) + 3, validation)])
    n = len(ids)
    on = rng.random((n, features)) < 0.4
    on[:, 0] = True
    w = rng.standard_normal((len(counts) + 3, features)) * 0.7
    logit = (on * w[ids]).sum(axis=1)
    y = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(np.float32)
    order = rng.permutation(n - validation)

    def split(rows):
        cols, vals = _padded([np.flatnonzero(on[r]) for r in rows])
        return {"y": y[rows], "entity": ids[rows],
                "own_cols": cols, "own_vals": vals}

    return {"train": split(order),
            "validation": split(np.arange(n - validation, n))}


@pytest.mark.parametrize("counts, features", [
    ([3000] + [1] * 300, 3),  # one heavy entity beside one-row entities
    ([700, 90, 5, 1, 1, 2, 40, 1], 1),  # an intercept alone: K = 1
], ids=["tail", "k1"])
def test_random_effect_alone_follows_the_reference(counts, features):
    raw = _entity_problem(np.asarray(counts), features, 11)
    entities = {"entity": len(counts) + 3}
    coordinates = {"per-entity": {
        "type": "random_effect", "shard_name": "own", "id_name": "entity"}}
    driver, program = _run_program(
        raw, entities, {"own": features}, coordinates)
    reference = glmix_plain_ragged.fit(raw, driver.shape, driver.train_json)
    values = compare.numbers(program, reference)
    # a Newton solve that stops once its objective moves by 1e-7 of itself
    # has its coefficients to ~3e-4 (the objective is flat to second order)
    assert values["coef_rel.per-entity"] < 1e-3, values
    assert values["val_score_rel"] < 1e-3, values
    assert values["step_loss_rel"] < 2e-6, values
    data = driver.coordinates["per-entity"].re_data
    assert sorted(b.rows_per_entity for b in data.buckets)[-1] >= max(counts)
    assert sum(b.num_entities for b in data.buckets) == len(counts)
    # every row trains: no cap, no sample
    assert len(data.passive_rows) == 0
    rows = sum(int((b.row_index >= 0).sum()) for b in data.buckets)
    assert rows == sum(counts)


# -- the reference in blocks is the reference ----------------------------------------


def test_blocked_reference_equals_the_unblocked_reference():
    shape = game_fit_mixed.Driver(CONFIG, TRAFFIC, 1, rows=2000).shape
    raw = movielens_mixed.generate(shape, 77)
    names = ("fixed", "per-user")
    train_json = {
        "task": "logistic", "num_iterations": 2, "evaluators": ["auc"],
        "coordinates": {name: dict(
            CONFIG["train"]["coordinates"][name],
            **TRAFFIC["per_type"][CONFIG["train"]["coordinates"][name][
                "type"]]) for name in names}}
    blocked = glmix_plain_ragged.fit(
        raw, shape, train_json, block_cells=1 << 14)
    assert len(glmix_plain_ragged.entity_blocks(
        raw["train"]["userId"], shape["users"], 21, 1 << 14)) > 3

    def plain(split):
        return {"cols": split["global_cols"], "vals": split["global_vals"],
                "y": split["y"], "users": split["userId"].astype(np.int64),
                "xu": glmix_plain_ragged.dense_rows(
                    split["user_cols"], split["user_vals"], 21)}

    unblocked = glmix_plain.fit(
        {k: plain(v) for k, v in raw.items()},
        {"fe_features": 32, "users": shape["users"]}, train_json)
    values = compare.numbers(blocked, unblocked)
    # the same solvers on the same rows; a stack's pad changes the order of
    # an entity's float32 sums, which can move a solve's last iteration
    # (and at two thousand rows float32 ends LBFGS with the rare flags
    # loose, as in the program's case above)
    assert values["coef_rel.per-user"] < 5e-3, values
    assert values["coef_rel.fixed"] < 2e-2, values
    assert values["val_score_rel"] < 3e-3, values
    assert values["step_loss_rel"] < 5e-5, values


# -- the rule that bounds the classes -------------------------------------------------


def test_classes_under_the_bound_are_left_alone():
    classes = np.array([[1, 1], [2, 1], [4, 1]])
    target = merge_geometry_classes(classes, np.array([5, 5, 5]), 8)
    assert list(target) == [0, 1, 2]


def test_merging_stops_at_the_bound_and_only_pads():
    rs = 2 ** np.arange(18)
    classes = np.stack([rs, np.ones_like(rs)], axis=1)
    counts = np.maximum(4000 // rs, 1)
    target = merge_geometry_classes(classes, counts, MAX_GEOMETRY_CLASSES)
    assert len(np.unique(target)) == MAX_GEOMETRY_CLASSES
    for t in np.unique(target):
        members = np.flatnonzero(target == t)
        # neighbours in R merge, and a class lands in one at least as tall
        assert np.array_equal(members, np.arange(members[0], members[-1] + 1))
    assert counts.sum() == sum(counts[target == t].sum()
                               for t in np.unique(target))


def test_a_merge_across_k_goes_last():
    # two thin classes that differ in K, and many that differ in R alone
    classes = np.array([[64, 16], [64, 32], [128, 32], [256, 32], [512, 32]])
    counts = np.array([1000, 1000, 10, 10, 10])
    target = merge_geometry_classes(classes, counts, 2)
    assert target[0] != target[1]  # K = 16 keeps its own factorisation


def test_a_heavy_tailed_id_column_builds_a_bounded_number_of_buckets():
    from photon_ml_tpu.game import build_game_dataset
    from photon_ml_tpu.ops.sparse import SparseBatch

    rng = np.random.default_rng(3)
    counts = np.concatenate([[20000, 5000, 1200], rng.integers(1, 300, 400)])
    ids = np.repeat(np.arange(len(counts)), counts)
    n = len(ids)
    y = (rng.random(n) < 0.5).astype(np.float32)
    batch = SparseBatch.from_coo(
        values=np.ones(n, np.float32), rows=np.arange(n),
        cols=np.zeros(n, np.int64), labels=y, num_features=1)
    data = build_game_dataset(
        response=y, feature_shards={"own": batch}, id_columns={"e": ids})
    fine = len(np.unique(random_effect_data._next_pow2_arr(counts)))
    assert fine > MAX_GEOMETRY_CLASSES
    built = build_random_effect_dataset(data, "e", "own")
    assert len(built.buckets) == MAX_GEOMETRY_CLASSES
    assert sum(b.num_entities for b in built.buckets) == len(counts)
    kept = sum(int((b.row_index >= 0).sum()) for b in built.buckets)
    assert kept == n and len(built.passive_rows) == 0
    # an entity's bucket and place still find its rows
    b = built.buckets[built.entity_bucket[0]]
    row = b.row_index[built.entity_pos[0]]
    assert np.array_equal(np.sort(row[row >= 0]), np.arange(20000))
