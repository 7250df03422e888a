"""Lanes leave a Newton bucket when they are done (``optim/newton.py::
newton_lanes``, dispatched by ``game/coordinates.py::_re_solver``).

A bucket's solve runs every lane at full width while more than
``compact_width(E)`` lanes are open, then gathers the open ones into
compacted rounds. Each lane must do exactly the arithmetic the plain
vmapped loop does: the per-lane outputs are compared BIT FOR BIT with the
plain program (``_re_solver(sharded=True)``, today's ``vmap`` of the
per-entity solve), on the dense packed route (row-major and feature-major)
and the COO route, on the hand SPD solve (K <= 32) and XLA's (K = 64). The
lanes in ``hard`` have features 30 times as large and need more Newton
iterations than the rest: planted stragglers."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.config import parse_optimizer_config
from photon_ml_tpu.game.coordinates import _re_solver
from photon_ml_tpu.ops.dense import DenseBatch
from photon_ml_tpu.ops.objective import make_objective
from photon_ml_tpu.ops.sparse import SparseBatch
from photon_ml_tpu.optim import newton
from photon_ml_tpu.optim.adapter import glm_adapter
from photon_ml_tpu.optim.common import BoxConstraints
from photon_ml_tpu.optim.factory import newton_config, newton_problem

CONFIG = parse_optimizer_config({
    "type": "newton", "max_iterations": 20, "tolerance": 1e-7,
    "regularization": "l2", "regularization_weight": 1.0})
KEY = dataclasses.replace(CONFIG, regularization_weight=0.0)
OBJ = make_objective("logistic", l2_weight=1.0)
FIELDS = ("w", "value", "grad", "iterations", "reason", "values",
          "grad_norms")


def _design(rng, E, R, K, hard=0):
    """``E`` logistic problems of ``R`` rows and ``K`` features, the last
    ``hard`` of them with features 30 times as large."""
    x = rng.normal(size=(E, R, K)).astype(np.float32) * 0.1
    if hard:
        x[E - hard:] *= 30.0
    y = (rng.random((E, R)) < 0.5).astype(np.float32)
    return x, y


def _batch(route: str, x, y):
    E, R, K = x.shape
    rest = (jnp.asarray(y), jnp.zeros((E, R)), jnp.ones((E, R)))
    if route == "packed":
        return (jnp.asarray(x.reshape(E, R * K)), *rest)
    if route == "kmajor":
        return (jnp.asarray(x.transpose(0, 2, 1).reshape(E, K * R)), *rest)
    return SparseBatch(
        values=jnp.asarray(x.reshape(E, R * K)),
        rows=jnp.broadcast_to(
            jnp.repeat(jnp.arange(R, dtype=jnp.int32), K), (E, R * K)),
        cols=jnp.broadcast_to(
            jnp.tile(jnp.arange(K, dtype=jnp.int32), R), (E, R * K)),
        labels=rest[0], offsets=rest[1], weights=rest[2], num_features=K)


def _solve(route, batch, w0, sharded, box=None, variances=False):
    solver = _re_solver(
        KEY, "logistic", box is not None, variances, packed=route != "coo",
        kmajor=route == "kmajor", sharded=sharded)
    return solver(OBJ, batch, w0, jnp.float32(0.0), box)


def _assert_bit_equal(got, want):
    for field in FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field)), np.asarray(getattr(want, field)),
            err_msg=field)


CASES = [
    # (id, E, R, K, hard, route)
    ("k1-packed", 600, 16, 1, 40, "packed"),
    ("k16-kmajor", 600, 8, 16, 40, "kmajor"),
    ("k32-coo", 300, 8, 32, 20, "coo"),
    ("k64-xla", 300, 8, 64, 20, "packed"),
    ("exactly-width-open", 600, 8, 8, 128, "packed"),
    ("no-stragglers", 600, 8, 8, 0, "packed"),
    ("batch-within-width", 100, 8, 8, 10, "packed"),
]


@pytest.mark.parametrize(
    "E, R, K, hard, route", [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_lanes_that_leave_match_the_plain_loop_bit_for_bit(
        rng, lane_counts_model, E, R, K, hard, route):
    x, y = _design(rng, E, R, K, hard)
    batch = _batch(route, x, y)
    w0 = jnp.zeros((E, K), jnp.float32)
    got, _ = _solve(route, batch, w0, sharded=False)
    want, _ = _solve(route, batch, w0, sharded=True)
    _assert_bit_equal(got, want)
    its = np.asarray(got.iterations)
    counts = tuple(int(c) for c in got.lanes)
    assert counts == lane_counts_model(its)
    assert its.sum() <= counts[0] <= E * its.max()
    # the plain loop runs every lane to the slowest
    assert tuple(int(c) for c in want.lanes) == (E * its.max(), 0, 0)
    width = newton.compact_width(E)
    if hard == width:
        assert counts[1:] == (width, 1)  # one round, no fill slot
    elif hard and width < E:
        assert counts[1] > 0 and counts[2] == 1
    else:
        assert counts[1:] == (0, 0)


def test_a_failed_lane_leaves_its_neighbours_bit_for_bit(rng):
    """A lane whose design holds a NaN and one whose Hessian overflows (no
    SPD factor: the step falls back to steepest descent) change nothing of
    the other lanes, compacted or not."""
    E, R, K = 600, 8, 8
    x, y = _design(rng, E, R, K, hard=40)
    x[E - 1, 0, 0] = np.nan  # a straggler's lane
    x[3, :, :] = 3e19  # an easy lane's: x * x overflows
    batch = _batch("packed", x, y)
    w0 = jnp.zeros((E, K), jnp.float32)
    got, _ = _solve("packed", batch, w0, sharded=False)
    want, _ = _solve("packed", batch, w0, sharded=True)
    _assert_bit_equal(got, want)
    assert int(got.lanes[1]) > 0
    assert not np.isfinite(np.asarray(got.value)[E - 1])
    clean, _ = _solve(
        "packed", _batch("packed", np.delete(x, [3, E - 1], 0),
                         np.delete(y, [3, E - 1], 0)),
        w0[:E - 2], sharded=False)
    np.testing.assert_array_equal(
        np.delete(np.asarray(got.w), [3, E - 1], 0), np.asarray(clean.w))


def test_boxes_and_variances_ride_with_their_lanes(rng):
    """Per-entity boxes are gathered with the lanes they bound; the
    variances are taken at every lane's final coefficients."""
    E, R, K = 600, 8, 8
    x, y = _design(rng, E, R, K, hard=40)
    batch = _batch("packed", x, y)
    w0 = jnp.zeros((E, K), jnp.float32)
    lower = np.full((E, K), -np.inf, np.float32)
    lower[:, 0] = -0.05
    box = BoxConstraints(lower=jnp.asarray(lower),
                         upper=jnp.full((E, K), jnp.inf, jnp.float32))
    got, got_var = _solve("packed", batch, w0, False, box, variances=True)
    want, want_var = _solve("packed", batch, w0, True, box, variances=True)
    _assert_bit_equal(got, want)
    np.testing.assert_array_equal(np.asarray(got_var), np.asarray(want_var))
    assert int(got.lanes[1]) > 0
    assert np.all(np.asarray(got.w)[:, 0] >= -0.05)


def test_more_open_lanes_than_the_width_take_several_rounds(rng):
    """From the start every lane is open: the rounds take the open lanes
    ``width`` at a time, fill the last round with a stopped lane, and end
    each lane where the plain loop ends it."""
    E, R, K, width = 300, 8, 8, 128
    x, y = _design(rng, E, R, K, hard=20)
    lanes = (jnp.asarray(x), jnp.asarray(y), jnp.zeros((E, R)),
             jnp.ones((E, R)))
    w0 = jnp.zeros((E, K), jnp.float32)
    config = newton_config(KEY)

    def problem(lane):
        return newton_problem(glm_adapter(OBJ, DenseBatch(*lane)))

    @jax.jit
    def rounds(lanes, w0):
        step = jax.vmap(lambda lane, s: newton._step(problem(lane), config, s))
        s = jax.vmap(lambda lane, w: newton._start(problem(lane), w, config))(
            lanes, w0)
        s, run, done, n = newton._compacted_rounds(
            step, lambda lane: lane, lanes, s, width)
        return newton._result(s), run, done, n

    got, run, done, n = rounds(lanes, w0)
    want = jax.jit(jax.vmap(lambda lane, w: newton.solve_newton_problem(
        problem(lane), w, config)))(lanes, w0)
    _assert_bit_equal(got, want)
    assert (int(done), int(n)) == (E, -(-E // width))
    assert int(run) >= int(np.asarray(got.iterations).sum())


def test_width_rule():
    """A sixteenth of the batch in whole 128-lane registers; a batch no
    wider than that runs the plain loop."""
    assert newton.compact_width(1) == 128
    assert newton.compact_width(2048) == 128
    assert newton.compact_width(2049) == 256
    assert newton.compact_width(41_659) == 2_688
    assert newton.compact_width(19_937) == 1_280


def _straggling_users(rng, users=600, hard=40, rows=4):
    """A GLMix dataset whose per-user coordinate is one bucket of
    ``users`` lanes, the last ``hard`` users with features 30 times as
    large."""
    from photon_ml_tpu.game import build_game_dataset

    n = users * rows
    who = np.repeat(np.arange(users), rows)
    xu = rng.normal(size=(n, 3)) * 0.1
    xu[who >= users - hard] *= 30.0
    y = (rng.random(n) < 0.5).astype(float)
    return build_game_dataset(
        response=y,
        feature_shards={"global": SparseBatch.from_dense(np.ones((n, 1)), y),
                        "user": SparseBatch.from_dense(xu, y)},
        id_columns={"userId": who})


def _coordinate(data, mesh=None):
    from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
    from photon_ml_tpu.game.random_effect_data import (
        build_random_effect_dataset,
    )

    red = build_random_effect_dataset(data, "userId", "user")
    return RandomEffectCoordinate("per-user", data, red, "logistic", CONFIG,
                                  mesh=mesh)


def _update(coord):
    from photon_ml_tpu import telemetry

    telemetry.reset()
    model = coord.update_model(coord.initialize_model(), None)
    return model, telemetry.snapshot()["counters"]


def test_a_straggling_bucket_reports_its_compacted_lanes(
        rng, lane_counts_model):
    """``re.<name>.lane_iterations_run`` is what the device ran (E for a
    full-width iteration, the width for a compacted one) and
    ``.compacted_lanes`` / ``.compact_rounds`` say that the lanes left;
    ``re.*`` are their sums."""
    coord = _coordinate(_straggling_users(rng))
    _, counters = _update(coord)
    tracker = coord.last_tracker
    assert len(coord.re_data.buckets) == 1
    run, compacted, rounds = lane_counts_model(tracker.iterations)
    assert compacted > 0 and rounds == 1
    assert tracker.lane_counts.tolist() == [[run, compacted, rounds]]
    for scope in ("re.per-user", "re"):
        assert counters[f"{scope}.lane_iterations_run"] == run
        assert counters[f"{scope}.compacted_lanes"] == compacted
        assert counters[f"{scope}.compact_rounds"] == rounds
        assert counters[f"{scope}.lane_iterations"] == tracker.iterations.sum()
    assert tracker.iterations.sum() <= run < len(tracker.iterations) * (
        tracker.iterations.max())


def test_a_mesh_keeps_the_plain_loop(rng):
    """Under a mesh the compiler partitions the lanes: the coordinate's
    solves are the plain vmapped loop (a global gather of the open lanes
    would cross devices), with the coefficients of one device."""
    from photon_ml_tpu.parallel import make_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs four virtual CPU devices")
    data = _straggling_users(rng)
    single, _ = _update(_coordinate(data))
    mesh = make_mesh({"model": 4}, devices=jax.devices()[:4])
    coord = _coordinate(data, mesh)
    sharded, counters = _update(coord)
    np.testing.assert_allclose(
        np.asarray(sharded.buckets[0].coefficients),
        np.asarray(single.buckets[0].coefficients), rtol=1e-5, atol=1e-6)
    its = coord.last_tracker.iterations
    assert counters["re.compacted_lanes"] == counters["re.compact_rounds"] == 0
    assert counters["re.lane_iterations_run"] == len(its) * its.max()
