"""The hand SPD solve over entity lanes (``optim/spd_solve.py``) and the
Newton step that takes it for K <= 32 (``optim/newton.py``)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.optim import newton
from photon_ml_tpu.optim.newton import NewtonConfig, newton_solve
from photon_ml_tpu.optim.spd_solve import (
    HAND_SOLVE_MAX_DIM,
    spd_step,
    takes_hand_solve,
)

EPS = float(np.finfo(np.float32).eps)


def _spd(rng, E, K, cond):
    """``E`` random SPD matrices of condition ``cond`` (float32, exactly
    symmetric) and right-hand sides; the float64 step of the float32 data."""
    Q = np.linalg.qr(rng.normal(size=(E, K, K)))[0]
    ev = cond ** rng.uniform(0, 1, size=(E, K))
    ev[:, 0] = 1.0
    ev[:, -1] = cond if K > 1 else 1.0
    H = (Q * ev[:, None, :]) @ np.swapaxes(Q, 1, 2)
    H = ((H + np.swapaxes(H, 1, 2)) / 2).astype(np.float32)
    g = rng.normal(size=(E, K)).astype(np.float32)
    ref = -np.linalg.solve(H.astype(np.float64),
                           g.astype(np.float64)[..., None])[..., 0]
    return H, g, ref


def _rel(x, ref):
    return np.linalg.norm(x - ref, axis=-1) / np.linalg.norm(ref, axis=-1)


def _xla_step(H, g):
    return -jax.scipy.linalg.cho_solve((jnp.linalg.cholesky(H), True), g)


@pytest.mark.parametrize("cond", [1e1, 1e4, 1e6])
@pytest.mark.parametrize("E", [1, 5, 300])
@pytest.mark.parametrize("K", [1, 2, 7, 16, 21, 32])
def test_hand_step_is_float32_grade(K, E, cond):
    rng = np.random.default_rng(1000 * K + E)
    H, g, ref = _spd(rng, E, K, cond)
    hand = _rel(np.asarray(jax.jit(jax.vmap(spd_step))(H, g)), ref)
    xla = _rel(np.asarray(jax.jit(jax.vmap(_xla_step))(H, g)), ref)
    assert hand.max() <= 4 * cond * EPS
    # no worse than XLA's on the same inputs. Both are float32 Cholesky
    # solves, so a single lane's error is the chance of its roundings (over
    # these cases the ratio of the worst lanes runs 0.06 to 3.5); over a
    # batch of 300 the MEAN error is what can be compared (0.58 to 1.36)
    assert hand.max() <= 4 * xla.max() + 8 * EPS
    if E >= 300:
        assert hand.mean() <= 1.5 * xla.mean() + EPS


@pytest.mark.parametrize("K", [1, 2, 7, 32])
def test_failed_lanes_take_minus_grad_and_touch_no_neighbour(K):
    rng = np.random.default_rng(K)
    H, g, _ = _spd(rng, 9, K, 1e2)
    bad = H.copy()
    bad[2] = -H[2]  # not positive definite
    bad[5, K - 1, 0] = bad[5, 0, K - 1] = np.nan
    bad[7, 0, 0] = np.inf
    solve = jax.jit(jax.vmap(spd_step))
    good_steps = np.asarray(solve(H, g))
    steps = np.asarray(solve(bad, g))
    for lane in (2, 5, 7):
        np.testing.assert_array_equal(steps[lane], -g[lane])
    others = [i for i in range(9) if i not in (2, 5, 7)]
    np.testing.assert_array_equal(steps[others], good_steps[others])


@pytest.mark.parametrize("K", [1, 7, 16, 32])
def test_vmapped_equals_a_loop_of_single_calls(K):
    """E = 1 goes through the same routine as a bucket's lanes."""
    H, g, ref = _spd(np.random.default_rng(K), 6, K, 1e1)
    batched = np.asarray(jax.vmap(spd_step)(H, g))
    looped = np.stack([np.asarray(spd_step(H[i], g[i])) for i in range(6)])
    assert _rel(looped, batched).max() <= 1e-5
    assert _rel(looped, ref).max() <= 4 * 1e1 * EPS


def test_unbatched_operand_under_vmap():
    H, g, ref = _spd(np.random.default_rng(3), 4, 5, 1e1)
    one_H = np.asarray(jax.vmap(spd_step, in_axes=(None, 0))(H[0], g))
    for i in range(4):
        np.testing.assert_allclose(
            one_H[i], np.asarray(spd_step(H[0], g[i])), rtol=1e-5, atol=1e-6)


_XLA_CALLS = re.compile(r"(?i)cholesky|potrf|triangular_solve|trsm|InvertDiag")


@pytest.mark.parametrize("K, hand", [
    (1, True), (HAND_SOLVE_MAX_DIM, True), (HAND_SOLVE_MAX_DIM + 1, False)])
def test_the_route_is_the_static_shape(K, hand):
    """K <= 32 lowers WITHOUT any factorisation or triangular-solve call,
    K = 33 lowers with XLA's ``cholesky`` as before."""
    assert takes_hand_solve(K) is hand
    text = jax.jit(jax.vmap(newton._newton_step)).lower(
        jnp.zeros((5, K, K)), jnp.zeros((5, K))).as_text()
    assert bool(_XLA_CALLS.search(text)) is not hand
    if not hand:
        assert "cholesky" in text


def _logistic_problems(rng, E, R, K):
    X = rng.normal(size=(E, R, K)).astype(np.float32) / np.sqrt(K)
    w = rng.normal(size=(E, K)).astype(np.float32)
    p = 1.0 / (1.0 + np.exp(-np.einsum("erk,ek->er", X, w)))
    y = (rng.uniform(size=(E, R)) < p).astype(np.float32)
    return jnp.asarray(X), jnp.asarray(y)


def _solve_logistic(X, y):
    hi = jax.lax.Precision.HIGHEST

    def one(X, y):
        def value(w):
            z = jnp.dot(X, w, precision=hi)
            return jnp.sum(jax.nn.softplus(z) - y * z) + 0.5 * jnp.dot(w, w)

        def hessian(w):
            s = jax.nn.sigmoid(jnp.dot(X, w, precision=hi))
            return jnp.einsum("rk,r,rl->kl", X, s * (1 - s), X,
                              precision=hi) + jnp.eye(X.shape[1])

        return newton_solve(
            jax.value_and_grad(value), hessian,
            jnp.zeros(X.shape[1], jnp.float32), NewtonConfig())

    return jax.jit(jax.vmap(one))(X, y)


@pytest.mark.parametrize("K", [16, 32])
def test_newton_ends_where_the_xla_route_ends(K, monkeypatch):
    X, y = _logistic_problems(np.random.default_rng(K), 24, 64, K)
    hand = _solve_logistic(X, y)
    monkeypatch.setattr(newton, "takes_hand_solve", lambda dim: False)
    xla = _solve_logistic(X, y)
    assert _rel(np.asarray(hand.w), np.asarray(xla.w)).max() <= 1e-5
    np.testing.assert_allclose(hand.value, xla.value, rtol=1e-6)
    assert int(hand.iterations.max()) <= int(xla.iterations.max())
    assert int(hand.iterations.sum()) <= int(xla.iterations.sum())


def test_counters_say_which_route_a_bucket_took(rng):
    """A coordinate with a K = 8 and a K = 40 (padded: 64) bucket counts its
    entities on the right sides, an update at a time."""
    from photon_ml_tpu import telemetry
    from photon_ml_tpu.game import build_game_dataset
    from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
    from photon_ml_tpu.game.random_effect_data import (
        build_random_effect_dataset,
    )
    from photon_ml_tpu.optim import (
        OptimizerConfig, OptimizerType, RegularizationContext,
        RegularizationType,
    )
    from photon_ml_tpu.ops.sparse import SparseBatch

    narrow, wide, rows, d = 5, 3, 48, 40
    users = np.repeat(np.arange(narrow + wide), rows)
    X = rng.normal(size=(len(users), d))
    X[users < narrow, 8:] = 0.0  # the first five users see 8 features
    y = (rng.uniform(size=len(users)) < 0.5).astype(np.float64)
    data = build_game_dataset(
        response=y, feature_shards={"f": SparseBatch.from_dense(X, y)},
        id_columns={"u": users})
    re_data = build_random_effect_dataset(data, "u", "f")
    widths = sorted(b.num_local_features for b in re_data.buckets)
    assert len(widths) == 2 and widths[0] <= 32 < widths[1]

    def make(optimizer_type):
        return RandomEffectCoordinate(
            name="per-u", data=data, re_data=re_data, loss_name="logistic",
            config=OptimizerConfig(
                optimizer_type=optimizer_type,
                regularization=RegularizationContext(RegularizationType.L2),
                regularization_weight=1.0, max_iterations=20,
                tolerance=1e-7))

    def counts():
        c = telemetry.snapshot()["counters"]
        return {k: c.get(k, 0) for k in (
            "re.per-u.hand_solve_lanes", "re.per-u.xla_solve_lanes",
            "re.hand_solve_lanes", "re.xla_solve_lanes")}

    coord = make(OptimizerType.NEWTON)
    before = counts()
    model = coord.update_model(coord.initialize_model(), None)
    once = counts()
    coord.update_model(model, None)
    twice = counts()
    for scope in ("re.per-u", "re"):
        for n, after in ((1, once), (2, twice)):
            assert (after[f"{scope}.hand_solve_lanes"]
                    - before[f"{scope}.hand_solve_lanes"]) == n * narrow
            assert (after[f"{scope}.xla_solve_lanes"]
                    - before[f"{scope}.xla_solve_lanes"]) == n * wide
    # L-BFGS solves factorise nothing: neither counter moves
    other = make(OptimizerType.LBFGS)
    other.update_model(other.initialize_model(), None)
    assert counts() == twice


def test_entity_sharded_bucket_solve_gathers_nothing(rng):
    """``place_entity_solve`` shards a bucket's entity axis over the mesh;
    GSPMD partitions the hand solve's ``[.., E]`` slabs with the rest of the
    vmapped solve: the same coefficients as on one device, and no collective
    that would bring the entity axis together."""
    from photon_ml_tpu.config import parse_optimizer_config
    from photon_ml_tpu.game.coordinates import _re_solver, place_entity_solve
    from photon_ml_tpu.ops.objective import make_objective
    from photon_ml_tpu.parallel import make_mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs four virtual CPU devices")
    import dataclasses

    E, R, K = 24, 32, 16
    X, y = _logistic_problems(rng, E, R, K)
    packed = (X.reshape(E, R * K), y, jnp.zeros((E, R)), jnp.ones((E, R)))
    w0 = jnp.zeros((E, K), jnp.float32)
    config = parse_optimizer_config({
        "type": "newton", "max_iterations": 20, "tolerance": 1e-7,
        "regularization": "l2", "regularization_weight": 1.0})
    solver = _re_solver(
        dataclasses.replace(config, regularization_weight=0.0), "logistic",
        False, False, packed=True)
    obj = make_objective("logistic", l2_weight=1.0)
    single, _ = solver(obj, packed, w0, jnp.float32(0.0), None)

    mesh = make_mesh({"model": 4}, devices=jax.devices()[:4])
    packed_p, w0_p, _ = place_entity_solve(mesh, "model", packed, w0)
    text = solver.lower(
        obj, packed_p, w0_p, jnp.float32(0.0), None).compile().as_text()
    assert not _XLA_CALLS.search(text)
    assert not re.search(r"all-gather|all-to-all|collective-permute", text)
    sharded, _ = solver(obj, packed_p, w0_p, jnp.float32(0.0), None)
    np.testing.assert_allclose(
        np.asarray(sharded.w), np.asarray(single.w), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(sharded.iterations, single.iterations)
