"""The factored coordinate's path since PR 33, against the path it replaced:
the refit of vec(A) without a Kronecker design (:class:`LatentRefitBatch`)
against the SAME objective over the Kronecker COO design built here (the
old path's arithmetic, kept as the oracle) and against ``jax.grad`` of the
plain margins; the dense-route latent solve against the COO-route solve;
device-resident validation scores against ``FactoredRandomEffectModel
.score``; the K-table sweeps of a tiled design; buckets classed by rows
alone; the mesh path on four virtual devices; what the telemetry says; and
since PR 34 the refit over the column-sorted second layout of a one-hot
design against the refit in the coordinate's own row order."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import telemetry
from photon_ml_tpu.game import build_game_dataset
from photon_ml_tpu.game import factored
from photon_ml_tpu.game.coordinates import _re_solver
from photon_ml_tpu.game.factored import (
    FactoredRandomEffectCoordinate,
    LatentRefitBatch,
)
from photon_ml_tpu.game.random_effect_data import (
    MAX_GEOMETRY_CLASSES,
    build_random_effect_dataset,
)
from photon_ml_tpu.ops import tiled
from photon_ml_tpu.ops.objective import make_objective
from photon_ml_tpu.ops.sparse import SparseBatch
from photon_ml_tpu.ops.tiled import TiledBatch
from photon_ml_tpu.optim import (
    OptimizerConfig,
    OptimizerType,
    RegularizationContext,
    RegularizationType,
)

K = 3


def _opt(kind=OptimizerType.LBFGS, lam=0.5, iters=60, tol=1e-9):
    return OptimizerConfig(
        optimizer_type=kind,
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=lam, max_iterations=iters, tolerance=tol,
    )


def _sparse_problem(rng, n=300, d=150, nnz_per_row=3, ragged=False):
    """A sparse design with several nonzeros a row (so nnz != rows), binary
    labels, per-row latent vectors."""
    counts = (rng.integers(1, 2 * nnz_per_row, n) if ragged
              else np.full(n, nnz_per_row))
    if ragged:
        counts[::17] = 9 * nnz_per_row  # a few long rows: tiles go sorted
    rows = np.repeat(np.arange(n), counts)
    cols = rng.integers(0, d, len(rows))
    vals = rng.normal(size=len(rows)).astype(np.float32)
    y = (rng.random(n) < 0.5).astype(np.float32)
    off = rng.normal(size=n).astype(np.float32) * 0.1
    wgt = rng.random(n).astype(np.float32) + 0.5
    c = rng.normal(size=(K, n)).astype(np.float32)
    return rows, cols, vals, y, off, wgt, c


def _design(layout, rows, cols, vals, y, d, monkeypatch):
    if layout == "coo":
        return SparseBatch.from_coo(
            values=vals, rows=rows, cols=cols, labels=y, num_features=d
        ).device()
    if layout == "tiled_sorted":
        monkeypatch.setattr(tiled, "strided_is_cheaper", lambda *a: False)
    design = TiledBatch.from_coo(
        values=vals, rows=rows, cols=cols, labels=y, num_features=d
    ).traced_as("mf")
    assert design.strided == (layout == "tiled_strided")
    return design


def _kronecker(rows, cols, vals, c, y, off, wgt, d):
    """The design the parent materialised: nonzero (i, j, v) of a row with
    latent vector c becomes (i, j*K + l, v * c[l]) for every l."""
    k = c.shape[0]
    return SparseBatch.from_coo(
        values=(vals[:, None] * c[:, rows].T).reshape(-1),
        rows=np.repeat(rows, k),
        cols=(cols[:, None] * k + np.arange(k)[None, :]).reshape(-1),
        labels=y, num_features=d * k, offsets=off, weights=wgt,
    ).device()


def _pad(x, n):
    return jnp.asarray(np.pad(np.asarray(x), (0, n - len(x))))


@pytest.mark.parametrize("loss", ["logistic", "squared", "poisson"])
@pytest.mark.parametrize("layout", ["coo", "tiled_strided", "tiled_sorted"])
def test_refit_batch_matches_the_kronecker_design(
        rng, monkeypatch, layout, loss):
    d = 150
    rows, cols, vals, y, off, wgt, c = _sparse_problem(
        rng, ragged=layout == "tiled_sorted")
    design = _design(layout, rows, cols, vals, y, d, monkeypatch)
    n_pad = design.num_rows
    batch = LatentRefitBatch(
        design=design, c_rows=jnp.asarray(np.pad(c, ((0, 0), (0, n_pad - c.shape[1])))),
        labels=_pad(y, n_pad), offsets=_pad(off, n_pad),
        weights=_pad(wgt, n_pad))
    kron = _kronecker(rows, cols, vals, c, y, off, wgt, d)
    assert batch.num_features == kron.num_features == d * K
    obj = make_objective(loss, l2_weight=0.7)
    w = jnp.asarray(rng.normal(size=d * K).astype(np.float32) * 0.1)
    v = jnp.asarray(rng.normal(size=d * K).astype(np.float32))
    tol = dict(rtol=2e-4, atol=2e-4)
    f, g = obj.value_and_grad(w, batch)
    f0, g0 = obj.value_and_grad(w, kron)
    np.testing.assert_allclose(f, f0, rtol=1e-5)
    np.testing.assert_allclose(g, g0, **tol)
    np.testing.assert_allclose(
        obj.margins(w, batch)[:len(y)], obj.margins(w, kron), **tol)
    np.testing.assert_allclose(
        obj.hessian_vector(w, v, batch), obj.hessian_vector(w, v, kron),
        rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(
        obj.hessian_diagonal(w, batch), obj.hessian_diagonal(w, kron),
        rtol=1e-3, atol=1e-3)
    z = obj.margins(w, batch)
    np.testing.assert_allclose(
        obj.value_and_grad_at_margins(w, z, batch)[1], g0, **tol)


def test_refit_gradient_is_the_autodiff_of_the_plain_margins(rng):
    d = 40
    rows, cols, vals, y, off, wgt, c = _sparse_problem(rng, n=120, d=d)
    design = SparseBatch.from_coo(
        values=vals, rows=rows, cols=cols, labels=y, num_features=d).device()
    batch = LatentRefitBatch(
        design=design, c_rows=jnp.asarray(c), labels=jnp.asarray(y),
        offsets=jnp.asarray(off), weights=jnp.asarray(wgt))
    x = np.zeros((len(y), d), np.float32)
    np.add.at(x, (rows, cols), vals)

    def plain(a):  # a [K, d]
        z = jnp.sum(jnp.asarray(c) * (a @ x.T), axis=0) + off
        loss = jnp.logaddexp(0.0, z) - y * z
        return jnp.sum(wgt * loss) + 0.5 * 0.3 * jnp.sum(a * a)

    a = jnp.asarray(rng.normal(size=(K, d)).astype(np.float32) * 0.2)
    obj = make_objective("logistic", l2_weight=0.3)
    f, g = obj.value_and_grad(a.T.reshape(-1), batch)
    f0, g0 = jax.value_and_grad(plain)(a)
    np.testing.assert_allclose(f, f0, rtol=1e-5)
    np.testing.assert_allclose(g.reshape(d, K).T, g0, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("layout", ["tiled_strided", "tiled_sorted"])
def test_tiled_design_serves_k_tables_in_one_sweep(rng, monkeypatch, layout):
    d = 300  # three column blocks
    rows, cols, vals, y, *_ = _sparse_problem(
        rng, n=260, d=d, ragged=layout == "tiled_sorted")
    design = _design(layout, rows, cols, vals, y, d, monkeypatch)
    x = design.to_dense()
    a = rng.normal(size=(5, d)).astype(np.float32)
    g = rng.normal(size=(5, design.num_rows)).astype(np.float32)
    np.testing.assert_allclose(
        design.project_rows(jnp.asarray(a)), a @ x.T, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        design.scatter_rows(jnp.asarray(g)), g @ x, rtol=1e-4, atol=1e-4)
    names = {
        e.params["name"]
        for fn, arg in ((design.project_rows, a), (design.scatter_rows, g))
        for e in jax.make_jaxpr(fn)(jnp.asarray(arg)).jaxpr.eqns
        if e.primitive.name == "pallas_call"}
    if layout == "tiled_strided":  # the K-wide calls, named apart
        assert names == {"mf_tables_k", "mf_margins_k", "mf_scatter_k"}


def _mf_data(rng, n_users=30, d=25, one_hot=True):
    """Users with 3 to 40 rows (several geometry classes), a one-hot or a
    three-nonzero shard, labels from a planted rank-K model."""
    counts = rng.integers(3, 40, n_users)
    users = np.repeat(np.arange(n_users), counts)
    rng.shuffle(users)
    n = len(users)
    per_row = 1 if one_hot else 3
    rows = np.repeat(np.arange(n), per_row)
    cols = rng.integers(0, d, n * per_row)
    vals = (np.ones(n * per_row) if one_hot
            else rng.normal(size=n * per_row)).astype(np.float32)
    a = rng.normal(size=(K, d))
    c = rng.normal(size=(n_users, K))
    x = np.zeros((n, d))
    np.add.at(x, (rows, cols), vals)
    z = np.einsum("nk,nk->n", x @ a.T, c[users])
    y = (rng.random(n) < 1 / (1 + np.exp(-z))).astype(np.float32)
    shard = SparseBatch.from_coo(
        values=vals, rows=rows, cols=cols, labels=y, num_features=d)
    data = build_game_dataset(
        response=y, feature_shards={"item": shard},
        id_columns={"userId": np.asarray([f"u{u:03d}" for u in users])})
    return data, users, x


def _coordinate(data, layout="coo", mesh=None, mf_iterations=1,
                kind=OptimizerType.NEWTON, name="mf", refit_cap=8):
    red = build_random_effect_dataset(
        data, "userId", "item", class_by_features=False)
    return FactoredRandomEffectCoordinate(
        name=name, data=data, re_data=red, loss_name="logistic",
        re_config=_opt(kind, lam=1.0, iters=20, tol=1e-7),
        latent_config=_opt(
            OptimizerType.LBFGS, lam=1.0, iters=refit_cap, tol=0.0),
        latent_dim=K, mf_iterations=mf_iterations, layout=layout, mesh=mesh)


@pytest.mark.parametrize("kind", [OptimizerType.NEWTON, OptimizerType.LBFGS])
def test_dense_route_latent_solve_matches_the_coo_route(rng, kind):
    """The parent wrapped the dense [E, R, K] latent design as a COO batch
    of R*K entries an entity for ``_re_solver(packed=False)``; the same
    solve on the dense route, from the feature-major flat design."""
    data, users, x = _mf_data(rng, one_hot=False)
    coord = _coordinate(data, kind=kind)
    model = coord.initialize_model()
    a = model.projection.matrix
    designs = coord.latent_designs(a)
    key = dataclasses.replace(coord.re_config, regularization_weight=0.0)
    coo_solver = _re_solver(key, "logistic")
    for b, x_flat in zip(coord.re_data.device_buckets_stripped(), designs):
        e, r = b.num_entities, b.rows_per_entity
        # the design itself: row (e, r)'s entry l is (A x_row)[l]
        ri = np.asarray(b.row_index)
        want = np.where(
            (ri >= 0)[..., None], (x @ np.asarray(a).T)[np.maximum(ri, 0)],
            0.0)
        got = np.asarray(x_flat).reshape(e, K, r).transpose(0, 2, 1)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        w0 = jnp.zeros((e, K), jnp.float32)
        dense = coord.solve_bucket(
            coord._re_obj, x_flat, b.labels, b.offsets, b.weights, w0)
        wrapped = SparseBatch(
            values=jnp.asarray(got.reshape(e, r * K)),
            rows=jnp.broadcast_to(
                jnp.repeat(jnp.arange(r, dtype=jnp.int32), K), (e, r * K)),
            cols=jnp.broadcast_to(
                jnp.tile(jnp.arange(K, dtype=jnp.int32), r), (e, r * K)),
            labels=b.labels, offsets=b.offsets, weights=b.weights,
            num_features=K)
        coo, _ = coo_solver(coord._re_obj, wrapped, w0, coord._re_l1, None)
        np.testing.assert_allclose(dense.w, coo.w, rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(dense.value, coo.value, rtol=1e-5)


@pytest.mark.parametrize("layout", ["coo", "tiled"])
@pytest.mark.parametrize("one_hot", [True, False])
def test_device_resident_validation_scores_match_the_model(
        rng, layout, one_hot):
    data, *_ = _mf_data(rng, one_hot=one_hot)
    coord = _coordinate(data, layout=layout)
    model = coord.update_model(coord.initialize_model(), None)
    # the coordinate's own rows, both ways
    n = data.num_rows
    np.testing.assert_allclose(
        np.asarray(coord.score(model))[:n], np.asarray(model.score(data))[:n],
        rtol=1e-4, atol=1e-5)
    # another dataset: fewer rows, a user the fit never saw
    other, *_ = _mf_data(np.random.default_rng(7), n_users=12,
                         one_hot=one_hot)
    ids = other.id_columns["userId"]
    vocab = ids.vocab.copy()
    vocab[0] = "never-seen"
    other = dataclasses.replace(other, id_columns={
        "userId": dataclasses.replace(ids, vocab=vocab)})
    telemetry.reset()
    first = coord.score_dataset(model, other)
    again = coord.score_dataset(model, other)
    want = np.asarray(model.score(other))
    assert first.shape == want.shape
    np.testing.assert_allclose(first, want, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(first, again)
    unseen = np.flatnonzero(ids.codes == 0)
    assert len(unseen) and not np.asarray(first)[unseen].any()
    counters = telemetry.snapshot()["counters"]
    assert counters["validate.mf_design_builds"] == 1  # uploaded once
    assert counters["validate.mf_design_hits"] == 1
    assert coord.score_dataset(model, data) is not None  # its own rows


def test_factored_buckets_are_classed_by_rows_alone(rng):
    data, users, _ = _mf_data(rng, n_users=60, one_hot=False)
    by_rows = build_random_effect_dataset(
        data, "userId", "item", class_by_features=False)
    both = build_random_effect_dataset(data, "userId", "item")
    rows = [b.rows_per_entity for b in by_rows.buckets]
    assert len(set(rows)) == len(rows) <= MAX_GEOMETRY_CLASSES
    assert len(by_rows.buckets) <= len(both.buckets)
    counts = np.bincount(users)
    for b in by_rows.buckets:  # nothing dropped, a class holds its widest
        own = (np.asarray(b.row_index) >= 0).sum(axis=1)
        assert own.max() <= b.rows_per_entity < 2 * max(own.max(), 1) + 1
        assert (np.asarray(b.projection) < b.num_global_features).sum(
            axis=1).max() <= b.num_local_features
    assert sum((np.asarray(b.row_index) >= 0).sum() for b in by_rows.buckets
               ) == counts.sum()


def test_refit_program_holds_no_array_of_kronecker_length(rng):
    """nnz x K: the length of the design the parent materialised (values,
    rows, cols, a permutation, a flat latent index). The refit's program
    holds none, on either layout."""
    data, *_ = _mf_data(rng, one_hot=False)
    coord = _coordinate(data)
    kron = coord._nnz * K
    assert kron not in {coord._design.num_rows * K, coord._design.num_rows}
    model = coord.initialize_model()
    offsets = coord._bucket_offsets(None)
    args = (coord._lat_obj, coord._design, coord._labels, coord._weights,
            offsets, coord._latents(model.latent),
            model.projection.matrix.T.reshape(-1), coord._lat_l1)
    jaxpr = jax.make_jaxpr(coord._lat_solver.__wrapped__)(*args)

    def sizes(j):
        for eqn in j.eqns:
            for v in eqn.outvars:
                yield int(np.prod(v.aval.shape)) if v.aval.shape else 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from sizes(sub)

    assert kron not in set(sizes(jaxpr.jaxpr))
    for leaf in jax.tree.leaves((coord._design, coord._labels)):
        assert leaf.size != kron


def test_update_reports_its_routes_spans_and_counters(rng):
    data, *_ = _mf_data(rng)
    telemetry.reset()
    coord = _coordinate(data, name="uxm")
    counters = telemetry.snapshot()["counters"]
    assert counters["mf.uxm.kron_nnz_materialised"] == 0
    assert counters["mf.uxm.refit_nnz"] == data.num_rows
    assert counters["mf.uxm.latent_dim"] == K
    assert counters["re.uxm.buckets"] == len(coord.re_data.buckets)
    assert counters["re.uxm.rows"] == data.num_rows
    assert counters["re.uxm.rows_padded"] >= data.num_rows
    model = coord.update_model(coord.initialize_model(), None)
    counters = telemetry.snapshot()["counters"]
    entities = sum(b.num_entities for b in coord.re_data.buckets)
    assert counters["mf.uxm.hand_solve_lanes"] == entities  # K <= 32
    assert counters["mf.uxm.xla_solve_lanes"] == 0
    assert counters["mf.uxm.refit_evaluations"] == counters[
        "mf.uxm.refit_iterations"] + 1
    assert 0 < counters["mf.uxm.lane_iterations"] <= counters[
        "mf.uxm.lane_iterations_run"]
    # every latent pass's bucket solves: what the device ran, at most
    # every lane to its bucket's slowest
    lanes = entities = 0
    for re_tracker, _ in coord.last_tracker.steps:
        assert re_tracker.lane_counts.shape == (len(coord.re_data.buckets), 3)
        lanes += re_tracker.lane_counts[:, 0].sum()
        entities += len(re_tracker.iterations) * re_tracker.iterations.max()
    assert counters["mf.uxm.lane_iterations_run"] == lanes <= entities
    for key in ("lane_iterations", "lane_iterations_run", "compacted_lanes",
                "compact_rounds"):
        assert counters[f"mf.{key}"] == counters[f"mf.uxm.{key}"]
    names = [s.name for s in telemetry.finished_spans()]
    for span in ("mf_layout", "mf_layout.group", "mf_layout.design",
                 "mf_upload", "mf_iteration:0", "latent_design",
                 "latent_tracker", "latent_refit"):
        assert span in names, (span, sorted(set(names)))
    assert any(n.startswith("latent_bucket:") and n.endswith(f"x{K}")
               for n in names)
    tracker = coord.last_tracker
    re_t, fe_t = tracker.steps[-1]
    assert tracker.final_value == fe_t.final_value
    assert tracker.iterations == float(np.mean(re_t.iterations))
    assert np.all(np.isfinite(np.asarray(model.latent)))


def test_mesh_path_on_four_devices_matches_one_device(rng):
    """Entity-sharded latent solves and a refit whose design is sharded
    over the same axis (GSPMD), against the one-device coordinate."""
    from jax.sharding import Mesh

    data, *_ = _mf_data(rng, n_users=24, one_hot=False)
    local = _coordinate(data, mf_iterations=2)
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("entity",))
    sharded = _coordinate(data, mesh=mesh, mf_iterations=2)
    assert not isinstance(sharded._design, TiledBatch)
    m_local = local.update_model(local.initialize_model(), None)
    m_shard = sharded.update_model(sharded.initialize_model(), None)
    np.testing.assert_allclose(
        m_shard.projection.matrix, m_local.projection.matrix,
        rtol=5e-3, atol=5e-4)
    np.testing.assert_allclose(
        sharded.score(m_shard), local.score(m_local), rtol=5e-3, atol=5e-4)
    assert factored.KRON_FREE_REFIT


# -- the column-sorted second layout (PR 34) ---------------------------------


def _refit(coord, latent, a, by_column):
    """One refit of vec(A) from ``a`` through the coordinate's own compiled
    solver, over the second layout or (``by_column`` None) in the
    coordinate's row order."""
    res = coord._lat_solver(
        coord._lat_obj, coord._design, coord._labels, coord._weights,
        coord._bucket_offsets(None), coord._latents(latent),
        a.T.reshape(-1), coord._lat_l1, by_column)
    return (np.asarray(res.w).reshape(-1, K).T, float(res.value),
            int(res.iterations))


def test_column_sorted_refit_is_the_coordinate_order_refit(rng):
    """Same design, same optimizer, same start: the rows walked by column
    give the coordinate-order refit's A, loss and iteration count."""
    data, *_ = _mf_data(rng, n_users=40, d=300)  # three column blocks
    # a cap under the iteration at which float32 ends the solve by itself
    # (the 6th or 7th here, by the last bits of the sums' order)
    coord = _coordinate(data, layout="tiled", refit_cap=5)
    by_column = coord._by_column
    assert by_column is not None and isinstance(coord._design, TiledBatch)
    assert by_column.design.num_rows >= data.num_rows
    # the slots hold every row once: labels, weights and entities permuted
    order = np.asarray(by_column.order)
    live = np.asarray(by_column.weights) > 0
    assert live.sum() == data.num_rows
    assert len(set(order[live])) == data.num_rows
    np.testing.assert_array_equal(
        np.asarray(coord._labels)[order[live]],
        np.asarray(by_column.labels)[live])
    model = coord.initialize_model()
    a = model.projection.matrix
    latent, _ = coord._latent_re_step(
        model.latent, a, coord._bucket_offsets(None))
    c_sorted = factored._latent_rows(latent, by_column.entity)
    c_rows = factored._c_rows(
        coord._latents(latent), coord._shapes, coord._design.num_rows)
    np.testing.assert_array_equal(
        np.asarray(c_sorted)[:, live], np.asarray(c_rows)[:, order[live]])
    a_col, loss_col, its_col = _refit(coord, latent, a, by_column)
    a_row, loss_row, its_row = _refit(coord, latent, a, None)
    assert its_col == its_row == 5
    np.testing.assert_allclose(loss_col, loss_row, rtol=1e-6)
    assert np.linalg.norm(a_col - a_row) <= 1e-5 * np.linalg.norm(a_row)
    # and the update itself takes the second layout
    telemetry.reset()
    stepped, _ = coord._latent_matrix_step(
        latent, a, coord._bucket_offsets(None))
    np.testing.assert_array_equal(stepped, a_col)
    calls = {
        e.params["name"]
        for e in jax.make_jaxpr(
            lambda w: LatentRefitBatch(
                design=by_column.design, c_rows=c_sorted,
                labels=by_column.labels, offsets=by_column.labels,
                weights=by_column.weights).fused_value_grad(w, 0.0, "logistic")
        )(a.T.reshape(-1)).jaxpr.eqns if e.primitive.name == "pallas_call"}
    assert calls == {
        "mf_tables_k", "mf_margins_k_sorted", "mf_scatter_k_sorted"}


@pytest.mark.parametrize("case", [
    "one_hot_tiled", "two_nonzeros_a_row", "one_hot_mesh", "one_hot_coo",
    "a_row_without_a_nonzero"])
def test_second_layout_is_chosen_by_the_design_alone(rng, case):
    """One nonzero in every row AND the Mosaic design: nothing else engages
    it, and counter ``mf.<name>.refit_column_sorted`` says which."""
    from jax.sharding import Mesh

    one_hot = case != "two_nonzeros_a_row"
    data, *_ = _mf_data(rng, n_users=12, one_hot=one_hot)
    if case == "a_row_without_a_nonzero":
        shard = data.shard("item")
        values = np.asarray(shard.values).copy()
        values[3] = 0.0
        data = dataclasses.replace(data, feature_shards={
            "item": dataclasses.replace(shard, values=values)})
    mesh = (Mesh(np.asarray(jax.devices()[:4]), ("entity",))
            if case == "one_hot_mesh" else None)
    telemetry.reset()
    coord = _coordinate(
        data, layout="coo" if case == "one_hot_coo" else "tiled", mesh=mesh,
        name="uxm")
    engaged = case == "one_hot_tiled"
    counters = telemetry.snapshot()["counters"]
    assert counters["mf.uxm.refit_column_sorted"] == int(engaged)
    assert counters["mf.uxm.refit_window_rows"] == (16 if engaged else 0)
    assert (coord._by_column is not None) == engaged
    names = {s.name for s in telemetry.finished_spans()}
    assert ({"mf_refit_layout.sort", "mf_refit_layout.pack"} <= names
            ) == engaged
    model = coord.update_model(coord.initialize_model(), None)
    assert np.all(np.isfinite(np.asarray(model.projection.matrix)))
    counters = telemetry.snapshot()["counters"]
    assert counters["mf.uxm.refit_evaluations"] == counters[
        "mf.uxm.refit_iterations"] + 1


@pytest.mark.parametrize("one_hot", [True, False])
def test_masked_factored_update_is_unchanged_by_the_second_layout(
        rng, one_hot):
    """``incremental/refit.py::MaskedFactoredRandomEffectCoordinate`` over a
    coordinate with (one-hot) and without the second layout: the touched
    entities' latent vectors are the full latent step's, the others stand
    bit for bit, the projection is frozen."""
    from photon_ml_tpu.incremental.refit import (
        MaskedFactoredRandomEffectCoordinate,
    )

    data, *_ = _mf_data(rng, n_users=20, one_hot=one_hot)
    inner = _coordinate(data, layout="tiled")
    assert (inner._by_column is not None) == one_hot
    model = inner.update_model(inner.initialize_model(), None)
    touched = np.zeros(inner.re_data.num_entities, bool)
    touched[[1, 5, 11]] = True
    masked = MaskedFactoredRandomEffectCoordinate(inner, touched)
    residual = jnp.asarray(
        rng.normal(size=inner._batch.num_rows).astype(np.float32) * 0.3)
    got = masked.update_model(model, residual)
    full, _ = inner._latent_re_step(
        model.latent, model.projection.matrix,
        inner._bucket_offsets(residual))
    flat = inner._entity_flat[touched]
    others = np.setdiff1d(np.arange(inner._n_flat), flat)
    np.testing.assert_array_equal(
        np.asarray(got.latent)[others], np.asarray(model.latent)[others])
    np.testing.assert_allclose(
        np.asarray(got.latent)[flat], np.asarray(full)[flat],
        rtol=1e-4, atol=1e-5)
    assert not np.array_equal(
        np.asarray(got.latent)[flat], np.asarray(model.latent)[flat])
    np.testing.assert_array_equal(
        got.projection.matrix, model.projection.matrix)
    assert masked.lanes_solved == 3


def test_program_agrees_with_the_plain_reference_through_the_mf_driver():
    """``benchmark/drivers/game_fit_mf.py`` at 8,192 rows (COO design, this
    CPU) against ``benchmark/reference/glmix_plain_mf.py``: the alternating
    fit of ``ml20m_mf.cd_fit``'s traffic, number by number. The refit is cut
    off at its cap, so this holds only while the reference's line search is
    the program's (PERF.md section 7, Since PR 33 (3))."""
    from benchmark.tests import readings

    files = readings.load("ml20m_mf.cd_fit")
    (line,) = readings.one_seed(
        files, seed=2147483777, rows=8192, control=False, fault=False,
        force_tiled=False)
    numbers = line["numbers"]
    assert numbers["coef_rel.user-x-movie"] < 4e-3, numbers
    assert numbers["val_score_rel"] < 4e-3, numbers
    assert numbers["coef_rel.fixed"] < 2e-2, numbers
    assert numbers["step_loss_rel"] < 1e-4, numbers
    assert numbers["first_loss_rel"] < 3e-6, numbers
    program_its, reference_its = line["solver_iterations"]
    assert len(program_its) == len(reference_its) == 4
