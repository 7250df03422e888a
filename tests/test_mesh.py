"""parallel/mesh.py + parallel/sharding.py unit coverage: the
``shard_map_compat`` spelling, the sharding primitives, and the tiled
layout's kernels running per shard under ``jax.shard_map`` (the Mosaic
compiler refuses GSPMD partitioning, so ``place_batch`` tags the batch
with its mesh) against the same kernels on one device.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from photon_ml_tpu.parallel import sharding as psharding
from photon_ml_tpu.parallel.mesh import make_mesh, shard_map_compat


@pytest.fixture
def mesh(multichip):
    return make_mesh({"data": 8})


# ---------------------------------------------------------------------------
# shard_map_compat
# ---------------------------------------------------------------------------


def test_compat_executes_a_psum(mesh):
    x = jnp.arange(8.0)

    def local_sum(block):
        return jax.lax.psum(jnp.sum(block), "data")

    f = shard_map_compat(local_sum, mesh, in_specs=P("data"), out_specs=P())
    assert float(jax.jit(f)(x)) == float(np.sum(np.arange(8.0)))


def _call_through(mesh, check=False):
    return shard_map_compat(
        lambda x: x, mesh, in_specs=P("data"), out_specs=P("data"),
        check=check,
    )


def test_compat_passes_check_as_check_vma(monkeypatch, mesh):
    seen = {}

    def fake_shard_map(f, mesh, in_specs, out_specs, **kwargs):
        seen.update(kwargs)
        return lambda *a: "top-level"

    monkeypatch.setattr(jax, "shard_map", fake_shard_map, raising=False)
    assert _call_through(mesh, check=True)() == "top-level"
    assert seen == {"check_vma": True}


# ---------------------------------------------------------------------------
# sharding primitives
# ---------------------------------------------------------------------------


def test_axis_resolution_named_and_legacy(multichip):
    named = make_mesh({"batch": 4, "model": 2})
    assert psharding.data_axis(named) == "batch"
    assert psharding.model_axis(named) == "model"
    legacy_data = make_mesh({"data": 8})
    assert psharding.data_axis(legacy_data) == "data"
    assert psharding.model_axis(legacy_data) is None
    legacy_entity = make_mesh({"entity": 8})
    assert psharding.data_axis(legacy_entity) is None
    assert psharding.model_axis(legacy_entity) == "entity"


def test_sharding_builders_reject_missing_axes(multichip):
    entity_only = make_mesh({"entity": 8})
    with pytest.raises(ValueError, match="batch/data axis"):
        psharding.batch_sharding(entity_only)
    batch_only = make_mesh({"batch": 8})
    with pytest.raises(ValueError, match="model/entity axis"):
        psharding.entity_sharding(batch_only)


def test_place_entities_shards_leading_axis(multichip):
    mesh = make_mesh({"model": 8})
    table = np.arange(16 * 4, dtype=np.float32).reshape(16, 4)
    placed = psharding.place_entities(table, mesh)
    assert placed.sharding.spec == P("model")
    sizes = {s.data.shape for s in placed.addressable_shards}
    assert sizes == {(2, 4)}
    np.testing.assert_array_equal(np.asarray(placed), table)


def test_place_batch_pads_and_shards_sparse(rng, multichip):
    from photon_ml_tpu.ops.sparse import SparseBatch

    X = rng.normal(size=(13, 5)) * (rng.random((13, 5)) < 0.7)
    y = (rng.random(13) > 0.5).astype(float)
    batch = SparseBatch.from_dense(X, y)
    mesh = make_mesh({"batch": 8})
    placed = psharding.place_batch(batch, mesh)
    assert placed.num_rows % 8 == 0
    assert placed.nnz % 8 == 0
    # padded rows are inert: weights 0 beyond the original row count
    w = np.asarray(placed.weights)
    assert np.all(w[batch.num_rows:] == 0)
    # objective parity: padding must not change the value/grad
    from photon_ml_tpu.ops.objective import make_objective

    obj = make_objective("logistic", l2_weight=0.3)
    wvec = jnp.asarray(rng.normal(size=batch.num_features) * 0.1, jnp.float32)
    v0, g0 = obj.value_and_grad(wvec, batch)
    v1, g1 = obj.value_and_grad(wvec, placed)
    np.testing.assert_allclose(v1, v0, rtol=1e-5)
    np.testing.assert_allclose(g1, g0, rtol=1e-4, atol=1e-5)


def test_place_batch_pads_tiles(rng, multichip):
    from photon_ml_tpu.ops.tiled import TiledBatch

    n, d = 300, 40
    X = rng.normal(size=(n, d)) * (rng.random((n, d)) < 0.4)
    y = (rng.random(n) > 0.5).astype(float)
    nz = np.nonzero(X)
    tb = TiledBatch.from_coo(
        values=X[nz], rows=nz[0], cols=nz[1], labels=y, num_features=d
    )
    mesh = make_mesh({"batch": 8})
    placed = psharding.place_batch(tb, mesh)
    assert placed.num_tiles % 8 == 0
    wvec = jnp.asarray(rng.normal(size=d) * 0.1, jnp.float32)
    z_ref = np.asarray(tb.dot_rows(wvec))
    z = np.asarray(placed.dot_rows(wvec))
    np.testing.assert_allclose(z[: len(z_ref)], z_ref, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# tiled kernels per shard (shard_map over the batch axis) vs one device
# ---------------------------------------------------------------------------


@pytest.fixture(params=["strided", "sorted"])
def tiled_pair(request, rng, multichip, monkeypatch):
    """(one-device TiledBatch, the same design placed over batch=4) with
    313 rows: 3 tiles pad to 4, so one shard is all padding. Under both row
    assignments: the mesh cuts tiles, never a tile's slots, so slot
    ``k*128 + r`` stays row r's on every shard."""
    from photon_ml_tpu.ops import tiled
    from photon_ml_tpu.ops.tiled import TiledBatch

    strided = request.param == "strided"
    monkeypatch.setattr(tiled, "strided_is_cheaper", lambda *_: strided)
    n, d = 313, 150
    X = rng.normal(size=(n, d)) * (rng.random((n, d)) < 0.2)
    y = (rng.random(n) > 0.5).astype(float)
    tb = TiledBatch.from_dense(
        X, y, offsets=rng.normal(size=n) * 0.1, weights=rng.random(n) + 0.5
    )
    mesh = make_mesh({"batch": 4, "model": 2})
    placed = psharding.place_batch(tb, mesh)
    assert placed.shard == (mesh, "batch")
    assert placed.vals.sharding.spec == P("batch")
    assert tb.strided == placed.strided == strided
    assert (placed.rlo is None) == strided
    return tb, placed


def _rows(placed, per_row):
    pad = placed.num_rows - per_row.shape[0]
    return jnp.pad(per_row, (0, pad))


_KERNEL_CASES = {
    "margins": lambda b, w, v, r: b.margins(w, 0.3),
    "dot_rows": lambda b, w, v, r: b.dot_rows(w),
    "margins_pair": lambda b, w, v, r: b.margins_pair(w, 0.3, v, -0.2),
    "scatter": lambda b, w, v, r: b.scatter_features(_rows(b, r)),
    "scatter_sq": lambda b, w, v, r: b.scatter_features_sq(_rows(b, r)),
    "value_grad": lambda b, w, v, r: b.fused_value_grad(w, 0.3, "logistic"),
    "hv": lambda b, w, v, r: b.fused_hessian_vector(
        w, 0.3, v, -0.2, "logistic"),
    "hv_at": lambda b, w, v, r: b.fused_hv_at(_rows(b, jnp.abs(r)), v, -0.2),
}


@pytest.mark.parametrize("case", sorted(_KERNEL_CASES))
def test_sharded_tiled_kernel_matches_one_device(case, tiled_pair, rng):
    tb, placed = tiled_pair
    d = tb.num_features
    w = jnp.asarray(rng.normal(size=d) * 0.1, jnp.float32)
    v = jnp.asarray(rng.normal(size=d) * 0.1, jnp.float32)
    r = jnp.asarray(rng.normal(size=tb.num_rows), jnp.float32)
    fn = _KERNEL_CASES[case]
    ref = jax.tree.leaves(fn(tb, w, v, r))
    got = jax.tree.leaves(jax.jit(lambda b: fn(b, w, v, r))(placed))
    assert len(ref) == len(got)
    for a, b in zip(ref, got):
        a, b = np.asarray(a), np.asarray(b)
        if b.shape != a.shape:  # per-row outputs carry the padded tiles
            assert np.all(b[a.shape[0]:] == b[a.shape[0]]), case
            b = b[: a.shape[0]]
        np.testing.assert_allclose(b, a, rtol=2e-5, atol=2e-5)


def test_a_shard_takes_the_tiles_a_step_of_its_own_count(rng, multichip,
                                                         monkeypatch):
    """60 tiles: one device runs 20 a grid step, a shard of 15 (batch=4)
    runs 15. The per-row passes equal the one device's to the last bit;
    the accumulators are four partial sums added, so to float32 rounding."""
    from photon_ml_tpu.ops import tiled
    from photon_ml_tpu.ops.tiled import TiledBatch

    steps = []
    for name in ("_margins_call", "_scatter_call"):
        real = getattr(tiled, name)
        monkeypatch.setattr(
            tiled, name,
            lambda T, G, *a, real=real, **k: steps.append((T, G)) or real(
                T, G, *a, **k))
    n, d = 60 * 128, 150
    rows = np.repeat(np.arange(n), 2)
    cols = rng.integers(0, d, size=len(rows))
    tb = TiledBatch.from_coo(
        rng.normal(size=len(rows)), rows, cols,
        (rng.random(n) > 0.5).astype(float), d)
    placed = psharding.place_batch(tb, make_mesh({"batch": 4, "model": 2}))
    assert tb.tiles_a_step() == 20 and placed.tiles_a_step(15) == 15
    w = jnp.asarray(rng.normal(size=d), jnp.float32)
    r = jnp.asarray(rng.normal(size=n), jnp.float32)
    z1, g1 = tb.margins(w, 0.3), tb.scatter_features(r)
    assert steps == [(60, 20), (60, 20)]
    z4, g4 = jax.jit(
        lambda b: (b.margins(w, 0.3), b.scatter_features(r)))(placed)
    assert steps[2:] == [(15, 15), (15, 15)]
    np.testing.assert_array_equal(np.asarray(z4), np.asarray(z1))
    np.testing.assert_allclose(
        np.asarray(g4), np.asarray(g1), rtol=2e-5, atol=2e-5)


def test_sharded_tiled_solve_matches_one_device(tiled_pair):
    """The whole LBFGS while-loop over the sharded tiles (gspmd_solve) lands
    on the one-device optimum, replicated over the mesh."""
    from photon_ml_tpu.optim import (
        OptimizerConfig, RegularizationContext, RegularizationType,
    )
    from photon_ml_tpu.optim.adapter import glm_adapter
    from photon_ml_tpu.optim.factory import build_objective, dispatch_solve
    from photon_ml_tpu.parallel.distributed import gspmd_solve

    tb, placed = tiled_pair
    cfg = OptimizerConfig(
        max_iterations=30, tolerance=1e-8,
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )
    w0 = jnp.zeros((tb.num_features,), jnp.float32)
    obj = build_objective("logistic", cfg)
    ref = jax.jit(
        lambda b: dispatch_solve(glm_adapter(obj, b), w0, cfg, jnp.float32(0))
    )(tb)
    mesh = placed.shard[0]
    res = gspmd_solve("logistic", placed, cfg, w0, mesh)
    assert res.w.sharding.is_fully_replicated
    # f32 sums in another order: same optimum value, iterates a hair apart
    np.testing.assert_allclose(float(res.value), float(ref.value), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(res.w), np.asarray(ref.w), rtol=1e-3, atol=2e-3)


def test_pad_count():
    assert psharding.pad_count(16, 8) == 16
    assert psharding.pad_count(17, 8) == 24
    assert psharding.pad_count(0, 8) == 0


def test_make_mesh_named_axes(multichip):
    mesh = make_mesh({"batch": 2, "model": 4})
    assert dict(mesh.shape) == {"batch": 2, "model": 4}
    assert isinstance(mesh, Mesh)
