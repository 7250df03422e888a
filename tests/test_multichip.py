"""Tier-1 multichip CI (ISSUE 6): sharded-vs-single-device parity on a
forced 8-device CPU mesh, in tests/ rather than only the MULTICHIP dryrun.

Acceptance pinned here:
  - the GSPMD FE solve (flat design committed P("batch"), one jit) and
    the entity-sharded GLMix CD/streaming loop reach the same final loss
    as the single-device run to 1e-6 (relative);
  - ``comms.*`` collective estimates are recorded for every multi-device
    solve;
  - repeated solves with refreshed per-row arrays do NOT grow the
    compiled-signature set (no recompile storms).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.ops.sparse import SparseBatch
from photon_ml_tpu.optim import (
    OptimizerConfig,
    OptimizerType,
    RegularizationContext,
    RegularizationType,
    solve,
)
from photon_ml_tpu.parallel import gspmd_solve, make_mesh, place_batch
from photon_ml_tpu.telemetry import metrics as telemetry_metrics
from photon_ml_tpu.telemetry import xla as telemetry_xla

_OPT = OptimizerConfig(
    optimizer_type=OptimizerType.LBFGS,
    max_iterations=80,
    tolerance=1e-10,
    regularization=RegularizationContext(RegularizationType.L2),
    regularization_weight=0.7,
)


def _fe_problem(rng, n=480, d=24):
    X = rng.normal(size=(n, d)) * (rng.random((n, d)) < 0.4)
    w_true = rng.normal(size=d)
    y = (rng.random(n) < 1 / (1 + np.exp(-(X @ w_true)))).astype(float)
    wt = rng.random(n) + 0.5
    return SparseBatch.from_dense(X, y, weights=wt)


@pytest.mark.multichip
def test_gspmd_fe_solve_single_device_parity(rng, multichip):
    batch = _fe_problem(rng)
    mesh = make_mesh({"batch": 8})
    placed = place_batch(batch, mesh)
    w0 = jnp.zeros(batch.num_features, jnp.float32)

    res_single = solve("logistic", batch, _OPT, w0)
    comms_before = telemetry_metrics.peek_counter("comms.bytes_total") or 0.0
    res_mesh = gspmd_solve("logistic", placed, _OPT, w0, mesh)

    v_s, v_m = float(res_single.value), float(res_mesh.value)
    # acceptance: same final loss to 1e-6 (relative)
    assert abs(v_m - v_s) <= 1e-6 * max(1.0, abs(v_s)), (v_m, v_s)
    np.testing.assert_allclose(res_mesh.w, res_single.w, rtol=5e-3, atol=5e-3)
    # the GSPMD outputs are pinned fully-replicated
    assert res_mesh.w.sharding.is_fully_replicated
    # comms recorded for the multi-device solve
    comms_after = telemetry_metrics.peek_counter("comms.bytes_total") or 0.0
    assert comms_after > comms_before
    assert (telemetry_metrics.peek_counter("comms.gspmd_solve.bytes") or 0) > 0


@pytest.mark.multichip
def test_gspmd_fe_solve_no_recompile_storm(rng, multichip):
    """Refreshed per-row arrays (the CD residual-update pattern) must hit
    the SAME compiled program — signature growth is the storm signal."""
    batch = _fe_problem(rng, n=320)
    mesh = make_mesh({"batch": 8})
    placed = place_batch(batch, mesh)
    w0 = jnp.zeros(batch.num_features, jnp.float32)
    gspmd_solve("logistic", placed, _OPT, w0, mesh)
    before = len(telemetry_xla.XLA_REGISTRY.signature_history("gspmd_solve"))
    import dataclasses

    from photon_ml_tpu.parallel.sharding import batch_sharding

    for salt in (1, 2, 3):
        offs = jax.device_put(
            jnp.full((placed.num_rows,), salt * 1e-3, jnp.float32),
            batch_sharding(mesh),
        )
        refreshed = dataclasses.replace(placed, offsets=offs)
        gspmd_solve("logistic", refreshed, _OPT, w0, mesh)
    after = len(telemetry_xla.XLA_REGISTRY.signature_history("gspmd_solve"))
    assert after == before, "per-update offsets changed the trace signature"


@pytest.mark.multichip
def test_streaming_cd_sharded_parity(rng, multichip):
    """Entity-sharded streaming CD loop == single-device loop: same final
    loss to 1e-6, same coefficients, comms recorded."""
    from photon_ml_tpu.game.streaming import (
        ShardedCoefficientTable,
        StreamingRandomEffectTrainer,
    )
    from photon_ml_tpu.ops.dense import DenseBatch

    n_ent, rows, k = 32, 6, 3
    Xe = rng.normal(size=(n_ent, rows, k)).astype(np.float32)
    We = rng.normal(size=(n_ent, k))
    ye = (
        rng.random((n_ent, rows))
        < 1 / (1 + np.exp(-np.einsum("erk,ek->er", Xe, We)))
    ).astype(np.float32)

    def run(mesh):
        table = ShardedCoefficientTable(n_ent, k, mesh=mesh)
        trainer = StreamingRandomEffectTrainer("logistic", _OPT, mesh=mesh)
        half = n_ent // 2

        def chunk(lo, hi):
            return DenseBatch(
                x=Xe[lo:hi], labels=ye[lo:hi],
                offsets=np.zeros((hi - lo, rows), np.float32),
                weights=np.ones((hi - lo, rows), np.float32),
            )

        stats = trainer.train(
            table, [(0, chunk(0, half)), (half, chunk(half, n_ent))]
        )
        return table, stats

    t_single, s_single = run(None)
    comms_before = telemetry_metrics.peek_counter("comms.bytes_total") or 0.0
    t_mesh, s_mesh = run(make_mesh({"model": 8}))

    assert t_mesh.sharding is not None
    # per-device residency: every device holds exactly 1/8 of the table
    shard_bytes = {
        s.data.nbytes for s in t_mesh.coefficients.addressable_shards
    }
    assert shard_bytes == {t_mesh.nbytes // 8}
    # acceptance: same final loss to 1e-6 (relative; sum over entities)
    lhs, rhs = s_mesh.total_final_value, s_single.total_final_value
    assert abs(lhs - rhs) <= 1e-6 * max(1.0, abs(rhs)), (lhs, rhs)
    np.testing.assert_allclose(
        np.asarray(t_mesh.coefficients),
        np.asarray(t_single.coefficients),
        rtol=2e-4, atol=2e-4,
    )
    comms_after = telemetry_metrics.peek_counter("comms.bytes_total") or 0.0
    assert comms_after > comms_before
    assert (
        telemetry_metrics.peek_counter("comms.streaming_chunk_solve.bytes")
        or 0
    ) > 0


@pytest.mark.multichip
@pytest.mark.slow
def test_estimator_2d_batch_model_mesh_parity(rng, multichip):
    """GameEstimator.fit over a named 2-D (batch, model) mesh reproduces
    the single-device GLMix fit — FE rows shard over 'batch', RE entity
    state over 'model', one physical mesh."""
    from photon_ml_tpu.game import (
        FixedEffectConfig,
        GameConfig,
        GameEstimator,
        RandomEffectConfig,
        build_game_dataset,
    )

    n, n_users = 240, 11
    Xg = rng.normal(size=(n, 6)) * (rng.random((n, 6)) < 0.6)
    Xg[:, 0] = 1.0
    Xu = rng.normal(size=(n, 3))
    users = rng.integers(0, n_users, size=n)
    wg = rng.normal(size=6)
    wu = rng.normal(size=(n_users, 3))
    margin = Xg @ wg + np.einsum("ij,ij->i", Xu, wu[users])
    y = (rng.random(n) < 1 / (1 + np.exp(-margin))).astype(float)
    gds = build_game_dataset(
        response=y,
        feature_shards={
            "global": SparseBatch.from_dense(Xg, y),
            "user": SparseBatch.from_dense(Xu, y),
        },
        id_columns={"userId": users},
    )
    config = GameConfig(
        task="logistic",
        coordinates={
            "fixed": FixedEffectConfig(shard_name="global", optimizer=_OPT),
            "per-user": RandomEffectConfig(
                shard_name="user", id_name="userId", optimizer=_OPT
            ),
        },
        num_iterations=2,
    )
    mesh = make_mesh({"batch": 4, "model": 2})
    r_mesh = GameEstimator(config).fit(gds, mesh=mesh)
    r_single = GameEstimator(config).fit(gds)
    np.testing.assert_allclose(
        r_mesh.model.models["fixed"].coefficients,
        r_single.model.models["fixed"].coefficients,
        rtol=5e-3, atol=5e-3,
    )
    for bm, bs in zip(
        r_mesh.model.models["per-user"].buckets,
        r_single.model.models["per-user"].buckets,
    ):
        np.testing.assert_allclose(
            bm.coefficients, bs.coefficients, rtol=5e-3, atol=5e-3
        )


@pytest.mark.multichip
def test_gspmd_solve_rejects_entity_only_mesh(rng, multichip):
    batch = _fe_problem(rng, n=64)
    mesh = make_mesh({"model": 8})
    with pytest.raises(ValueError, match="batch/data axis"):
        gspmd_solve(
            "logistic", batch, _OPT,
            jnp.zeros(batch.num_features, jnp.float32), mesh,
        )


@pytest.mark.multichip
def test_estimator_rejects_mesh_with_unknown_axes(rng, multichip):
    """A provisioned mesh whose axes nothing recognizes must fail loudly,
    not silently train single-device."""
    from photon_ml_tpu.game import (
        FixedEffectConfig,
        GameConfig,
        GameEstimator,
        build_game_dataset,
    )

    X = rng.normal(size=(40, 4))
    y = (rng.random(40) > 0.5).astype(float)
    gds = build_game_dataset(
        response=y, feature_shards={"global": SparseBatch.from_dense(X, y)}
    )
    config = GameConfig(
        task="logistic",
        coordinates={"fixed": FixedEffectConfig(shard_name="global",
                                                optimizer=_OPT)},
        num_iterations=1,
    )
    mesh = make_mesh({"x": 4, "y": 2})
    with pytest.raises(ValueError, match="neither a batch/data"):
        GameEstimator(config).fit(gds, mesh=mesh)


# ---------------------------------------------------------------------------
# ISSUE 16: per-device HBM high-watermarks across a multichip fleet
# ---------------------------------------------------------------------------


class _StatsDevice:
    def __init__(self, did, in_use, limit=16 * 2**30):
        self.id = did
        self.stats = {"bytes_in_use": in_use, "bytes_limit": limit}

    def memory_stats(self):
        return self.stats


def test_watermark_spread_across_eight_devices():
    """Per-device HBM peaks are max-tracked independently per device and
    per phase; the spread (max-min of current usage) exposes the skewed
    member — exactly the imbalance a fleet report needs to attribute."""
    from photon_ml_tpu import telemetry
    from photon_ml_tpu.telemetry import memory as tmem

    devices = [_StatsDevice(i, (i + 1) * 2**20) for i in range(8)]
    tmem.record_device_watermarks(devices, phase="fit")
    # device 3 spikes during scoring, everyone else dips
    for d in devices:
        d.stats["bytes_in_use"] = 2**20
    devices[3].stats["bytes_in_use"] = 12 * 2**20
    tmem.record_device_watermarks(devices, phase="score")

    g = telemetry.snapshot()["gauges"]
    # global per-device peaks hold the max across BOTH phases
    assert g["memory.device.3.peak_bytes"] == 12 * 2**20
    assert g["memory.device.7.peak_bytes"] == 8 * 2**20
    # per-phase peaks stay attributed to their phase
    assert g["memory.phase.fit.device.3.peak_bytes"] == 4 * 2**20
    assert g["memory.phase.score.device.3.peak_bytes"] == 12 * 2**20
    assert g["memory.phase.score.device.0.peak_bytes"] == 2**20
    # the live spread names the imbalance: 12 MiB vs 1 MiB
    assert tmem.device_spread_bytes() == 11 * 2**20
