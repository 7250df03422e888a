"""Deterministic fault injection: plan semantics, typed errors, env
transport, and the registry catalog the crash matrix + lint L016 key on.

In-process injection tests live here (nan-poisoned solves, flaky-read
retries at each subsystem's seam); the true-crash (`exit`) matrix runs
through tools/chaos.py in tests/test_chaos.py.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from photon_ml_tpu import faults


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    """Every test leaves the process unarmed — an armed plan leaking into
    another test would inject faults nobody asked for."""
    yield
    faults.clear_plan()


# ---------------------------------------------------------------------------
# the catalog: every production seam, enumerable and stable
# ---------------------------------------------------------------------------

#: Every fault point the package registers, by owning subsystem. This
#: list is load-bearing twice: the test below fails when a seam appears
#: or vanishes without this catalog (and the README) being updated, and
#: static-analysis rule L016 keys on these literals to prove each point
#: is named by at least one test.
EXPECTED_POINTS = {
    # checkpoint atomic-write protocol (write-path: the crash matrix set)
    "checkpoint.save.before_tmp",
    "checkpoint.save.before_manifest",
    "checkpoint.save.before_rename",
    "checkpoint.save.after_rename",
    "checkpoint.manifest.read",
    # training loops
    "cd.step.boundary",
    "guard.solve_health",
    "streaming.solve.result",
    "streaming.chunk.boundary",
    # ingest pipeline
    "ingest.decode.read",
    "ingest.ring.acquire",
    "ingest.upload.chunk",
    # serving
    "serving.dispatch",
    "serving.async_dispatch",
    "serving.registry.poll",
    "serving.registry.load",
    "serving.nearline_event",
    "serving.nearline_apply",
    # distributed fleet seams (the distributed crash matrix set)
    "multihost.init",
    "fleet.heartbeat",
    "checkpoint.peer_manifest",
    "parallel.collective.entry",
    # serving-fleet seams (distributed, but they fire in router/member
    # processes — tools/chaos.py --serving-fleet owns their matrix)
    "serving.member_load",
    "serving.route_fanout",
    "serving.resize_swap",
    # fleet observability (supervisor-side: neither matrix — status is
    # observability, never control; covered by tests/test_fleet_status)
    "fleet.status_write",
    # incremental warm-start retrains (plain points — the warm restore
    # and delta scan are read-only, and the publish rides the registry's
    # tmp-then-rename; the incremental crash row in
    # tests/test_incremental.py kills at incremental.publish and proves
    # the base checkpoint and registry stay intact)
    "incremental.warm_restore",
    "incremental.delta_scan",
    "incremental.publish",
    # request-scoped tracing (plain point — the dump itself rides
    # utils.atomic tmp-then-rename; tools/chaos.py --serving-fleet row
    # flight_dump_kill kills mid-dump and proves fleet discovery never
    # adopts the torn .tmp; ring/parse coverage in tests/test_requests)
    "telemetry.flight_dump",
    # freshness-conductor daemon cycle seams (plain points — every write
    # in a cycle rides the registry's tmp-then-rename or lands in a
    # fresh escalation generation dir; tools/chaos.py --pipeline
    # hard-kills the cli pipeline daemon at each of pipeline.cycle_start,
    # pipeline.reconcile, and pipeline.escalate and proves the base
    # checkpoint stays byte-identical and the registry partial-free)
    "pipeline.cycle_start",
    "pipeline.reconcile",
    "pipeline.escalate",
    # quality observability seams (plain points — the publish gate fires
    # before ANY registry write so a kill leaves the registry untouched,
    # and a drift-flush failure drops one snapshot section and nothing
    # else; both armed in tests/test_quality.py and the chaos --quality
    # row)
    "quality.publish_gate",
    "quality.drift_flush",
}

WRITE_PATH_POINTS = [
    "checkpoint.save.after_rename",
    "checkpoint.save.before_manifest",
    "checkpoint.save.before_rename",
    "checkpoint.save.before_tmp",
]

#: the multi-process seams (sorted). tools/chaos.py --fleet runs the
#: training-fleet subset (one 2-process kill-one-member row per seam);
#: the serving.* entries fire in serving router/member processes and are
#: exercised by tools/chaos.py --serving-fleet instead
DISTRIBUTED_POINTS = [
    "checkpoint.peer_manifest",
    "fleet.heartbeat",
    "multihost.init",
    "parallel.collective.entry",
    "serving.member_load",
    "serving.resize_swap",
    "serving.route_fanout",
]


def test_registry_catalog_is_complete_and_stable():
    # import every module that owns a seam: registration is import-time
    import photon_ml_tpu.game.checkpoint  # noqa: F401
    import photon_ml_tpu.game.coordinate_descent  # noqa: F401
    import photon_ml_tpu.game.streaming  # noqa: F401
    import photon_ml_tpu.ingest.buffers  # noqa: F401
    import photon_ml_tpu.ingest.decode  # noqa: F401
    import photon_ml_tpu.ingest.pipeline  # noqa: F401
    import photon_ml_tpu.serving.batcher  # noqa: F401
    import photon_ml_tpu.serving.nearline  # noqa: F401
    import photon_ml_tpu.serving.registry  # noqa: F401
    import photon_ml_tpu.serving.router  # noqa: F401
    import photon_ml_tpu.serving.shard  # noqa: F401
    import photon_ml_tpu.parallel.distributed  # noqa: F401
    import photon_ml_tpu.parallel.fleet_status  # noqa: F401
    import photon_ml_tpu.parallel.multihost  # noqa: F401
    import photon_ml_tpu.incremental  # noqa: F401
    import photon_ml_tpu.pipeline  # noqa: F401
    import photon_ml_tpu.telemetry.requests  # noqa: F401
    import photon_ml_tpu.quality.drift  # noqa: F401
    import photon_ml_tpu.quality.gate  # noqa: F401

    registered = faults.registered_points()
    assert set(registered) == EXPECTED_POINTS
    assert faults.write_path_points() == WRITE_PATH_POINTS
    assert faults.distributed_points() == DISTRIBUTED_POINTS
    for name, info in registered.items():
        assert info.name == name
        assert info.description  # a seam nobody can describe is a smell


def test_reregistration_is_idempotent_but_write_path_conflicts_raise():
    import photon_ml_tpu.game.checkpoint  # noqa: F401

    assert faults.register_point(
        "checkpoint.manifest.read"
    ) == "checkpoint.manifest.read"
    with pytest.raises(ValueError, match="write_path"):
        faults.register_point("checkpoint.manifest.read", write_path=True)
    with pytest.raises(ValueError, match="distributed"):
        faults.register_point("checkpoint.manifest.read", distributed=True)


# ---------------------------------------------------------------------------
# plan semantics
# ---------------------------------------------------------------------------


def test_nth_hit_fires_exactly_once_on_the_nth_call():
    plan = faults.FaultPlan(
        [faults.FaultRule("t.nth", nth=3)]
    )
    faults.install_plan(plan)
    faults.fault_point("t.nth")
    faults.fault_point("t.nth")
    with pytest.raises(faults.InjectedFault, match="t.nth"):
        faults.fault_point("t.nth")
    faults.fault_point("t.nth")  # 4th hit: silent again
    assert plan.hit_counts() == {"t.nth": 4}


def test_io_action_is_an_oserror():
    faults.install_plan(
        faults.FaultPlan([faults.FaultRule("t.io", action="io")])
    )
    with pytest.raises(OSError) as ei:
        faults.fault_point("t.io")
    assert isinstance(ei.value, faults.InjectedFault)
    assert ei.value.point == "t.io"


def test_probability_draws_are_seed_deterministic():
    def pattern(seed):
        plan = faults.FaultPlan(
            [faults.FaultRule("t.p", action="raise", probability=0.5)],
            seed=seed,
        )
        out = []
        for _ in range(64):
            out.append(plan.hit("t.p") is not None)
        return out

    a, b = pattern(7), pattern(7)
    assert a == b  # same seed, same schedule
    assert pattern(8) != a  # different seed, different schedule
    assert any(a) and not all(a)


def test_plan_validation_rejects_malformed_rules():
    with pytest.raises(faults.FaultPlanError, match="unknown fault action"):
        faults.FaultRule("x", action="explode")
    with pytest.raises(faults.FaultPlanError, match="mutually exclusive"):
        faults.FaultRule("x", nth=1, probability=0.5)
    with pytest.raises(faults.FaultPlanError, match="nth must be >= 1"):
        faults.FaultRule("x", nth=0)
    with pytest.raises(faults.FaultPlanError, match="probability"):
        faults.FaultRule("x", probability=1.5)
    with pytest.raises(faults.FaultPlanError, match="duplicate"):
        faults.FaultPlan([faults.FaultRule("x"), faults.FaultRule("x")])
    with pytest.raises(faults.FaultPlanError, match="malformed"):
        faults.FaultPlan.from_json("{nope")
    with pytest.raises(faults.FaultPlanError, match="unknown rule keys"):
        faults.FaultPlan.from_json(
            {"rules": [{"point": "x", "severity": "bad"}]}
        )


def test_plan_roundtrips_through_json_and_names_unregistered_points():
    plan = faults.FaultPlan(
        [
            faults.FaultRule("checkpoint.manifest.read", action="io",
                             nth=2),
            faults.FaultRule("no.such.point", action="exit", exit_code=99),
        ],
        seed=5,
    )
    doc = plan.to_json()
    again = faults.FaultPlan.from_json(json.dumps(doc))
    assert again.to_json() == doc
    assert again.seed == 5
    import photon_ml_tpu.game.checkpoint  # noqa: F401 (registers)

    assert again.unregistered_points() == ["no.such.point"]


def test_env_transport_arms_without_code_cooperation(monkeypatch, tmp_path):
    doc = {"rules": [{"point": "t.env", "action": "raise"}]}
    monkeypatch.setenv(faults.ENV_VAR, json.dumps(doc))
    plan = faults.install_from_env()
    assert plan is not None and plan.points == ["t.env"]
    assert faults.warn_if_armed() is True
    with pytest.raises(faults.InjectedFault):
        faults.fault_point("t.env")
    # @file indirection for plans too big for an env var
    p = tmp_path / "plan.json"
    p.write_text(json.dumps(doc))
    monkeypatch.setenv(faults.ENV_VAR, f"@{p}")
    assert faults.install_from_env().points == ["t.env"]
    monkeypatch.delenv(faults.ENV_VAR)
    assert faults.install_from_env() is None
    assert faults.warn_if_armed() is False


def test_unarmed_fault_point_is_a_noop_and_counts_nothing():
    from photon_ml_tpu import telemetry

    faults.clear_plan()
    faults.fault_point("t.anything")
    assert telemetry.snapshot()["counters"].get("faults.injected") is None


def test_injections_are_counted_per_point():
    from photon_ml_tpu import telemetry

    telemetry.reset()
    try:
        faults.install_plan(
            faults.FaultPlan([faults.FaultRule("t.counted")])
        )
        with pytest.raises(faults.InjectedFault):
            faults.fault_point("t.counted")
        counters = telemetry.snapshot()["counters"]
        assert counters["faults.injected"] == 1
        assert counters["faults.injected.t.counted"] == 1
    finally:
        telemetry.reset()


# ---------------------------------------------------------------------------
# value-corruption seams
# ---------------------------------------------------------------------------


def test_corrupt_array_poisons_first_element_numpy_and_jax():
    import jax.numpy as jnp

    faults.install_plan(
        faults.FaultPlan(
            [faults.FaultRule("t.nan", action="nan", nth=1)]
        )
    )
    host = np.ones((2, 3))
    out = faults.corrupt_array("t.nan", host)
    assert np.isnan(out[0, 0]) and not np.isnan(host[0, 0])  # copy, not mutate
    # second hit: untouched pass-through
    assert faults.corrupt_array("t.nan", host) is host

    faults.install_plan(
        faults.FaultPlan([faults.FaultRule("t.nan2", action="nan")])
    )
    dev = jnp.ones((4,))
    poisoned = faults.corrupt_array("t.nan2", dev)
    assert bool(jnp.isnan(poisoned[0]))


def test_corrupt_health_forces_diverged_verdict():
    import jax.numpy as jnp

    faults.install_plan(
        faults.FaultPlan(
            [faults.FaultRule("guard.solve_health", action="nan")]
        )
    )
    assert not bool(
        faults.corrupt_health("guard.solve_health", jnp.bool_(True))
    )
    # unarmed point: verdict passes through
    assert bool(faults.corrupt_health("t.other", jnp.bool_(True)))


def test_corrupt_sites_degrade_non_nan_actions_to_their_trigger():
    faults.install_plan(
        faults.FaultPlan([faults.FaultRule("t.deg", action="io")])
    )
    with pytest.raises(faults.InjectedIOError):
        faults.corrupt_array("t.deg", np.ones(3))


# ---------------------------------------------------------------------------
# in-process seam integration: the nan seam drives the streaming guard
# ---------------------------------------------------------------------------


def test_nan_injection_at_solve_result_drives_guard_rollback(rng):
    """Arming `streaming.solve.result` with a nan rule makes a HEALTHY
    chunk diverge on demand: the guard retries damped, rolls back, and
    the run survives — divergence recovery without crafting NaN data."""
    import jax.numpy as jnp  # noqa: F401

    from photon_ml_tpu import telemetry
    from photon_ml_tpu.game.streaming import (
        ShardedCoefficientTable,
        StreamingRandomEffectTrainer,
    )
    from photon_ml_tpu.ops.dense import DenseBatch
    from photon_ml_tpu.optim import (
        OptimizerConfig,
        RegularizationContext,
        RegularizationType,
    )
    from photon_ml_tpu.optim.guard import GuardSpec

    n_ent, rows, k = 8, 6, 3
    X = rng.normal(size=(n_ent, rows, k))
    y = (rng.random((n_ent, rows)) < 0.5).astype(float)

    def chunk(lo, hi):
        return DenseBatch(
            x=X[lo:hi].astype(np.float32),
            labels=y[lo:hi].astype(np.float32),
            offsets=np.zeros((hi - lo, rows), np.float32),
            weights=np.ones((hi - lo, rows), np.float32),
        )

    cfg = OptimizerConfig(
        max_iterations=40,
        tolerance=1e-8,
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=0.3,
    )
    telemetry.reset()
    try:
        # chunk 0's solve result is poisoned on EVERY attempt (nth=1 and
        # nth=2 cover the first solve + its damped retry), so the guard
        # must roll it back; chunk 1 is untouched and trains
        faults.install_plan(
            faults.FaultPlan(
                [faults.FaultRule("streaming.solve.result",
                                  action="nan", probability=1.0)],
                seed=1,
            )
        )
        table = ShardedCoefficientTable(n_ent, k)
        trainer = StreamingRandomEffectTrainer(
            "logistic", cfg, guard=GuardSpec(max_retries=1)
        )
        trainer.train(table, [(0, chunk(0, 4))])
        faults.clear_plan()
        trainer.train(table, [(4, chunk(4, n_ent))], start_chunk=0)
        got = table.to_numpy()
        np.testing.assert_array_equal(got[:4], 0.0)  # rolled back
        assert np.any(np.abs(got[4:]) > 0)  # healthy rows trained
        counters = telemetry.snapshot()["counters"]
        assert counters["solves.rolled_back"] == 1
        assert counters["faults.injected"] >= 2  # solve + damped retry
    finally:
        telemetry.reset()


def test_raise_injection_at_chunk_boundary_leaves_resumable_state(
    rng, tmp_path
):
    """An InjectedFault at `streaming.chunk.boundary` surfaces as a typed
    error AFTER the previous boundary's checkpoint was certified — the
    rerun resumes from it and completes."""
    import jax.numpy as jnp

    from photon_ml_tpu.game.checkpoint import (
        CheckpointSpec,
        StreamingCheckpointManager,
    )
    from photon_ml_tpu.game.streaming import (
        ShardedCoefficientTable,
        StreamingRandomEffectTrainer,
    )
    from photon_ml_tpu.ops.dense import DenseBatch
    from photon_ml_tpu.optim import (
        OptimizerConfig,
        RegularizationContext,
        RegularizationType,
    )

    n_ent, rows, k = 8, 6, 3
    X = rng.normal(size=(n_ent, rows, k))
    y = (rng.random((n_ent, rows)) < 0.5).astype(float)

    def chunk(lo, hi):
        return DenseBatch(
            x=X[lo:hi].astype(np.float32),
            labels=y[lo:hi].astype(np.float32),
            offsets=np.zeros((hi - lo, rows), np.float32),
            weights=np.ones((hi - lo, rows), np.float32),
        )

    chunks = [(0, chunk(0, 4)), (4, chunk(4, n_ent))]
    cfg = OptimizerConfig(
        max_iterations=40,
        tolerance=1e-8,
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=0.3,
    )
    trainer = StreamingRandomEffectTrainer("logistic", cfg, prefetch=False)

    ref = ShardedCoefficientTable(n_ent, k)
    trainer.train(ref, chunks)
    expected = ref.to_numpy()

    mgr = StreamingCheckpointManager(
        CheckpointSpec(directory=str(tmp_path / "ckpt"), every=1)
    )
    table = ShardedCoefficientTable(n_ent, k)
    faults.install_plan(
        faults.FaultPlan(
            [faults.FaultRule("streaming.chunk.boundary", nth=2)]
        )
    )
    with pytest.raises(faults.InjectedFault,
                       match="streaming.chunk.boundary"):
        trainer.train(table, chunks, checkpointer=mgr)
    faults.clear_plan()
    state = mgr.restore()
    assert state is not None and state.next_chunk == 1
    table2 = ShardedCoefficientTable(n_ent, k)
    table2.write_chunk(0, jnp.asarray(state.coefficients))
    trainer.train(table2, chunks, checkpointer=mgr,
                  start_chunk=state.next_chunk)
    np.testing.assert_array_equal(table2.to_numpy(), expected)
