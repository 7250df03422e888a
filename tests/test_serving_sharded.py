"""Sharded nearline serving (ISSUE 12): entity-sharded engine on the
8-device CPU mesh, the continuous batcher + asyncio front end, nearline
per-entity updates, fault seams (serving.async_dispatch,
serving.nearline_event, serving.nearline_apply) with the hard-kill
chaos row, and the sustained-load SLO smoke slice."""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu import faults, telemetry
from photon_ml_tpu.game.models import (
    FixedEffectModel,
    GameModel,
    RandomEffectBucketModel,
    RandomEffectModel,
)
from photon_ml_tpu.optim.factory import (
    OptimizerConfig,
    RegularizationContext,
    RegularizationType,
)
from photon_ml_tpu.parallel.mesh import make_mesh
from photon_ml_tpu.parallel.sharding import ElasticPlacementError
from photon_ml_tpu.serving import (
    AsyncScoringServer,
    BadRequest,
    ContinuousBatcher,
    MicroBatcher,
    ModelRegistry,
    NearlineUpdater,
    Overloaded,
    ScoringEngine,
    ScoringServer,
    ScoringService,
    publish_version,
    scan_versions,
)
from photon_ml_tpu.testing import generate_game_dataset


def _make_model(truth, scale=1.0, n_buckets=2, task="logistic"):
    """FE + per-user RE GameModel straight from planted coefficients."""
    w_users = truth["w_users"] * scale
    n_users, local_k = w_users.shape
    fe = FixedEffectModel(
        coefficients=jnp.asarray(truth["w_global"] * scale, jnp.float32),
        shard_name="global",
    )
    entity_bucket = (np.arange(n_users) % n_buckets).astype(np.int64)
    entity_pos = np.zeros(n_users, np.int64)
    buckets = []
    for b in range(n_buckets):
        codes_b = np.nonzero(entity_bucket == b)[0]
        entity_pos[codes_b] = np.arange(len(codes_b))
        proj = np.tile(np.arange(local_k, dtype=np.int32), (len(codes_b), 1))
        buckets.append(
            RandomEffectBucketModel(
                coefficients=jnp.asarray(w_users[codes_b], jnp.float32),
                projection=jnp.asarray(proj),
                entity_codes=jnp.asarray(codes_b, jnp.int32),
            )
        )
    re = RandomEffectModel(
        id_name="userId",
        shard_name="user",
        buckets=tuple(buckets),
        entity_bucket=entity_bucket,
        entity_pos=entity_pos,
        vocab=np.arange(n_users),
    )
    return GameModel(task=task, models={"fixed": fe, "perUser": re})


def _request_rows(truth, data, indices):
    Xg, Xu, users = truth["Xg"], truth["Xu"], truth["users"]
    rows = []
    for i in indices:
        rows.append(
            {
                "features": {
                    "global": [
                        [j, float(Xg[i, j])]
                        for j in range(Xg.shape[1])
                        if Xg[i, j] != 0
                    ],
                    "user": [
                        [j, float(Xu[i, j])]
                        for j in range(Xu.shape[1])
                        if Xu[i, j] != 0
                    ],
                },
                "ids": {"userId": int(users[i])},
                "offset": float(data.offset[i]),
            }
        )
    return rows


@pytest.fixture(scope="module")
def mesh_world():
    """32 users (16 per geometry bucket — divisible by the 8-device
    entity axis) so the same model serves replicated AND sharded."""
    data, truth = generate_game_dataset(
        n_users=32, rows_per_user=6, fe_dim=6, re_dim=4, seed=11
    )
    return data, truth


_INDEX_MAPS = {
    "global": [f"g{j}" for j in range(6)],
    "user": [f"u{j}" for j in range(4)],
}


def _entity_mesh(n=8):
    return make_mesh({"model": n})


def _post(port, path, body, timeout=15):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _get(port, path, timeout=15):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=timeout
    ) as resp:
        return json.loads(resp.read())


# ---------------------------------------------------------------------------
# entity-sharded engine
# ---------------------------------------------------------------------------


def test_sharded_engine_matches_predict_mean(mesh_world, multichip):
    """RE tables placed across the 8-device entity axis score identically
    to the replicated engine and to the batch predict_mean path."""
    data, truth = mesh_world
    model = _make_model(truth)
    expected = np.asarray(model.predict_mean(data))[: data.num_rows]
    rows = _request_rows(truth, data, range(data.num_rows))
    engine = ScoringEngine(
        model, max_batch=32, version="sharded", mesh=_entity_mesh()
    ).warmup()
    assert engine.entity_axis == "model"
    got = engine.score_rows(rows)
    np.testing.assert_allclose(got, expected, atol=1e-6)
    # the tables really are distributed: one device holds 1/8 of the rows
    table = engine.re_tables(0)[0][1]
    shard_shapes = {s.data.shape for s in table.addressable_shards}
    assert shard_shapes == {(2, 4)}  # 16 entities / 8 devices


def test_sharded_engine_rejects_indivisible_axis_with_valid_sizes(
    mesh_world, multichip
):
    """An entity count that does not divide the serving mesh's entity
    axis lists the axis sizes that CAN hold the table (the elastic
    restore formatting), not a bare modulus."""
    data, truth = mesh_world
    model = _make_model(truth, n_buckets=3)  # 32 users -> buckets of 11/11/10
    with pytest.raises(ElasticPlacementError) as ei:
        ScoringEngine(model, mesh=_entity_mesh())
    message = str(ei.value)
    assert "valid target axis sizes" in message
    assert "serving mesh" in message
    assert "[1]" in message  # 11 entities: only a 1-wide axis divides


def test_sharded_engine_from_streamed_checkpoint(
    tmp_path, mesh_world, multichip
):
    """load(re_checkpoints=...) restores a sharded training checkpoint's
    table straight onto the serving mesh via restore_placed and serves
    the CHECKPOINT's coefficients, not the model dir's."""
    from photon_ml_tpu.data.model_store import save_game_model
    from photon_ml_tpu.game.checkpoint import (
        CheckpointSpec,
        StreamCheckpointState,
        StreamingCheckpointManager,
    )

    import dataclasses

    data, truth = mesh_world
    fresh = _make_model(truth, n_buckets=1)
    # stale differs ONLY in the RE table (the thing the checkpoint
    # replaces); FE stays identical so parity isolates the restore
    re_sub = fresh.models["perUser"]
    stale = fresh.with_model(
        "perUser",
        dataclasses.replace(
            re_sub,
            buckets=(
                dataclasses.replace(
                    re_sub.buckets[0],
                    coefficients=jnp.zeros_like(
                        re_sub.buckets[0].coefficients
                    ),
                ),
            ),
        ),
    )
    model_dir = str(tmp_path / "model")
    save_game_model(stale, model_dir)
    for shard, names in _INDEX_MAPS.items():
        from photon_ml_tpu.data.index_map import IndexMap

        IndexMap(names).save(
            os.path.join(model_dir, "feature-indexes", shard)
        )
    ckpt_dir = str(tmp_path / "ckpt")
    mgr = StreamingCheckpointManager(CheckpointSpec(directory=ckpt_dir))
    mgr.save(
        StreamCheckpointState(
            next_chunk=1,
            coefficients=np.asarray(
                fresh.models["perUser"].buckets[0].coefficients
            ),
        )
    )
    engine = ScoringEngine.load(
        model_dir,
        max_batch=16,
        mesh=_entity_mesh(),
        re_checkpoints={"perUser": ckpt_dir},
    ).warmup()
    expected = np.asarray(fresh.predict_mean(data))[: data.num_rows]
    got = engine.score_rows(_request_rows(truth, data, range(data.num_rows)))
    np.testing.assert_allclose(got, expected, atol=1e-6)
    # and the read-only restore manager refuses to write
    from photon_ml_tpu.game.checkpoint import CheckpointError

    ro = StreamingCheckpointManager.open_for_restore(ckpt_dir)
    with pytest.raises(CheckpointError, match="read-only"):
        ro.save(
            StreamCheckpointState(next_chunk=2, coefficients=np.zeros((2, 2)))
        )


# ---------------------------------------------------------------------------
# continuous batcher + deadline edges (ISSUE satellite)
# ---------------------------------------------------------------------------


def test_continuous_batcher_never_waits_on_a_timer():
    """A lone request dispatches immediately even with a huge deadline
    configured — the continuous scheduler has no timer to wait out."""
    b = ContinuousBatcher(
        lambda rows: (np.zeros(len(rows), np.float32), "v"),
        max_batch=8, max_delay_ms=10_000.0,
    ).start()
    try:
        t0 = time.monotonic()
        b.submit([{}]).result(timeout=10)
        assert time.monotonic() - t0 < 5.0  # not the 10s deadline
    finally:
        b.stop()


def test_continuous_batcher_admits_into_next_bucket_as_capacity_frees():
    """Requests arriving while a batch is in flight ride the NEXT bucket
    together: batch size grows with offered load instead of a deadline."""
    dispatched = []
    gate = threading.Event()

    def scorer(rows):
        dispatched.append(len(rows))
        if len(dispatched) == 1:
            gate.wait(timeout=10)  # hold the first batch in flight
        return np.zeros(len(rows), np.float32), "v"

    b = ContinuousBatcher(scorer, max_batch=8, queue_depth=100).start()
    try:
        first = b.submit([{}])
        time.sleep(0.1)  # dispatcher now blocked in scorer on batch 1
        later = [b.submit([{}]) for _ in range(4)]
        gate.set()
        assert len(first.result(timeout=10)["scores"]) == 1
        for f in later:
            f.result(timeout=10)
    finally:
        b.stop()
    assert dispatched[0] == 1
    assert dispatched[1] == 4  # all four queued units rode one bucket


def test_batcher_request_arriving_exactly_at_bucket_full():
    """A unit that lands when the forming batch is exactly at max_batch
    rows must ride the NEXT dispatch, not overflow or stall this one."""
    dispatched = []
    gate = threading.Event()

    def scorer(rows):
        dispatched.append(len(rows))
        if len(dispatched) == 1:
            gate.wait(timeout=10)
        return np.zeros(len(rows), np.float32), "v"

    b = ContinuousBatcher(scorer, max_batch=4, queue_depth=100).start()
    try:
        first = b.submit([{}])
        time.sleep(0.1)
        fill = b.submit([{}] * 4)  # exactly max_batch rows on its own
        extra = b.submit([{}])  # must NOT join fill's bucket
        gate.set()
        first.result(timeout=10)
        assert len(fill.result(timeout=10)["scores"]) == 4
        assert len(extra.result(timeout=10)["scores"]) == 1
    finally:
        b.stop()
    assert dispatched == [1, 4, 1]


def test_batcher_timed_out_future_cancelled_mid_dispatch():
    """A caller that times out cancels its future while the unit is
    ALREADY in dispatch: result delivery must tolerate the cancelled
    future and the dispatcher must survive to serve the next request."""
    entered = threading.Event()
    gate = threading.Event()

    def scorer(rows):
        entered.set()
        gate.wait(timeout=10)
        return np.zeros(len(rows), np.float32), "v"

    b = MicroBatcher(scorer, max_batch=4, max_delay_ms=1.0).start()
    try:
        doomed = b.submit([{}])
        assert entered.wait(timeout=10)  # the unit is inside the scorer
        assert doomed.cancel() is False or True  # running future: either way
        doomed.cancel()
        gate.set()
        time.sleep(0.1)
        # the dispatcher survived the InvalidStateError path
        assert len(b.submit([{}]).result(timeout=10)["scores"]) == 1
    finally:
        gate.set()
        b.stop()


def test_shed_accounting_matches_returned_503s_exactly(mesh_world):
    """Under a burst, the serving.shed counter and the 503 responses are
    the SAME number — shed accounting can't drift from what callers saw."""
    data, truth = mesh_world
    engine = ScoringEngine(_make_model(truth), max_batch=4).warmup()
    gate = threading.Event()
    base = telemetry.snapshot()["counters"].get("serving.shed", 0)

    def slow_scorer(rows):
        gate.wait(timeout=10)
        return engine.score_rows(rows), engine.version

    service = ScoringService.__new__(ScoringService)
    service._source = engine
    service.request_timeout_s = 30.0
    service._batcher = ContinuousBatcher(
        slow_scorer, max_batch=4, queue_depth=4
    )
    service._updater = None
    server = ScoringServer(service, port=0).start()
    try:
        rows = _request_rows(truth, data, range(2))
        results = []
        lock = threading.Lock()

        def client():
            try:
                _post(server.port, "/v1/score", {"rows": rows})
                code = 200
            except urllib.error.HTTPError as e:
                code = e.code
            with lock:
                results.append(code)

        threads = [threading.Thread(target=client) for _ in range(8)]
        for t in threads:
            t.start()
        # in flight at most one batch (4 rows = 2 requests) and queued at
        # most 4 rows: at least 4 of the 8 requests are shed at once, however
        # slowly a loaded machine starts the clients (a fixed 0.3 s sleep
        # opened the gate before they had all arrived)
        deadline = time.time() + 8.0
        while time.time() < deadline:
            with lock:
                if len(results) >= 4:
                    break
            time.sleep(0.02)
        gate.set()
        for t in threads:
            t.join(timeout=30)
        got_503 = sum(1 for c in results if c == 503)
        assert got_503 > 0  # the burst actually overflowed the queue
        assert sum(1 for c in results if c == 200) == len(results) - got_503
        shed = telemetry.snapshot()["counters"].get("serving.shed", 0) - base
        assert shed == got_503
    finally:
        gate.set()
        server.stop()


# ---------------------------------------------------------------------------
# asyncio front end
# ---------------------------------------------------------------------------


def test_async_server_scores_and_maps_errors(mesh_world):
    data, truth = mesh_world
    model = _make_model(truth)
    engine = ScoringEngine(model, max_batch=8, version="v-aio").warmup()
    service = ScoringService(engine, max_batch=8, batcher="continuous")
    server = AsyncScoringServer(service, port=0).start()
    try:
        rows = _request_rows(truth, data, range(4))
        expected = np.asarray(model.predict_mean(data))[:4]
        result = _post(server.port, "/v1/score", {"rows": rows})
        np.testing.assert_allclose(result["scores"], expected, atol=1e-6)
        assert result["model_version"] == "v-aio"
        health = _get(server.port, "/healthz")
        assert health["status"] == "serving" and health["warm"]
        metrics = _get(server.port, "/metricsz")
        assert "counters" in metrics and "xla_executables" in metrics
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(server.port, "/v1/score", {"not_rows": []})
        assert ei.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(server.port, "/nope")
        assert ei.value.code == 404
        # keep-alive: one connection, two requests
        import http.client

        conn = http.client.HTTPConnection("127.0.0.1", server.port, timeout=15)
        try:
            for _ in range(2):
                conn.request(
                    "POST", "/v1/score",
                    body=json.dumps({"rows": rows[:1]}),
                    headers={"Content-Type": "application/json"},
                )
                resp = conn.getresponse()
                assert resp.status == 200
                resp.read()
        finally:
            conn.close()
    finally:
        server.stop()


def test_health_and_metrics_stay_responsive_while_scoring_is_wedged(
    mesh_world,
):
    """The ISSUE-named fix: /healthz and /metricsz must answer with
    bounded latency while the scoring path is saturated/wedged (engine
    mid-warmup, batcher queue full, dispatcher blocked) — on BOTH front
    ends, because they read telemetry registries and never queue behind
    the batcher."""
    data, truth = mesh_world
    engine = ScoringEngine(_make_model(truth), max_batch=4).warmup()
    gate = threading.Event()

    def wedged_scorer(rows):
        gate.wait(timeout=30)
        return engine.score_rows(rows), engine.version

    for server_cls, batcher in (
        (ScoringServer, "deadline"),
        (AsyncScoringServer, "continuous"),
    ):
        service = ScoringService.__new__(ScoringService)
        service._source = engine
        service.request_timeout_s = 30.0
        batcher_cls = (
            ContinuousBatcher if batcher == "continuous" else MicroBatcher
        )
        service._batcher = batcher_cls(
            wedged_scorer, max_batch=4, queue_depth=8
        )
        service._updater = None
        server = server_cls(service, port=0).start()
        try:
            rows = _request_rows(truth, data, range(2))
            # wedge the dispatcher and fill some queue
            pending = threading.Thread(
                target=lambda: service._batcher.submit(rows), daemon=True
            )
            pending.start()
            time.sleep(0.1)
            for path in ("/healthz", "/metricsz"):
                t0 = time.monotonic()
                body = _get(server.port, path, timeout=5)
                assert time.monotonic() - t0 < 2.0, (server_cls, path)
                assert body
        finally:
            gate.set()
            server.stop()
            gate.clear()


# ---------------------------------------------------------------------------
# nearline personalization
# ---------------------------------------------------------------------------


_NEARLINE_CONFIG = OptimizerConfig(
    max_iterations=30,
    tolerance=1e-8,
    regularization=RegularizationContext(reg_type=RegularizationType.L2),
    regularization_weight=0.5,
)


def test_nearline_resolve_matches_direct_solve(mesh_world):
    """The nearline row swap equals solving the same warm-started
    per-entity problem directly: projection mapping, residual offsets
    (fixed-effect margin folded in), and the in-place commit all line
    up with the training solver's answer."""
    from photon_ml_tpu.game.coordinates import _re_solver
    from photon_ml_tpu.ops.dense import DenseBatch
    from photon_ml_tpu.ops.losses import get_loss
    from photon_ml_tpu.optim.factory import build_objective

    data, truth = mesh_world
    model = _make_model(truth)
    engine = ScoringEngine(model, max_batch=8, version="t").warmup()
    updater = NearlineUpdater(
        engine, id_name="userId", config=_NEARLINE_CONFIG, rows_per_solve=4
    )
    target = 6  # bucket 0, some position
    events = [
        {
            "ids": {"userId": target},
            "features": {
                "global": [[0, 1.0], [2, -0.5]],
                "user": [[0, 1.0], [1, 0.5], [3, -1.0]],
            },
            "label": 1.0,
            "offset": 0.2,
        },
        {
            "ids": {"userId": target},
            "features": {"user": [[2, 2.0]]},
            "label": 0.0,
        },
    ]
    # expected: assemble the dense local problem by hand
    w_global = truth["w_global"]
    bucket = int(np.asarray(model.models["perUser"].entity_bucket)[target])
    pos = int(np.asarray(model.models["perUser"].entity_pos)[target])
    w0 = np.asarray(
        model.models["perUser"].buckets[bucket].coefficients
    )[pos]
    R, K = 4, 4
    x = np.zeros((1, R, K), np.float32)
    x[0, 0, [0, 1, 3]] = [1.0, 0.5, -1.0]
    x[0, 1, 2] = 2.0
    labels = np.zeros((1, R), np.float32)
    labels[0, 0] = 1.0
    offsets = np.zeros((1, R), np.float32)
    offsets[0, 0] = 0.2 + 1.0 * w_global[0] - 0.5 * w_global[2]
    weights = np.zeros((1, R), np.float32)
    weights[0, :2] = 1.0
    obj = build_objective(get_loss("logistic").name, _NEARLINE_CONFIG)
    solver = _re_solver(_NEARLINE_CONFIG, "logistic")
    res, _ = solver(
        obj,
        DenseBatch(
            x=jnp.asarray(x), labels=jnp.asarray(labels),
            offsets=jnp.asarray(offsets), weights=jnp.asarray(weights),
        ),
        jnp.asarray(w0[None, :]),
        jnp.float32(0.0),
        None,
    )
    expected_row = np.asarray(res.w)[0]

    accepted = updater.submit(events)
    assert accepted == 2
    stats = updater.flush()
    assert stats == {"entities": 1, "rows": 2, "applies": 1}
    got_row = np.asarray(engine.re_tables(0)[bucket][1])[pos]
    np.testing.assert_allclose(got_row, expected_row, atol=1e-6)
    assert not np.allclose(got_row, w0)  # the solve actually moved


def test_nearline_event_validation_and_buffer_semantics(mesh_world):
    _, truth = mesh_world
    engine = ScoringEngine(_make_model(truth), max_batch=8)
    updater = NearlineUpdater(
        engine, id_name="userId", config=_NEARLINE_CONFIG,
        rows_per_solve=2, queue_depth=4,
    )
    with pytest.raises(BadRequest, match="'ids' must contain"):
        updater.submit([{"features": {}, "label": 1.0}])
    with pytest.raises(BadRequest, match="'label' must be a number"):
        updater.submit([{"ids": {"userId": 1}, "label": "x"}])
    with pytest.raises(BadRequest, match="col, value"):
        updater.submit([
            {"ids": {"userId": 1}, "label": 1.0,
             "features": {"user": [["named", "", 1.0]]}}
        ])
    # unknown entities are dropped+counted, not errors
    base = telemetry.snapshot()["counters"].get(
        "serving.nearline.unknown_entities", 0
    )
    assert updater.submit(
        [{"ids": {"userId": 424242}, "label": 1.0, "features": {}}]
    ) == 0
    assert telemetry.snapshot()["counters"][
        "serving.nearline.unknown_entities"
    ] == base + 1
    # queue depth sheds with the typed Overloaded
    ev = {"ids": {"userId": 1}, "label": 1.0, "features": {}}
    updater.submit([ev] * 2)
    updater.submit([dict(ev, ids={"userId": 2})] * 2)
    with pytest.raises(Overloaded, match="nearline buffer at capacity"):
        updater.submit([dict(ev, ids={"userId": 3})])
    # per-entity ring keeps the NEWEST rows_per_solve events
    assert len(updater._buffers["1"]) == 2


def test_nearline_untouched_entities_bit_identical(mesh_world):
    data, truth = mesh_world
    model = _make_model(truth)
    engine = ScoringEngine(model, max_batch=32, version="t").warmup()
    rows = _request_rows(truth, data, range(data.num_rows))
    before = engine.score_rows(rows).copy()
    updater = NearlineUpdater(
        engine, id_name="userId", config=_NEARLINE_CONFIG, rows_per_solve=2
    )
    target = 5
    updater.submit([
        {"ids": {"userId": target}, "label": 1.0,
         "features": {"user": [[0, 1.0]]}}
    ])
    updater.flush()
    after = engine.score_rows(rows)
    users = truth["users"]
    touched = np.asarray([int(u) == target for u in users[: data.num_rows]])
    assert touched.any()
    # the updated entity's scores moved; everyone else's are BIT-identical
    assert not np.allclose(before[touched], after[touched])
    np.testing.assert_array_equal(before[~touched], after[~touched])


def test_nearline_publish_roundtrip(tmp_path, mesh_world):
    """publish() persists the LIVE (nearline-updated) tables as the next
    registry version: a fresh registry load scores exactly like the
    mutated in-memory engine."""
    data, truth = mesh_world
    model = _make_model(truth)
    registry_dir = str(tmp_path / "registry")
    publish_version(registry_dir, model, _INDEX_MAPS)
    engine = ScoringEngine(model, max_batch=16, version="v-00000001").warmup()
    updater = NearlineUpdater(
        engine, id_name="userId", config=_NEARLINE_CONFIG,
        rows_per_solve=2, publish_dir=registry_dir,
        publish_interval_s=0.0, index_maps=_INDEX_MAPS,
    )
    assert updater.publish() is None  # nothing applied yet
    updater.submit([
        {"ids": {"userId": 9}, "label": 1.0,
         "features": {"user": [[1, 1.0]]}}
    ])
    updater.flush()
    path = updater.publish()
    assert path is not None and path.endswith("v-00000002")
    meta = json.loads(
        open(os.path.join(path, "model-metadata.json")).read()
    )
    assert meta["extra"]["nearline_seq"] == 1
    registry = ModelRegistry(registry_dir, max_batch=16, warm=False,
                             poll_interval=60).start()
    try:
        assert registry.engine.version == "v-00000002"
        rows = _request_rows(truth, data, range(data.num_rows))
        np.testing.assert_allclose(
            registry.engine.score_rows(rows),
            engine.score_rows(rows),
            atol=1e-6,
        )
    finally:
        registry.stop()


# ---------------------------------------------------------------------------
# fault seams (L016) + the chaos row
# ---------------------------------------------------------------------------


def test_async_dispatch_fault_seam_isolated_to_callers():
    """An injected fault at serving.async_dispatch fails the riding
    requests with the typed error; the continuous dispatcher survives."""
    b = ContinuousBatcher(
        lambda rows: (np.zeros(len(rows), np.float32), "v"), max_batch=4
    ).start()
    try:
        faults.install_plan(faults.FaultPlan([
            faults.FaultRule("serving.async_dispatch", action="raise", nth=1),
        ]))
        doomed = b.submit([{}])
        with pytest.raises(faults.InjectedFault):
            doomed.result(timeout=10)
        faults.clear_plan()
        assert len(b.submit([{}]).result(timeout=10)["scores"]) == 1
    finally:
        faults.clear_plan()
        b.stop()


def test_nearline_event_fault_seam(mesh_world):
    _, truth = mesh_world
    engine = ScoringEngine(_make_model(truth), max_batch=8)
    updater = NearlineUpdater(
        engine, id_name="userId", config=_NEARLINE_CONFIG
    )
    try:
        faults.install_plan(faults.FaultPlan([
            faults.FaultRule("serving.nearline_event", action="raise",
                             nth=1),
        ]))
        with pytest.raises(faults.InjectedFault):
            updater.submit(
                [{"ids": {"userId": 1}, "label": 1.0, "features": {}}]
            )
    finally:
        faults.clear_plan()
    assert updater.submit(
        [{"ids": {"userId": 1}, "label": 1.0, "features": {}}]
    ) == 1


def test_nearline_apply_fault_leaves_tables_untouched(mesh_world):
    """A fault at the serving.nearline_apply commit point aborts BEFORE
    the table swap: the serving tables and nearline_seq are exactly as
    before — no torn in-memory state."""
    data, truth = mesh_world
    engine = ScoringEngine(_make_model(truth), max_batch=16).warmup()
    rows = _request_rows(truth, data, range(8))
    before = engine.score_rows(rows).copy()
    updater = NearlineUpdater(
        engine, id_name="userId", config=_NEARLINE_CONFIG, rows_per_solve=2
    )
    updater.submit([
        {"ids": {"userId": 3}, "label": 1.0,
         "features": {"user": [[0, 1.0]]}}
    ])
    try:
        faults.install_plan(faults.FaultPlan([
            faults.FaultRule("serving.nearline_apply", action="raise",
                             nth=1),
        ]))
        with pytest.raises(faults.InjectedFault):
            updater.flush()
    finally:
        faults.clear_plan()
    assert engine.nearline_seq == 0
    np.testing.assert_array_equal(engine.score_rows(rows), before)
    # the aborted bucket's events were REQUEUED, not discarded: the next
    # (un-faulted) flush applies them
    assert updater.flush()["applies"] == 1
    assert engine.nearline_seq == 1


def test_nearline_oov_only_event_leaves_row_untouched(mesh_world):
    """An event whose features all miss the entity's local projection
    carries no data about the row: with a weight-1 zero-design row the
    pure L2 re-solve would wipe the live coefficients to ~0. Such events
    must be dropped whole and the live row left untouched."""
    data, truth = mesh_world
    engine = ScoringEngine(_make_model(truth), max_batch=32).warmup()
    rows = _request_rows(truth, data, range(data.num_rows))
    before = engine.score_rows(rows).copy()
    updater = NearlineUpdater(
        engine, id_name="userId", config=_NEARLINE_CONFIG, rows_per_solve=2
    )
    base = telemetry.snapshot()["counters"].get(
        "serving.nearline.dropped_events", 0
    )
    # col 99 is outside every projection row (local space is cols 0..3);
    # an explicit weight of 0 is a tombstone, NOT a falsy-default 1.0
    assert updater.submit([
        {"ids": {"userId": 5}, "label": 1.0,
         "features": {"user": [[99, 1.0]]}},
        {"ids": {"userId": 6}, "label": 1.0, "features": {}},
        {"ids": {"userId": 7}, "label": 1.0, "weight": 0.0,
         "features": {"user": [[0, 1.0]]}},
    ]) == 3
    assert updater.flush() == {"entities": 0, "rows": 0, "applies": 0}
    assert engine.nearline_seq == 0
    np.testing.assert_array_equal(engine.score_rows(rows), before)
    assert telemetry.snapshot()["counters"][
        "serving.nearline.dropped_events"
    ] == base + 3


def test_nearline_bucket_failure_isolated_and_requeued(mesh_world):
    """One bucket's commit failure must not discard the OTHER bucket's
    apply, and the failed bucket's events retry on the next flush."""
    data, truth = mesh_world
    engine = ScoringEngine(_make_model(truth), max_batch=16).warmup()
    updater = NearlineUpdater(
        engine, id_name="userId", config=_NEARLINE_CONFIG, rows_per_solve=2
    )
    # userId 2 -> geometry bucket 0 (solved first), userId 3 -> bucket 1
    updater.submit([
        {"ids": {"userId": 2}, "label": 1.0,
         "features": {"user": [[0, 1.0]]}},
        {"ids": {"userId": 3}, "label": 0.0,
         "features": {"user": [[1, 1.0]]}},
    ])
    try:
        faults.install_plan(faults.FaultPlan([
            faults.FaultRule("serving.nearline_apply", action="raise",
                             nth=1),
        ]))
        with pytest.raises(faults.InjectedFault):
            updater.flush()
    finally:
        faults.clear_plan()
    # bucket 0 failed (requeued), bucket 1 applied
    assert engine.nearline_seq == 1
    assert "2" in updater._buffers and "3" not in updater._buffers
    assert updater.flush()["entities"] == 1
    assert engine.nearline_seq == 2


def test_nearline_submit_accepts_new_entities_after_swap(mesh_world):
    """After a hot swap the cached host view is stale: submit must not
    drop events for entities that exist only in the NEW model — the
    pre-check is skipped until flush rebuilds the view."""
    data, truth = mesh_world
    small = dict(truth)
    small["w_users"] = truth["w_users"][:16]
    old_engine = ScoringEngine(_make_model(small), max_batch=8)
    new_engine = ScoringEngine(_make_model(truth), max_batch=8)

    class Src:
        def __init__(self, engine):
            self.engine = engine

    src = Src(old_engine)
    updater = NearlineUpdater(
        src, id_name="userId", config=_NEARLINE_CONFIG, rows_per_solve=2
    )
    # userId 20 exists only in the 32-user model: dropped while the view
    # matches the live engine, accepted unchecked right after the swap
    ev = {"ids": {"userId": 20}, "label": 1.0,
          "features": {"user": [[0, 1.0]]}}
    assert updater.submit([ev]) == 0
    src.engine = new_engine
    assert updater.submit([ev]) == 1
    res = updater.flush()
    assert res["entities"] == 1
    assert new_engine.nearline_seq == 1
    assert old_engine.nearline_seq == 0


def test_nearline_applied_rows_counts_real_entities(mesh_world):
    """serving.nearline.applied_rows counts real entity rows, not the
    power-of-two padded lanes the solve dispatches."""
    data, truth = mesh_world
    engine = ScoringEngine(_make_model(truth), max_batch=16).warmup()
    updater = NearlineUpdater(
        engine, id_name="userId", config=_NEARLINE_CONFIG, rows_per_solve=2
    )
    base = telemetry.snapshot()["counters"].get(
        "serving.nearline.applied_rows", 0
    )
    # three entities in bucket 0: 3 lanes padded to 4 on device
    updater.submit([
        {"ids": {"userId": u}, "label": 1.0,
         "features": {"user": [[0, 1.0]]}}
        for u in (0, 2, 4)
    ])
    assert updater.flush()["entities"] == 3
    assert telemetry.snapshot()["counters"][
        "serving.nearline.applied_rows"
    ] == base + 3


_CHAOS_WORKER = r"""
import json, sys
import numpy as np
from photon_ml_tpu.serving import ModelRegistry, NearlineUpdater
from photon_ml_tpu.optim.factory import (
    OptimizerConfig, RegularizationContext, RegularizationType,
)

registry_dir = sys.argv[1]
registry = ModelRegistry(registry_dir, max_batch=8, warm=False,
                         poll_interval=60).start()
try:
    updater = NearlineUpdater(
        registry, id_name="userId",
        config=OptimizerConfig(
            max_iterations=10,
            regularization=RegularizationContext(
                reg_type=RegularizationType.L2),
            regularization_weight=0.5,
        ),
        rows_per_solve=2, publish_dir=registry_dir,
        publish_interval_s=0.0,
        index_maps={"global": [f"g{j}" for j in range(6)],
                    "user": [f"u{j}" for j in range(4)]},
    )
    updater.submit([{"ids": {"userId": 2}, "label": 1.0,
                     "features": {"user": [[0, 1.0]]}}])
    updater.flush()      # serving.nearline_apply hit 1: the table swap
    path = updater.publish()  # hit 2: the registry publish
    print(json.dumps({"published": path}))
finally:
    registry.stop()
"""


def test_chaos_hard_kill_during_nearline_swap_keeps_registry_consistent(
    tmp_path, mesh_world
):
    """The chaos row: a subprocess hard-killed (os._exit, no unwinding)
    at the serving.nearline_apply commit — at the in-memory swap AND at
    the registry publish — must leave the on-disk registry serving a
    consistent version: the old one, never a torn one. An unarmed rerun
    then publishes cleanly and the registry hot-swaps forward."""
    _, truth = mesh_world
    registry_dir = str(tmp_path / "registry")
    publish_version(registry_dir, _make_model(truth), _INDEX_MAPS)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"

    def run(plan):
        e = dict(env)
        if plan is not None:
            e["PHOTON_FAULT_PLAN"] = json.dumps(plan)
        else:
            e.pop("PHOTON_FAULT_PLAN", None)
        return subprocess.run(
            [sys.executable, "-c", _CHAOS_WORKER, registry_dir],
            capture_output=True, text=True, timeout=600, cwd=repo, env=e,
        )

    for nth in (1, 2):  # kill at the table swap, then at the publish
        proc = run({"rules": [{"point": "serving.nearline_apply",
                               "action": "exit", "nth": nth}]})
        assert proc.returncode == faults.DEFAULT_EXIT_CODE, proc.stderr[-2000:]
        versions = [v for v, _p in scan_versions(registry_dir)]
        assert versions == [1], (nth, versions)
        # the registry still loads and serves the intact old version
        registry = ModelRegistry(registry_dir, max_batch=8, warm=False,
                                 poll_interval=60).start()
        try:
            assert registry.engine.version == "v-00000001"
        finally:
            registry.stop()

    proc = run(None)  # unarmed: the publish lands atomically
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["published"].endswith("v-00000002")
    assert [v for v, _p in scan_versions(registry_dir)] == [1, 2]


# ---------------------------------------------------------------------------
# the e2e acceptance: sharded + async + hot swap + nearline, mid-traffic
# ---------------------------------------------------------------------------


def test_sharded_async_serving_survives_swap_and_nearline_mid_traffic(
    tmp_path, mesh_world, multichip
):
    """ISSUE 12 acceptance: RE tables across the forced 8-device CPU
    mesh, concurrent HTTP scores matching predict_mean to 1e-6, correct
    across BOTH a registry hot-swap and a nearline per-entity update
    applied mid-traffic (updated entity reflects the re-solve, untouched
    entities bit-identical), zero failed requests, jit-compile counter
    flat post-warmup."""
    data, truth = mesh_world
    mesh = _entity_mesh()
    m1 = _make_model(truth)
    m2 = _make_model(truth, scale=0.5)
    expected = {
        "v-00000001": np.asarray(m1.predict_mean(data))[: data.num_rows],
        "v-00000002": np.asarray(m2.predict_mean(data))[: data.num_rows],
    }
    registry_dir = str(tmp_path / "registry")
    publish_version(registry_dir, m1, _INDEX_MAPS)
    registry = ModelRegistry(
        registry_dir, max_batch=16, poll_interval=0.2,
        mesh=mesh, entity_axis="model",
    ).start()
    updater = NearlineUpdater(
        registry, id_name="userId", config=_NEARLINE_CONFIG,
        rows_per_solve=2,
    )
    service = ScoringService(
        registry, max_batch=16, queue_depth=10_000, batcher="continuous"
    ).attach_nearline(updater)
    server = AsyncScoringServer(service, port=0).start()
    port = server.port
    indices = list(range(12))  # rows of users 0 and 1 (6 rows each)
    target = int(truth["users"][0])  # the updated entity IS in the rows
    warm_entity = int(truth["users"][-1])  # ...the warmup entity is NOT
    assert warm_entity not in {int(truth["users"][i]) for i in indices}
    t_mask = np.asarray(
        [int(truth["users"][i]) == target for i in indices]
    )
    assert t_mask.any() and not t_mask.all()
    try:
        assert _get(port, "/healthz")["entity_axis"] == "model"
        rows = _request_rows(truth, data, indices)

        # warm every moving part OFF the measured window: score buckets
        # (registry warmed at load), the nearline solve + row-swap traces
        # (same mini-batch shape as the mid-traffic update, against an
        # entity whose rows are NOT scored here so predict_mean parity
        # holds), and the v2 engine structure (shared executable: same
        # structure + same sharding)
        updater.submit([{
            "ids": {"userId": warm_entity}, "label": 0.0,
            "features": {"user": [[0, 0.0]]},
        }])
        updater.flush()
        _post(port, "/v1/score", {"rows": rows})
        compiles_before = telemetry.snapshot()["counters"].get(
            "jit_compiles", 0
        )

        failures, seen_versions = [], set()
        stop = threading.Event()
        nearline_applied = threading.Event()
        update_sent = threading.Event()
        post_update_scores, in_window_touched = [], []

        def check(result, version):
            if nearline_applied.is_set() and version == "v-00000002":
                return  # checked against the re-solved row below
            exp = expected[version][indices]
            if update_sent.is_set() and version == "v-00000002":
                # the update may commit between this request's scoring and
                # the flag below being set (a loaded machine opens that
                # window): the touched rows are held, once the re-solved
                # row is known, to the value before or the value after
                in_window_touched.append(result[t_mask])
                result, exp = result[~t_mask], exp[~t_mask]
            np.testing.assert_allclose(result, exp, atol=1e-6)

        def client():
            while not stop.is_set():
                try:
                    got = _post(port, "/v1/score", {"rows": rows})
                    check(np.asarray(got["scores"]), got["model_version"])
                    seen_versions.add(got["model_version"])
                    if nearline_applied.is_set():
                        post_update_scores.append(np.asarray(got["scores"]))
                except Exception as e:  # noqa: BLE001 — asserted empty
                    failures.append(repr(e))

        threads = [threading.Thread(target=client) for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.3)
        # disturbance 1: hot swap to v2, mid-traffic
        publish_version(registry_dir, m2, _INDEX_MAPS)
        deadline = time.monotonic() + 60
        while (
            "v-00000002" not in seen_versions
            and time.monotonic() < deadline
        ):
            time.sleep(0.05)
        assert "v-00000002" in seen_versions
        # disturbance 2: nearline per-entity update via POST /v1/update.
        # pre_update is the v2 ENGINE's served answer (1e-6 to
        # predict_mean; the bit-identity claim below is engine-vs-engine)
        pre_update = np.asarray(
            _post(port, "/v1/score", {"rows": rows})["scores"]
        )
        np.testing.assert_allclose(
            pre_update, expected["v-00000002"][indices], atol=1e-6
        )
        update_sent.set()
        accepted = _post(port, "/v1/update", {"events": [
            {"ids": {"userId": target}, "label": 1.0,
             "features": {"user": [[0, 1.0], [2, -1.0]]}},
        ]})
        assert accepted == {"accepted": 1}
        updater.flush()  # deterministic commit (no cadence thread racing)
        nearline_applied.set()
        time.sleep(0.4)
        stop.set()
        for t in threads:
            t.join(timeout=60)
        assert not failures, failures[:3]

        # post-update scores: untouched entities BIT-identical to v2,
        # the updated entity moved to the re-solved row's scores
        final = np.asarray(
            _post(port, "/v1/score", {"rows": rows})["scores"]
        )
        np.testing.assert_array_equal(
            np.float32(final[~t_mask]), np.float32(pre_update[~t_mask])
        )
        assert not np.allclose(final[t_mask], pre_update[t_mask])
        engine_direct = registry.engine.score_rows(rows)
        np.testing.assert_allclose(final, engine_direct, atol=1e-7)
        for got in in_window_touched:  # never a third value
            assert (
                np.allclose(got, pre_update[t_mask], atol=1e-6)
                or np.allclose(got, final[t_mask], atol=1e-6)
            ), (got, pre_update[t_mask], final[t_mask])
        if post_update_scores:
            # the last mid-traffic response landed well after the apply
            np.testing.assert_allclose(
                post_update_scores[-1], final, atol=1e-7
            )

        # zero recompiles across warmup-complete traffic, the hot swap
        # (same structure + same mesh sharding -> shared executable),
        # and the nearline update (warmed trace)
        assert (
            telemetry.snapshot()["counters"].get("jit_compiles", 0)
            == compiles_before
        )
        health = _get(port, "/healthz")
        assert health["model_version"] == "v-00000002"
        assert health["nearline_seq"] >= 1
    finally:
        server.stop()
        registry.stop()


def test_serving_report_section_roundtrip():
    """The RunReport Serving section renders from live serving counters
    (requests, swaps, nearline applies + lag) in both JSON and markdown."""
    from photon_ml_tpu.telemetry.report import RunReport

    snapshot = {
        "counters": {
            "serving.requests": 2242,
            "serving.scored_rows": 8968,
            "serving.shed": 3,
            "serving.model_swaps": 2,
            "serving.nearline.applies": 3,
            "serving.nearline.applied_rows": 96,
            "serving.unseen_entities": 1,
        },
        "gauges": {},
        "histograms": {
            "serving.total_ms": {
                "count": 2242, "mean": 33.5, "p50": 33.4, "p99": 35.1,
            },
            "serving.batch_size": {"count": 600, "mean": 14.8},
            "serving.nearline.update_lag_ms": {
                "count": 96, "mean": 9.0, "p99": 11.4,
            },
        },
    }
    report = RunReport(snapshot=snapshot, spans=[], sources={})
    doc = report.to_json()
    assert doc["serving"]["requests"] == 2242
    assert doc["serving"]["nearline_lag_p99_ms"] == 11.4
    md = report.to_markdown()
    assert "## Serving" in md
    assert "p99 35.1 ms" in md
    assert "3 nearline apply(ies) covering 96 entity row(s)" in md
    assert "p99 event->applied 11.4 ms" in md
    assert "3 request(s) shed" in md
