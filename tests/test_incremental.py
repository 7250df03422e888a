"""Continuous-freshness loop: incremental warm-start retrains (ISSUE 14).

The acceptance spine: full fit → checkpoint → 5% delta → warm-start
refresh produces a model whose untouched RE lanes are BIT-IDENTICAL to
the base, whose validation metric matches a from-scratch fit on the
combined data within tolerance, and whose solve-count/lane-skip
telemetry proves the structural speedup (re-solved lanes ≈ the touched
fraction, zero-touched bucket solves skipped entirely). Plus the
satellites: streaming-checkpoint warm starts with vocabulary growth
(new rows zero-init, existing rows bit-identical, indivisible-axis
errors typed), registry lineage on /healthz and in `cli report`, the
incremental fault seams ("incremental.warm_restore",
"incremental.delta_scan", "incremental.publish" — L016 coverage), and
the crash row: a hard kill at incremental.publish leaves the base
checkpoint and the registry intact.
"""

from __future__ import annotations

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from photon_ml_tpu import incremental, telemetry
from photon_ml_tpu.faults import (
    FaultPlan,
    FaultRule,
    InjectedFault,
    clear_plan,
    install_plan,
)
from photon_ml_tpu.game import (
    FixedEffectConfig,
    GameConfig,
    GameEstimator,
    RandomEffectConfig,
    build_game_dataset,
)
from photon_ml_tpu.game.checkpoint import CheckpointSpec
from photon_ml_tpu.game.coordinate_descent import ValidationSpec, _evaluate
from photon_ml_tpu.ops.sparse import SparseBatch
from photon_ml_tpu.optim import (
    OptimizerConfig,
    RegularizationContext,
    RegularizationType,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_D = 8
_N_USERS = 40
_TOUCHED = (3, 17)  # base users the delta touches; plus one NEW user


def _build(Xm, us, ys):
    r, c = np.nonzero(Xm)
    b = SparseBatch.from_coo(
        values=Xm[r, c], rows=r, cols=c, labels=ys, num_features=_D
    )
    return build_game_dataset(
        response=ys,
        feature_shards={"g": b},
        id_columns={"userId": np.array([f"u{u:03d}" for u in us])},
    )


def _opt(**kw):
    base = dict(
        max_iterations=50,
        tolerance=1e-8,
        regularization=RegularizationContext(RegularizationType.L2),
        regularization_weight=1.0,
    )
    base.update(kw)
    return OptimizerConfig(**base)


def _config(**kw):
    return GameConfig(
        task="logistic",
        coordinates={
            "fixed": FixedEffectConfig(shard_name="g", optimizer=_opt()),
            "perUser": RandomEffectConfig(
                shard_name="g", id_name="userId", optimizer=_opt()
            ),
        },
        num_iterations=2,
        evaluators=["auc"],
        **kw,
    )


@pytest.fixture(scope="module")
def glmix(tmp_path_factory):
    """Base fit + checkpoint, delta, combined, incremental refresh, and
    the from-scratch reference — the whole acceptance spine, built once."""
    rng = np.random.default_rng(7)
    tmp = tmp_path_factory.mktemp("incremental")
    n_base = 2000
    X = rng.normal(size=(n_base, _D))
    users = rng.integers(0, _N_USERS, n_base)
    w = rng.normal(size=_D)
    u_eff = rng.normal(size=_N_USERS + 1) * 0.8

    def make_rows(Xm, us):
        logits = Xm @ w + u_eff[us]
        return (rng.random(len(us)) < 1 / (1 + np.exp(-logits))).astype(
            float
        )

    y_base = make_rows(X, users)
    base_data = _build(X, users, y_base)
    # ~5% delta: 2 touched existing users + 1 genuinely NEW user
    du = np.array(list(_TOUCHED) * 15 + [_N_USERS] * 10)
    Xd = rng.normal(size=(len(du), _D))
    yd = make_rows(Xd, du)
    comb_data = _build(
        np.vstack([X, Xd]),
        np.concatenate([users, du]),
        np.concatenate([y_base, yd]),
    )
    delta_data = _build(Xd, du, yd)
    Xv = rng.normal(size=(800, _D))
    uv = rng.integers(0, _N_USERS, 800)
    val_data = _build(Xv, uv, make_rows(Xv, uv))

    ckpt = str(tmp / "base-ckpt")
    config = _config()
    est = GameEstimator(config)
    base_fit = est.fit(
        base_data,
        validation_data=val_data,
        checkpoint_spec=CheckpointSpec(directory=ckpt, resume=False),
    )
    telemetry.reset()
    ws = incremental.load_warm_start(ckpt)
    scan = incremental.scan_delta(
        delta_data, {"userId": ws.model.models["perUser"].vocab}
    )
    res = GameEstimator(config).fit_incremental(
        comb_data, ws, delta=scan, validation_data=val_data
    )
    # telemetry is reset after every test (conftest isolation), so the
    # counters/spans of the incremental fit — and the report built from
    # them — must be captured NOW, inside the fixture
    snap = telemetry.snapshot()
    from photon_ml_tpu.telemetry.report import RunReport

    report = RunReport.from_live()
    ref = GameEstimator(config).fit(comb_data, validation_data=val_data)
    return dict(
        tmp=tmp, ckpt=ckpt, config=config, base_fit=base_fit, ws=ws,
        scan=scan, res=res, ref=ref, comb_data=comb_data,
        delta_data=delta_data, val_data=val_data, snap=snap,
        report=report,
    )


def _entity_coeffs(model, coord="perUser"):
    """entity value -> {global feature id: coefficient} (geometry-free;
    untouched entities keep identical geometry base-vs-refreshed, so
    dict equality IS bitwise row equality)."""
    re = model.models[coord]
    out = {}
    for bm in re.buckets:
        P = np.asarray(bm.projection)
        W = np.asarray(bm.coefficients)
        codes = np.asarray(bm.entity_codes)
        for e in range(len(codes)):
            val = re.vocab[codes[e]]
            out[val] = {
                int(g): float(W[e, k]) for k, g in enumerate(P[e])
            }
    return out


# ---------------------------------------------------------------------------
# warm-start loading + lineage
# ---------------------------------------------------------------------------


def test_load_warm_start_step_kind_records_lineage(glmix):
    ws = glmix["ws"]
    assert ws.lineage.kind == "step"
    assert ws.lineage.step == 3  # 2 iterations x 2 coordinates - 1
    assert ws.lineage.digest and len(ws.lineage.digest) == 64
    assert ws.model is not None and "perUser" in ws.model.models
    doc = ws.lineage.to_json()
    assert doc["kind"] == "step" and doc["checkpoint_dir"] == os.path.abspath(
        glmix["ckpt"]
    )


def test_load_warm_start_model_dir_kind(glmix, tmp_path):
    from photon_ml_tpu.data.model_store import save_game_model

    save_game_model(glmix["base_fit"].model, str(tmp_path / "m"))
    ws = incremental.load_warm_start(str(tmp_path / "m"))
    assert ws.lineage.kind == "model"
    assert ws.model.models.keys() == glmix["base_fit"].model.models.keys()


def test_load_warm_start_bad_dirs_are_typed(tmp_path):
    with pytest.raises(incremental.WarmStartError, match="does not exist"):
        incremental.load_warm_start(str(tmp_path / "nope"))
    empty = tmp_path / "empty"
    empty.mkdir()
    with pytest.raises(incremental.WarmStartError, match="nothing to"):
        incremental.load_warm_start(str(empty))


# ---------------------------------------------------------------------------
# the acceptance spine
# ---------------------------------------------------------------------------


def test_untouched_lanes_bit_identical_to_base(glmix):
    base_map = _entity_coeffs(glmix["base_fit"].model)
    inc_map = _entity_coeffs(glmix["res"].model)
    touched_vals = {f"u{u:03d}" for u in _TOUCHED}
    checked = 0
    for val, coeffs in base_map.items():
        if val in touched_vals:
            continue
        checked += 1
        for g, wv in coeffs.items():
            # exact float equality: the untouched lane was transplanted
            # by element take and never re-solved
            assert inc_map[val][g] == wv, (val, g)
    assert checked >= _N_USERS - len(_TOUCHED) - 2


def test_touched_and_new_lanes_did_resolve(glmix):
    base_map = _entity_coeffs(glmix["base_fit"].model)
    inc_map = _entity_coeffs(glmix["res"].model)
    for u in _TOUCHED:
        val = f"u{u:03d}"
        assert any(
            inc_map[val][g] != wv for g, wv in base_map[val].items()
        ), f"touched entity {val} kept its base coefficients"
    # the NEW user exists only in the refreshed model, with a real solve
    new_val = f"u{_N_USERS:03d}"
    assert new_val not in base_map
    assert any(abs(v) > 1e-8 for v in inc_map[new_val].values())
    assert glmix["res"].new_entities >= 1


def test_quality_matches_from_scratch_fit(glmix):
    spec = ValidationSpec(data=glmix["val_data"], evaluators=["auc"])
    m_inc = _evaluate(glmix["res"].model, spec)["auc"]
    m_ref = _evaluate(glmix["ref"].model, spec)["auc"]
    assert abs(m_inc - m_ref) < 0.02, (m_inc, m_ref)


def test_structural_speedup_lane_telemetry(glmix):
    res = glmix["res"]
    # 3 touched entities (2 existing + 1 new) out of 41 active: the
    # re-solved lane set must be the touched set, nothing more — the
    # structural form of the >=10x time-to-fresh claim
    assert res.lanes_solved >= 3
    assert res.lanes_skipped > 10 * res.lanes_solved / 2  # >~5x lanes kept
    total = res.lanes_solved + res.lanes_skipped
    assert res.lanes_solved / total < 0.2
    assert res.buckets_skipped >= 1  # some bucket held zero touched
    assert res.bucket_solves >= 1
    snap = glmix["snap"]["counters"]
    assert snap.get("incremental.lanes_solved", 0) >= res.lanes_solved
    assert snap.get("incremental.buckets_skipped", 0) >= res.buckets_skipped


def test_freshness_report_round_trip(glmix):
    report = glmix["report"]
    fresh = report.freshness_summary()
    assert fresh is not None
    assert fresh["lanes_solved"] >= 3
    assert fresh["lanes_skipped"] > 0
    assert 0 < fresh["lanes_solved_fraction"] < 0.5
    assert fresh["touched_fraction"] == pytest.approx(3 / 41, abs=0.05)
    md = report.to_markdown()
    assert "## Freshness" in md
    assert "kept bit-identical" in md
    doc = report.to_json()
    assert doc["freshness"]["lanes_solved"] == fresh["lanes_solved"]
    assert "time_to_fresh_s" in report.key_metrics()


def test_incremental_refuses_checkpointing_into_its_base(glmix):
    with pytest.raises(incremental.WarmStartError, match="base"):
        GameEstimator(glmix["config"]).fit_incremental(
            glmix["comb_data"],
            glmix["ws"],
            delta=glmix["scan"],
            checkpoint_spec=CheckpointSpec(directory=glmix["ckpt"]),
        )


def test_local_lambda_sweep_selects_with_policies(glmix):
    factors = incremental.local_lambda_factors(points=3, span=4.0)
    assert factors == [4.0, 1.0, 0.25]
    res = GameEstimator(glmix["config"]).fit_incremental(
        glmix["comb_data"],
        glmix["ws"],
        delta=glmix["scan"],
        validation_data=glmix["val_data"],
        lambda_factors=factors,
        policy="parsimonious",
        rel_tol=0.05,
    )
    sel = res.selection
    assert sel is not None and sel.policy == "parsimonious"
    assert len(sel.metrics) == 3 and np.isfinite(sel.metrics).all()
    assert sel.metric == "auc"
    # parsimonious ties toward the MORE regularized (lower index) lane
    best = int(np.nanargmax(sel.metrics))
    assert sel.index <= best
    # untouched lanes stay bit-identical through the whole sweep
    base_map = _entity_coeffs(glmix["base_fit"].model)
    inc_map = _entity_coeffs(res.model)
    untouched = f"u{(set(range(_N_USERS)) - set(_TOUCHED)).pop():03d}"
    assert inc_map[untouched] == base_map[untouched]


def test_entity_absent_from_base_and_delta_still_resolves(tmp_path):
    """A shifted base window can admit entities through the COMBINED
    data that neither the base model nor the delta shards name. Their
    transplant rows are zero-init, so the masked solve must treat them
    as touched — skipping them would publish an all-zero random effect."""
    rng = np.random.default_rng(21)
    n = 400
    X = rng.normal(size=(n, _D))
    users = rng.integers(0, 3, n)  # users u000..u002
    y = (rng.random(n) < 1 / (1 + np.exp(-(X @ rng.normal(size=_D))))
         ).astype(float)
    base_sel = users != 2  # the base window never saw u002
    base_data = _build(X[base_sel], users[base_sel], y[base_sel])
    comb_data = _build(X, users, y)
    delta_sel = users == 1  # the delta only touches u001
    delta_data = _build(X[delta_sel][:20], users[delta_sel][:20],
                        y[delta_sel][:20])

    config = _config()
    ckpt = str(tmp_path / "ckpt")
    GameEstimator(config).fit(
        base_data,
        checkpoint_spec=CheckpointSpec(directory=ckpt, resume=False),
    )
    ws = incremental.load_warm_start(ckpt)
    scan = incremental.scan_delta(
        delta_data, {"userId": ws.model.models["perUser"].vocab}
    )
    res = GameEstimator(config).fit_incremental(comb_data, ws, delta=scan)
    inc_map = _entity_coeffs(res.model)
    # u002 was in neither the base vocab nor the delta's touched set,
    # yet its lane re-solved to real coefficients
    assert any(abs(v) > 1e-8 for v in inc_map["u002"].values())
    assert res.new_entities >= 1
    # u000 (untouched, transplanted) stayed bit-identical to the base
    base_map = _entity_coeffs(
        incremental.load_warm_start(ckpt).model
    )
    assert inc_map["u000"] == base_map["u000"]


def test_lambda_sweep_without_validation_is_typed(glmix):
    with pytest.raises(ValueError, match="validation"):
        GameEstimator(glmix["config"]).fit_incremental(
            glmix["comb_data"], glmix["ws"], delta=glmix["scan"],
            lambda_factors=[4.0, 1.0],
        )


# ---------------------------------------------------------------------------
# streaming warm starts + vocabulary growth
# ---------------------------------------------------------------------------


def test_streaming_warm_start_restores_table(tmp_path):
    import jax.numpy as jnp

    from photon_ml_tpu.game.checkpoint import (
        StreamCheckpointState,
        StreamingCheckpointManager,
    )

    table = np.arange(48, dtype=np.float32).reshape(16, 3)
    mgr = StreamingCheckpointManager(
        CheckpointSpec(directory=str(tmp_path / "s"), resume=False)
    )
    mgr.save(StreamCheckpointState(next_chunk=5, coefficients=jnp.asarray(table)))
    ws = incremental.load_warm_start(str(tmp_path / "s"))
    assert ws.lineage.kind == "streaming"
    assert ws.lineage.next_chunk == 5 and ws.next_chunk == 5
    assert ws.model is None and ws.table is not None
    np.testing.assert_array_equal(np.asarray(ws.table.coefficients), table)
    # a bare table cannot seed the estimator path — typed refusal
    with pytest.raises(incremental.WarmStartError, match="bare"):
        GameEstimator(_config()).fit_incremental(
            _build(np.zeros((4, _D)), [0, 1, 2, 3],
                   np.array([0.0, 1, 0, 1])),
            ws,
        )


def test_grow_entity_rows_zero_init_and_bit_identical(tmp_path):
    import jax.numpy as jnp

    table = np.arange(30, dtype=np.float32).reshape(10, 3)
    grown = incremental.grow_entity_rows(jnp.asarray(table), 14)
    assert grown.shape == (14, 3)
    np.testing.assert_array_equal(np.asarray(grown)[:10], table)
    assert not np.asarray(grown)[10:].any()
    with pytest.raises(incremental.WarmStartError, match="shrink"):
        incremental.grow_entity_rows(jnp.asarray(table), 8)


def test_grow_entity_rows_sharded_elastic(tmp_path, multichip):
    """Checkpoint holding FEWER entities than the current index map,
    restored + grown onto a mesh: new rows zero-init, existing rows
    bit-identical, indivisible axis still the typed error."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from photon_ml_tpu.game.checkpoint import (
        StreamCheckpointState,
        StreamingCheckpointManager,
    )
    from photon_ml_tpu.parallel.sharding import ElasticPlacementError

    mesh = Mesh(np.array(jax.devices()[:4]), ("model",))
    rng = np.random.default_rng(3)
    table = rng.normal(size=(12, 4)).astype(np.float32)
    mgr = StreamingCheckpointManager(
        CheckpointSpec(directory=str(tmp_path / "s"), resume=False)
    )
    mgr.save(StreamCheckpointState(next_chunk=1,
                                   coefficients=jnp.asarray(table)))
    ws = incremental.load_warm_start(str(tmp_path / "s"), mesh=mesh)
    assert ws.table.mesh is mesh
    grown = incremental.grow_entity_rows(
        ws.table.coefficients, 16, mesh=mesh
    )
    host = np.asarray(grown)
    np.testing.assert_array_equal(host[:12], table)  # bit-identical
    assert not host[12:].any()  # zero-init growth
    # wrap the grown table without re-placing (the warm-start contract)
    from photon_ml_tpu.game.streaming import ShardedCoefficientTable

    wrapped = ShardedCoefficientTable.from_coefficients(grown, mesh=mesh)
    assert wrapped.num_entities == 16
    with pytest.raises(ElasticPlacementError, match="valid"):
        incremental.grow_entity_rows(ws.table.coefficients, 13, mesh=mesh)


# ---------------------------------------------------------------------------
# delta scans: in-core and out-of-core agree
# ---------------------------------------------------------------------------


def test_delta_scan_stream_agrees_with_in_core(tmp_path):
    from photon_ml_tpu.data.avro import (
        TRAINING_EXAMPLE_AVRO,
        build_index_maps_from_avro,
        read_game_dataset_from_avro,
        write_avro,
    )
    from photon_ml_tpu.ingest import IngestSpec

    rng = np.random.default_rng(11)

    def recs(n, users):
        for i in range(n):
            yield {
                "uid": str(i),
                "label": float(i % 2),
                "features": [
                    {"name": f"f{rng.integers(0, 10)}", "term": "",
                     "value": float(rng.normal())}
                    for _ in range(4)
                ],
                "metadataMap": {"userId": str(users[i % len(users)])},
                "weight": None,
                "offset": None,
            }

    delta_path = str(tmp_path / "delta.avro")
    write_avro(delta_path, TRAINING_EXAMPLE_AVRO,
               recs(300, [5, 9, 23, 77]), block_records=64)
    # base vocabularies are sorted-unique by construction (IdColumn /
    # RandomEffectModel.vocab); 77 is the new entity
    base_vocabs = {
        "userId": np.sort(np.array([str(u) for u in range(30)]))
    }
    imaps = build_index_maps_from_avro(
        [delta_path], feature_shards={"g": ("features",)}
    )
    data, _ = read_game_dataset_from_avro(
        [delta_path], feature_shards={"g": ("features",)},
        id_columns=("userId",), index_maps=imaps, return_index_maps=True,
    )
    in_core = incremental.scan_delta(data, base_vocabs,
                                     paths=[delta_path])
    streamed = incremental.scan_delta_stream(
        [delta_path], base_vocabs, index_maps=imaps,
        feature_shards={"g": ("features",)},
        spec=IngestSpec(chunk_rows=64, workers=2),
    )
    # the digest is content-aware: a rewrite with the SAME basename and
    # byte size (different dir, one flipped byte) must change it
    with open(delta_path, "rb") as fh:
        raw = bytearray(fh.read())
    raw[16] ^= 0xFF
    (tmp_path / "rewrite").mkdir()
    rewritten = str(tmp_path / "rewrite" / "delta.avro")
    with open(rewritten, "wb") as fh:
        fh.write(raw)
    assert (incremental.delta_digest([rewritten])
            != incremental.delta_digest([delta_path]))
    a, b = in_core.for_id("userId"), streamed.for_id("userId")
    np.testing.assert_array_equal(a.touched_values, b.touched_values)
    np.testing.assert_array_equal(a.new_values, b.new_values)
    assert a.new_values.tolist() == ["77"]
    assert in_core.digest == streamed.digest
    assert streamed.delta_rows == 300
    snap = telemetry.snapshot()
    assert snap["counters"].get("incremental.touched_entities", 0) >= 8
    assert 0 < snap["gauges"]["incremental.touched_fraction"] <= 1


# ---------------------------------------------------------------------------
# the streamed loop end-to-end: ChunkStream base fit -> streamed scan ->
# streamed combined re-read -> masked refresh
# ---------------------------------------------------------------------------


def test_streamed_incremental_end_to_end(tmp_path):
    """The WHOLE incremental loop out-of-core: base data assembled
    through the ChunkStream reader (multi-chunk, parallel decode), delta
    scanned with scan_delta_stream, the combined window re-read streamed
    with the SAME pinned index maps, and a warm-started masked refresh —
    untouched lanes still bit-identical to the base fit."""
    from photon_ml_tpu.data.avro import (
        TRAINING_EXAMPLE_AVRO,
        build_index_maps_from_avro,
        write_avro,
    )
    from photon_ml_tpu.ingest import IngestSpec
    from photon_ml_tpu.ingest.assemble import read_game_dataset_streamed

    rng = np.random.default_rng(17)
    d, n_users, n_base, n_delta = _D, 8, 600, 45
    X = rng.normal(size=(n_base + n_delta, d))
    users = np.concatenate([
        rng.integers(0, n_users, n_base),
        np.array([1, 4, n_users] * (n_delta // 3)),  # u1, u4 + NEW u8
    ])
    w = rng.normal(size=d)
    u_eff = rng.normal(size=n_users + 1)
    logits = X @ w + u_eff[users]
    y = (rng.random(len(users)) < 1 / (1 + np.exp(-logits))).astype(float)

    def recs(lo, hi):
        for i in range(lo, hi):
            yield {
                "uid": str(i),
                "label": float(y[i]),
                "features": [
                    {"name": f"c{j}", "term": "", "value": float(X[i, j])}
                    for j in range(d)
                ],
                "metadataMap": {"userId": f"u{users[i]:03d}"},
                "weight": None,
                "offset": None,
            }

    train_path = str(tmp_path / "base.avro")
    delta_path = str(tmp_path / "delta.avro")
    write_avro(train_path, TRAINING_EXAMPLE_AVRO, recs(0, n_base),
               block_records=64)
    write_avro(delta_path, TRAINING_EXAMPLE_AVRO,
               recs(n_base, n_base + n_delta), block_records=64)
    shards = {"g": ("features",)}
    spec = IngestSpec(chunk_rows=128, workers=2)
    # index maps pinned over base ∪ delta: the base and combined reads
    # must agree on feature geometry for the transplant to line up
    imaps = build_index_maps_from_avro([train_path, delta_path], shards)
    base_data = read_game_dataset_streamed(
        [train_path], feature_shards=shards, index_maps=imaps,
        id_columns=("userId",), spec=spec,
    )
    config = _config()
    ckpt = str(tmp_path / "ckpt")
    base_fit = GameEstimator(config).fit(
        base_data,
        checkpoint_spec=CheckpointSpec(directory=ckpt, resume=False),
    )
    ws = incremental.load_warm_start(ckpt)
    scan = incremental.scan_delta_stream(
        [delta_path], {"userId": ws.model.models["perUser"].vocab},
        index_maps=imaps, feature_shards=shards, spec=spec,
    )
    comb_data = read_game_dataset_streamed(
        [train_path, delta_path], feature_shards=shards, index_maps=imaps,
        id_columns=("userId",), spec=spec,
    )
    res = GameEstimator(config).fit_incremental(comb_data, ws, delta=scan)

    base_map = _entity_coeffs(base_fit.model)
    inc_map = _entity_coeffs(res.model)
    touched = {"u001", "u004"}
    checked = 0
    for val, coeffs in base_map.items():
        if val in touched:
            continue
        checked += 1
        assert inc_map[val] == coeffs, val  # bit-identical through i/o
    assert checked >= n_users - len(touched) - 1
    for val in touched:
        assert any(
            inc_map[val][g] != wv for g, wv in base_map[val].items()
        ), f"touched entity {val} kept its base coefficients"
    new_val = f"u{n_users:03d}"
    assert new_val not in base_map
    assert any(abs(v) > 1e-8 for v in inc_map[new_val].values())
    assert res.lanes_solved >= 3 and res.lanes_skipped >= 1
    assert scan.digest == incremental.delta_digest([delta_path])


# ---------------------------------------------------------------------------
# masked solves for FACTORED coordinates (frozen projection)
# ---------------------------------------------------------------------------


def _latent_rows(model, coord="perUser"):
    """entity value -> latent row (host copy) for a factored coordinate."""
    m = model.models[coord]
    lat = np.asarray(m.latent)
    flat = np.asarray(m.entity_flat)
    return {
        m.vocab[c]: lat[flat[c]]
        for c in range(len(m.vocab)) if flat[c] >= 0
    }


def test_masked_factored_coordinate_parity(tmp_path):
    """Factored (projected) coordinates get the same masked treatment:
    untouched latent rows EXACT from the transplant, touched + new rows
    matching a full unmasked re-solve under the same frozen projection
    (the seeded Gaussian A is identical across all three fits)."""
    rng = np.random.default_rng(23)
    d, k, n_users, n_base, n_delta = _D, 3, 10, 900, 60
    X = rng.normal(size=(n_base + n_delta, d))
    users = np.concatenate([
        rng.integers(0, n_users, n_base),
        np.array([2, 7, n_users] * (n_delta // 3)),  # u2, u7 + NEW u10
    ])
    w = rng.normal(size=d)
    u_eff = rng.normal(size=n_users + 1)
    logits = X @ w + u_eff[users]
    y = (rng.random(len(users)) < 1 / (1 + np.exp(-logits))).astype(float)
    base_data = _build(X[:n_base], users[:n_base], y[:n_base])
    comb_data = _build(X, users, y)
    delta_data = _build(X[n_base:], users[n_base:], y[n_base:])

    # a SINGLE factored coordinate: per-entity latent solves are convex
    # and independent, so the masked re-solve and the full re-solve land
    # on the same optimum for every touched entity
    config = GameConfig(
        task="logistic",
        coordinates={
            "perUser": RandomEffectConfig(
                shard_name="g", id_name="userId", optimizer=_opt(),
                projector="random", projected_dim=k,
            ),
        },
        num_iterations=1,
    )
    ckpt = str(tmp_path / "ckpt")
    base_fit = GameEstimator(config).fit(
        base_data,
        checkpoint_spec=CheckpointSpec(directory=ckpt, resume=False),
    )
    ws = incremental.load_warm_start(ckpt)
    scan = incremental.scan_delta(
        delta_data, {"userId": ws.model.models["perUser"].vocab}
    )
    res = GameEstimator(config).fit_incremental(comb_data, ws, delta=scan)
    ref = GameEstimator(config).fit(comb_data)

    base_rows = _latent_rows(base_fit.model)
    inc_rows = _latent_rows(res.model)
    ref_rows = _latent_rows(ref.model)
    touched = {"u002", "u007", f"u{n_users:03d}"}
    checked = 0
    for val, row in base_rows.items():
        if val in touched:
            continue
        checked += 1
        # untouched latent rows are the TRANSPLANT: exact float equality
        np.testing.assert_array_equal(inc_rows[val], row, err_msg=val)
    assert checked >= n_users - 2
    for val in touched:
        np.testing.assert_allclose(
            inc_rows[val], ref_rows[val], atol=1e-3, rtol=1e-3,
            err_msg=f"masked re-solve of {val} off the full re-solve",
        )
        if val in base_rows:
            assert not np.array_equal(inc_rows[val], base_rows[val]), val
    # the structural evidence flows through the same lane counters
    assert res.lanes_solved >= 3
    assert res.lanes_skipped >= n_users - 3
    assert res.bucket_solves >= 1


def test_transplant_factored_dim_mismatch_is_typed(tmp_path):
    """A base latent table of a DIFFERENT latent_dim cannot seed the new
    coordinate — typed WarmStartError, not a silent shape blowup."""
    rng = np.random.default_rng(29)
    n = 300
    X = rng.normal(size=(n, _D))
    users = rng.integers(0, 4, n)
    y = (rng.random(n) < 0.5).astype(float)
    data = _build(X, users, y)

    def cfg(k):
        return GameConfig(
            task="logistic",
            coordinates={
                "perUser": RandomEffectConfig(
                    shard_name="g", id_name="userId", optimizer=_opt(),
                    projector="random", projected_dim=k,
                ),
            },
            num_iterations=1,
        )

    ckpt = str(tmp_path / "ckpt")
    GameEstimator(cfg(3)).fit(
        data, checkpoint_spec=CheckpointSpec(directory=ckpt, resume=False)
    )
    ws = incremental.load_warm_start(ckpt)
    with pytest.raises(incremental.WarmStartError, match="latent"):
        GameEstimator(cfg(4)).fit_incremental(data, ws)


# ---------------------------------------------------------------------------
# stale-delta refusal (publish gate + cli refresh --force)
# ---------------------------------------------------------------------------


def test_check_delta_freshness_refuses_matching_digest(glmix, tmp_path):
    reg = str(tmp_path / "registry")
    res = glmix["res"]
    incremental.publish_incremental(
        reg, res.model, {"g": [f"c{j}" for j in range(_D)]},
        res.lineage, delta=res.delta,
    )
    # unchanged delta: typed refusal naming the version that already
    # trained on it (a stuck cron must not publish no-op versions)
    with pytest.raises(incremental.StaleDeltaError, match="v-00000001"):
        incremental.check_delta_freshness(reg, res.delta.digest)
    # --force and a genuinely new digest both pass
    incremental.check_delta_freshness(reg, res.delta.digest, force=True)
    incremental.check_delta_freshness(reg, "0" * 64)
    # a missing or empty registry never refuses (first publish must work)
    incremental.check_delta_freshness(
        str(tmp_path / "nope"), res.delta.digest
    )


# ---------------------------------------------------------------------------
# fault seams (L016 coverage: incremental.warm_restore,
# incremental.delta_scan, incremental.publish)
# ---------------------------------------------------------------------------


def test_incremental_fault_seams_fire_typed(glmix, tmp_path):
    install_plan(FaultPlan([FaultRule("incremental.warm_restore",
                                      action="raise")]))
    try:
        with pytest.raises(InjectedFault):
            incremental.load_warm_start(glmix["ckpt"])
    finally:
        clear_plan()

    install_plan(FaultPlan([FaultRule("incremental.delta_scan",
                                      action="raise")]))
    try:
        with pytest.raises(InjectedFault):
            incremental.scan_delta(
                glmix["delta_data"],
                {"userId": glmix["ws"].model.models["perUser"].vocab},
            )
    finally:
        clear_plan()

    install_plan(FaultPlan([FaultRule("incremental.publish",
                                      action="raise")]))
    try:
        with pytest.raises(InjectedFault):
            incremental.publish_incremental(
                str(tmp_path / "reg"),
                glmix["res"].model,
                {"g": [f"c{j}" for j in range(_D)]},
                glmix["res"].lineage,
            )
    finally:
        clear_plan()
    # an aborted publish left no version behind
    assert not os.path.isdir(tmp_path / "reg") or not any(
        n.startswith("v-") for n in os.listdir(tmp_path / "reg")
    )


# ---------------------------------------------------------------------------
# registry lineage: publish -> engine -> /healthz
# ---------------------------------------------------------------------------


def test_publish_lineage_roundtrip_and_healthz(glmix, tmp_path):
    from photon_ml_tpu.serving.engine import ScoringEngine
    from photon_ml_tpu.serving.server import ScoringService

    reg = str(tmp_path / "registry")
    res = glmix["res"]
    path = incremental.publish_incremental(
        reg,
        res.model,
        {"g": [f"c{j}" for j in range(_D)]},
        res.lineage,
        delta=res.delta,
        base_version="v-00000007",
    )
    with open(os.path.join(path, "model-metadata.json")) as fh:
        meta = json.load(fh)
    lineage = meta["extra"]["lineage"]
    assert lineage["base_version"] == "v-00000007"
    assert lineage["warm_start_checkpoint"] == res.lineage.checkpoint_dir
    assert lineage["base_kind"] == "step"
    assert lineage["delta_digest"] == res.delta.digest
    assert lineage["touched_fraction"] == pytest.approx(3 / 40, abs=0.01)

    engine = ScoringEngine.load(path, max_batch=4)
    assert engine.lineage == lineage
    health = ScoringService(engine).health()
    assert health["lineage"]["warm_start_checkpoint"] == (
        res.lineage.checkpoint_dir
    )
    assert health["lineage"]["delta_digest"] == res.delta.digest


# ---------------------------------------------------------------------------
# CLI end-to-end + the crash row
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def cli_base(tmp_path_factory):
    """One CLI base train with a checkpoint dir + delta shard, shared by
    the e2e refresh test and the crash row."""
    from photon_ml_tpu.data.avro import TRAINING_EXAMPLE_AVRO, write_avro

    rng = np.random.default_rng(99)
    tmp = tmp_path_factory.mktemp("cli_incremental")
    n, d, n_users = 240, _D, 6
    X = rng.normal(size=(n + 60, d))
    users = np.concatenate([
        rng.integers(0, n_users, n),
        np.array([1, 2, n_users] * 20),  # delta touches u1, u2 + NEW u6
    ])
    w = rng.normal(size=d)
    u_eff = rng.normal(size=n_users + 1)
    logits = X @ w + u_eff[users]
    y = (rng.random(len(users)) < 1 / (1 + np.exp(-logits))).astype(float)

    def recs(lo, hi):
        for i in range(lo, hi):
            yield {
                "uid": str(i),
                "label": float(y[i]),
                "features": [
                    {"name": f"c{j}", "term": "", "value": float(X[i, j])}
                    for j in range(d)
                ],
                "metadataMap": {"userId": str(users[i])},
                "weight": None,
                "offset": None,
            }

    train_path = str(tmp / "train.avro")
    delta_path = str(tmp / "delta.avro")
    write_avro(train_path, TRAINING_EXAMPLE_AVRO, recs(0, n))
    write_avro(delta_path, TRAINING_EXAMPLE_AVRO, recs(n, n + 60))
    config = {
        "task": "logistic",
        "input": {
            "format": "avro",
            "paths": [train_path],
            "feature_shards": {"global": ["features"]},
            "id_columns": ["userId"],
        },
        "coordinates": {
            "fixed": {
                "type": "fixed_effect",
                "shard_name": "global",
                "optimizer": {"regularization": "l2",
                              "regularization_weight": 0.1},
            },
            "perUser": {
                "type": "random_effect",
                "shard_name": "global",
                "id_name": "userId",
                "optimizer": {"regularization": "l2",
                              "regularization_weight": 1.0},
            },
        },
        "num_iterations": 1,
        "output_dir": str(tmp / "base-model"),
        "checkpoint": {"dir": str(tmp / "base-ckpt"), "resume": False},
    }
    cfg_path = tmp / "train.json"
    cfg_path.write_text(json.dumps(config))
    _run_cli(["train", "--config", str(cfg_path)], cwd=tmp)
    return dict(tmp=tmp, config=config, cfg_path=cfg_path,
                delta_path=delta_path)


def _run_cli(args, cwd, env_extra=None, expect_rc=0):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra or {})
    proc = subprocess.run(
        [sys.executable, "-m", "photon_ml_tpu.cli", *args],
        capture_output=True, text=True, cwd=str(cwd), env=env, timeout=600,
    )
    assert proc.returncode == expect_rc, (
        proc.returncode, proc.stderr[-3000:]
    )
    if expect_rc:
        return None
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _tree_digest(root):
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in sorted(os.walk(root)):
        dirnames.sort()
        for name in sorted(filenames):
            p = os.path.join(dirpath, name)
            h.update(os.path.relpath(p, root).encode())
            with open(p, "rb") as fh:
                h.update(fh.read())
    return h.hexdigest()


def test_cli_refresh_end_to_end(cli_base):
    from photon_ml_tpu.data.model_store import load_game_model

    tmp = cli_base["tmp"]
    ckpt = cli_base["config"]["checkpoint"]["dir"]
    reg = str(tmp / "registry")
    report = str(tmp / "refresh-report.md")
    summary = _run_cli(
        [
            "refresh",
            "--config", str(cli_base["cfg_path"]),
            "--warm-start", ckpt,
            "--delta", cli_base["delta_path"],
            "--registry-dir", reg,
            "--output-dir", str(tmp / "fresh-model"),
            "--report-out", report,
        ],
        cwd=tmp,
    )
    fresh = summary["freshness"]
    assert fresh["base"]["kind"] == "step"
    assert fresh["lanes_solved"] >= 3
    assert fresh["lanes_skipped"] >= 1
    assert fresh["delta"]["coordinates"]["userId"]["new_entities"] == 1
    assert fresh["time_to_fresh_s"] > 0
    assert fresh["published_version"].endswith("v-00000001")

    # untouched RE lanes bit-identical between base and refreshed models
    base_model = load_game_model(str(tmp / "base-model" / "final"))
    fresh_model = load_game_model(str(tmp / "fresh-model" / "final"))
    base_map = _entity_coeffs(base_model)
    fresh_map = _entity_coeffs(fresh_model)
    untouched = [v for v in base_map if v not in ("1", "2")]
    assert untouched
    for val in untouched:
        assert fresh_map[val] == base_map[val], val

    # a refreshed model dir carries the same feature artifacts a trained
    # one does: index maps AND the per-shard feature statistics
    assert os.path.isdir(
        tmp / "fresh-model" / "final" / "feature-indexes" / "global"
    )
    assert os.path.exists(
        tmp / "fresh-model" / "feature-stats" / "global.avro"
    )

    # the registry version carries lineage; loads into a serving engine
    with open(os.path.join(reg, "v-00000001",
                           "model-metadata.json")) as fh:
        meta = json.load(fh)
    assert meta["extra"]["lineage"]["base_kind"] == "step"
    assert meta["extra"]["lineage"]["delta_digest"]

    # the run report rendered the Freshness section
    with open(report) as fh:
        md = fh.read()
    assert "## Freshness" in md and "kept bit-identical" in md


def test_crash_at_publish_preserves_base_and_registry(cli_base):
    """The incremental crash row: a hard kill (os._exit 113) at the
    incremental.publish seam mid-refresh leaves the BASE checkpoint
    byte-identical and the registry without any partial version; the
    unarmed rerun publishes cleanly."""
    tmp = cli_base["tmp"]
    ckpt = cli_base["config"]["checkpoint"]["dir"]
    reg = str(tmp / "crash-registry")
    before = _tree_digest(ckpt)
    plan = json.dumps({
        "rules": [{"point": "incremental.publish", "action": "exit",
                   "exit_code": 113}]
    })
    _run_cli(
        [
            "refresh",
            "--config", str(cli_base["cfg_path"]),
            "--warm-start", ckpt,
            "--delta", cli_base["delta_path"],
            "--registry-dir", reg,
            "--output-dir", str(tmp / "crash-model"),
        ],
        cwd=tmp,
        env_extra={"PHOTON_FAULT_PLAN": plan},
        expect_rc=113,
    )
    # the base checkpoint is byte-identical — the refresh never writes it
    assert _tree_digest(ckpt) == before
    # no partial registry version (tmp debris is ignored by scans)
    assert not os.path.isdir(reg) or not any(
        n.startswith("v-") for n in os.listdir(reg)
    )
    # unarmed rerun succeeds and publishes v1
    summary = _run_cli(
        [
            "refresh",
            "--config", str(cli_base["cfg_path"]),
            "--warm-start", ckpt,
            "--delta", cli_base["delta_path"],
            "--registry-dir", reg,
            "--output-dir", str(tmp / "crash-model-2"),
        ],
        cwd=tmp,
    )
    assert summary["freshness"]["published_version"].endswith("v-00000001")
    assert _tree_digest(ckpt) == before


def test_cli_refresh_stale_delta_refusal_and_force(cli_base):
    """`cli refresh` refuses (typed, rc != 0) a delta whose digest the
    newest registry version already recorded — the stuck-cron guard —
    and publishes nothing; --force deliberately republishes."""
    tmp = cli_base["tmp"]
    ckpt = cli_base["config"]["checkpoint"]["dir"]
    reg = str(tmp / "stale-registry")

    def args(out_name, *extra):
        return [
            "refresh",
            "--config", str(cli_base["cfg_path"]),
            "--warm-start", ckpt,
            "--delta", cli_base["delta_path"],
            "--registry-dir", reg,
            "--output-dir", str(tmp / out_name),
            *extra,
        ]

    _run_cli(args("stale-model-1"), cwd=tmp)  # publishes v-00000001

    # the SAME delta again: typed refusal, nothing published
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-m", "photon_ml_tpu.cli",
         *args("stale-model-2")],
        capture_output=True, text=True, cwd=str(tmp), env=env, timeout=600,
    )
    assert proc.returncode != 0
    assert "StaleDeltaError" in proc.stderr
    assert "--force" in proc.stderr  # the override is named in the error
    assert sorted(
        n for n in os.listdir(reg) if n.startswith("v-")
    ) == ["v-00000001"]

    # --force: the deliberate republish goes through
    summary = _run_cli(args("stale-model-3", "--force"), cwd=tmp)
    assert summary["freshness"]["published_version"].endswith("v-00000002")
