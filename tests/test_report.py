"""ISSUE 3 (observability interpretation layer): HBM accounting, the
progress heartbeat, run reports, the `cli report` perf gate, and the
end-to-end acceptance path (fit -> report -> compare)."""

import json
import time

import numpy as np
import pytest

from photon_ml_tpu import telemetry
from photon_ml_tpu.telemetry import memory
from photon_ml_tpu.telemetry.progress import Heartbeat
from photon_ml_tpu.telemetry.report import (
    MetricDelta,
    RunReport,
    build_phase_tree,
    compare_metrics,
    report_path,
)


@pytest.fixture
def fake_hbm():
    """Deterministic 16 GB device with 10 GB in use (CPU has no stats)."""
    memory.set_stats_provider(
        lambda: {"bytes_in_use": 10 * 2**30, "bytes_limit": 16 * 2**30}
    )
    yield
    memory.set_stats_provider(None)


# -- memory accounting --------------------------------------------------------


def test_hbm_stats_none_on_statless_backend():
    # the CPU test mesh publishes no memory stats: probes return None and
    # the headroom check reports "unknown", never a false warning
    assert memory.hbm_stats() is None
    assert memory.check_headroom(2**40, label="huge") is None
    assert memory.record_phase_memory("fit") is None
    assert (
        "memory.headroom_warnings"
        not in telemetry.snapshot()["counters"]
    )


def test_check_headroom_warns_before_predicted_oom(fake_hbm, caplog):
    import logging

    # 16*0.92 - 10 = ~4.7 GB free
    assert memory.check_headroom(2**30, label="small") is True
    with caplog.at_level(
        logging.WARNING, logger="photon_ml_tpu.telemetry.memory"
    ):
        assert memory.check_headroom(8 * 2**30, label="re chunk") is False
    assert any("re chunk" in r.message for r in caplog.records)
    snap = telemetry.snapshot()
    assert snap["counters"]["memory.headroom_warnings"] == 1
    assert snap["gauges"]["memory.free_bytes"] > 0


def test_record_phase_memory_tracks_peaks(fake_hbm):
    in_use = memory.record_phase_memory("coordinate:fixed")
    assert in_use == 10 * 2**30
    memory.set_stats_provider(
        lambda: {"bytes_in_use": 12 * 2**30, "bytes_limit": 16 * 2**30}
    )
    memory.record_phase_memory("coordinate:fixed")
    memory.set_stats_provider(
        lambda: {"bytes_in_use": 6 * 2**30, "bytes_limit": 16 * 2**30}
    )
    memory.record_phase_memory("coordinate:fixed")
    g = telemetry.snapshot()["gauges"]
    # last sample wins the in_use gauge; the peak holds the max
    assert g["memory.phase.coordinate:fixed.bytes_in_use"] == 6 * 2**30
    assert g["memory.phase.coordinate:fixed.peak_bytes"] == 12 * 2**30


def test_estimate_table_and_batch_bytes():
    assert memory.estimate_table_bytes(1000, 50) == 1000 * 50 * 4
    assert memory.estimate_table_bytes(10, 3, itemsize=8) == 240
    from photon_ml_tpu.ops.dense import DenseBatch

    b = DenseBatch(
        x=np.zeros((4, 3), np.float32),
        labels=np.zeros(4, np.float32),
        offsets=np.zeros(4, np.float32),
        weights=np.zeros(4, np.float32),
    )
    assert memory.estimate_batch_bytes(b) == (4 * 3 + 3 * 4) * 4


# -- heartbeat ----------------------------------------------------------------


def test_heartbeat_beat_contents(fake_hbm, tmp_path):
    out = tmp_path / "hb.jsonl"
    hb = Heartbeat(interval=60, jsonl_path=str(out))
    telemetry.counter("progress.rows").inc(5000)
    telemetry.counter("progress.coeffs").inc(300)
    telemetry.gauge("checkpoint.last_save_ts").set(
        telemetry.trace.TRACER.now()
    )
    telemetry.gauge("checkpoint.last_step").set(7)
    with telemetry.span("fit"):
        with telemetry.span("coordinate:perUser"):
            line = hb.beat()
    assert line["type"] == "heartbeat"
    assert line["span"] == "fit > coordinate:perUser"
    assert line["rows_per_s"] > 0 and line["coeffs_per_s"] > 0
    assert line["rows_total"] == 5000
    assert line["hbm_bytes_in_use"] == 10 * 2**30
    assert line["checkpoint_age_s"] >= 0
    assert line["checkpoint_last_step"] == 7
    # rates persist as gauges for the final snapshot / run report
    g = telemetry.snapshot()["gauges"]
    assert g["progress.rows_per_sec"] > 0
    # the sink got the same line; deltas reset so a second beat reads 0
    (rec,) = [json.loads(x) for x in out.read_text().splitlines()]
    assert rec["seq"] == 1
    line2 = hb.beat()
    assert line2["rows_per_s"] == 0.0 and line2["seq"] == 2


def test_device_spread_from_gauges_and_heartbeat(fake_hbm):
    """Per-device HBM spread (max-min): computed from the published
    memory.device.* gauges (make_mesh publishes them; CPU probes are
    statless) and surfaced on heartbeat lines + its own gauge."""
    from photon_ml_tpu.telemetry import memory as tmem

    telemetry.gauge("memory.device.0.bytes_in_use").set(10 * 2**20)
    telemetry.gauge("memory.device.1.bytes_in_use").set(4 * 2**20)
    assert tmem.device_spread_bytes() == 6 * 2**20
    assert (
        telemetry.snapshot()["gauges"]["memory.device_spread_bytes"]
        == 6 * 2**20
    )
    line = Heartbeat(interval=60).beat()
    assert line["hbm_device_spread_bytes"] == 6 * 2**20


def test_device_spread_unknown_with_one_device():
    from photon_ml_tpu.telemetry import memory as tmem

    telemetry.gauge("memory.device.0.bytes_in_use").set(10 * 2**20)
    assert tmem.device_spread_bytes() is None
    line = Heartbeat(interval=60).beat()
    assert "hbm_device_spread_bytes" not in line


def test_report_renders_device_spread():
    from photon_ml_tpu.telemetry.report import RunReport

    telemetry.gauge("memory.device.0.bytes_in_use").set(3 * 2**30)
    telemetry.gauge("memory.device.1.bytes_in_use").set(1 * 2**30)
    md = RunReport.from_live().to_markdown()
    assert "spread" in md
    assert "2 devices" in md


def test_heartbeat_daemon_thread_emits_and_stops(tmp_path):
    out = tmp_path / "hb.jsonl"
    hb = Heartbeat(interval=0.02, jsonl_path=str(out))
    with hb:
        deadline = time.monotonic() + 5.0
        while not out.exists() and time.monotonic() < deadline:
            time.sleep(0.005)
    assert out.exists(), "daemon thread never beat"
    n_at_stop = len(out.read_text().splitlines())
    assert n_at_stop >= 1
    time.sleep(0.1)  # stopped: no further beats
    assert len(out.read_text().splitlines()) == n_at_stop
    assert hb._thread is None


def test_heartbeat_rejects_bad_interval():
    with pytest.raises(ValueError, match="interval"):
        Heartbeat(interval=0)


# -- report building ----------------------------------------------------------


def _span(id, parent, name, ts, dur, thread="MainThread"):
    return {
        "type": "span", "id": id, "parent": parent, "name": name,
        "ts": ts, "dur": dur, "thread": thread, "attrs": {}, "events": [],
    }


SPANS = [
    _span(1, None, "fit", 0.0, 10.0),
    _span(2, 1, "cd_iteration", 0.5, 4.0),
    _span(3, 2, "coordinate:fixed", 0.5, 2.5),
    _span(4, 2, "coordinate:perUser", 3.0, 1.5),
    _span(5, 1, "cd_iteration", 5.0, 4.5),
    _span(6, 5, "coordinate:fixed", 5.0, 2.0),
    _span(7, 5, "coordinate:perUser", 7.0, 2.5),
]


def test_build_phase_tree_aggregates_by_path():
    root = build_phase_tree(SPANS)
    fit = root.children["fit"]
    assert fit.count == 1 and fit.total_s == 10.0
    cd = fit.children["cd_iteration"]
    assert cd.count == 2 and cd.total_s == pytest.approx(8.5)
    assert cd.children["coordinate:fixed"].total_s == pytest.approx(4.5)
    assert cd.children["coordinate:perUser"].total_s == pytest.approx(4.0)
    # self time subtracts children at each level
    assert fit.self_s == pytest.approx(1.5)
    assert cd.self_s == pytest.approx(0.0)


def test_build_phase_tree_orphan_parent_roots_at_survivor():
    # span 9's parent 8 was dropped from a bounded buffer
    spans = SPANS + [_span(9, 8, "leaked", 9.0, 0.5)]
    root = build_phase_tree(spans)
    assert root.children["leaked"].count == 1  # rooted, not lost


_LOWER = {"ratio": -1, "p99_ms": -1, "rate": +1}


@pytest.mark.parametrize(
    "current, baseline, directions, expected",
    [
        # -20% rows/s is AT the threshold, not beyond: ok
        ({"rows_per_sec": 80.0}, {"rows_per_sec": 100.0}, None,
         {"rows_per_sec": False}),
        # +50% compiles (lower-is-better): regression
        ({"jit_compiles": 30.0}, {"jit_compiles": 20.0}, None,
         {"jit_compiles": True}),
        # 5% faster = improvement
        ({"fit_seconds": 95.0}, {"fit_seconds": 100.0}, None,
         {"fit_seconds": False}),
        # utilization collapsed (higher-is-better): regression
        ({"mfu": 0.1}, {"mfu": 0.5}, None, {"mfu": True}),
        # zero baselines and unknown metrics are skipped
        ({"x": 1.0}, {"x": 0.0}, None, {}),
        ({"mystery": 1.0}, {"mystery": 2.0}, None, {}),
        # the caller's own directions: a lower-is-better ratio regresses
        # when it RISES, passes when it drops
        ({"ratio": 3.0}, {"ratio": 2.0}, _LOWER, {"ratio": True}),
        ({"ratio": 1.5}, {"ratio": 2.0}, _LOWER, {"ratio": False}),
        ({"p99_ms": 20.0}, {"p99_ms": 10.0}, _LOWER, {"p99_ms": True}),
        # ... and KEY_METRIC_DIRECTIONS no longer applies
        ({"jit_compiles": 30.0}, {"jit_compiles": 20.0}, _LOWER, {}),
        # a metric the baseline predates (or a side lacks) is skipped;
        # the rest is still compared
        ({"ratio": 9.0, "rate": 100.0}, {"rate": 95.0}, _LOWER,
         {"rate": False}),
        ({"rate": 100.0}, {"ratio": 2.0, "rate": 200.0}, _LOWER,
         {"rate": True}),
    ],
)
def test_compare_metrics_directions_and_threshold(
    current, baseline, directions, expected
):
    deltas = compare_metrics(
        current, baseline, threshold=0.2, directions=directions
    )
    assert {d.metric: d.regressed for d in deltas} == expected


def test_run_report_load_merge_and_markdown(tmp_path):
    trace = tmp_path / "run.trace.jsonl"
    with open(trace, "w") as fh:
        fh.write(json.dumps({"type": "trace_header"}) + "\n")
        for s in SPANS:
            fh.write(json.dumps(s) + "\n")
        fh.write("{truncated last line")
    tele = tmp_path / "run.metrics.jsonl"
    snapshot = {
        "counters": {
            "jit_compiles": 12,
            "jit_compile_seconds": 3.5,
            "device_fetches": 40,
            "device_fetch_seconds": 4.2,
            "trace.dropped_spans": 2,
            "memory.headroom_warnings": 1,
        },
        "gauges": {
            "progress.rows_per_sec": 5e5,
            "progress.coeffs_per_sec": 1e4,
            "memory.bytes_in_use": 10 * 2**30,
            "memory.bytes_limit": 16 * 2**30,
            "memory.phase.coordinate:fixed.peak_bytes": 11 * 2**30,
        },
        "histograms": {
            "device_fetch_seconds": {"count": 40, "p50": 0.1, "p95": 0.2}
        },
    }
    with open(tele, "w") as fh:
        fh.write(
            json.dumps({"type": "heartbeat", "seq": 1, "uptime_s": 30.0,
                        "span": "fit", "rows_per_s": 4e5}) + "\n"
        )
        fh.write(
            json.dumps({"type": "metrics", "snapshot": snapshot}) + "\n"
        )
    ckpt = tmp_path / "ckpt" / "step-00000003"
    ckpt.mkdir(parents=True)
    (ckpt / "manifest.json").write_text(json.dumps({
        "format_version": 1, "step": 3, "best_metric": 0.71,
        "frozen": ["perUser"],
        "consecutive_rollbacks": {"perUser": 2},
        "history": [
            {"iteration": 0, "coordinate": "fixed", "seconds": 2.5,
             "metrics": {"auc": 0.7}},
            {"iteration": 0, "coordinate": "perUser", "seconds": 1.5,
             "solve_retries": 2, "rolled_back": True},
            {"iteration": 1, "coordinate": "fixed", "seconds": 2.0,
             "metrics": {"auc": 0.71}},
        ],
    }))

    report = RunReport.load(
        trace=str(trace), telemetry=str(tele),
        checkpoint_dir=str(tmp_path / "ckpt"),
    )
    km = report.key_metrics()
    assert km["fit_seconds"] == 10.0
    assert km["rows_per_sec"] == 5e5
    assert km["jit_compiles"] == 12
    assert km["dropped_spans"] == 2

    coords = report.coordinate_summary()
    by = {c["coordinate"]: c for c in coords}
    assert by["fixed"]["steps"] == 2
    assert by["fixed"]["last_metrics"] == {"auc": 0.71}
    assert by["perUser"]["rollbacks"] == 1
    assert by["perUser"]["solve_retries"] == 2
    assert by["perUser"]["frozen"] is True

    md = report.to_markdown()
    # the full phase-time tree, nested
    assert "- `fit` — n=1" in md
    assert "  - `cd_iteration` — n=2" in md
    assert "    - `coordinate:fixed` — n=2" in md
    assert "    - `coordinate:perUser` — n=2" in md
    # accounting, memory, coordinates, heartbeats, drop warning
    assert "`jit_compiles` | 12" in md
    assert "headroom warning" in md
    assert "`coordinate:fixed` | 11.0 GiB" in md
    assert "1 beat(s)" in md
    assert "2 span(s) were dropped" in md

    # round-trip: the saved JSON is a usable compare baseline
    doc = report.save_json(str(tmp_path / "report.json"))
    assert doc["key_metrics"] == km
    deltas = report.compare(
        json.load(open(tmp_path / "report.json")), threshold=0.2
    )
    assert deltas and not any(d.regressed for d in deltas)
    # doctored baseline (2x the rows/s): current run has regressed
    doctored = dict(doc, key_metrics=dict(km, rows_per_sec=km["rows_per_sec"] * 2))
    regressed = [d for d in report.compare(doctored) if d.regressed]
    assert [d.metric for d in regressed] == ["rows_per_sec"]
    md2 = report.to_markdown(deltas=report.compare(doctored))
    assert "**REGRESSED**" in md2


def test_report_path_sibling():
    assert report_path("x/run.trace.jsonl") == "x/run.trace.report.md"
    assert report_path("run") == "run.report.md"


def test_metric_delta_is_json_safe():
    d = MetricDelta("m", 1.0, 2.0, -0.5, True)
    json.dumps(d.to_dict())


# -- train CLI wiring ---------------------------------------------------------


def test_train_parse_heartbeat_variants():
    from photon_ml_tpu.cli.train import _parse_heartbeat

    hb = _parse_heartbeat({}, None)  # on by default
    assert hb is not None and hb.interval == 30.0 and hb.jsonl_path is None
    # every documented "off" spelling disables without crashing
    assert _parse_heartbeat({"heartbeat": False}, None) is None
    assert _parse_heartbeat({"heartbeat": 0}, None) is None
    assert _parse_heartbeat({"heartbeat": None}, None) is None
    # {} means enabled with defaults; a bare number is the interval
    assert _parse_heartbeat({"heartbeat": {}}, None).interval == 30.0
    assert _parse_heartbeat({"heartbeat": 10}, None).interval == 10.0
    hb = _parse_heartbeat(
        {"heartbeat": {"every": 5, "out": "hb.jsonl"}}, "m.jsonl"
    )
    assert hb.interval == 5.0 and hb.jsonl_path == "hb.jsonl"
    # sink defaults to telemetry_out so the report finds the beats
    hb = _parse_heartbeat({"heartbeat": {"every": 5}}, "m.jsonl")
    assert hb.jsonl_path == "m.jsonl"
    assert _parse_heartbeat({"heartbeat": {"every": 0}}, None) is None
    with pytest.raises(ValueError, match="unknown heartbeat"):
        _parse_heartbeat({"heartbeat": {"interval": 5}}, None)


def test_train_maybe_write_report_from_live(tmp_path):
    from photon_ml_tpu.cli.train import _maybe_write_report

    summary = {}
    _maybe_write_report({}, summary, None, None)  # no report_out: no-op
    assert summary == {}
    with telemetry.span("fit"):
        pass
    report_out = tmp_path / "run.report.md"
    _maybe_write_report(
        {"report_out": str(report_out)}, summary, None, None
    )
    assert summary["report"] == str(report_out)
    assert "- `fit`" in report_out.read_text()
    doc = json.loads((tmp_path / "run.report.json").read_text())
    assert doc["type"] == "run_report"


# -- e2e acceptance -----------------------------------------------------------


def test_e2e_fit_report_compare(tmp_path):
    """ISSUE 3 acceptance: a small GameEstimator.fit with trace+telemetry
    sinks -> `cli report` produces a markdown report with the full
    phase-time tree; heartbeat lines were emitted; `cli report --compare
    --fail-on-regress` exits nonzero against a doctored baseline showing
    a >20% rows/s regression and 0 against the undoctored one."""
    from photon_ml_tpu.cli.report import main as report_main
    from photon_ml_tpu.game.checkpoint import CheckpointSpec
    from photon_ml_tpu.game.estimator import (
        FixedEffectConfig,
        GameConfig,
        GameEstimator,
        RandomEffectConfig,
    )
    from photon_ml_tpu.optim.factory import OptimizerConfig
    from photon_ml_tpu.testing import generate_game_dataset

    data, _ = generate_game_dataset(
        task="logistic", n_users=6, rows_per_user=10, fe_dim=4, re_dim=2
    )
    trace_out = tmp_path / "run.trace.jsonl"
    tele_out = tmp_path / "run.metrics.jsonl"
    ckpt_dir = tmp_path / "ckpt"
    telemetry.reset()
    telemetry.configure(trace_out=str(trace_out))
    opt = OptimizerConfig(max_iterations=5)
    estimator = GameEstimator(GameConfig(
        task="logistic",
        coordinates={
            "fixed": FixedEffectConfig(shard_name="global", optimizer=opt),
            "perUser": RandomEffectConfig(
                shard_name="user", id_name="userId", optimizer=opt
            ),
        },
        num_iterations=2,
    ))
    # a sub-second-interval heartbeat so even this tiny fit beats
    with Heartbeat(interval=0.05, jsonl_path=str(tele_out)):
        estimator.fit(
            data,
            checkpoint_spec=CheckpointSpec(directory=str(ckpt_dir)),
        )
    telemetry.flush_metrics(str(tele_out))

    # heartbeat lines WERE emitted during the fit
    hb_lines = [
        json.loads(x)
        for x in tele_out.read_text().splitlines()
        if json.loads(x).get("type") == "heartbeat"
    ]
    assert hb_lines, "no heartbeat lines during the fit"
    assert any(x["rows_total"] > 0 for x in hb_lines)

    # the snapshot carries the report's rate + progress metrics
    snap = telemetry.snapshot()
    assert snap["gauges"]["progress.rows_per_sec"] > 0
    assert snap["counters"]["progress.rows"] == 6 * 10 * 2 * 2  # rows*coords*iters
    telemetry.reset()

    md_path = tmp_path / "report.md"
    json_path = tmp_path / "report.json"
    rc = report_main([
        "--trace", str(trace_out),
        "--telemetry", str(tele_out),
        "--checkpoint-dir", str(ckpt_dir),
        "--out", str(md_path),
        "--json", str(json_path),
    ])
    assert rc == 0
    md = md_path.read_text()
    # the full phase-time tree
    assert "- `fit` — n=1" in md
    assert "  - `cd_iteration` — n=2" in md
    assert "    - `coordinate:fixed` — n=2" in md
    assert "    - `coordinate:perUser` — n=2" in md
    assert "`build_coordinates`" in md
    # convergence history from the checkpoint manifests
    assert "## Coordinates" in md and "`perUser` | 2" in md
    assert "## Heartbeats" in md

    # undoctored baseline: exit 0
    rc = report_main([
        "--trace", str(trace_out), "--telemetry", str(tele_out),
        "--out", str(tmp_path / "cmp.md"),
        "--compare", str(json_path), "--fail-on-regress",
    ])
    assert rc == 0
    # doctored baseline: rows/s 2x better than measured -> >20% regression
    doc = json.loads(json_path.read_text())
    assert doc["key_metrics"]["rows_per_sec"] > 0
    doc["key_metrics"]["rows_per_sec"] *= 2.0
    doctored = tmp_path / "doctored.json"
    doctored.write_text(json.dumps(doc))
    rc = report_main([
        "--trace", str(trace_out), "--telemetry", str(tele_out),
        "--out", str(tmp_path / "cmp2.md"),
        "--compare", str(doctored), "--fail-on-regress",
    ])
    assert rc == 3
    assert "**REGRESSED**" in (tmp_path / "cmp2.md").read_text()


def test_cli_report_requires_a_source():
    from photon_ml_tpu.cli.report import main as report_main

    with pytest.raises(SystemExit) as exc:
        report_main([])
    assert exc.value.code == 2


def test_cli_report_bad_baseline(tmp_path):
    from photon_ml_tpu.cli.report import main as report_main

    trace = tmp_path / "t.jsonl"
    trace.write_text("")
    rc = report_main(
        ["--trace", str(trace), "--compare", str(tmp_path / "missing.json")]
    )
    assert rc == 1


# ---------------------------------------------------------------------------
# ISSUE 8: sweep awareness — heartbeat fields + per-config report table
# ---------------------------------------------------------------------------


def test_heartbeat_sweep_progress_fields():
    """sweep_configs_done/total ride the heartbeat line while a sweep is
    running, and are absent otherwise."""
    line = Heartbeat(interval=60).beat()
    assert "sweep_configs_total" not in line
    telemetry.gauge("sweep.configs_total").set(16)
    telemetry.gauge("sweep.configs_done").set(5)
    line = Heartbeat(interval=60).beat()
    assert line["sweep_configs_total"] == 16
    assert line["sweep_configs_done"] == 5


def test_report_sweep_table_round_trip(tmp_path):
    """The sweep runner's sweep_config spans + sweep.* gauges render as a
    per-config convergence table, round-tripping through the on-disk
    trace/telemetry JSONL (the satellite acceptance)."""
    trace_path = str(tmp_path / "sweep.trace.jsonl")
    tele_path = str(tmp_path / "sweep.metrics.jsonl")
    telemetry.configure(trace_out=trace_path)
    telemetry.gauge("sweep.configs_total").set(3)
    telemetry.gauge("sweep.configs_done").set(3)
    telemetry.gauge("sweep.selected_index").set(1)
    telemetry.gauge("sweep.selected_metric").set(0.81)
    telemetry.counter("sweep.solves").inc(6)
    for g, (lam, iters, reason, metric) in enumerate(
        [(10.0, 12, "FunctionValuesConverged", 0.74),
         (1.0, 20, "MaxIterations", 0.81),
         (0.1, 18, "GradientConverged", None)]
    ):
        with telemetry.span(
            "sweep_config", index=g, **{"lambda": lam},
            iterations=iters, reason=reason, final_loss=100.0 + g,
            metric=metric, metric_name="auc",
        ):
            pass
    telemetry.flush_metrics(tele_path)

    # live view
    live = RunReport.from_live()
    sweep = live.sweep_summary()
    assert sweep["configs_total"] == 3
    assert sweep["selected_index"] == 1
    assert [c["index"] for c in sweep["configs"]] == [0, 1, 2]
    assert sweep["configs"][1]["reason"] == "MaxIterations"
    assert sweep["configs"][2]["metric"] is None
    assert sweep["solves"] == 6

    # disk round trip
    telemetry.reset()  # close the sink; report reads files only
    report = RunReport.load(trace=trace_path, telemetry=tele_path)
    sweep2 = report.sweep_summary()
    assert sweep2["configs"] == sweep["configs"]
    assert report.key_metrics()["sweep_selected_metric"] == 0.81
    md = report.to_markdown()
    assert "## Hyperparameter sweep" in md
    assert "selected config **#1**" in md
    assert "| 0 | 10 | 12 | FunctionValuesConverged |" in md
    doc = report.save_json(str(tmp_path / "r.json"))
    assert doc["sweep"]["selected_index"] == 1


def test_report_without_sweep_has_no_section():
    report = RunReport.from_live()
    assert report.sweep_summary() is None
    assert "Hyperparameter sweep" not in report.to_markdown()


def test_report_ingestion_section_round_trip():
    """The RunReport "Ingestion" section answers the one operational
    question: did the solve ever wait on data?"""
    from photon_ml_tpu import telemetry
    from photon_ml_tpu.telemetry.report import RunReport

    telemetry.metrics.counter("ingest.rows").inc(120_000)
    telemetry.metrics.counter("ingest.chunks").inc(12)
    telemetry.metrics.gauge("ingest.rows_per_sec").set(1.2e6)
    telemetry.metrics.gauge("ingest.staging_bytes").set(64 * 2**20)
    live = RunReport.from_live()
    ing = live.ingestion_summary()
    assert ing["rows"] == 120_000
    assert ing["chunks"] == 12
    assert ing["solve_waits"] == 0
    md = live.to_markdown()
    assert "## Ingestion" in md
    assert "never waited on data" in md
    assert live.key_metrics()["ingest_rows_per_sec"] == 1.2e6
    assert live.to_json()["ingestion"]["rows"] == 120_000

    # now the ingest-bound variant
    telemetry.metrics.counter("ingest.solve_waits").inc(5)
    telemetry.metrics.histogram("ingest.solve_wait_s").observe_many(
        [0.1] * 5
    )
    md2 = RunReport.from_live().to_markdown()
    assert "waited on data 5 time(s)" in md2


def test_report_without_ingest_has_no_section():
    from photon_ml_tpu.telemetry.report import RunReport

    live = RunReport.from_live()
    assert live.ingestion_summary() is None
    assert "## Ingestion" not in live.to_markdown()
    assert "ingest_rows_per_sec" not in live.key_metrics()


def test_report_recovery_section_round_trip():
    """The "Recovery" section makes "the run recovered" auditable:
    sharded saves with the max single-shard fetch (the no-host-gather
    proof), elastic resumes, corrupt-skip fallbacks, absorbed
    transient-IO retries, and — loudly — deliberate injections."""
    from photon_ml_tpu import telemetry
    from photon_ml_tpu.telemetry.report import RunReport

    telemetry.metrics.counter("checkpoint.saves").inc(3)
    telemetry.metrics.counter("checkpoint.shard_saves").inc(24)
    telemetry.metrics.gauge("checkpoint.max_shard_fetch_bytes").set(
        5 * 2**20
    )
    telemetry.metrics.counter("checkpoint.restores").inc(1)
    telemetry.metrics.counter("checkpoint.corrupt").inc(1)
    telemetry.metrics.counter("recovery.elastic_resumes").inc(1)
    telemetry.metrics.counter("ingest.read_retries").inc(2)
    telemetry.metrics.counter("serving.version_retries").inc(1)
    telemetry.metrics.counter("faults.injected").inc(4)
    telemetry.metrics.counter(
        "faults.injected.checkpoint.save.before_rename"
    ).inc(4)
    live = RunReport.from_live()
    rec = live.recovery_summary()
    assert rec["checkpoint_saves"] == 3
    assert rec["checkpoint_shard_saves"] == 24
    assert rec["max_shard_fetch_bytes"] == 5 * 2**20
    assert rec["recovery_elastic_resumes"] == 1
    assert rec["faults_injected_by_point"] == {
        "checkpoint.save.before_rename": 4
    }
    md = live.to_markdown()
    assert "## Recovery" in md
    assert "never the full table" in md
    assert "1 elastic" in md
    assert "corrupt/partial checkpoint(s) skipped" in md
    assert "2 transient-IO retry(ies) absorbed on ingest chunk reads" in md
    assert "deliberately injected" in md
    assert "checkpoint.save.before_rename" in md
    assert live.to_json()["recovery"]["checkpoint_restores"] == 1


def test_report_recovery_fleet_rows_round_trip():
    """Fleet-recovery accounting (supervised multi-process fits): member
    deaths + survivor relaunches, coordinated-checkpoint quorum
    outcomes, and absorbed distributed-init retries each get their own
    Recovery row — and any one of them alone is enough to materialize
    the section."""
    from photon_ml_tpu import telemetry
    from photon_ml_tpu.telemetry.report import RunReport

    telemetry.metrics.counter("recovery.fleet_member_deaths").inc(1)
    telemetry.metrics.counter("recovery.fleet_relaunches").inc(1)
    telemetry.metrics.counter("checkpoint.peer_manifests").inc(6)
    telemetry.metrics.counter("checkpoint.quorum_timeouts").inc(2)
    telemetry.metrics.counter("multihost.init_retries").inc(3)
    live = RunReport.from_live()
    rec = live.recovery_summary()
    assert rec["recovery_fleet_member_deaths"] == 1
    assert rec["recovery_fleet_relaunches"] == 1
    assert rec["checkpoint_peer_manifests"] == 6
    assert rec["checkpoint_quorum_timeouts"] == 2
    assert rec["multihost_init_retries"] == 3
    md = live.to_markdown()
    assert "## Recovery" in md
    assert "fleet: 1 member death(s), 1 survivor relaunch(es)" in md
    assert "6 per-process manifest(s) written, 2 quorum timeout(s)" in md
    assert "3 distributed-init retry(ies) absorbed" in md
    assert (
        live.to_json()["recovery"]["recovery_fleet_relaunches"] == 1
    )


def test_report_without_recovery_activity_has_no_section():
    from photon_ml_tpu.telemetry.report import RunReport

    live = RunReport.from_live()
    assert live.recovery_summary() is None
    assert "## Recovery" not in live.to_markdown()


def test_heartbeat_ingest_fields():
    """Heartbeats surface live ingest throughput — and only when an
    ingest pipeline actually ran (absence stays unknown, never zero)."""
    from photon_ml_tpu import telemetry
    from photon_ml_tpu.telemetry.progress import Heartbeat

    hb = Heartbeat(interval=60)
    line = hb.beat()
    assert "ingest_rows_per_s" not in line  # no pipeline: no field
    telemetry.metrics.counter("ingest.rows").inc(50_000)
    telemetry.metrics.gauge("ingest.queue_depth").set(2)
    line = hb.beat()
    assert line["ingest_rows_per_s"] > 0
    assert line["ingest_queue_depth"] == 2
    assert "ingest_stalls" not in line  # zero stalls: field omitted
    telemetry.metrics.counter("ingest.stalls").inc()
    line = hb.beat()
    assert line["ingest_stalls"] == 1


# ---------------------------------------------------------------------------
# ISSUE 16: executable-level roofline profiler in heartbeats + reports
# ---------------------------------------------------------------------------


def _record_profile(name, seconds, exclusive, flops, nbytes, n=1):
    """Drive the profile registry directly: n sampled dispatches of
    ``name`` at the given per-dispatch honest timing / cost."""
    from photon_ml_tpu.telemetry import profile

    for _ in range(n):
        profile.PROFILE_REGISTRY.count_dispatch(name, ("f32[8]",), 1)
        profile.PROFILE_REGISTRY.record_sample(
            name, ("f32[8]",), seconds, exclusive, 0.0, flops, nbytes
        )


def test_heartbeat_hot_exec_round_trip(tmp_path):
    """The heartbeat's hot_exec field names the executable with the top
    exclusive-time DELTA over the last interval, rides the JSONL sink
    through tail_heartbeat_fields, and stays absent (unknown) when no
    dispatch was profiled — never a stale winner."""
    from photon_ml_tpu.telemetry.progress import tail_heartbeat_fields

    out = tmp_path / "hb.jsonl"
    hb = Heartbeat(interval=60, jsonl_path=str(out))
    line = hb.beat()
    assert "hot_exec" not in line  # nothing profiled yet: unknown

    _record_profile("alpha", 3.0, 3.0, None, None)
    _record_profile("beta", 1.0, 1.0, None, None)
    line = hb.beat()
    assert line["hot_exec"] == "alpha"
    rec = tail_heartbeat_fields(str(out))
    assert rec is not None and rec["hot_exec"] == "alpha"

    # next interval: only beta advances -> the DELTA winner flips
    _record_profile("beta", 2.0, 2.0, None, None)
    assert hb.beat()["hot_exec"] == "beta"
    # idle interval: no new samples, no winner, field omitted
    assert "hot_exec" not in hb.beat()


def test_report_hot_executables_round_trip(tmp_path):
    """Hot-executables table: built from the profile.exec.* gauges at
    report time, ranked by exclusive seconds, carrying MFU / intensity /
    bound class and the xla.exec.* compile split; survives the JSON
    baseline and a metrics-JSONL reload."""
    from photon_ml_tpu.telemetry import xla

    xla.set_peaks(1e12, 1e11)
    # 4 dispatches, 0.5 s each, intensity 1.25 (< balance 10): HBM-bound
    _record_profile("glm_value_grad", 0.5, 0.4, 1e10, 8e9, n=4)
    _record_profile("tiny", 0.01, 0.01, None, None)
    telemetry.metrics.counter(
        "xla.exec.glm_value_grad.recompiles"
    ).inc(2)
    telemetry.metrics.counter(
        "xla.exec.glm_value_grad.compile_seconds"
    ).inc(1.5)

    report = RunReport.from_live()
    hot = report.hot_executables()
    assert [e["name"] for e in hot] == ["glm_value_grad", "tiny"]
    top = hot[0]
    assert top["est_exclusive_seconds"] == pytest.approx(1.6)
    assert top["dispatches"] == 4
    assert top["mfu"] == pytest.approx(0.02)
    assert top["bound_class"] == "HBM-bound"
    assert top["recompiles"] == 2
    assert top["compile_seconds"] == pytest.approx(1.5)
    assert top["timing_suspect"] is False

    km = report.key_metrics()
    assert km["exec.glm_value_grad.mfu"] == pytest.approx(0.02)

    md = report.to_markdown()
    assert "## Hot executables" in md
    assert "`glm_value_grad`" in md
    assert "HBM-bound" in md
    assert "| MFU |" in md

    # JSON baseline round trip
    doc = report.save_json(str(tmp_path / "r.json"))
    loaded = json.loads((tmp_path / "r.json").read_text())
    assert loaded["hot_executables"][0]["name"] == "glm_value_grad"
    assert doc["key_metrics"]["exec.glm_value_grad.mfu"] == km[
        "exec.glm_value_grad.mfu"
    ]

    # metrics-JSONL reload reconstructs the same table
    tele = tmp_path / "run.metrics.jsonl"
    telemetry.flush_metrics(str(tele))
    reloaded = RunReport.load(telemetry=str(tele))
    rehot = reloaded.hot_executables()
    assert rehot[0]["name"] == "glm_value_grad"
    assert rehot[0]["bound_class"] == "HBM-bound"


def test_report_without_profiles_has_no_hot_section():
    live = RunReport.from_live()
    assert live.hot_executables() == []
    assert "## Hot executables" not in live.to_markdown()


def test_report_renders_timing_suspect_warning():
    from photon_ml_tpu.telemetry import xla

    xla.set_peaks(1e12, 1e11)
    # forged-clock rate: 1e9 FLOPs in a nanosecond >> device peak
    _record_profile("liar", 1e-9, 1e-9, 1e9, 1e6)
    md = RunReport.from_live().to_markdown()
    assert "`liar ⚠`" in md
    assert "timing suspect" in md
    assert "physically impossible" in md


def test_cli_report_hot_flag(tmp_path):
    """`cli report --hot` renders ONLY the hot-executables table."""
    from photon_ml_tpu.cli.report import main as report_main

    _record_profile("solve", 2.0, 2.0, None, None)
    tele = tmp_path / "run.metrics.jsonl"
    telemetry.flush_metrics(str(tele))
    telemetry.reset()

    out = tmp_path / "hot.md"
    rc = report_main(
        ["--telemetry", str(tele), "--hot", "--out", str(out)]
    )
    assert rc == 0
    md = out.read_text()
    assert "## Hot executables" in md
    assert "`solve`" in md
    assert "# Run report" not in md  # the full report is suppressed

    # no profiled dispatches: an explanatory line, not an empty file
    empty_tele = tmp_path / "empty.metrics.jsonl"
    empty_tele.write_text(
        json.dumps({"type": "metrics", "snapshot": {
            "counters": {}, "gauges": {}, "histograms": {},
        }}) + "\n"
    )
    rc = report_main(
        ["--telemetry", str(empty_tele), "--hot", "3",
         "--out", str(tmp_path / "none.md")]
    )
    assert rc == 0
    assert "No profiled executables" in (tmp_path / "none.md").read_text()


def test_cli_report_compare_notes_and_skips_exec_metrics(
    tmp_path, capsys
):
    """Per-executable rows in --compare: renamed/new executables are
    note-and-skipped on stderr; a regression on a SHARED executable's
    MFU still flags."""
    from photon_ml_tpu.cli.report import main as report_main
    from photon_ml_tpu.telemetry import xla

    xla.set_peaks(1e12, 1e11)
    # shared: mfu 0.02; new_kernel: only in the current run
    _record_profile("shared", 0.5, 0.5, 1e10, 8e9, n=2)
    _record_profile("new_kernel", 0.2, 0.2, 2e10, 1e9)
    tele = tmp_path / "run.metrics.jsonl"
    telemetry.flush_metrics(str(tele))
    telemetry.reset()

    baseline = {
        "key_metrics": {
            # shared at 5x the current MFU: an MFU regression
            "exec.shared.mfu": 0.1,
            # old_kernel: renamed away since the baseline
            "exec.old_kernel.mfu": 0.3,
        }
    }
    base_path = tmp_path / "baseline.json"
    base_path.write_text(json.dumps(baseline))

    rc = report_main([
        "--telemetry", str(tele),
        "--out", str(tmp_path / "cmp.md"),
        "--compare", str(base_path), "--fail-on-regress",
    ])
    err = capsys.readouterr().err
    assert rc == 3  # the shared executable's MFU regressed
    assert "exec.new_kernel.mfu" in err and "is new" in err
    assert "exec.old_kernel.mfu" in err
    assert "only in the baseline" in err
    md = (tmp_path / "cmp.md").read_text()
    cmp_md = md[md.index("## Comparison vs baseline"):]
    assert "`exec.shared.mfu`" in cmp_md and "**REGRESSED**" in cmp_md
    # the one-sided rows were skipped, not compared
    assert "exec.new_kernel.mfu" not in cmp_md
    assert "exec.old_kernel.mfu" not in cmp_md


# -- the update / score / validate split (ISSUE 24) ---------------------------


def _fe_cd(on_update=None):
    """A one-coordinate CD run over tiny data; ``on_update`` fires inside
    the coordinate's update (where a monitor thread's beat would land)."""
    from photon_ml_tpu.game import (
        FixedEffectCoordinate,
        ValidationSpec,
        build_game_dataset,
        run_coordinate_descent,
    )
    from photon_ml_tpu.ops.sparse import SparseBatch
    from photon_ml_tpu.optim import OptimizerConfig

    rng = np.random.default_rng(11)
    X = rng.normal(size=(64, 4))
    y = (rng.random(64) < 0.5).astype(float)
    gds = build_game_dataset(
        response=y, feature_shards={"g": SparseBatch.from_dense(X, y)})
    coord = FixedEffectCoordinate(
        "fixed", gds, "g", "logistic", OptimizerConfig(max_iterations=3))
    if on_update is not None:
        solve = coord.update_model

        def update_model(model, residual):
            on_update()
            return solve(model, residual)

        coord.update_model = update_model
    return run_coordinate_descent(
        {"fixed": coord}, task="logistic", num_iterations=1,
        validation=ValidationSpec(data=gds, evaluators=["auc"]))


def test_heartbeat_span_field_names_the_update():
    telemetry.reset()
    hb = Heartbeat(interval=60)
    lines = []
    _fe_cd(on_update=lambda: lines.append(hb.beat()))
    (line,) = lines
    assert line["span"] == (
        "coordinate_descent > cd_iteration > coordinate:fixed > update")
    telemetry.reset()


def test_phase_tree_splits_a_coordinate_and_self_times_add_up():
    telemetry.reset()
    _fe_cd()
    root = build_phase_tree([s.to_dict() for s in telemetry.finished_spans()])
    cd = root.children["coordinate_descent"]
    step = cd.children["cd_iteration"].children["coordinate:fixed"]
    assert set(step.children) == {"update", "score", "validate"}
    assert set(cd.children) == {"initial_scores", "cd_iteration"}

    def self_sum(node):
        return node.self_s + sum(self_sum(c) for c in node.children.values())

    # nothing is counted twice and nothing is lost: the self times of the
    # whole subtree are the root span's seconds
    assert self_sum(cd) == pytest.approx(cd.total_s, abs=1e-6)
    assert step.self_s < step.total_s
    md = RunReport(spans=[s.to_dict() for s in telemetry.finished_spans()],
                   snapshot=telemetry.snapshot()).to_markdown()
    assert "update" in md and "validate" in md and "initial_scores" in md
    telemetry.reset()
