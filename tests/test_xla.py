"""ISSUE 5 (hardware-level observability): the instrumented-jit executable
registry, recompile attribution, roofline peaks, collective estimates, the
run report's Device utilization section, heartbeat MFU fields, and the
`cli profile` capture path."""

import json
import logging
import os

import numpy as np
import pytest

from photon_ml_tpu import telemetry
from photon_ml_tpu.telemetry import xla
from photon_ml_tpu.telemetry.report import RunReport


FAKE_COST = {"flops": 1000.0, "bytes accessed": 640.0}
FAKE_MEM = {
    "temp_size_in_bytes": 128,
    "argument_size_in_bytes": 256,
    "output_size_in_bytes": 8,
    "generated_code_size_in_bytes": 4096,
}


@pytest.fixture
def fake_analysis():
    """Deterministic injected cost/memory analysis."""
    xla.set_analysis_provider(lambda compiled: (FAKE_COST, FAKE_MEM))
    yield
    xla.set_analysis_provider(None)


# -- registry round-trip ------------------------------------------------------


def test_registry_round_trip_with_injected_provider(fake_analysis):
    f = xla.instrumented_jit(lambda x: x * 2.0, name="double")
    x = np.ones((8,), np.float32)
    np.testing.assert_allclose(np.asarray(f(x)), 2.0)
    np.testing.assert_allclose(np.asarray(f(x)), 2.0)

    recs = xla.XLA_REGISTRY.executables("double")
    assert len(recs) == 1
    rec = recs[0]
    assert rec.calls == 2
    assert rec.flops == 1000.0
    assert rec.bytes_accessed == 640.0
    assert rec.temp_bytes == 128
    assert rec.argument_bytes == 256
    assert rec.output_bytes == 8
    assert rec.generated_code_bytes == 4096
    assert rec.compile_seconds >= 0
    assert rec.signature == ("f32[8]",)

    snap = telemetry.snapshot()["counters"]
    assert snap["xla.compiles"] == 1
    assert snap["xla.calls"] == 2
    assert snap["xla.flops_total"] == 2000.0
    assert snap["xla.bytes_total"] == 1280.0
    assert snap["xla.exec.double.calls"] == 2
    assert "xla.recompiles" not in snap

    # the registry snapshot is JSON-safe and ranked
    json.dumps(xla.XLA_REGISTRY.snapshot())


def test_unknown_degradation_when_analysis_unavailable():
    # a backend without cost/memory analysis: fields are None ("unknown"),
    # never zero, and nothing crashes
    xla.set_analysis_provider(lambda compiled: (None, None))
    f = xla.instrumented_jit(lambda x: x + 1.0, name="nocost")
    f(np.zeros((4,), np.float32))
    rec = xla.XLA_REGISTRY.executables("nocost")[0]
    assert rec.flops is None and rec.bytes_accessed is None
    assert rec.temp_bytes is None
    snap = telemetry.snapshot()["counters"]
    assert snap["xla.compiles"] == 1
    assert "xla.flops_total" not in snap  # unknown is not zero

    # a provider that RAISES degrades the same way
    def broken(compiled):
        raise RuntimeError("no analysis on this backend")

    xla.set_analysis_provider(broken)
    g = xla.instrumented_jit(lambda x: x - 1.0, name="nocost2")
    g(np.zeros((4,), np.float32))
    assert xla.XLA_REGISTRY.executables("nocost2")[0].flops is None


def test_real_cost_analysis_on_default_backend():
    # the CPU backend DOES publish cost analysis in this environment; the
    # real path must produce positive flops for a matmul
    f = xla.instrumented_jit(lambda a, b: a @ b, name="mm")
    f(np.ones((16, 8), np.float32), np.ones((8, 4), np.float32))
    rec = xla.XLA_REGISTRY.executables("mm")[0]
    assert rec.flops is None or rec.flops > 0  # None only if backend lacks it
    if rec.flops is not None:
        assert telemetry.snapshot()["counters"]["xla.flops_total"] > 0


# -- recompile attribution ----------------------------------------------------


def test_recompile_attributed_to_signature_delta(fake_analysis, caplog):
    f = xla.instrumented_jit(lambda x: x.sum(), name="sum_it")
    with telemetry.span("host"):
        f(np.zeros((4,), np.float32))
        f(np.zeros((4,), np.float32))  # same signature: no recompile
        f(np.zeros((9,), np.float32))  # shape change: recompile #1
    snap = telemetry.snapshot()["counters"]
    assert snap["xla.compiles"] == 2
    assert snap["xla.recompiles"] == 1
    assert snap["xla.exec.sum_it.recompiles"] == 1
    history = xla.XLA_REGISTRY.signature_history("sum_it")
    assert history == [("f32[4]",), ("f32[9]",)]
    # the span carries the recompile event with the exact delta
    span = telemetry.finished_spans("host")[0]
    ev = [e for e in span.events if e["name"] == "recompile"]
    assert len(ev) == 1
    assert "f32[4] -> f32[9]" in ev[0]["attrs"]["delta"]

    # a third distinct signature crosses RECOMPILE_WARN_THRESHOLD: one
    # structured warning naming the executable and the delta
    with caplog.at_level(
        logging.WARNING, logger="photon_ml_tpu.telemetry.xla"
    ):
        f(np.zeros((17,), np.float32))
    msgs = [r.message for r in caplog.records]
    assert any("recompile storm" in m and "sum_it" in m for m in msgs)
    assert any("f32[9] -> f32[17]" in m for m in msgs)
    # dtype changes attribute too
    f(np.zeros((17,), np.int32))
    history = xla.XLA_REGISTRY.signature_history("sum_it")
    assert history[-1] == ("i32[17]",)


def test_multi_shape_executables_are_not_recompile_storms(
    fake_analysis, caplog
):
    # the serving engine's batch buckets / per-bucket RE solvers compile a
    # signature SET by design: registered + accounted, never a storm
    f = xla.instrumented_jit(
        lambda x: x.sum(), name="bucketed", multi_shape=True
    )
    with caplog.at_level(
        logging.WARNING, logger="photon_ml_tpu.telemetry.xla"
    ):
        for n in (1, 2, 4, 8):
            f(np.zeros((n,), np.float32))
    snap = telemetry.snapshot()["counters"]
    assert snap["xla.exec.bucketed.compiles"] == 4
    assert "xla.recompiles" not in snap
    assert not any("recompile storm" in r.message for r in caplog.records)
    # every bucket's executable is still in the registry with its cost
    assert len(xla.XLA_REGISTRY.executables("bucketed")) == 4


def test_engine_warmup_counts_no_recompiles(fake_analysis):
    jnp = pytest.importorskip("jax.numpy")

    from photon_ml_tpu.game.models import FixedEffectModel, GameModel
    from photon_ml_tpu.serving.engine import ScoringEngine

    model = GameModel(
        task="logistic",
        models={
            "fixed": FixedEffectModel(
                coefficients=jnp.asarray([0.1, 0.2]), shard_name="global"
            )
        },
    )
    ScoringEngine(model, max_batch=8, version="v-w").warmup()
    # four buckets compiled, zero flagged as recompiles (the gate metric
    # must not fail a healthy warmup)
    counters = telemetry.snapshot()["counters"]
    assert "xla.recompiles" not in counters


def test_python_scalars_do_not_fragment_signatures(fake_analysis):
    # traced python scalars are typed, not valued, in the signature —
    # calling with different VALUES must not look like a recompile
    f = xla.instrumented_jit(lambda x, s: x * s, name="scale")
    f(np.ones((3,), np.float32), 2.0)
    f(np.ones((3,), np.float32), 7.0)
    assert telemetry.snapshot()["counters"]["xla.compiles"] == 1


def test_aot_failure_falls_back_to_plain_jit(fake_analysis):
    f = xla.instrumented_jit(lambda x: x * 3.0, name="fb")
    real_jit = f._jit

    class _LowerBoom:
        def lower(self, *a, **k):
            raise RuntimeError("AOT unsupported here")

        def __call__(self, *a, **k):
            return real_jit(*a, **k)

    f._jit = _LowerBoom()
    out = f(np.ones((2,), np.float32))
    np.testing.assert_allclose(np.asarray(out), 3.0)
    snap = telemetry.snapshot()["counters"]
    assert snap["xla.fallback_calls"] == 1
    assert snap["xla.compiles"] == 1  # still registered (cost unknown)
    assert xla.XLA_REGISTRY.executables("fb")[0].flops is None


# -- peaks / collectives ------------------------------------------------------


def test_device_peaks_injection():
    assert xla.device_peaks() == (None, None)  # CPU: unknown
    xla.set_peaks(1e12, 5e10)
    assert xla.device_peaks() == (1e12, 5e10)
    g = telemetry.snapshot()["gauges"]
    assert g["device.peak_flops"] == 1e12
    assert g["device.peak_hbm_bytes_per_sec"] == 5e10
    xla.reset()
    assert xla.device_peaks() == (None, None)


class _Kind:
    def __init__(self, kind):
        self.device_kind = kind


@pytest.mark.parametrize("kind, peaks", [
    ("TPU v5 lite", (197e12, 819e9)),  # what jax calls the v5e
    ("TPU v5e", (197e12, 819e9)),
    ("TPU v5p", (459e12, 2765e9)),
    ("TPU v4", (275e12, 1228e9)),
    # not keys of the table: unknown, never a neighbour's numbers
    ("TPU v5 lite pod", (None, None)),
    ("TPU v5x", (None, None)),
    ("TPU7x", (None, None)),
    ("cpu", (None, None)),
])
def test_device_peaks_match_the_exact_kind(monkeypatch, kind, peaks):
    import jax

    monkeypatch.setattr(jax, "devices", lambda *a: [_Kind(kind)])
    monkeypatch.setenv("PHOTON_PEAK_FLOPS", "2e12")  # no longer read
    assert xla.device_peaks() == peaks


def test_aot_compile_failure_is_logged_with_its_text(caplog):
    """A swallowed AOT refusal must name itself at WARNING level: the jit
    re-dispatch raises it again from a less obvious place."""
    import logging

    f = telemetry.instrumented_jit(lambda x: x + 1.0, name="loud")

    class _LowerBoom:
        def lower(self, *a, **k):
            raise NotImplementedError(
                "Mosaic kernels cannot be automatically partitioned")

        def __call__(self, *a, **k):
            return f._fn(*a, **k)

    f._jit = _LowerBoom()
    with caplog.at_level(logging.WARNING, "photon_ml_tpu.telemetry.xla"):
        f(np.ones((2,), np.float32))
    assert any(
        "loud" in r.getMessage()
        and "cannot be automatically partitioned" in r.getMessage()
        for r in caplog.records
    )
    assert telemetry.snapshot()["counters"]["xla.fallback_calls"] == 1


def test_collective_bytes_math():
    assert xla.collective_bytes("psum", 1, 1000) == 0  # elided
    assert xla.collective_bytes("psum", 4, 1000) == 1500  # 2*(3/4)
    assert xla.collective_bytes("all_gather", 4, 1000) == 750
    with pytest.raises(ValueError):
        xla.collective_bytes("all_to_all", 4, 1000)


def test_record_collective_gauges_and_span(fake_analysis):
    with telemetry.span("solve"):
        n = xla.record_collective("fe", "psum", 8, 4000, count=10)
    assert n == xla.collective_bytes("psum", 8, 4000) * 10
    snap = telemetry.snapshot()
    assert snap["counters"]["comms.bytes_total"] == n
    assert snap["counters"]["comms.fe.bytes"] == n
    # the per-call gauge is ONE collective's bytes, not the count total
    assert snap["gauges"]["comms.fe.bytes_per_call"] == xla.collective_bytes(
        "psum", 8, 4000
    )
    assert telemetry.finished_spans("solve")[0].attrs["comms_bytes"] == n
    # single-device: nothing recorded (no fake zeros)
    assert xla.record_collective("fe1", "psum", 1, 4000) == 0
    assert "comms.fe1.bytes" not in telemetry.snapshot()["counters"]


def test_distributed_solve_records_comms_estimate(rng):
    # the mesh-sharded FE solve publishes a comms.* estimate derived from
    # the mesh axis size and gradient payload
    import jax.numpy as jnp

    from photon_ml_tpu.ops.sparse import SparseBatch
    from photon_ml_tpu.optim.factory import OptimizerConfig
    from photon_ml_tpu.parallel.distributed import distributed_solve
    from photon_ml_tpu.parallel.mesh import make_mesh, shard_rows

    pytest.importorskip("jax")
    n, d = 64, 5
    vals = rng.normal(size=n * 3)
    rows = np.repeat(np.arange(n), 3)
    cols = rng.integers(0, d, n * 3)
    y = (rng.random(n) > 0.5).astype(float)
    batch = SparseBatch.from_coo(
        values=vals, rows=rows, cols=cols, labels=y, num_features=d
    )
    mesh = make_mesh()
    stacked = shard_rows(batch, int(mesh.devices.size))
    cfg = OptimizerConfig(max_iterations=3)
    try:
        distributed_solve(
            "logistic", stacked, cfg, jnp.zeros((d,), jnp.float32), mesh
        )
    except AttributeError:
        pass  # jax.shard_map missing on this jax (pre-existing seed skip)
    counters = telemetry.snapshot()["counters"]
    expected = xla.collective_bytes(
        "psum", int(mesh.devices.size), d * 4 + 4
    ) * 3
    assert counters["comms.distributed_solve.bytes"] == expected


# -- heartbeat fields ---------------------------------------------------------


def test_heartbeat_gains_mfu_and_comms_fraction(fake_analysis):
    from photon_ml_tpu.telemetry.progress import Heartbeat

    xla.set_peaks(1e9, None)
    hb = Heartbeat(interval=60.0)
    line = hb.beat()
    assert "mfu" not in line and "comms_fraction" not in line  # no work yet
    # probing must not REGISTER the counters: a zero in the snapshot
    # would read as "0 FLOPs" downstream instead of "unknown"
    assert "xla.flops_total" not in telemetry.snapshot()["counters"]
    assert "comms.bytes_total" not in telemetry.snapshot()["counters"]
    f = xla.instrumented_jit(lambda x: x + 1, name="hb_work")
    f(np.zeros((4,), np.float32))
    xla.record_collective("hb", "psum", 4, 1000)
    line = hb.beat()
    assert line["mfu"] > 0
    comms = xla.collective_bytes("psum", 4, 1000)
    assert line["comms_fraction"] == pytest.approx(
        comms / (comms + FAKE_COST["bytes accessed"])
    )
    # peaks unknown: the mfu field is OMITTED, not zero
    xla.reset()
    xla.set_analysis_provider(lambda compiled: (FAKE_COST, FAKE_MEM))
    g = xla.instrumented_jit(lambda x: x + 2, name="hb_work2")
    g(np.zeros((4,), np.float32))
    line = hb.beat()
    assert "mfu" not in line


# -- run report: Device utilization -------------------------------------------


def test_device_utilization_none_without_accounting():
    report = RunReport.from_live()
    assert report.device_utilization() is None
    assert "Device utilization" not in report.to_markdown()


def test_device_utilization_unknown_rendering(fake_analysis):
    # cost known but peaks unknown: MFU/BW render the explicit string
    # "unknown", phases still carry FLOPs
    f = xla.instrumented_jit(lambda x: x * 2, name="phase_work")
    with telemetry.span("fit"):
        f(np.ones((4,), np.float32))
    report = RunReport.from_live()
    du = report.device_utilization()
    assert du["mfu"] is None and du["flops_total"] == FAKE_COST["flops"]
    assert du["phases"][0]["phase"] == "fit"
    assert du["phases"][0]["flops"] == FAKE_COST["flops"]
    md = report.to_markdown()
    assert "## Device utilization" in md
    assert "- MFU: unknown" in md
    assert "device peak FLOP/s unknown" in md


def test_comms_fraction_unknown_without_hbm_bytes():
    # comms recorded but NO cost analysis (bytes unknown): the fraction
    # denominator is unknowable — "unknown", never a fabricated 100%
    xla.set_analysis_provider(lambda compiled: (None, None))
    f = xla.instrumented_jit(lambda x: x + 1, name="nk")
    with telemetry.span("fit"):
        f(np.zeros((2,), np.float32))
        xla.record_collective("s", "psum", 4, 1000)
    du = RunReport.from_live().device_utilization()
    assert du["comms_bytes_total"] > 0
    assert du["comms_fraction"] is None
    md = RunReport.from_live().to_markdown()
    assert "comms fraction unknown" in md


def test_device_utilization_full(fake_analysis):
    xla.set_peaks(1e12, 1e11)
    f = xla.instrumented_jit(lambda x: x * 2, name="work")
    with telemetry.span("fit"):
        with telemetry.span("coordinate:fixed"):
            f(np.ones((4,), np.float32))
            xla.record_collective("solve", "psum", 8, 4000)
    report = RunReport.from_live()
    du = report.device_utilization()
    assert du["mfu"] > 0 and du["bandwidth_utilization"] > 0
    assert du["comms_bytes_total"] == xla.collective_bytes("psum", 8, 4000)
    assert 0 < du["comms_fraction"] < 1
    assert du["compile_time_share"] is not None
    # the child phase rolls up into the parent's subtree numbers
    phases = {p["phase"]: p for p in du["phases"]}
    assert phases["fit"]["flops"] == FAKE_COST["flops"]
    assert phases["fit > coordinate:fixed"]["flops"] == FAKE_COST["flops"]
    top = du["top_executables"]
    assert top and top[0]["name"] == "work"
    md = report.to_markdown(deltas=None)
    assert "## Device utilization" in md
    assert "Top executables by cost" in md and "`work`" in md
    # key metrics expose mfu for the CI gate
    assert report.key_metrics()["mfu"] == pytest.approx(du["mfu"])
    # and the JSON document carries the whole structure
    doc = report.to_json()
    assert doc["device_utilization"]["mfu"] == pytest.approx(du["mfu"])


# -- serving per-bucket compile state -----------------------------------------


def test_engine_compile_summary_per_bucket(fake_analysis):
    jnp = pytest.importorskip("jax.numpy")

    from photon_ml_tpu.game.models import FixedEffectModel, GameModel
    from photon_ml_tpu.serving.engine import ScoringEngine

    model = GameModel(
        task="logistic",
        models={
            "fixed": FixedEffectModel(
                coefficients=jnp.asarray([0.5, -0.25, 0.1]),
                shard_name="global",
            )
        },
    )
    engine = ScoringEngine(model, max_batch=4, version="v-1").warmup()
    summary = engine.compile_summary()
    assert set(summary) == {"1", "2", "4"}
    for entry in summary.values():
        assert entry["compile_seconds"] >= 0
        assert entry["flops"] == FAKE_COST["flops"]
        assert entry["calls"] >= 1


# -- e2e acceptance: fit -> report with finite MFU -----------------------------


def test_e2e_fit_report_device_utilization(tmp_path):
    """ISSUE 5 acceptance: a default-backend fit + `cli report` run whose
    Device utilization section reports per-phase FLOPs, MFU, bandwidth
    utilization, compile-time share, and collective-bytes state (explicit
    "unknown" where the backend/peaks offer nothing)."""
    from photon_ml_tpu.cli.report import main as report_main
    from photon_ml_tpu.game.estimator import (
        FixedEffectConfig,
        GameConfig,
        GameEstimator,
    )
    from photon_ml_tpu.optim.factory import OptimizerConfig
    from photon_ml_tpu.testing import generate_game_dataset

    # pin peaks so MFU is finite on the CPU test backend
    xla.set_peaks(1e12, 1e11)
    data, _ = generate_game_dataset(
        task="logistic", n_users=4, rows_per_user=8, fe_dim=4, re_dim=2
    )
    trace_out = tmp_path / "run.trace.jsonl"
    tele_out = tmp_path / "run.metrics.jsonl"
    telemetry.configure(trace_out=str(trace_out))
    estimator = GameEstimator(GameConfig(
        task="logistic",
        coordinates={
            "fixed": FixedEffectConfig(
                shard_name="global",
                optimizer=OptimizerConfig(max_iterations=3),
            ),
        },
        num_iterations=1,
    ))
    estimator.fit(data)
    telemetry.flush_metrics(str(tele_out))

    live = RunReport.from_live()
    du = live.device_utilization()
    assert du is not None
    # the CPU backend publishes cost analysis here: finite MFU
    assert du["flops_total"] > 0
    assert np.isfinite(du["mfu"]) and du["mfu"] > 0
    assert np.isfinite(du["bandwidth_utilization"])
    assert du["compile_time_share"] is not None
    assert any("coordinate:fixed" in p["phase"] for p in du["phases"])

    md_path = tmp_path / "report.md"
    rc = report_main([
        "--trace", str(trace_out),
        "--telemetry", str(tele_out),
        "--out", str(md_path),
    ])
    assert rc == 0
    md = md_path.read_text()
    assert "## Device utilization" in md
    assert "- MFU: " in md and "- MFU: unknown" not in md
    assert "Top executables by cost" in md
    assert "`fe_solve`" in md


# -- cli profile --------------------------------------------------------------


def test_cli_profile_wraps_a_train_run(tmp_path):
    """`cli profile -- train ...` produces a profiler capture dir next to
    the span trace, the capture holds the span tree (every span mirrors
    itself as a ``photon:`` annotation, no switch), and the wrapped
    command's exit code comes back."""
    from jax.profiler import ProfileData

    from photon_ml_tpu.cli.__main__ import main as cli_main

    rng = np.random.default_rng(7)
    lib = tmp_path / "train.libsvm"
    lines = []
    for i in range(64):
        x = rng.normal(size=3)
        label = 1 if x.sum() + 0.1 * rng.normal() > 0 else 0
        feats = " ".join(f"{j + 1}:{x[j]:.4f}" for j in range(3))
        lines.append(f"{label} {feats}")
    lib.write_text("\n".join(lines) + "\n")
    config = {
        "task": "logistic",
        "input": {
            "format": "libsvm", "paths": [str(lib)],
            "shard_name": "features",
        },
        "coordinates": {
            "fixed": {
                "type": "fixed_effect", "shard_name": "features",
                "optimizer": {"max_iterations": 3},
            }
        },
        "num_iterations": 1,
        "heartbeat": False,
    }
    cfg_path = tmp_path / "t.json"
    cfg_path.write_text(json.dumps(config))
    prof_dir = tmp_path / "prof"
    trace_out = tmp_path / "run.trace.jsonl"
    rc = cli_main([
        "profile", "--profile-dir", str(prof_dir), "--",
        "train", "--config", str(cfg_path), "--trace-out", str(trace_out),
    ])
    assert rc == 0
    # capture dir exists alongside the span trace
    assert prof_dir.is_dir()
    captured = [
        os.path.join(r, f)
        for r, _d, files in os.walk(prof_dir)
        for f in files
    ]
    assert captured, "profiler capture dir is empty"
    assert trace_out.exists()
    # the capture holds the program's spans, on the host plane
    (xplane,) = [p for p in captured if p.endswith(".xplane.pb")]
    mirrored = {
        e.name
        for plane in ProfileData.from_file(xplane).planes
        if plane.name == "/host:CPU"
        for line in plane.lines for e in line.events
        if e.name.startswith("photon:")
    }
    assert {"photon:fit", "photon:build_coordinates",
            "photon:coordinate_descent", "photon:coordinate:fixed",
            "photon:update"} <= mirrored
    # the knob that used to turn the mirror off is gone
    with pytest.raises(SystemExit):
        cli_main(["profile", "--profile-dir", str(prof_dir),
                  "--no-annotations", "--", "train", "--config",
                  str(cfg_path)])


def test_cli_profile_requires_wrapped_command(tmp_path):
    from photon_ml_tpu.cli.profile import main as profile_main

    with pytest.raises(SystemExit):
        profile_main(["--profile-dir", str(tmp_path / "p")])
