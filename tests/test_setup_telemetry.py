"""Set-up seen from inside the program (ISSUE 35): the compile pipeline's
phases as counters, span events and executable-record fields, telemetry's
own analysis seconds, the package's import seconds, and the spans of
dataset assembly and of a coordinate's build; plus the arithmetic of the
benchmark readers that read them."""

import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import monitoring

from photon_ml_tpu import telemetry
from photon_ml_tpu.telemetry import device, metrics
from photon_ml_tpu.telemetry.trace import Span
from photon_ml_tpu.telemetry.xla import XLA_REGISTRY, instrumented_jit

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PHASES = [
    ("/jax/core/compile/jaxpr_trace_duration", "jit_trace_seconds",
     "jaxpr_trace"),
    ("/jax/core/compile/jaxpr_to_mlir_module_duration", "jit_lower_seconds",
     "lowering"),
    ("/jax/compilation_cache/cache_retrieval_time_sec",
     "jit_cache_load_seconds", "cache_load"),
]


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    yield
    telemetry.reset()


def _counter(name):
    return telemetry.snapshot()["counters"].get(name)


# -- the compile pipeline's phases --------------------------------------------


@pytest.mark.parametrize("event, counter, label", PHASES)
def test_phase_event_raises_its_counter_and_marks_the_open_span(
        event, counter, label):
    assert _counter(counter) == 0  # declared, even after a reset
    with telemetry.span("setup_step"):
        monitoring.record_event_duration_secs(
            event, 0.25, fun_name="jit(my_program)")
    assert _counter(counter) == pytest.approx(0.25)
    (s,) = telemetry.finished_spans("setup_step")
    (ev,) = [e for e in s.events if e["name"] == label]
    assert ev["attrs"] == {"seconds": 0.25, "fun_name": "jit(my_program)"}


def test_backend_compile_event_counts_whole_and_marks_the_span():
    event = "/jax/core/compile/backend_compile_duration"
    with telemetry.span("setup_step"):
        monitoring.record_event_duration_secs(
            event, 0.5, fun_name="jit(my_program)")
    assert _counter("jit_compile_seconds") == pytest.approx(0.5)
    assert _counter("jit_compile_seconds_eager") == pytest.approx(0.5)
    (s,) = telemetry.finished_spans("setup_step")
    (ev,) = [e for e in s.events if e["name"] == "compile"]
    assert ev["attrs"]["eager"] is True
    assert ev["attrs"]["fun_name"] == "jit(my_program)"


def test_a_phase_inside_another_counts_once():
    """jax traces a jitted callee inside its caller's trace, and reads the
    cache inside the backend event: each phase counts its own seconds, so
    the counters add up to wall time, not more."""
    trace_ev = "/jax/core/compile/jaxpr_trace_duration"
    compile_ev = "/jax/core/compile/backend_compile_duration"
    load_ev = "/jax/compilation_cache/cache_retrieval_time_sec"
    with telemetry.span("outer"):
        monitoring.record_scalar(trace_ev, time.time(), fun_name="caller")
        monitoring.record_scalar(trace_ev, time.time(), fun_name="callee")
        monitoring.record_event_duration_secs(trace_ev, 0.3, fun_name="callee")
        monitoring.record_event_duration_secs(trace_ev, 1.0, fun_name="caller")
        monitoring.record_scalar(compile_ev, time.time(), fun_name="jit(f)")
        monitoring.record_event_duration_secs(load_ev, 0.75)
        monitoring.record_event_duration_secs(
            compile_ev, 1.0, fun_name="jit(f)")
    assert _counter("jit_trace_seconds") == pytest.approx(1.0)
    assert _counter("jit_cache_load_seconds") == pytest.approx(0.75)
    # the backend event stays whole: on a cache hit it holds the load
    assert _counter("jit_compile_seconds") == pytest.approx(1.0)
    (s,) = telemetry.finished_spans("outer")
    names = [e["name"] for e in s.events]
    # the callee's trace and the load are inside the caller's and the
    # compile's events: one event a phase of a program
    assert names == ["jaxpr_trace", "compile"]


def test_instrumented_compile_fills_the_phases_of_its_record():
    salt = float(len(XLA_REGISTRY.executables())) + 0.375

    def fn(x):
        return jnp.tanh(x) * salt + jnp.cos(x)

    f = instrumented_jit(fn, name="setup_phase_probe")
    jax.clear_caches()  # a real trace + lower, not jax's in-memory hit
    with telemetry.span("first_call"):
        np.asarray(f(jnp.ones((3, 11))))
    (rec,) = XLA_REGISTRY.executables("setup_phase_probe")
    assert rec.trace_seconds > 0 and rec.lower_seconds > 0
    assert rec.backend_seconds + rec.cache_load_seconds > 0
    named = (rec.trace_seconds + rec.lower_seconds + rec.cache_load_seconds
             + rec.backend_seconds)
    assert named <= rec.compile_seconds + 1e-6
    counters = telemetry.snapshot()["counters"]
    for phase in ("trace", "lower", "cache_load", "backend"):
        assert counters[f"xla.exec.setup_phase_probe.{phase}_seconds"] == (
            pytest.approx(getattr(rec, f"{phase}_seconds")))
    assert counters["xla.analysis_seconds"] > 0
    assert not any(k.endswith(".mosaic_kernels")
                   for k in telemetry.snapshot()["gauges"])
    (s,) = telemetry.finished_spans("first_call")
    mine = [e for e in s.events
            if e["attrs"].get("executable") == "setup_phase_probe"]
    assert {"jaxpr_trace", "lowering", "compile"} <= {e["name"] for e in mine}


def test_a_listener_that_raises_never_fails_a_compile(monkeypatch):
    def broken(*_a, **_k):
        raise RuntimeError("telemetry is broken")

    # every listener of the compile hooks goes through it
    monkeypatch.setattr(device, "_open_phases", broken)
    salt = time.perf_counter()

    @jax.jit
    def fresh(v):
        return v * salt + jnp.sin(v)

    out = fresh(jnp.ones((2, 9)))
    np.testing.assert_allclose(
        np.asarray(out), np.ones((2, 9)) * salt + np.sin(1.0), rtol=1e-6)
    f = instrumented_jit(lambda v: v + salt, name="setup_broken_probe")
    np.testing.assert_allclose(np.asarray(f(jnp.zeros(5))), salt, rtol=1e-6)


# -- the import clock ----------------------------------------------------------


def test_import_seconds_survive_a_reset():
    before = _counter("import.seconds")
    assert before > 0  # the package's own import, jax and pallas in it
    telemetry.reset()
    assert _counter("import.seconds") == before


def test_import_seconds_count_the_outermost_import_once():
    """A fresh process: the package, then two subpackages imported later;
    the counter holds each outermost import once, so it cannot exceed the
    wall time around all three."""
    code = (
        "import time; t0 = time.perf_counter()\n"
        "import photon_ml_tpu\n"
        "from photon_ml_tpu import _import_clock as c\n"
        "first = c.seconds()\n"
        "import photon_ml_tpu.game, photon_ml_tpu.config\n"
        "wall = time.perf_counter() - t0\n"
        "from photon_ml_tpu import telemetry\n"
        "n = telemetry.snapshot()['counters']['import.seconds']\n"
        "print(first, n, wall)\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    first, total, wall = map(float, out.stdout.split())
    assert 0 < first < total <= wall


def test_a_counter_provider_is_published_and_survives_reset():
    metrics.register_counter_provider("setup_test.kept", lambda: 2.5)
    try:
        assert _counter("setup_test.kept") == 2.5
        telemetry.reset()
        assert _counter("setup_test.kept") == 2.5
    finally:
        metrics.REGISTRY._counter_providers.pop("setup_test.kept")


# -- dataset assembly and a coordinate's build ----------------------------------


def test_dataset_assembly_opens_its_spans():
    from photon_ml_tpu.game import build_game_dataset
    from photon_ml_tpu.ops.sparse import SparseBatch

    rows = np.array([1, 0, 2, 2])  # out of order: the sort runs
    batch = SparseBatch.from_coo(
        values=np.ones(4), rows=rows, cols=np.array([0, 1, 0, 1]),
        labels=np.array([0.0, 1.0, 1.0]), num_features=2)
    np.testing.assert_array_equal(batch.rows[:4], [0, 1, 2, 2])
    build_game_dataset(
        response=np.array([0.0, 1.0, 1.0]), feature_shards={"g": batch},
        id_columns={"userId": np.array(["b", "a", "b"])})
    by_id = {s.span_id: s for s in telemetry.finished_spans()}
    parent = {s.name: by_id[s.parent_id].name if s.parent_id else None
              for s in by_id.values()}
    assert parent["dataset.sparse_batch"] is None
    assert parent["dataset.game"] is None
    for child in ("dataset.validate", "dataset.sort", "dataset.pad"):
        assert parent[child] == "dataset.sparse_batch"
    assert parent["dataset.ids"] == "dataset.game"
    assert parent["dataset.pad_rows"] == "dataset.game"


def test_coordinate_build_names_its_own_steps():
    from photon_ml_tpu.config import parse_game_config
    from photon_ml_tpu.game import GameEstimator
    from photon_ml_tpu.testing import generate_game_dataset

    data = generate_game_dataset(n_users=6, rows_per_user=8, seed=3)[0]
    est = GameEstimator(parse_game_config({
        "task": "logistic",
        "coordinates": {
            "fixed": {"type": "fixed_effect", "shard_name": "global",
                      "optimizer": {"type": "lbfgs"}},
            "per-user": {"type": "random_effect", "shard_name": "user",
                         "id_name": "userId",
                         "optimizer": {"type": "newton"}},
        },
    }))
    est._build_coordinates(data, mesh=None)
    by_id = {s.span_id: s for s in telemetry.finished_spans()}
    under = {}
    for s in by_id.values():
        if s.parent_id in by_id:
            under.setdefault(by_id[s.parent_id].name, set()).add(s.name)
    assert {"build.normalization", "build.rows", "build.objective"} <= (
        under["build:fixed"])
    assert {"build.table_estimate", "build.layout_report",
            "build.objective"} <= under["build:per-user"]


# -- the benchmark readers -------------------------------------------------------


def _span(i, name, ts, dur, parent=None):
    s = Span(name, i, parent, ts, "MainThread", {})
    s.dur = dur
    return s


def _tree():
    """Set-up as the driver makes it: two shards and a dataset, the
    coordinates' build, the warm-up fit; then a window fit."""
    return [
        _span(1, "dataset.sparse_batch", 10.0, 2.0),
        _span(2, "dataset.pad", 11.0, 0.5, parent=1),
        _span(3, "dataset.sparse_batch", 12.5, 1.0),
        _span(4, "dataset.game", 14.0, 0.5),
        _span(5, "dataset.ids", 14.1, 0.25, parent=4),
        _span(6, "build_coordinates", 15.0, 6.0),
        _span(7, "build:fixed", 15.0, 5.5, parent=6),
        _span(8, "build_coordinates", 22.0, 0.0625),
        _span(9, "coordinate_descent", 22.5, 8.0),
        # the window: starts after the warm-up ends, counts in neither
        _span(10, "dataset.sparse_batch", 40.0, 3.0),
        _span(11, "build_coordinates", 44.0, 0.0625),
        _span(12, "coordinate_descent", 45.0, 2.0),
    ]


def test_setup_span_seconds_sums_the_outermost_setup_spans(monkeypatch):
    from benchmark.readers import setup_span_seconds

    monkeypatch.setattr(telemetry, "finished_spans", _tree)
    value = setup_span_seconds.read({}, span=r"dataset\..*")
    assert value == pytest.approx(2.0 + 1.0 + 0.5)
    assert setup_span_seconds.read({}, span=r"build:.*") == (
        pytest.approx(5.5))
    assert setup_span_seconds.read({}, span=r"nothing\..*") is None
    monkeypatch.setattr(telemetry, "finished_spans", lambda: _tree()[:8])
    assert setup_span_seconds.read({}, span=r"dataset\..*") is None


def test_setup_unattributed_is_setup_less_what_is_named(monkeypatch):
    from benchmark.readers import setup_unattributed

    monkeypatch.setattr(telemetry, "finished_spans", _tree)
    ctx = {"setup_s": 40.0, "spans": {"generate_data": 3.0},
           "counters": {"setup_end": {"import.seconds": 4.0}}}
    params = {"counter": "import.seconds", "driver_spans": ["generate_data"]}
    # roots before the warm-up's end: 2 + 1 + 0.5 + 6 + 0.0625 + 8
    assert setup_unattributed.read(ctx, **params) == pytest.approx(
        40.0 - 4.0 - 3.0 - 17.5625)
    # overlapping roots (another thread's) count once
    assert setup_unattributed.covered(
        [(0.0, 2.0), (1.0, 3.0), (5.0, 6.0)]) == pytest.approx(4.0)
    # a program without the counter, the driver's span or the root: nothing
    without = {**ctx, "counters": {"setup_end": {}}}
    assert setup_unattributed.read(without, **params) is None
    assert setup_unattributed.read({**ctx, "spans": {}}, **params) is None
    monkeypatch.setattr(telemetry, "finished_spans", lambda: _tree()[:8])
    assert setup_unattributed.read(ctx, **params) is None


def test_kept_counter_delta_reads_nothing_where_a_counter_is_missing():
    from benchmark.readers import kept_counter_delta

    ctx = {"counters": {"setup_end": {"a": 2.0, "b": 0.5}}}
    read = kept_counter_delta.read
    assert read(ctx, ["a", "b"], "process_start", "setup_end") == 2.5
    assert read(ctx, ["a"], "process_start", "setup_end") == 2.0
    assert read(ctx, ["a", "c"], "process_start", "setup_end") is None
    assert read(ctx, ["a"], "process_start", "window_end") is None
