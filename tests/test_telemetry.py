"""Telemetry subsystem: span trees, metrics registry, device accounting,
the timed()/Timer integration, event-bus hardening, and tracker telemetry.
"""

import json
import os
import sys
import threading
import types

import numpy as np
import pytest

from photon_ml_tpu import telemetry
from photon_ml_tpu.telemetry import trace as ttrace


@pytest.fixture(autouse=True)
def _clean_telemetry():
    telemetry.reset()
    yield
    telemetry.reset()


# -- spans -------------------------------------------------------------------


def test_span_tree_nesting_and_attrs():
    with telemetry.span("outer", phase="x") as outer:
        with telemetry.span("inner") as inner:
            inner.set_attr(k=1)
        assert inner.parent_id == outer.span_id
    spans = {s.name: s for s in telemetry.finished_spans()}
    assert spans["outer"].parent_id is None
    assert spans["outer"].dur is not None and spans["outer"].dur >= 0
    assert spans["inner"].attrs == {"k": 1}
    assert spans["outer"].attrs == {"phase": "x"}
    # children close before parents
    assert spans["inner"].ts >= spans["outer"].ts


def test_span_events_attach_to_current_span():
    telemetry.add_event("orphan")  # no open span: must be a silent no-op
    with telemetry.span("s"):
        telemetry.add_event("marker", code=7)
    (s,) = telemetry.finished_spans("s")
    assert [e["name"] for e in s.events] == ["marker"]
    assert s.events[0]["attrs"] == {"code": 7}


def test_spans_are_per_thread_roots():
    done = threading.Event()

    def worker():
        with telemetry.span("worker_root"):
            pass
        done.set()

    with telemetry.span("main_root"):
        t = threading.Thread(target=worker, name="w0")
        t.start()
        t.join()
    assert done.wait(1)
    (w,) = telemetry.finished_spans("worker_root")
    # a span opened on another thread is NOT parented under main's span
    assert w.parent_id is None
    assert w.thread == "w0"


def test_jsonl_sink_and_chrome_export(tmp_path):
    out = tmp_path / "trace.jsonl"
    telemetry.configure(trace_out=str(out))
    with telemetry.span("fit"):
        with telemetry.span("step"):
            telemetry.add_event("device_fetch", bytes=4)
    lines = [json.loads(x) for x in out.read_text().splitlines()]
    assert lines[0]["type"] == "trace_header"
    spans = [x for x in lines if x["type"] == "span"]
    assert [s["name"] for s in spans] == ["step", "fit"]  # close order
    assert spans[0]["parent"] == spans[1]["id"]

    perfetto = tmp_path / "trace.json"
    n = telemetry.export_chrome_trace(str(out), str(perfetto))
    doc = json.loads(perfetto.read_text())
    events = doc["traceEvents"]
    assert n == len(events)
    complete = [e for e in events if e["ph"] == "X"]
    instants = [e for e in events if e["ph"] == "i"]
    assert {e["name"] for e in complete} == {"fit", "step"}
    assert instants[0]["name"] == "device_fetch"
    # microsecond timebase, monotone non-negative
    assert all(e["ts"] >= 0 for e in events if "ts" in e)


def test_configure_truncates_stale_trace_file(tmp_path):
    out = tmp_path / "trace.jsonl"
    out.write_text('{"type": "span", "name": "stale_run"}\n')
    telemetry.configure(trace_out=str(out))
    with telemetry.span("fresh"):
        pass
    lines = [json.loads(x) for x in out.read_text().splitlines()]
    # one session per file: the stale run is gone, header leads
    assert lines[0]["type"] == "trace_header"
    assert [x["name"] for x in lines if x["type"] == "span"] == ["fresh"]


def test_reset_clears_other_threads_open_spans():
    leaked = threading.Event()
    release = threading.Event()

    def worker():
        cm = ttrace.TRACER.span("leaked_parent")
        cm.__enter__()
        leaked.set()
        release.wait(5)
        with ttrace.TRACER.span("post_reset"):
            pass

    t = threading.Thread(target=worker, name="leaky")
    t.start()
    assert leaked.wait(5)
    telemetry.reset()  # must clear the WORKER's open stack too
    release.set()
    t.join()
    (post,) = telemetry.finished_spans("post_reset")
    assert post.parent_id is None  # not parented under the stale span


def test_tracer_survives_out_of_order_exit():
    tr = ttrace.Tracer()
    outer_cm = tr.span("outer")
    outer_cm.__enter__()
    inner_cm = tr.span("inner")
    inner_cm.__enter__()
    # exit OUTER first (a leaked inner span); tracer must not corrupt
    outer_cm.__exit__(None, None, None)
    assert tr.current() is None
    with tr.span("next"):
        pass
    assert {s.name for s in tr.finished_spans()} >= {"outer", "next"}


def test_tracer_counts_dropped_spans_on_buffer_overflow():
    """Satellite: buffer overflow must not be silent — drops are counted
    in `trace.dropped_spans` and surfaced through snapshot()."""
    ttrace.TRACER.configure(buffer_limit=5)
    for i in range(12):
        with telemetry.span(f"s{i}"):
            pass
    assert len(telemetry.finished_spans()) == 5
    assert ttrace.TRACER.dropped_spans == 7
    assert telemetry.snapshot()["counters"]["trace.dropped_spans"] == 7
    # reset restores the default buffer limit AND clears drop accounting
    telemetry.reset()
    assert ttrace.TRACER._buffer_limit == ttrace.DEFAULT_BUFFER_LIMIT
    assert ttrace.TRACER.dropped_spans == 0


def test_active_span_path_visible_from_other_thread():
    seen = {}
    ready = threading.Event()
    release = threading.Event()

    def watcher():
        ready.wait(5)
        seen["path"] = telemetry.active_span_path()
        release.set()

    t = threading.Thread(target=watcher, name="watcher")
    t.start()
    with telemetry.span("fit"):
        with telemetry.span("coordinate:x"):
            ready.set()
            assert release.wait(5)
    t.join()
    assert seen["path"] == "fit > coordinate:x"
    assert telemetry.active_span_path() == ""  # nothing open now


def test_to_chrome_trace_multi_thread_spans():
    """Satellite: spans finishing on multiple threads export with one
    thread lane (tid + thread_name metadata) per thread."""
    barrier = threading.Barrier(3)

    def worker():
        barrier.wait(5)
        with telemetry.span("work"):
            telemetry.add_event("tick")

    threads = [
        threading.Thread(target=worker, name=f"w{i}") for i in range(2)
    ]
    for t in threads:
        t.start()
    with telemetry.span("main_work"):
        barrier.wait(5)
    for t in threads:
        t.join()
    records = [s.to_dict() for s in telemetry.finished_spans()]
    doc = telemetry.to_chrome_trace(records)
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    lanes = {e["args"]["name"]: e["tid"] for e in meta}
    assert {"w0", "w1", "MainThread"} <= set(lanes)
    assert len(set(lanes.values())) == len(lanes)  # distinct tids
    # each worker span rides ITS thread's tid, instants included
    by_name = {}
    for e in events:
        if e["ph"] in ("X", "i"):
            by_name.setdefault(e["name"], set()).add(e["tid"])
    assert by_name["work"] == {lanes["w0"], lanes["w1"]}
    assert by_name["tick"] == {lanes["w0"], lanes["w1"]}
    assert by_name["main_work"] == {lanes["MainThread"]}


# -- metrics -----------------------------------------------------------------


def test_counters_gauges_histograms_snapshot():
    telemetry.counter("c").inc()
    telemetry.counter("c").inc(2.5)
    telemetry.gauge("g").set(7)
    h = telemetry.histogram("h")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    snap = telemetry.snapshot()
    assert snap["counters"]["c"] == pytest.approx(3.5)
    assert snap["gauges"]["g"] == 7.0
    hs = snap["histograms"]["h"]
    assert hs["count"] == 4
    assert hs["sum"] == pytest.approx(10.0)
    assert hs["min"] == 1.0 and hs["max"] == 4.0
    assert hs["p50"] in (2.0, 3.0)
    # snapshot is JSON-safe
    json.dumps(snap)


def test_histogram_reservoir_bounded_and_percentiles_sane():
    h = telemetry.histogram("big")
    h.observe_many(float(i) for i in range(100_000))
    s = h.summary()
    assert s["count"] == 100_000
    assert s["sum"] == pytest.approx(sum(range(100_000)))
    assert len(h._sample) <= 4096
    # uniform reservoir over 0..1e5: p50 within a loose band
    assert 30_000 < s["p50"] < 70_000
    assert s["min"] == 0.0 and s["max"] == 99_999.0
    # the vectorized bulk path and the scalar path agree on exact stats
    h2 = telemetry.histogram("big_np")
    h2.observe_many(np.arange(100_000, dtype=np.int32))  # array input
    for k in ("count", "sum", "min", "max"):
        assert h2.summary()[k] == s[k]


def test_histogram_summary_empty_and_single_value():
    h = telemetry.histogram("edge")
    assert h.summary() == {"count": 0}  # empty: count only, no percentiles
    h.observe_many([])  # empty bulk observe: a no-op, not an error
    h.observe_many(iter(()))  # empty ITERATOR (no __len__) too
    assert h.summary() == {"count": 0}
    h.observe_many([2.5])  # single value: every stat collapses onto it
    s = h.summary()
    assert s["count"] == 1
    assert s["sum"] == s["min"] == s["max"] == s["mean"] == 2.5
    assert all(s[f"p{p}"] == 2.5 for p in (5, 25, 50, 75, 95, 99))


def test_histogram_observe_many_reservoir_cap_overflow():
    """Bulk observes that CROSS the reservoir cap keep exact aggregate
    stats, a bounded sample, and in-range percentiles."""
    h = telemetry.histogram("cap_cross")
    h.observe_many(np.arange(4000, dtype=np.float64))  # under cap (4096)
    assert len(h._sample) == 4000
    h.observe_many(np.arange(4000, 50_000, dtype=np.float64))  # crosses it
    s = h.summary()
    assert s["count"] == 50_000
    assert s["sum"] == pytest.approx(sum(range(50_000)))
    assert s["min"] == 0.0 and s["max"] == 49_999.0
    assert len(h._sample) == 4096  # cap held after the crossing
    assert all(0.0 <= v <= 49_999.0 for v in h._sample)
    # another bulk round entirely IN the replacement regime
    h.observe_many(np.full(10_000, -7.0))
    assert h.summary()["count"] == 60_000
    assert h.summary()["min"] == -7.0
    assert len(h._sample) == 4096
    # a scalar observe after bulk stays consistent too
    h.observe(123.0)
    assert h.summary()["count"] == 60_001


def test_metrics_flush_jsonl(tmp_path):
    telemetry.counter("x").inc(3)
    path = tmp_path / "metrics.jsonl"
    snap = telemetry.flush_metrics(str(path))
    telemetry.counter("x").inc()
    telemetry.flush_metrics(str(path))  # appends
    lines = [json.loads(x) for x in path.read_text().splitlines()]
    assert len(lines) == 2
    assert lines[0]["type"] == "metrics"
    assert lines[0]["snapshot"]["counters"]["x"] == 3
    assert lines[1]["snapshot"]["counters"]["x"] == 4
    assert snap["counters"]["x"] == 3


def test_metrics_thread_safety():
    c = telemetry.counter("threaded")

    def spin():
        for _ in range(10_000):
            c.inc()

    threads = [threading.Thread(target=spin) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 40_000


# -- device accounting -------------------------------------------------------


def test_sync_fetch_counts_fetches_bytes_and_span_event():
    import jax.numpy as jnp

    x = jnp.arange(8, dtype=jnp.float32)
    with telemetry.span("host"):
        out = telemetry.sync_fetch(x, label="t")
    assert isinstance(out, np.ndarray)
    np.testing.assert_array_equal(out, np.arange(8, dtype=np.float32))
    snap = telemetry.snapshot()
    assert snap["counters"]["device_fetches"] == 1
    assert snap["counters"]["device_fetch_bytes"] == 32
    assert snap["counters"]["device_fetch_seconds"] >= 0
    (s,) = telemetry.finished_spans("host")
    assert s.events and s.events[0]["name"] == "device_fetch"
    assert s.events[0]["attrs"]["bytes"] == 32


def test_compile_hook_counts_jit_compiles():
    import jax
    import jax.numpy as jnp

    assert telemetry.install_compile_hooks()
    before = telemetry.snapshot()["counters"].get("jit_compiles", 0)
    # a fresh closure + unusual shape forces a fresh XLA compile
    salt = len(telemetry.finished_spans()) + 17.5

    @jax.jit
    def fresh(v):
        return v * salt + jnp.tanh(v)

    with telemetry.span("compile_here"):
        fresh(jnp.ones((3, 5, 7)))
    after = telemetry.snapshot()["counters"].get("jit_compiles", 0)
    assert after >= before + 1
    assert telemetry.snapshot()["histograms"]["jit_compile_seconds"]["count"] >= 1
    (s,) = telemetry.finished_spans("compile_here")
    assert any(e["name"] == "compile" for e in s.events)


# -- timing integration ------------------------------------------------------


def test_timer_uses_monotonic_clock(monkeypatch):
    import time as _time

    from photon_ml_tpu.utils.timing import Timer

    t = Timer().start()
    # a wall-clock step must NOT affect the measured duration
    monkeypatch.setattr(
        _time, "time", lambda: _time.monotonic() + 3600.0
    )
    assert t.stop() < 60.0


def test_timed_opens_a_span_and_logs(caplog):
    import logging

    from photon_ml_tpu.utils.timing import timed

    with caplog.at_level(logging.INFO, logger="photon_ml_tpu"):
        with timed("phase_x") as t:
            pass
    assert t.seconds >= 0.0
    assert any("phase_x" in r.message for r in caplog.records)
    (s,) = telemetry.finished_spans("phase_x")
    assert s.dur is not None


def test_setup_logging_file_handler_uses_abspath(tmp_path, monkeypatch):
    import logging

    from photon_ml_tpu.utils.timing import setup_logging

    root = logging.getLogger("photon_ml_tpu")
    old = list(root.handlers)
    root.handlers = []
    try:
        monkeypatch.chdir(tmp_path)
        setup_logging(log_file="rel.log")
        (h,) = [x for x in root.handlers if isinstance(x, logging.FileHandler)]
        assert os.path.isabs(h.baseFilename)
        assert h.baseFilename == str(tmp_path / "rel.log")
        # dedup agrees with the handler path: re-adding is a no-op
        setup_logging(log_file=str(tmp_path / "rel.log"))
        assert (
            len([x for x in root.handlers
                 if isinstance(x, logging.FileHandler)]) == 1
        )
        h.close()
    finally:
        root.handlers = old


# -- event bus ---------------------------------------------------------------


def test_emitter_register_idempotent_and_unregister():
    from photon_ml_tpu.utils.events import EventEmitter, TrainingStartEvent

    seen = []
    em = EventEmitter()
    em.register(seen.append)
    em.register(seen.append)  # duplicate: must NOT double-fire
    em.send(TrainingStartEvent(num_rows=1))
    assert len(seen) == 1
    em.unregister(seen.append)
    em.unregister(seen.append)  # unknown: no-op
    em.send(TrainingStartEvent(num_rows=2))
    assert len(seen) == 1


def test_emitter_send_counts_per_event_type():
    from photon_ml_tpu.utils.events import (
        EventEmitter,
        TrainingFinishEvent,
        TrainingStartEvent,
    )

    em = EventEmitter()
    em.send(TrainingStartEvent(num_rows=1))
    em.send(TrainingStartEvent(num_rows=2))
    em.send(TrainingFinishEvent(best_metric=None, seconds=0.0))
    c = telemetry.snapshot()["counters"]
    assert c["events.TrainingStartEvent"] == 2
    assert c["events.TrainingFinishEvent"] == 1


def test_load_listener_error_paths():
    from photon_ml_tpu.utils.events import load_listener

    # importable fixture module with the three shapes under test
    mod = types.ModuleType("_telemetry_listener_fixture")

    class Listener:
        def __init__(self):
            self.events = []

        def __call__(self, event):
            self.events.append(event)

    class Needy:
        def __init__(self, required):
            pass

    mod.Listener = Listener
    mod.Needy = Needy
    mod.NOT_CALLABLE = 42
    sys.modules["_telemetry_listener_fixture"] = mod
    try:
        # classes are instantiated (newInstance() analog)
        fn = load_listener("_telemetry_listener_fixture:Listener")
        fn("evt")
        assert fn.events == ["evt"]
        # bad spec: no dots at all
        with pytest.raises(ValueError, match="dotted path"):
            load_listener("nodots")
        # resolves but is not callable
        with pytest.raises(ValueError, match="not callable"):
            load_listener("_telemetry_listener_fixture:NOT_CALLABLE")
        # class whose zero-arg instantiation fails
        with pytest.raises(ValueError, match="cannot load"):
            load_listener("_telemetry_listener_fixture:Needy")
        # missing module / missing attribute
        with pytest.raises(ValueError, match="cannot load"):
            load_listener("no.such.module:thing")
        with pytest.raises(ValueError, match="cannot load"):
            load_listener("_telemetry_listener_fixture:missing")
    finally:
        del sys.modules["_telemetry_listener_fixture"]


# -- tracker telemetry -------------------------------------------------------


def test_re_tracker_from_device_parts_empty():
    from photon_ml_tpu.optim.trackers import RandomEffectOptimizationTracker

    t = RandomEffectOptimizationTracker.from_device_parts([], [], [])
    assert len(t.iterations) == 0 and len(t.reasons) == 0
    assert t.final_values is not None and len(t.final_values) == 0
    assert t.iteration_stats()["count"] == 0
    assert t.count_convergence_reasons() == {}
    pcts = t.percentile_summary()
    assert pcts["iterations"] == {f"p{p}": 0.0 for p in (5, 25, 50, 75, 95)}
    assert t.to_summary_string().startswith("entities=0")


def test_re_tracker_from_device_parts_single_entity_round_trip():
    import jax.numpy as jnp

    from photon_ml_tpu.optim.trackers import RandomEffectOptimizationTracker

    t = RandomEffectOptimizationTracker.from_device_parts(
        [jnp.asarray([5], jnp.int32)],
        [jnp.asarray([1], jnp.int32)],
        [jnp.asarray([0.125], jnp.float32)],
    )
    np.testing.assert_array_equal(t.iterations, [5])
    np.testing.assert_array_equal(t.reasons, [1])
    # the f32 terminal value must survive the i32 bitcast ride exactly
    np.testing.assert_array_equal(t.final_values, np.float32([0.125]))
    pcts = t.percentile_summary()
    assert all(v == 5.0 for v in pcts["iterations"].values())
    assert all(v == pytest.approx(0.125) for v in pcts["final_loss"].values())
    # the packed crossing is accounted as ONE device fetch
    snap = telemetry.snapshot()
    assert snap["counters"]["device_fetches"] == 1
    assert snap["counters"]["re_solved_entities"] == 1
    assert snap["histograms"]["re_solve_iterations"]["count"] == 1


def test_fe_tracker_feeds_histogram():
    from photon_ml_tpu.optim.trackers import FixedEffectOptimizationTracker

    class _Res:
        iterations = 7
        reason = 0
        value = 0.5
        grad_norms = np.zeros(8)

    t = FixedEffectOptimizationTracker.from_result(_Res())
    assert t.iterations == 7
    snap = telemetry.snapshot()
    assert snap["counters"]["fe_solves"] == 1
    assert snap["histograms"]["fe_solve_iterations"]["count"] == 1


# -- lint gate ---------------------------------------------------------------


def test_check_lint_rejects_fake_timing_in_library_code(tmp_path):
    # the _Lint monolith moved into the tools.analysis package (ISSUE 7);
    # the per-file rules live in LocalLint and emit structured findings
    import ast

    from tools.analysis.local import LocalLint

    src = (
        "import time\n"
        "import jax\n"
        "def f(x):\n"
        "    t0 = time.time()\n"
        "    jax.block_until_ready(x)\n"
        "    return time.monotonic() - t0\n"
    )
    # from-import forms must not evade the rules
    evasive = (
        "from time import time as now\n"
        "from jax import block_until_ready\n"
        "def f(x):\n"
        "    t0 = now()\n"
        "    block_until_ready(x)\n"
        "    return t0\n"
    )
    ev = LocalLint("photon_ml_tpu/z.py", ast.parse(evasive), library=True)
    ev_codes = [f.code for f in ev.findings]
    assert "L006" in ev_codes and "L007" in ev_codes
    tree = ast.parse(src)
    lib = LocalLint("photon_ml_tpu/x.py", tree, library=True)
    codes = [f.code for f in lib.findings]
    assert "L006" in codes and "L007" in codes
    # scripts/tests keep their freedom
    script = LocalLint("chip_smoke.py", ast.parse(src), library=False)
    assert not any(f.code in ("L006", "L007") for f in script.findings)
    # a USED result is not flagged (only bare statements are timing syncs)
    used = ast.parse("import jax\ndef g(x):\n    return jax.block_until_ready(x)\n")
    lib2 = LocalLint("photon_ml_tpu/y.py", used, library=True)
    assert not any(f.code == "L007" for f in lib2.findings)


def test_check_lint_rejects_bare_print_in_library_code():
    """L009 satellite: bare print() is rejected in library code, allowed
    in CLI modules (stdout is their interface) and in scripts/tests."""
    import ast

    from tools.analysis.local import LocalLint

    src = 'def f():\n    print("hi")\n'
    lib = LocalLint("photon_ml_tpu/game/x.py", ast.parse(src), library=True)
    assert any(f.code == "L009" for f in lib.findings)
    cli = LocalLint(
        "photon_ml_tpu/cli/train.py", ast.parse(src), library=True
    )
    assert not any(f.code == "L009" for f in cli.findings)
    script = LocalLint("chip_smoke.py", ast.parse(src), library=False)
    assert not any(f.code == "L009" for f in script.findings)
    # method calls named print (e.g. logger-ish objects) are not flagged
    method = LocalLint(
        "photon_ml_tpu/game/y.py",
        ast.parse("def f(doc):\n    doc.print()\n"),
        library=True,
    )
    assert not any(f.code == "L009" for f in method.findings)


# -- reset / env configuration ------------------------------------------------


def test_reset_restores_configure_from_env_state(tmp_path, monkeypatch):
    """Satellite: reset() must fully restore defaults — the env-registered
    atexit flush and env-pointed trace sink must not leak across tests."""
    import atexit

    metrics_out = tmp_path / "env.metrics.jsonl"
    trace_out = tmp_path / "env.trace.jsonl"
    monkeypatch.setenv("PHOTON_TELEMETRY_OUT", str(metrics_out))
    monkeypatch.setenv("PHOTON_TRACE_OUT", str(trace_out))
    telemetry.configure_from_env()
    flush = telemetry._env_state["atexit_flush"]
    assert flush is not None
    assert ttrace.TRACER._sink_path == str(trace_out)
    # calling again replaces (not stacks) the atexit registration
    telemetry.configure_from_env()
    assert telemetry._env_state["atexit_flush"] is not flush

    telemetry.reset()
    assert telemetry._env_state["atexit_flush"] is None
    assert ttrace.TRACER._sink_path is None
    # the unregistered flush must NOT fire at exit: registering the stale
    # handle again would be the leak; simulate by checking unregister took
    atexit.unregister(flush)  # no-op either way; just must not raise

    # stats-provider injection is also restored by reset()
    from photon_ml_tpu.telemetry import memory

    memory.set_stats_provider(lambda: {"bytes_in_use": 1, "bytes_limit": 2})
    assert memory.hbm_stats() == {"bytes_in_use": 1, "bytes_limit": 2}
    telemetry.reset()
    assert memory._stats_provider is None


# -- spans on the profiler's clock, counters that mean what they say ----------


def _tiny_fe_fit(validation_evaluators=None, guard=None):
    from photon_ml_tpu.game import (
        FixedEffectCoordinate,
        ValidationSpec,
        build_game_dataset,
        run_coordinate_descent,
    )
    from photon_ml_tpu.ops.sparse import SparseBatch
    from photon_ml_tpu.optim import OptimizerConfig, OptimizerType

    rng = np.random.default_rng(3)
    X = rng.normal(size=(96, 5))
    y = (rng.random(96) < 0.5).astype(float)
    gds = build_game_dataset(
        response=y, feature_shards={"g": SparseBatch.from_dense(X, y)})
    coord = FixedEffectCoordinate(
        "fixed", gds, "g", "logistic",
        OptimizerConfig(optimizer_type=OptimizerType.LBFGS, max_iterations=3))
    validation = None
    if validation_evaluators:
        validation = ValidationSpec(data=gds, evaluators=validation_evaluators)
    return lambda: run_coordinate_descent(
        {"fixed": coord}, task="logistic", num_iterations=1,
        validation=validation, guard=guard)


def test_profiler_capture_holds_the_span_tree(tmp_path):
    """Every span mirrors itself as a ``photon:<name>`` TraceAnnotation with
    no switch to turn on: a capture started by anyone holds the CD tree on
    its host plane, children inside their parents on the trace's clock."""
    import jax
    from jax.profiler import ProfileData

    fit = _tiny_fe_fit(["auc"])
    fit()  # compile outside the capture
    jax.profiler.start_trace(str(tmp_path))
    try:
        fit()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    found = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("photon:"):
                    found.setdefault(e.name[7:], []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    assert set(found) >= {
        "coordinate_descent", "initial_scores", "cd_iteration",
        "coordinate:fixed", "update", "score", "validate"}
    (cd,) = found["coordinate_descent"]
    for name in ("initial_scores", "cd_iteration", "coordinate:fixed",
                 "update", "score", "validate"):
        for a, b in found[name]:
            assert cd[0] <= a and b <= cd[1], name
    (step,) = found["coordinate:fixed"]
    for name in ("update", "score", "validate"):
        (inner,) = found[name]
        assert step[0] <= inner[0] and inner[1] <= step[1]


def test_spans_work_with_no_capture_running():
    # the mirror is unconditional: no factory to set, nothing to tear down
    assert not hasattr(ttrace.TRACER, "set_annotation_factory")
    assert not hasattr(ttrace, "set_annotation_factory")
    with telemetry.span("plain") as s:
        pass
    assert s.dur is not None and ttrace.ANNOTATION_PREFIX == "photon:"


@pytest.mark.parametrize("evaluators,guarded,expected", [
    (None, False, 3),            # initial scores, tracker, score
    (["auc"], False, 4),         # + one per evaluator
    (["auc", "logistic_loss"], True, 6),  # + the guard's health fetch
])
def test_device_fetches_per_update_and_evaluator(
        monkeypatch, evaluators, guarded, expected):
    """``device_fetch_seconds`` is the host's whole blocked time on the
    training path because every crossing goes through ``sync_fetch``: per
    fit one per coordinate's initial scores; per FE update ONE packed
    tracker fetch (+1 under a guard); one per score; one per evaluator."""
    from photon_ml_tpu.optim.guard import GuardSpec
    from photon_ml_tpu.telemetry import xla

    # the dispatch sampler's own fetches (first dispatch of an executable)
    # are not the training path's
    monkeypatch.setattr(xla, "_dispatch_profiler", None)
    fit = _tiny_fe_fit(evaluators, GuardSpec() if guarded else None)
    before = telemetry.snapshot()["counters"].get("device_fetches", 0)
    fit()
    after = telemetry.snapshot()["counters"]["device_fetches"]
    assert after - before == expected
    labels = [
        e["attrs"]["label"] for s in telemetry.finished_spans()
        for e in s.events if e["name"] == "device_fetch"]
    assert labels.count("fe_tracker") == 1
    assert labels.count("initial_scores:fixed") == 1
    assert sum(x.startswith("evaluate:") for x in labels) == len(
        evaluators or [])
    (update,) = telemetry.finished_spans("update")
    assert "fe_tracker" in [e["attrs"].get("label") for e in update.events]


def test_eager_compile_counter_skips_instrumented_jit():
    import jax.numpy as jnp

    def count(name):
        return telemetry.snapshot()["counters"].get(name, 0)

    x = jnp.ones((3, 5, 11))
    eager0, all0 = count("jit_compiles_eager"), count("jit_compiles")
    _ = jnp.tanh(x) * 1.25  # eager one-op programs of an unusual shape
    eager1, all1 = count("jit_compiles_eager"), count("jit_compiles")
    assert eager1 > eager0 and eager1 - eager0 == all1 - all0
    assert count("jit_compile_seconds_eager") > 0

    salt = 23.5
    fresh = telemetry.instrumented_jit(
        lambda v: jnp.tanh(v) * salt + v, name="eager_counter_probe")
    fresh(x)
    assert count("jit_compiles") > all1
    assert count("jit_compiles_eager") == eager1  # accounted, not eager
