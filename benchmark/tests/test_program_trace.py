"""The readers of the program's own spans and executables (PR 24): on
hand-made event lists, on the v5e trace recorded BEFORE the program named
its executables (PR 23), on one recorded after, and through the command off
the chip."""

import json
import os

import pytest

from benchmark import program_trace, run, tracing
from benchmark.readers import (
    program_span_seconds,
    trace_idle_by_span,
    trace_kernel_roofline,
    trace_module,
)

DATA = os.path.join(os.path.dirname(__file__), "data")
RECORDED = os.path.join(DATA, "fe_600k_rows.xplane.pb")
NAMED = os.path.join(DATA, "fe_600k_rows_named.xplane.pb")

# one fit by hand: [0, 100) ns; initial scores, one coordinate with its three
# leaves and 4 ns of its own between them, 6 ns of the loop's own at the end
SPANS = [
    ("coordinate_descent", 0.0, 100.0),
    ("initial_scores", 2.0, 8.0),
    ("cd_iteration", 10.0, 84.0),
    ("coordinate:fixed", 10.0, 80.0),
    ("update", 12.0, 50.0),
    ("score", 62.0, 10.0),
    ("validate", 74.0, 16.0),
    ("build_coordinates", 200.0, 10.0),  # a cached build, outside the fit
]


def ctx_with(spans=SPANS, modules=None, devices=None):
    return {
        "trace": tracing.Trace(devices=devices or {}, annotations=[]),
        "program_trace": program_trace.ProgramTrace(
            roots=program_trace.nest(spans), modules=modules or {}),
        "notes": {},
    }


def test_spans_nest_by_their_intervals():
    cd, build = program_trace.nest(SPANS)
    assert (cd.name, build.name) == ("coordinate_descent", "build_coordinates")
    assert [c.name for c in cd.children] == ["initial_scores", "cd_iteration"]
    (step,) = cd.children[1].children
    assert [c.name for c in step.children] == ["update", "score", "validate"]
    assert step.children[0].parent is step
    assert step.self_ns == 4.0 and cd.self_ns == 8.0
    assert [s.name for s in cd.leaves()] == [
        "initial_scores", "update", "score", "validate"]
    assert program_trace.path(step.children[0], cd) == (
        "cd_iteration>coordinate:fixed>update")


def test_span_seconds_by_name_parent_and_self_time():
    ctx = ctx_with()
    read = program_span_seconds.read
    cd = "coordinate_descent"
    assert read(ctx, "initial_scores", cd) == pytest.approx(8e-9)
    assert read(ctx, "update", cd, parent="coordinate:fixed") == (
        pytest.approx(50e-9))
    assert read(ctx, "update", cd, parent="coordinate:per-user") is None
    assert read(ctx, "validate", cd) == pytest.approx(16e-9)
    # self time of the loop: 8 (coordinate_descent) + 4 (cd_iteration) + 4
    own = read(ctx, "coordinate_descent|cd_iteration|coordinate:.*", cd,
               self_time=True)
    assert own == pytest.approx(16e-9)
    # the parts are the whole
    parts = sum(read(ctx, n, cd) for n in (
        "initial_scores", "update", "score", "validate"))
    assert parts + own == pytest.approx(100e-9)
    # two fits in the window: seconds PER fit
    twice = SPANS + [(n, s + 1000.0, d) for n, s, d in SPANS]
    assert read(ctx_with(twice), "update", cd) == pytest.approx(50e-9)
    # a span that is not under the root, a root that is not there, a run
    # that was not traced
    assert read(ctx, "layout", cd) is None
    assert read(ctx_with([("fit", 0.0, 5.0)]), "update", cd) is None
    assert read({"notes": {}}, "update", cd) is None


def test_span_seconds_from_the_process_take_the_first_root_only():
    from photon_ml_tpu import telemetry

    telemetry.reset()
    read = program_span_seconds.read
    assert read({}, "layout", "build_coordinates", source="process") is None
    with telemetry.span("build_coordinates"):
        with telemetry.span("build:fixed"):
            with telemetry.span("layout") as a:
                pass
            with telemetry.span("upload"):
                pass
        with telemetry.span("build:per-user"):
            with telemetry.span("layout") as b:
                pass
    with telemetry.span("build_coordinates"):  # a later, cached call
        with telemetry.span("layout"):
            pass
    got = read({}, "layout", "build_coordinates", source="process")
    assert got == pytest.approx(a.dur + b.dur, rel=1e-6)
    assert read({}, "nothing", "build_coordinates", source="process") is None
    telemetry.reset()


def test_idle_goes_to_the_leaf_that_holds_it_or_to_nobody():
    # busy [12, 40) and [44, 62) inside `update`; [62, 70) in `score`;
    # [78, 90) in `validate`. Gaps: [0,12) mid 6 -> initial_scores;
    # [40,44) mid 42 -> update (a gap IN a leaf); [70,78) mid 74 -> validate
    # starts at 74: validate; [90,100) mid 95 -> no leaf (a gap BETWEEN
    # leaves, in the loop's own time)
    events = [("a", 12.0, 28.0), ("b", 44.0, 18.0), ("c", 62.0, 8.0),
              ("d", 78.0, 12.0)]
    (cd, _) = program_trace.nest(SPANS)
    gaps = program_trace.idle_by_leaf(events, cd)
    assert gaps == pytest.approx({
        "initial_scores": 12e-9,
        "cd_iteration>coordinate:fixed>update": 4e-9,
        "cd_iteration>coordinate:fixed>validate": 8e-9,
        "unattributed": 10e-9,
    })
    ctx = ctx_with(devices={"/device:TPU:0": events})
    assert trace_idle_by_span.read(ctx) == pytest.approx(10e-9)
    assert ctx["notes"]["idle_by_span"] == pytest.approx(gaps)
    assert list(ctx["notes"]["idle_by_span"])[0] == "initial_scores"
    # no spans, no device, no trace: nothing
    assert trace_idle_by_span.read(ctx_with([("fit", 0.0, 9.0)], devices={
        "/device:TPU:0": events})) is None
    assert trace_idle_by_span.read(ctx_with()) is None
    assert trace_idle_by_span.read({"notes": {}}) is None


def test_device_plane_is_moved_onto_the_host_clock_by_its_lead():
    # the last leaf (`validate`) ends at 90 on a fetch; the device's last
    # event ends at 87: its plane runs 3 ahead, and moved by that the solve
    # starts inside `update` ([12, 62)) and not before it
    modules = {"/device:TPU:0": [
        ("jit_fe_solve(2)", 10.0, 45.0), ("jit_add(4)", 86.0, 1.0)]}
    (cd, _) = program_trace.nest(SPANS)
    assert program_trace.device_lead([cd], modules) == {"/device:TPU:0": 3.0}
    trace = program_trace.ProgramTrace(
        roots=[cd], modules=modules, lead={"/device:TPU:0": 3.0})
    moved = trace.on_host_clock("/device:TPU:0", modules["/device:TPU:0"])
    assert moved[0] == ("jit_fe_solve(2)", 13.0, 45.0)
    assert trace.on_host_clock("/device:TPU:1", moved) is moved
    # a device that ends after the program's last fetch is not moved back
    late = {"d": [("x", 95.0, 10.0)]}
    assert program_trace.device_lead([cd], late) == {"d": 0.0}
    assert program_trace.device_lead([], modules) == {}


def test_modules_by_pattern_and_unnamed(monkeypatch):
    modules = {"/device:TPU:0": [
        ("jit_fe_score_tiled(1)", 3.0, 5.0),
        ("jit_fe_solve(2)", 12.0, 45.0),
        ("jit_fe_tracker_pack(3)", 58.0, 1.0),
        ("jit_convert_element_type(4)", 75.0, 1.0),  # matches no executable
        ("jit_fe_solve(2)", 300.0, 45.0),  # outside the traced fit
    ]}
    ctx = ctx_with(modules=modules)
    assert trace_module.read(ctx, pattern=r"^jit_fe_solve\(") == (
        pytest.approx(45e-9))
    assert trace_module.read(ctx, pattern=r"^jit_re_solve\(") is None

    class Registry:
        @staticmethod
        def executables():
            import types

            return [types.SimpleNamespace(name=n) for n in (
                "fe_solve", "fe_score_tiled", "fe_tracker_pack")]

    from photon_ml_tpu import telemetry

    monkeypatch.setattr(telemetry, "XLA_REGISTRY", Registry)
    assert trace_module.read(ctx, unnamed=True) == 1.0
    # no XLA Modules line (off the chip), no fit span, no trace: nothing
    assert trace_module.read(ctx_with(), unnamed=True) is None
    assert trace_module.read(
        ctx_with([("fit", 0.0, 9.0)], modules=modules), unnamed=True) is None
    assert trace_module.read({"notes": {}}, pattern="x") is None


def test_recorded_trace_from_before_the_names():
    """PR 23's v5e trace: 110 launches in the fit, three of them the
    program's executables, then called ``jit_run`` and ``jit_score``; no
    ``photon:`` span and no kernel of the new names."""
    trace = program_trace.read(RECORDED)
    assert trace.roots == [] and trace.units() == []
    (events,) = trace.modules.values()
    assert len(program_trace.unnamed_modules(events, set())) == 110
    assert len(program_trace.unnamed_modules(
        events, {"jit_run", "jit_score"})) == 107
    assert sorted(
        program_trace.module_name(e[0]) for e in events
        if program_trace.module_name(e[0]) in ("jit_run", "jit_score")
    ) == ["jit_run", "jit_score", "jit_score"]
    ctx = {
        "trace": tracing.load(RECORDED), "program_trace": trace, "notes": {},
        "shapes": {"coordinates": {"fixed": {
            "T": 4688, "nnz": 600000 * 20}}},
        "peaks": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
    }
    for pattern in ("^%tiled_margins", "^%tiled_scatter"):
        assert trace_kernel_roofline.read(
            ctx, pattern, "tiled_pass", "fixed") is None
    assert trace_kernel_roofline.read(
        ctx, 'custom_call_target="tpu_custom_call"', "tiled_pass",
        "fixed") > 0
    assert trace_module.read(ctx, pattern=r"^jit_fe_solve\(") is None
    assert trace_idle_by_span.read(ctx) is None


def test_recorded_trace_with_the_names():
    """The same cell at 600,000 rows recorded on a v5e by PR 24's program:
    the span tree is on the host plane, every Mosaic event starts with a
    ``%tiled_`` name, and the two per-kernel shares split what
    ``fe_kernels_roofline`` lumps together."""
    trace = program_trace.read(NAMED)
    (unit,) = trace.units()
    assert [c.name for c in unit.children] == [
        "initial_scores", "cd_iteration"]
    (step,) = unit.children[1].children
    assert [c.name for c in step.children] == ["update", "score", "validate"]
    ((plane, events),) = trace.modules.items()
    # the device plane's stamps run 2.2 ms ahead of the host's here: the
    # first scoring pass "starts" before the span that launched it opens
    first = next(e for e in events if e[0].startswith("jit_fe_score_tiled"))
    assert first[1] < unit.children[0].start
    assert trace.lead[plane] == pytest.approx(2.233103e6)
    events = trace.on_host_clock(plane, events)
    launched_in = {
        program_trace.module_name(e[0]): leaf.name
        for leaf in (step.children[0], unit.children[0])
        for e in program_trace.inside(events, [(leaf.start, leaf.end)])
        if e[0].startswith("jit_fe_s")}
    assert launched_in == {
        "jit_fe_solve": "update", "jit_fe_score_tiled": "initial_scores"}
    window = [(unit.start, unit.end)]
    assert len(program_trace.inside(events, window)) == len(events) == 111
    names = [program_trace.module_name(e[0]) for e in events]
    assert names.count("jit_fe_solve") == 1
    assert names.count("jit_fe_score_tiled") == 2
    assert names.count("jit_fe_tracker_pack") == 1
    assert "jit_run" not in names and "jit_score" not in names
    assert len(program_trace.unnamed_modules(events, {
        "jit_fe_solve", "jit_fe_score_tiled", "jit_fe_tracker_pack",
    }, window)) == 107

    ops = tracing.load(NAMED)
    (dev,) = ops.devices.values()
    mosaic = [e for e in dev if "tpu_custom_call" in e[0]]
    assert mosaic and all(
        e[0].startswith(("%tiled_margins", "%tiled_scatter")) for e in mosaic)
    g_s, g_n = tracing.kernel_seconds(dev, "^%tiled_margins")
    s_s, s_n = tracing.kernel_seconds(dev, "^%tiled_scatter")
    all_s, all_n = tracing.kernel_seconds(dev, "tpu_custom_call")
    assert g_n + s_n == all_n and g_s + s_s == pytest.approx(all_s)
    ctx = {
        "trace": ops, "program_trace": trace, "notes": {},
        "shapes": {"coordinates": {"fixed": {
            "T": 4688, "nnz": 600000 * 20}}},
        "peaks": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
    }
    gather = trace_kernel_roofline.read(
        ctx, "^%tiled_margins", "tiled_pass", "fixed")
    scatter = trace_kernel_roofline.read(
        ctx, "^%tiled_scatter", "tiled_pass", "fixed")
    both = trace_kernel_roofline.read(
        ctx, "tpu_custom_call", "tiled_pass", "fixed")
    assert 0 < scatter < both < gather < 100
    # the time-weighted mean of the two is the lumped share
    assert (gather * g_s + scatter * s_s) / all_s == pytest.approx(both)
    solve = trace_module.read(ctx, pattern=r"^jit_fe_solve\(")
    update = program_span_seconds.read(
        ctx, "update", "coordinate_descent", parent="coordinate:fixed")
    assert 0 < solve <= update
    idle = trace_idle_by_span.read(ctx)
    assert idle is not None and idle <= sum(
        ctx["notes"]["idle_by_span"].values())


def test_the_command_off_the_chip_prints_every_span_metric(capsys):
    rc = run.main([
        "--workload", "glm_fe.lbfgs_fit", "--seed", "2147483659",
        "--seconds", "0.1", "--trace", "1", "--rehearsal-rows", "20000"])
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    line = json.loads(out.out.strip().splitlines()[-1])
    got = {k: v["value"] for k, v in line["metrics"].items()}
    for name in (
            "layout_host_s", "upload_s", "eager_compile_s",
            "initial_scores_s_per_fit", "fe_update_s_per_fit",
            "fe_score_s_per_fit", "validate_s_per_fit", "cd_self_s_per_fit",
            "host_blocked_s_per_fit", "idle_unattributed_s_per_fit"):
        assert got[name] >= 0, name
    # the parts of the fit are the fit
    (fit_s,) = line["seconds_per_unit"]
    parts = sum(got[n] for n in (
        "initial_scores_s_per_fit", "fe_update_s_per_fit",
        "fe_score_s_per_fit", "validate_s_per_fit", "cd_self_s_per_fit"))
    assert parts == pytest.approx(fit_s, rel=0.01)
    assert got["layout_host_s"] + got["upload_s"] <= got[
        "build_coordinates_s"]
    assert got["host_blocked_s_per_fit"] <= fit_s
    assert "idle_by_span" in line["notes"]
    # what needs a device's `XLA Modules` line or its peaks stays silent
    for name in ("fe_gather_roofline", "fe_scatter_roofline",
                 "fe_solve_device_s_per_fit", "eager_programs_per_fit"):
        assert name not in got
