"""The reduction from a profiler trace to busy time, kernel time and gaps:
on hand-made events, and on a small trace recorded on a v5e (PR 23: the
fixed-effect cell at 600,000 rows, one traced fit of 9 LBFGS iterations)."""

import os

import pytest

from benchmark import tracing

RECORDED = os.path.join(
    os.path.dirname(__file__), "data", "fe_600k_rows.xplane.pb")
KERNELS = 'custom_call_target="tpu_custom_call"'


def test_busy_is_the_union_and_self_time_excludes_children():
    events = [  # a while of 10 with two children, a gap, then a lone op
        ("while", 0.0, 10.0), ("a", 1.0, 3.0), ("b", 5.0, 4.0),
        ("c", 20.0, 5.0),
    ]
    assert tracing.busy_intervals(events) == [(0.0, 10.0), (20.0, 25.0)]
    assert tracing.busy_seconds(events) == pytest.approx(15e-9)
    assert tracing.busy_seconds(events, (5.0, 22.0)) == pytest.approx(7e-9)
    own = tracing.self_seconds(events)
    assert own == pytest.approx(
        {"while": 3e-9, "a": 3e-9, "b": 4e-9, "c": 5e-9})
    assert tracing.kernel_seconds(events, "^[ab]$") == (pytest.approx(7e-9), 2)


def test_gaps_go_to_the_innermost_annotation():
    events = [("x", 2.0, 2.0), ("y", 10.0, 2.0)]
    notes = [("unit", 0.0, 20.0), ("coordinate:fixed", 0.0, 8.0),
             ("coordinate:per-user", 8.0, 12.0)]
    gaps = dict(tracing.idle_gaps(events, (0.0, 20.0), notes))
    # [0,2) and [4,10) have their midpoints in 'fixed'; [12,20) in 'per-user'
    assert gaps == pytest.approx(
        {"coordinate:fixed": 8e-9, "coordinate:per-user": 8e-9})
    assert tracing.idle_gaps(events, (0.0, 20.0), [])[0][0] == "unattributed"


def test_short_name_keeps_what_identifies_a_kernel():
    text = ('%body.23 = f32[128,79]{1,0:T(8,128)S(1)} custom-call(f32[4688,1,'
            '2560]{2,1,0:T(1,128)} %x), custom_call_target="tpu_custom_call"')
    assert tracing.short_name(text) == (
        "%body.23 custom-call f32[128,79] tpu_custom_call")


def test_recorded_trace_gives_the_known_busy_share_and_kernel_time():
    from jax.profiler import ProfileData

    trace = tracing.load(RECORDED)
    events = trace.devices["/device:TPU:0"]
    window = tracing.annotation_window(trace.annotations, "unit")
    # what the run that recorded it printed (chiprun_out/probe1, PR 23)
    assert (window[1] - window[0]) * 1e-9 == pytest.approx(0.638703838)
    assert tracing.busy_seconds(events, window) == pytest.approx(0.611964212)
    # the kernels, against a plain loop over the file that knows no nesting:
    # 9 gather + 9 scatter calls of the LBFGS loop, the two before it, and
    # the two scoring passes
    total, calls = 0.0, 0
    for plane in ProfileData.from_file(RECORDED).planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        if "tpu_custom_call" in e.name:
                            total += e.duration_ns * 1e-9
                            calls += 1
    assert calls == 22
    seconds, n = tracing.kernel_seconds(events, KERNELS)
    assert (n, seconds) == (22, pytest.approx(total))
    assert seconds == pytest.approx(0.589584466)
    # the while loops own next to nothing; the kernels own the time
    own = tracing.self_seconds(events)
    top = max(own, key=own.get)
    assert "tpu_custom_call" in top and own[top] == pytest.approx(0.28280372)
    gaps = tracing.idle_gaps(events, window, trace.annotations)
    assert gaps == [["coordinate:fixed", pytest.approx(0.026739626)]]
