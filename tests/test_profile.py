"""ISSUE 16 (executable-level roofline profiler): the dispatch sampler's
honest timing, sampling determinism, dispatch-key merging, exclusive-time
nesting, the sampling cadence behind the overhead budget, bound-class
attribution, the
timing-honesty self-check, HBM high-watermarks, and the xprof capture
window."""

import logging
import types

import numpy as np
import pytest

from photon_ml_tpu import telemetry
from photon_ml_tpu.telemetry import memory, metrics, profile, trace, xla


def _rec(name, signature=("f32[4]",), flops=None, bytes_accessed=None):
    """A minimal ExecutableRecord stand-in for driving profile_dispatch
    directly (the sampler only reads these four fields)."""
    return types.SimpleNamespace(
        name=name,
        signature=signature,
        flops=flops,
        bytes_accessed=bytes_accessed,
    )


# -- sampling determinism -----------------------------------------------------


def test_sampling_is_deterministic_every_nth_and_first():
    profile.set_sample_every(4)
    f = telemetry.instrumented_jit(lambda x: x + 1.0, name="det")
    x = np.zeros((4,), np.float32)
    for _ in range(10):
        f(x)
    (entry,) = profile.PROFILE_REGISTRY.entries("det")
    assert entry.dispatches == 10
    # dispatches 1, 5, 9: the FIRST dispatch is always sampled, then
    # every 4th — a deterministic per-entry counter, not a coin flip
    assert entry.sampled == 3
    assert metrics.counter("profile.sampled").value == 3
    # each sample synchronized through the sanctioned crossing
    fetch_events = [
        e
        for s in trace.finished_spans()
        for e in s.events
        if e.get("name") == "device_fetch"
        and str(e.get("label", "")).startswith("profile:det")
    ]
    # events attach to an open span only when one exists; the counter is
    # the ground truth either way
    assert metrics.counter("device_fetches").value >= 3


def test_single_dispatch_still_profiles():
    # default sampling is 1/64, but a run with ONE dispatch must still
    # produce a profile (the first dispatch of every entry is sampled)
    f = telemetry.instrumented_jit(lambda x: x * 2.0, name="once")
    f(np.ones((4,), np.float32))
    (entry,) = profile.PROFILE_REGISTRY.entries("once")
    assert entry.dispatches == 1
    assert entry.sampled == 1
    assert entry.sampled_seconds > 0


def test_sample_every_env_override(monkeypatch):
    monkeypatch.setenv("PHOTON_PROFILE_SAMPLE_EVERY", "2")
    profile.reset()  # clear the env cache so the override is read
    f = telemetry.instrumented_jit(lambda x: x + 1.0, name="env")
    x = np.zeros((2,), np.float32)
    for _ in range(4):
        f(x)
    (entry,) = profile.PROFILE_REGISTRY.entries("env")
    assert entry.sampled == 2  # dispatches 1 and 3


# -- dispatch-key merging -----------------------------------------------------


def test_distinct_signatures_merge_per_name():
    # distinct dispatch keys (shape change = new signature, the same
    # mechanism that separates shardings) stay distinct entries and merge
    # per NAME in the report view
    profile.set_sample_every(1)
    f = telemetry.instrumented_jit(lambda x: x + 1.0, name="shapes")
    for _ in range(3):
        f(np.zeros((4,), np.float32))
    for _ in range(2):
        f(np.zeros((8,), np.float32))
    entries = profile.PROFILE_REGISTRY.entries("shapes")
    assert len(entries) == 2
    assert {e.dispatches for e in entries} == {3, 2}
    merged = profile.merged_profiles()["shapes"]
    assert merged["dispatches"] == 5
    assert merged["sampled"] == 5


def test_merged_cost_is_sample_weighted():
    # two shardings of one name with different cost analyses: the merged
    # per-dispatch cost weights by sample count, so the rarely-run
    # sharding does not skew intensity
    reg = profile.PROFILE_REGISTRY
    reg.count_dispatch("w", ("f32[8]@x",), 1)
    reg.record_sample("w", ("f32[8]@x",), 1.0, 1.0, 0.0, 100.0, 10.0)
    for _ in range(3):
        reg.count_dispatch("w", ("f32[8]@y",), 1)
        reg.record_sample("w", ("f32[8]@y",), 1.0, 1.0, 0.0, 500.0, 50.0)
    merged = profile.merged_profiles()["w"]
    assert merged["flops_per_dispatch"] == pytest.approx(400.0)
    assert merged["bytes_per_dispatch"] == pytest.approx(40.0)
    assert merged["intensity"] == pytest.approx(10.0)


# -- exclusive time under nesting (forged clock) ------------------------------


def test_exclusive_time_subtracts_nested_sampled_dispatches():
    profile.set_sample_every(1)
    now = [0.0]
    profile.set_clock(lambda: now[0])

    inner_rec = _rec("inner")
    outer_rec = _rec("outer")

    def inner_target(*a, **k):
        now[0] += 2.0  # 2 forged seconds of inner device work
        return 7  # array-free output: no fetch, timing stands as-is

    def outer_target(*a, **k):
        profile.profile_dispatch(inner_rec, inner_target, (), {})
        now[0] += 3.0  # 3 forged seconds of the outer's OWN work
        return 7

    profile.profile_dispatch(outer_rec, outer_target, (), {})

    (inner,) = profile.PROFILE_REGISTRY.entries("inner")
    (outer,) = profile.PROFILE_REGISTRY.entries("outer")
    assert inner.sampled_seconds == pytest.approx(2.0)
    assert inner.sampled_exclusive_seconds == pytest.approx(2.0)
    # inclusive 5s, minus the 2s nested sampled dispatch
    assert outer.sampled_seconds == pytest.approx(5.0)
    assert outer.sampled_exclusive_seconds == pytest.approx(3.0)
    excl = profile.exclusive_seconds_by_name()
    assert excl["outer"] == pytest.approx(3.0)
    assert excl["inner"] == pytest.approx(2.0)


def test_target_exception_propagates_without_a_sample():
    profile.set_sample_every(1)

    def boom(*a, **k):
        raise ValueError("no result, no sample")

    with pytest.raises(ValueError):
        profile.profile_dispatch(_rec("boom"), boom, (), {})
    (entry,) = profile.PROFILE_REGISTRY.entries("boom")
    assert entry.dispatches == 1
    assert entry.sampled == 0
    # the measurement stack unwound: a later dispatch still works
    profile.profile_dispatch(_rec("ok"), lambda: 1, (), {})
    (ok,) = profile.PROFILE_REGISTRY.entries("ok")
    assert ok.sampled == 1


# -- overhead budget ----------------------------------------------------------


def test_steady_state_samples_one_dispatch_in_sixty_four():
    """The overhead budget, in counts the CPU can keep: at the default
    cadence a steady loop pays the synchronizing fetch and the bookkeeping
    on one dispatch in 64 and skips the other 63. (What that costs in
    seconds is a device number: the chip benchmark's, not tier-1's.)"""
    import jax.numpy as jnp

    profile.set_sample_every(64)  # pin the default cadence explicitly
    f = telemetry.instrumented_jit(lambda x: x @ x + 1.0, name="overhead")
    x = jnp.ones((64, 64), jnp.float32)
    np.asarray(f(x))  # compile + first-dispatch sample, outside the window
    sampled0 = metrics.counter("profile.sampled").value
    fetches0 = metrics.counter("device_fetches").value
    n = 320
    for _ in range(n):
        f(x)
    (entry,) = profile.PROFILE_REGISTRY.entries("overhead")
    assert entry.dispatches == n + 1
    # dispatches 65, 129, 193, 257, 321 of this entry
    sampled = metrics.counter("profile.sampled").value - sampled0
    assert sampled == n // 64 == 5
    assert entry.sampled == sampled + 1
    # one accounted fetch per sample and none for the 315 skipped
    # dispatches: nothing else in the loop crosses to the host
    assert metrics.counter("device_fetches").value - fetches0 == sampled
    assert n - sampled == 315
    # the seconds it did cost are recorded for whoever measures them
    assert metrics.counter("profile.overhead_seconds").value > 0.0


# -- bound classes ------------------------------------------------------------


def test_bound_class_attribution():
    peak_flops, peak_bw = 1e12, 1e11  # balance point: 10 FLOPs/byte
    # memory leg binds: intensity 2 < 10
    assert (
        profile.bound_class(1.0, 2e11, 1e11, peak_flops, peak_bw, 0.2)
        == profile.BOUND_HBM
    )
    # compute leg binds at healthy MFU
    assert (
        profile.bound_class(1.0, 9e11, 1e9, peak_flops, peak_bw, 0.9)
        == profile.BOUND_MXU
    )
    # compute-side but the MXU is idle -> VPU-bound
    assert (
        profile.bound_class(0.5, 4e11, 1e9, peak_flops, peak_bw, 0.04)
        == profile.BOUND_VPU
    )
    # roofline-predicted time far below measured -> dispatch-bound
    assert (
        profile.bound_class(1.0, 1e9, 1e6, peak_flops, peak_bw, 0.001)
        == profile.BOUND_DISPATCH
    )
    # missing evidence is never a class
    assert (
        profile.bound_class(1.0, None, 1e9, peak_flops, peak_bw, None)
        == profile.BOUND_UNKNOWN
    )
    assert (
        profile.bound_class(1.0, 1e9, 1e6, None, None, None)
        == profile.BOUND_UNKNOWN
    )
    assert profile.bound_class_name(profile.BOUND_HBM) == "HBM-bound"
    assert profile.bound_class_name(None) == "unknown"
    assert profile.bound_class_name(99) == "unknown"


# -- timing honesty self-check ------------------------------------------------


def test_timing_suspect_flags_rates_above_device_peak(caplog):
    xla.set_peaks(1e12, 1e11)
    reg = profile.PROFILE_REGISTRY
    reg.count_dispatch("liar", ("f32[4]",), 1)
    # forged clock limit: 1e9 FLOPs "measured" in a nanosecond is
    # 1e18 FLOP/s against a 1e12 peak — physically impossible
    reg.record_sample("liar", ("f32[4]",), 1e-9, 1e-9, 0.0, 1e9, 1e6)
    merged = profile.merged_profiles()["liar"]
    assert merged["timing_suspect"] is True
    with caplog.at_level(
        logging.WARNING, logger="photon_ml_tpu.telemetry.profile"
    ):
        profile.publish_metrics()
        profile.publish_metrics()
    snap = telemetry.snapshot()
    assert snap["gauges"]["profile.exec.liar.timing_suspect"] == 1
    assert snap["counters"]["profile.timing_suspect_total"] >= 1
    # warn-once latch: two publishes, one warning
    warnings = [
        r for r in caplog.records if "timing suspect" in r.getMessage()
    ]
    assert len(warnings) == 1
    assert "liar" in warnings[0].getMessage()


def test_honest_rate_is_not_suspect():
    xla.set_peaks(1e12, 1e11)
    reg = profile.PROFILE_REGISTRY
    reg.count_dispatch("honest", ("f32[4]",), 1)
    reg.record_sample("honest", ("f32[4]",), 1.0, 1.0, 0.0, 1e9, 1e6)
    merged = profile.merged_profiles()["honest"]
    assert merged["timing_suspect"] is False
    assert merged["mfu"] == pytest.approx(1e-3)
    profile.publish_metrics()
    gauges = telemetry.snapshot()["gauges"]
    assert "profile.exec.honest.timing_suspect" not in gauges


def test_unknown_peaks_mean_unknown_not_suspect():
    # no resolved peaks: mfu/bound stay unknown and the self-check cannot
    # fire (absence of evidence is not dishonesty)
    reg = profile.PROFILE_REGISTRY
    reg.count_dispatch("nopeaks", ("f32[4]",), 1)
    reg.record_sample("nopeaks", ("f32[4]",), 1e-9, 1e-9, 0.0, 1e9, 1e6)
    merged = profile.merged_profiles()["nopeaks"]
    if xla.device_peaks() == (None, None):
        assert merged["timing_suspect"] is False
        assert merged["mfu"] is None
        assert merged["bound_code"] == profile.BOUND_UNKNOWN


# -- publish / metrics round trip ---------------------------------------------


def test_publish_metrics_gauges_round_trip(tmp_path):
    import json

    xla.set_peaks(1e12, 1e11)
    reg = profile.PROFILE_REGISTRY
    for _ in range(4):
        reg.count_dispatch("hot", ("f32[8]",), 1)
        reg.record_sample("hot", ("f32[8]",), 0.5, 0.4, 0.01, 1e10, 8e9)
    path = str(tmp_path / "telemetry.jsonl")
    telemetry.flush_metrics(path)  # publishes derived gauges first
    with open(path, encoding="utf-8") as fh:
        snap = json.loads(fh.readline())["snapshot"]
    g = snap["gauges"]
    assert g["profile.exec.hot.dispatches"] == 4
    assert g["profile.exec.hot.sampled"] == 4
    assert g["profile.exec.hot.est_exclusive_seconds"] == pytest.approx(
        1.6
    )
    assert g["profile.exec.hot.mean_dispatch_seconds"] == pytest.approx(
        0.5
    )
    assert g["profile.exec.hot.mfu"] == pytest.approx(0.02)
    assert g["profile.exec.hot.intensity"] == pytest.approx(1.25)
    assert g["profile.exec.hot.bound_code"] == profile.BOUND_HBM


def test_exclusive_seconds_by_name_registers_nothing():
    before = set(telemetry.snapshot()["gauges"])
    assert profile.exclusive_seconds_by_name() == {}
    assert set(telemetry.snapshot()["gauges"]) == before


# -- HBM high-watermarks ------------------------------------------------------


class _FakeDevice:
    def __init__(self, did, in_use, limit=16 * 2**30):
        self.id = did
        self._stats = {"bytes_in_use": in_use, "bytes_limit": limit}

    def memory_stats(self):
        return self._stats


def test_watermarks_max_track_per_device_and_phase():
    d0, d1 = _FakeDevice(0, 100), _FakeDevice(1, 700)
    memory.record_device_watermarks([d0, d1], phase="fit")
    d0._stats["bytes_in_use"] = 500
    d1._stats["bytes_in_use"] = 300  # dips: the peak must NOT follow
    memory.record_device_watermarks([d0, d1], phase="fit")
    g = telemetry.snapshot()["gauges"]
    assert g["memory.device.0.peak_bytes"] == 500
    assert g["memory.device.1.peak_bytes"] == 700
    assert g["memory.phase.fit.device.0.peak_bytes"] == 500
    assert g["memory.phase.fit.device.1.peak_bytes"] == 700
    # the last-sample gauges still track the dip
    assert g["memory.device.1.bytes_in_use"] == 300


def test_watermarks_absent_on_statless_backends():
    class _Statless:
        id = 0

        def memory_stats(self):
            return None

    assert memory.record_device_watermarks([_Statless()]) == {}
    assert not any(
        ".peak_bytes" in name
        for name in telemetry.snapshot()["gauges"]
    )


def test_sampler_records_watermarks_under_open_span():
    profile.set_sample_every(1)
    provider_stats = {"bytes_in_use": 4096, "bytes_limit": 2**30}
    d = _FakeDevice(3, 4096)
    with trace.span("fit"):
        # the sampler probes real devices (statless on CPU); drive the
        # watermark recorder directly with a fake device to prove the
        # phase attribution path the sampler uses
        span = trace.current_span()
        memory.record_device_watermarks([d], phase=span.name)
    g = telemetry.snapshot()["gauges"]
    assert g["memory.phase.fit.device.3.peak_bytes"] == 4096


# -- xprof capture window -----------------------------------------------------


def test_xprof_window_arms_and_stops_via_hooks():
    calls = []
    profile.set_xprof_hooks(
        lambda d: calls.append(("start", d)),
        lambda: calls.append(("stop",)),
    )
    assert profile.configure_xprof("/tmp/xp", arm_at=3, capture=2,
                                   force=True)
    f = telemetry.instrumented_jit(lambda x: x + 1.0, name="xp")
    x = np.zeros((2,), np.float32)
    for _ in range(6):
        f(x)
    assert ("start", "/tmp/xp") in calls
    assert ("stop",) in calls
    assert calls.index(("start", "/tmp/xp")) < calls.index(("stop",))
    assert telemetry.snapshot()["gauges"]["profile.xprof_armed"] == 1


def test_xprof_refuses_cpu_backend_without_force():
    assert profile.configure_xprof("/tmp/xp") is False


def test_xprof_reset_closes_open_window():
    calls = []
    profile.set_xprof_hooks(
        lambda d: calls.append("start"), lambda: calls.append("stop")
    )
    profile.configure_xprof("/tmp/xp", arm_at=0, capture=100, force=True)
    f = telemetry.instrumented_jit(lambda x: x + 1.0, name="xpreset")
    f(np.zeros((2,), np.float32))
    assert "start" in calls and "stop" not in calls
    profile.reset()  # run teardown: the window must not stay open
    assert "stop" in calls


def test_xprof_start_failure_disarms_without_killing_dispatch():
    def broken(d):
        raise RuntimeError("capture machinery wedged")

    profile.set_xprof_hooks(broken, lambda: None)
    profile.configure_xprof("/tmp/xp", arm_at=0, capture=2, force=True)
    f = telemetry.instrumented_jit(lambda x: x * 3.0, name="xpfail")
    out = f(np.ones((2,), np.float32))  # must not raise
    np.testing.assert_allclose(np.asarray(out), 3.0)


# -- lifecycle ----------------------------------------------------------------


def test_reset_rearms_the_sampler():
    telemetry.reset()  # the test-isolation path
    f = telemetry.instrumented_jit(lambda x: x + 1.0, name="rearmed")
    f(np.zeros((2,), np.float32))
    (entry,) = profile.PROFILE_REGISTRY.entries("rearmed")
    assert entry.sampled == 1
