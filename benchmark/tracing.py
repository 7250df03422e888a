"""From a profiler trace (``.xplane.pb``) to device busy time, kernel time
and idle gaps. Read with nothing but ``jax.profiler.ProfileData``.

What a v5e trace holds (looked at by hand, PR 23): one plane
``/device:TPU:<n>`` per chip whose line ``XLA Ops`` carries one event per
executed HLO op, control-flow ops (``while``, ``conditional``, ``call``)
enclosing the events of their bodies on the same line; the host's threads
are lines of ``/host:CPU``. All planes share one clock, nanoseconds from
the start of the trace. Busy time is therefore the UNION of the op
intervals, an op's own time is its duration less what its children cover,
and a kernel's time is the sum over the events its name pattern matches.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
#: host annotations the benchmark itself writes (``TraceAnnotation``)
ANNOTATION_PREFIX = "bench:"


@dataclasses.dataclass
class Trace:
    #: device plane name -> [(op name, start_ns, duration_ns)], by start
    devices: dict[str, list[tuple[str, float, float]]]
    #: the benchmark's own host annotations, [(name, start_ns, duration_ns)]
    annotations: list[tuple[str, float, float]]


def find_xplane(directory: str) -> str:
    files = glob.glob(
        os.path.join(directory, "plugins", "profile", "*", "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(files, key=os.path.getmtime)


def load(path: str, host_ops_as_device: bool = False) -> Trace:
    """Read a trace. ``host_ops_as_device`` is for rehearsals off the chip:
    XLA's CPU client writes its ops (events with an ``hlo_op`` stat) on host
    lines, and they then stand in for a device plane named ``cpu``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    annotations = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events
                    ]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        annotations.append(
                            (e.name[len(ANNOTATION_PREFIX):],
                             float(e.start_ns), float(e.duration_ns)))
                    elif host_ops_as_device and line.name.startswith(
                            "tf_XLAPjRtCpuClient") and e.duration_ns > 0:
                        if any(k == "hlo_op" for k, _ in e.stats):
                            devices.setdefault("cpu", []).append(
                                (e.name, float(e.start_ns),
                                 float(e.duration_ns)))
    for events in devices.values():
        events.sort(key=lambda e: (e[1], -e[2]))
    annotations.sort(key=lambda e: e[1])
    return Trace(devices=devices, annotations=annotations)


def busy_intervals(events) -> list[tuple[float, float]]:
    """Union of [start, end) over events sorted by start."""
    out: list[list[float]] = []
    for _, start, dur in events:
        end = start + dur
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def busy_seconds(events, window=None) -> float:
    total = 0.0
    for a, b in busy_intervals(events):
        if window is not None:
            a, b = max(a, window[0]), min(b, window[1])
        if b > a:
            total += b - a
    return total * 1e-9


def self_seconds(events) -> dict[str, float]:
    """Per op name, the time the op ran itself: its duration less what the
    events nested inside it cover (a ``while`` owns only its own overhead)."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0) * 1e-9

    for name, start, dur in events:
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


def kernel_seconds(events, pattern: str) -> tuple[float, int]:
    """Summed duration and count of the events whose name matches."""
    rx = re.compile(pattern)
    durs = [dur for name, _, dur in events if rx.search(name)]
    return sum(durs) * 1e-9, len(durs)


def idle_gaps(events, window, annotations, top: int = 10):
    """The longest idle gaps inside ``window`` (ns), each named by the
    benchmark's host annotation that holds its midpoint."""
    gaps = []
    cursor = window[0]
    for a, b in busy_intervals(events):
        if b <= window[0] or a >= window[1]:
            continue
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    if cursor < window[1]:
        gaps.append((cursor, window[1]))
    by_name: dict[str, float] = {}
    for a, b in gaps:
        mid = (a + b) / 2
        holding = [(d, n) for n, s, d in annotations if s <= mid < s + d]
        name = min(holding)[1] if holding else "unattributed"  # innermost
        by_name[name] = by_name.get(name, 0.0) + (b - a) * 1e-9
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return [[n, s] for n, s in ranked]


def short_name(text: str) -> str:
    """An op's event name on a TPU is its whole HLO text; keep the
    instruction name, opcode, result shape and custom-call target."""
    name, _, rest = text.partition(" = ")
    if not rest:
        return text[:120]
    op = re.search(r" ([a-z][a-z0-9_.\-]*)\(", rest)
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    shape = re.match(r"\(?[a-z0-9]+\[[0-9,]*\]", rest)
    parts = [name, op.group(1) if op else "", shape.group(0) if shape else ""]
    if target:
        parts.append(target.group(1))
    return " ".join(p for p in parts if p)[:120]


def annotation_window(annotations, name: str):
    """[start, end) in ns of the first annotation called ``name``."""
    for n, s, d in annotations:
        if n == name:
            return (s, s + d)
    return None
